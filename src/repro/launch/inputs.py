"""Abstract inputs (ShapeDtypeStruct + shardings) for every
(architecture × input shape × mesh) combination — the dry-run's stand-ins.
No device allocation happens here.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.config import INPUT_SHAPES, ModelConfig, ShapeConfig
from repro.common import sharding as shd
from repro.common.params import abstract_tree
from repro.core import moe as moe_core
from repro.core.moe import MoERuntime
from repro.models import model as mdl
from repro.optim.adamw import OptState
from repro.train.step import TrainState, state_shardings


def ep_size(mesh: Mesh) -> int:
    return mesh.shape["model"]


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def mesh_batch_size(mesh: Mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def make_runtime(cfg: ModelConfig, mesh: Mesh, *,
                 impl: str = "ring", use_pallas: bool = False,
                 unroll: bool = False, capacity: int = 0,
                 rules_overrides: Optional[dict] = None) -> mdl.Runtime:
    """The distributed runtime: the sparse FSSDP MoE layer over the
    mesh's ``model`` axis (a 1x1 mesh runs it on one device)."""
    rules = shd.resolve_rules(mesh, rules_overrides)
    moe_rt = MoERuntime(
        mesh=mesh, ep_axis="model", batch_axes=batch_axes(mesh),
        impl=impl if impl != "ep" else "none",
        m=(cfg.moe.slots_per_device if impl in ("ring", "a2a") else 0),
        capacity=capacity, use_pallas=use_pallas)
    return mdl.Runtime(mesh=mesh, rules=rules, moe=moe_rt,
                       use_pallas=use_pallas, unroll=unroll)


# ---------------------------------------------------------------------------
# Parameters / optimizer / plan tables
# ---------------------------------------------------------------------------
def param_shardings(cfg: ModelConfig, mesh: Mesh):
    return state_shardings(cfg, mesh).params


def abstract_params(cfg: ModelConfig, mesh: Mesh):
    decls = mdl.param_decls(cfg, ep_size(mesh))
    return abstract_tree(decls, cfg.param_dtype,
                         shardings=param_shardings(cfg, mesh))


def abstract_state(cfg: ModelConfig, mesh: Mesh) -> TrainState:
    params = abstract_params(cfg, mesh)
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                         sharding=p.sharding)
    opt = OptState(mu=jax.tree.map(f32, params),
                   nu=jax.tree.map(f32, params),
                   count=jax.ShapeDtypeStruct((), jnp.int32))
    return TrainState(params=params, opt=opt,
                      step=jax.ShapeDtypeStruct((), jnp.int32))


def abstract_plan(cfg: ModelConfig, mesh: Mesh):
    if not cfg.moe.enabled:
        return None
    ep = ep_size(mesh)
    k_local = -(-cfg.moe.num_experts // ep)
    pa = moe_core.abstract_plan_arrays(cfg, ep, cfg.moe.slots_per_device,
                                       k_local)
    rep = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), pa)


def concrete_plan(cfg: ModelConfig, ep: int, impl: str = "ring",
                  loads: Optional[np.ndarray] = None):
    """Real plan tables (runtime values) for executing distributed steps."""
    from repro.core.placement import ep_materialization, homogeneous_sharding
    from repro.core.schedule import sparse_materialization
    L = moe_core.num_moe_layers(cfg)
    sh = homogeneous_sharding(L, cfg.moe.num_experts, ep)
    if impl == "ep":
        return moe_core.plan_to_arrays(ep_materialization(sh))
    if loads is None:
        loads = np.ones((L, cfg.moe.num_experts))
    plan = sparse_materialization(sh, loads, t=cfg.moe.num_experts,
                                  m=cfg.moe.slots_per_device, impl=impl)
    return moe_core.plan_to_arrays(plan)


# ---------------------------------------------------------------------------
# Batches / caches per input shape
# ---------------------------------------------------------------------------
def effective_seq(cfg: ModelConfig, shape: ShapeConfig) -> int:
    if cfg.max_decoder_len:
        return min(shape.seq_len, cfg.max_decoder_len)
    return shape.seq_len


def abstract_batch(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh
                   ) -> Dict[str, Any]:
    """Training / prefill batch stand-ins."""
    rules = shd.resolve_rules(mesh)
    b = shape.global_batch
    s = effective_seq(cfg, shape)
    sds = jax.ShapeDtypeStruct
    plus = 1 if shape.mode == "train" else 0
    def bsh(shp, axes):
        return shd.shape_aware_sharding(shp, axes, rules, mesh)

    if cfg.frontend == "vision":
        eshp = (b, s, cfg.d_model)
        out = {"embeds": sds(eshp, jnp.dtype(cfg.dtype),
                             sharding=bsh(eshp, ("batch", None, None)))}
        if shape.mode == "train":
            out["labels"] = sds((b, s), jnp.int32,
                                sharding=bsh((b, s), ("batch", None)))
        return out
    if cfg.is_encoder_decoder:
        eshp = (b, cfg.encoder_seq_len, cfg.d_model)
        return {
            "encoder_input": sds(eshp, jnp.dtype(cfg.dtype),
                                 sharding=bsh(eshp, ("batch", None, None))),
            "tokens": sds((b, s + plus), jnp.int32,
                          sharding=bsh((b, s + plus), ("batch", None))),
        }
    return {"tokens": sds((b, s + plus), jnp.int32,
                          sharding=bsh((b, s + plus), ("batch", None)))}


def abstract_decode_inputs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """(cache, tokens, pos) stand-ins for serve_step."""
    rules = shd.resolve_rules(mesh)
    b = shape.global_batch
    s = effective_seq(cfg, shape)
    cache = mdl.init_cache(cfg, b, s, abstract=True)
    ax = mdl.cache_logical_axes(cfg, b, mesh_batch_size(mesh))
    is_axes = lambda t: isinstance(t, tuple) and all(
        x is None or isinstance(x, str) for x in t)
    ax = jax.tree.map(lambda t: t, ax, is_leaf=is_axes)
    cache = jax.tree.map(
        lambda sdsv, a: jax.ShapeDtypeStruct(
            sdsv.shape, sdsv.dtype,
            sharding=shd.shape_aware_sharding(sdsv.shape, a, rules, mesh)),
        cache, ax)
    tokens = jax.ShapeDtypeStruct(
        (b, 1), jnp.int32,
        sharding=shd.shape_aware_sharding((b, 1), ("batch", None), rules,
                                          mesh))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return cache, tokens, pos


# ---------------------------------------------------------------------------
# Applicability (DESIGN.md §Arch-applicability)
# ---------------------------------------------------------------------------
def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.supports_long_context():
        return ("pure full-attention architecture: no sub-quadratic variant "
                "in the published design — long_500k skipped (DESIGN.md)")
    return None


def shape_note(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    s = effective_seq(cfg, shape)
    if s != shape.seq_len:
        return (f"seq capped at the architecture's maximum "
                f"({cfg.max_decoder_len}); lowered at seq={s}")
    return None

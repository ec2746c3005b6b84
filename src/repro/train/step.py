"""Train step builder: loss, grads, AdamW update — one jitted function.

The FSSDP placement tables (PlanArrays) are ordinary runtime inputs: the
Hecate scheduler re-plans every iteration with zero recompilation.  This
holds under the software-pipelined materialization too — the forward
shifts the SAME stacked tables by one MoE layer to drive the one-layer-
ahead SparseAllGather prefetch (repro.models.model._pipelined_blocks), so
plan swaps still never retrace.

Under gradient accumulation the materialization is HOISTED out of the
microbatch loop: ``moe_core.materialize_stack`` builds every MoE layer's
compute slots once at the head of the step (one stacked traceable
SparseAllGather region) and every microbatch's forward consumes them via
``premat=`` — L materialization gathers per accumulated step instead of
L·n (jaxpr-asserted in tests/test_step_overlap.py).  In "save" mode the
hoisted slots are ONE shared set of chunk residuals instead of n: each
microbatch's backward contributes a chunk cotangent, the scan accumulates
them, and a single explicit ``jax.linear_transpose`` of the stacked
gather — the stacked SparseReduceScatter — lands the sum on the owning
buffer shards once per step.  In "gather" mode the hoisted slots are
detached (the regather VJP owns the buffer grad and re-gathers per
microbatch, one layer ahead of its consumers — see
``moe_core.moe_layer_regather_pipelined``).  What the backward does about
the materialized chunks remains ``cfg.moe.rematerialize`` ("save" |
"gather" | "block", see repro.core.moe).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import sharding as shd
from repro.common.config import ModelConfig, TrainConfig
from repro.common.faults import GRAD_SCALE_KEY
from repro.core import moe as moe_core
from repro.core.moe import MoEAux, PlanArrays, num_moe_layers
from repro.models import model as mdl
from repro.optim import adamw


class TrainState(NamedTuple):
    params: Any
    opt: adamw.OptState
    step: jnp.ndarray


def init_state(cfg: ModelConfig, key, ep: int = 1,
               mesh: Optional[Mesh] = None) -> TrainState:
    """Fresh params + AdamW state.  With a mesh the state is created
    directly in its sharded layout (no device ever holds all of it)."""
    def make(k):
        params = mdl.init_params(cfg, k, ep)
        return TrainState(params=params, opt=adamw.init(params),
                          step=jnp.zeros((), jnp.int32))
    if mesh is None:
        return make(key)
    return jax.jit(make, out_shardings=state_shardings(cfg, mesh))(key)


def state_shardings(cfg: ModelConfig, mesh: Mesh) -> TrainState:
    """NamedShardings of the train state: params (and both AdamW
    moments) per their logical axes, counters replicated."""
    ps = shd.decl_shardings(mdl.param_decls(cfg, mesh.shape["model"]), mesh)
    rep = NamedSharding(mesh, P())
    return TrainState(params=ps, opt=adamw.OptState(mu=ps, nu=ps, count=rep),
                      step=rep)


def cross_entropy(logits, labels, ignore: int = -1):
    """logits (B,S,V) f32; labels (B,S) int32. Mean over valid tokens."""
    mask = (labels != ignore).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def chunked_xent(cfg: ModelConfig, embed_params, hidden, labels,
                 n_chunks: int = 8, ignore: int = -1):
    """Streaming next-token loss: unembed + logsumexp one sequence chunk at
    a time (checkpointed), so the (B, S, V) f32 logits tensor never exists —
    it would be tens of GB/device for 150k-vocab models at train_4k."""
    from repro.models import layers as ly
    b, s, d = hidden.shape
    while s % n_chunks:
        n_chunks -= 1
    c = s // n_chunks
    hs = hidden.reshape(b, n_chunks, c, d).swapaxes(0, 1)   # (n,B,c,D)
    ls = labels.reshape(b, n_chunks, c).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, hl):
        h, l = hl
        logits = ly.unembed(embed_params, h, cfg.final_logit_softcap)
        mask = (l != ignore).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(l, 0)[..., None], axis=-1)[..., 0]
        nll, cnt = carry
        return (nll + ((lse - ll) * mask).sum(), cnt + mask.sum()), None

    (nll, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (hs, ls))
    return nll / jnp.maximum(cnt, 1.0)


def _unpack_batch(cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    """Returns (fwd_kwargs, labels)."""
    if cfg.frontend is not None and not cfg.is_encoder_decoder:
        return {"embeds": batch["embeds"]}, batch["labels"]
    if cfg.is_encoder_decoder:
        toks = batch["tokens"]
        return ({"tokens": toks[:, :-1],
                 "encoder_input": batch["encoder_input"]}, toks[:, 1:])
    toks = batch["tokens"]
    return {"tokens": toks[:, :-1]}, toks[:, 1:]


def loss_fn(cfg: ModelConfig, rt: mdl.Runtime, params, batch,
            pa: Optional[PlanArrays], causal: bool = True, premat=None):
    kwargs, labels = _unpack_batch(cfg, batch)
    hidden, aux = mdl.forward(cfg, rt, params, pa=pa, causal=causal,
                              return_hidden=True, premat=premat, **kwargs)
    with jax.named_scope("lm_head"):
        loss = chunked_xent(cfg, params["embed"], hidden, labels)
    metrics = {"xent": loss}
    if aux is not None:
        # aux leaves: (n_sb, c, ...) -> (L_moe, ...)
        aux = jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), aux)
        aux_l = cfg.moe.aux_loss_weight * aux.aux_loss.sum()
        z_l = cfg.moe.router_z_loss_weight * aux.z_loss.sum()
        loss = loss + aux_l + z_l
        metrics.update(
            aux_loss=aux_l, z_loss=z_l,
            expert_counts=jax.lax.stop_gradient(aux.counts),
            device_loads=jax.lax.stop_gradient(aux.device_loads),
            dropped_frac=aux.dropped_frac.mean(),
            # fraction of expert-compute rows that are padding — the work
            # the group-size-aware grouped GEMM skips (mean over layers)
            pad_frac=jax.lax.stop_gradient(aux.pad_frac).mean())
    metrics["loss"] = loss
    return loss, metrics


def build_train_step(cfg: ModelConfig, rt: mdl.Runtime, tc: TrainConfig,
                     causal: bool = True, grad_shardings=None,
                     hoist_premat: Optional[bool] = None):
    """Returns fn(state, batch, pa) -> (state, metrics).  Jit it with the
    desired in/out shardings (see repro.launch).

    grad_shardings: optional pytree of NamedShardings matching params.
    Constraining gradients AT THE PRODUCER makes GSPMD reduce-scatter
    weight grads onto their owning shards instead of all-reducing full
    tensors everywhere (measured on qwen1.5-110b: the unconstrained step
    all-reduced 1.4 TB/device/step of f32 weight grads — §Perf).

    hoist_premat: None (auto — hoist the SparseAllGathers out of the
    gradient-accumulation loop whenever the pipelined MoE path is active
    and tc.microbatch > 1), or force on/off.  ``False`` keeps the legacy
    per-microbatch materialization (each microbatch's forward re-issues
    all L gathers) — the parity baseline in tests/test_step_overlap.py.
    """

    n = max(tc.microbatch, 1)
    hoist = (cfg.moe.enabled and rt.moe.mesh is not None and n > 1
             and mdl._use_pipeline(cfg, rt)) if hoist_premat is None \
        else hoist_premat
    dt = jnp.dtype(cfg.dtype)

    def _loss(p, b, a, pm):
        return loss_fn(cfg, rt, p, b, a, causal, premat=pm)

    _g = jax.value_and_grad(_loss, has_aux=True)
    # save-mode hoisting also differentiates the SHARED premat: each
    # microbatch emits a chunk cotangent, the scan sums them, and one
    # linear_transpose of the stacked gather (below) turns the sum into
    # the buffer gradient — the per-step stacked SparseReduceScatter
    _g2 = jax.value_and_grad(_loss, argnums=(0, 3), has_aux=True)

    def grad_fn(p, b, a, pm=None, with_premat_grad=False):
        if with_premat_grad:
            out, (g, gp) = _g2(p, b, a, pm)
        else:
            out, g = _g(p, b, a, pm)
            gp = None
        if grad_shardings is not None:
            g = jax.lax.with_sharding_constraint(g, grad_shardings)
        return out, g, gp

    def train_step(state: TrainState, batch, pa: Optional[PlanArrays]):
        # fault-injection hook (repro.common.faults, "train.nan_grads"):
        # an armed run adds GRAD_SCALE_KEY to the batch and the step
        # multiplies it into the grads — an unarmed batch never carries
        # the key, so the production trace is unchanged
        batch = dict(batch)
        fault_scale = batch.pop(GRAD_SCALE_KEY, None)
        hoisted = hoist and pa is not None and n > 1
        premat = None
        if hoisted:
            # ALL L layers' compute slots, built once per step — one
            # stacked traceable SparseAllGather region at the step head,
            # shared by every microbatch's forward (premat=)
            premat = moe_core.materialize_stack(
                cfg, rt.moe, state.params["moe_buffer"], pa, dtype=dt,
                name=False)
            if cfg.moe.rematerialize == "gather":
                # the regather VJP owns the buffer grad (it re-gathers per
                # microbatch); detaching keeps the stacked producer out of
                # AD — no dead step-level transpose
                premat = jax.lax.stop_gradient(premat)
        premat_grad = hoisted and cfg.moe.rematerialize == "save"
        if n == 1:
            (_, metrics), grads, _ = grad_fn(state.params, batch, pa)
        else:
            # gradient accumulation: scan over microbatches so only one
            # microbatch's activations are ever live (large models at
            # train_4k need this to fit HBM — see EXPERIMENTS.md §Dry-run)
            micro = jax.tree.map(
                lambda a: a.reshape((n, a.shape[0] // n) + a.shape[1:]),
                batch)

            def mb_body(acc, mb):
                g_acc, gp_acc, m_acc = acc
                (_, m), g, gp = grad_fn(state.params, mb, pa, premat,
                                        premat_grad)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                if premat_grad:
                    gp_acc = gp_acc + gp.astype(jnp.float32)
                m_acc = jax.tree.map(jnp.add, m_acc, m)
                return (g_acc, gp_acc, m_acc), None

            zeros_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (_, m0), g0, gp0 = grad_fn(state.params,
                                       jax.tree.map(lambda a: a[0], micro),
                                       pa, premat, premat_grad)
            gp0 = gp0.astype(jnp.float32) if premat_grad else jnp.zeros(())
            (grads, gpm, msum), _ = jax.lax.scan(
                mb_body, (jax.tree.map(jnp.add, zeros_g, g0), gp0, m0),
                jax.tree.map(lambda a: a[1:], micro))
            inv = 1.0 / n
            grads = jax.tree.map(lambda g: g * inv, grads)
            metrics = jax.tree.map(lambda m: m * inv, msum)
            if premat_grad:
                # stacked SparseReduceScatter: ONE transpose of the
                # step-level gather lands the accumulated chunk cotangent
                # on the owning buffer shards
                with jax.named_scope("sprs"):
                    dbuf = jax.linear_transpose(
                        lambda b: moe_core.materialize_stack(
                            cfg, rt.moe, b, pa, dtype=dt, name=False),
                        state.params["moe_buffer"])(gpm.astype(dt))[0]
                grads = dict(grads)
                grads["moe_buffer"] = grads["moe_buffer"] \
                    + dbuf.astype(jnp.float32) * inv
            if "expert_counts" in metrics:
                metrics["expert_counts"] = metrics["expert_counts"] * n
        if fault_scale is not None:
            grads = jax.tree.map(
                lambda g: g * jnp.asarray(fault_scale, g.dtype), grads)
        # step-health guard (tc.step_guard): skip the optimizer update on
        # a non-finite loss or grad global norm.  The gnorm is already on
        # the clipping path and step_ok rides the step's one metrics
        # readback — no extra device sync.
        with jax.named_scope("optimizer"):
            extra_ok = (jnp.isfinite(metrics["loss"]) if tc.step_guard
                        else None)
            new_params, new_opt, opt_metrics = adamw.update(
                grads, state.opt, state.params, tc,
                skip_nonfinite=tc.step_guard, extra_ok=extra_ok)
        metrics.update(opt_metrics)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


def build_eval_step(cfg: ModelConfig, rt: mdl.Runtime, causal: bool = True):
    def eval_step(params, batch, pa: Optional[PlanArrays]):
        _, metrics = loss_fn(cfg, rt, params, batch, pa, causal)
        return metrics
    return eval_step

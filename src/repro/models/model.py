"""Model assembly: config -> params / forward / decode, scan over superblocks.

A model is a stack of ``num_superblocks`` identical *superblocks* (one tile
of ``cfg.layer_pattern``), executed with ``jax.lax.scan`` so HLO size is
O(1) in depth (512-device compiles stay fast).  MoE FFNs read from the
single cross-layer FSSDP chunk buffer (``repro.core.moe``); everything else
is plain pytree params stacked along the scan axis.

With a mesh, MoE materialization is SOFTWARE-PIPELINED one layer ahead
(``_pipelined_blocks``): the scan carries the next MoE layer's prefetched
compute slots, so each layer's SparseAllGather overlaps the previous
layer's attention/FFN compute.  ``forward(premat=...)`` takes the
STEP-HOISTED slots instead (``moe_core.materialize_stack`` built all L
layers once, before the train step's gradient-accumulation loop) and
issues no materialization collectives at all.  ``cfg.moe.rematerialize``
picks what the backward does about those slots (save | gather | block),
and in gather mode ``cfg.moe.bwd_prefetch`` threads the explicit
BACKWARD re-gather pipeline through the blocks — layer l−1's re-gather
is issued before layer l's backward kernels, transported as the
cotangent of a chunk-shaped pipe channel in the scan carry (see the
``repro.core.moe`` docstring).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.common.config import ModelConfig
from repro.common import sharding as shd
from repro.common.params import Param, axes_tree, init_tree, stack_params
from repro.core import moe as moe_core
from repro.core.moe import MoERuntime, PlanArrays
from repro.models import attention as attn
from repro.models import layers as ly
from repro.models import mamba2 as mb


@dataclasses.dataclass
class Runtime:
    """Distribution context threaded through the model."""
    mesh: Optional[Mesh] = None
    rules: Optional[Dict[str, Any]] = None
    moe: MoERuntime = dataclasses.field(default_factory=MoERuntime)
    use_pallas: bool = False
    # Unroll the superblock scan into a Python loop.  Used by the dry-run's
    # cost extrapolation: XLA cost_analysis counts a while-loop body ONCE
    # (verified on this jax build), so the roofline lowers depth-1 and
    # depth-2 unrolled variants and extrapolates exactly (blocks are
    # homogeneous by construction).
    unroll: bool = False

    @property
    def num_devices(self) -> int:
        return self.mesh.size if self.mesh is not None else 1

    def constrain(self, x, axes):
        if self.mesh is None or self.rules is None:
            return x
        return shd.constrain(x, axes, self.rules, self.mesh)


def _scan(rt: Runtime, body, carry, xs):
    """lax.scan or an unrolled Python loop (see Runtime.unroll)."""
    if not rt.unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _moe_positions(cfg: ModelConfig) -> Tuple[int, ...]:
    """Positions within a superblock that carry an MoE FFN (must be
    consistent across superblocks — validated)."""
    pl = len(cfg.layer_pattern)
    pos = tuple(j for j in range(pl) if cfg.is_moe_layer(j))
    for sb in range(cfg.num_superblocks):
        got = tuple(j for j in range(pl) if cfg.is_moe_layer(sb * pl + j))
        assert got == pos, (
            f"{cfg.name}: MoE period {cfg.moe.period} incompatible with "
            f"layer_pattern length {pl} — expand the pattern")
    return pos


# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------
def _sublayer_decl(cfg: ModelConfig, kind: str, is_moe: bool, cross: bool):
    d = cfg.d_model
    p: Dict[str, Any] = {"ln1": ly.norm_params(d)}
    if kind in ("attn", "local"):
        p["attn"] = attn.attn_params(cfg)
    elif kind == "mamba":
        p["mamba"] = mb.mamba_params(cfg)
    else:
        raise ValueError(kind)
    if cross:
        p["lnx"] = ly.norm_params(d)
        p["xattn"] = attn.attn_params(cfg, cross=True)
    if kind != "mamba":
        p["ln2"] = ly.norm_params(d)
        if not is_moe:
            p["mlp"] = ly.mlp_params(d, cfg.d_ff, cfg.act)
    elif is_moe:  # hybrid: mamba layer followed by MoE FFN (jamba)
        p["ln2"] = ly.norm_params(d)
    return p


def param_decls(cfg: ModelConfig, ep: int = 1):
    """Full parameter declaration tree (Param descriptors)."""
    moe_pos = _moe_positions(cfg) if cfg.moe.enabled else ()
    sb = {}
    for j, kind in enumerate(cfg.layer_pattern):
        sb[f"l{j}"] = _sublayer_decl(cfg, kind, j in moe_pos,
                                     cross=cfg.is_encoder_decoder)
    decls: Dict[str, Any] = {
        "embed": ly.embed_params(cfg.vocab_size, cfg.d_model,
                                 cfg.tie_embeddings),
        "blocks": stack_params(sb, cfg.num_superblocks),
        "final_norm": ly.norm_params(cfg.d_model),
    }
    if cfg.moe.enabled:
        decls["router"] = moe_core.router_param(cfg)
        decls["moe_buffer"] = moe_core.moe_buffer_param(cfg, ep)
    if cfg.is_encoder_decoder:
        enc_sb = {"l0": _sublayer_decl(cfg, "attn", False, cross=False)}
        decls["encoder"] = {
            "blocks": stack_params(enc_sb, cfg.encoder_layers),
            "final_norm": ly.norm_params(cfg.d_model),
        }
    return decls


def param_logical_axes(cfg: ModelConfig, ep: int = 1):
    return axes_tree(param_decls(cfg, ep))


def init_params(cfg: ModelConfig, key, ep: int = 1):
    return init_tree(param_decls(cfg, ep), key, cfg.param_dtype)


# ---------------------------------------------------------------------------
# MoE FFN wrapper: flatten tokens, pad to device count, run the FSSDP core
# ---------------------------------------------------------------------------
def _moe_ffn(cfg: ModelConfig, rt: Runtime, x, wr, buf, pa: PlanArrays,
             premat=None, pipe=None, pa_prev=None, warm_start=False):
    """Returns (y, aux, pipe_out).  ``pipe``/``pa_prev``/``warm_start``
    drive the explicit backward re-gather pipeline (gather mode with
    ``cfg.moe.bwd_prefetch`` — see moe_core.moe_layer_regather_pipelined);
    ``pipe_out`` is None whenever no pipe channel is threaded."""
    b, s, d = x.shape
    t = b * s
    n_dev = rt.num_devices
    pad = (-t) % max(n_dev, 1)
    xt = x.reshape(t, d)
    # Stage the reshard explicitly: batch-sharded -> token-sharded is a
    # local SPLIT over the model axis; the return path gathers over the
    # model axis only, WITHIN each data group.  Without the intermediate
    # ("tokens_batch") constraint GSPMD lowers the boundary as a full
    # replicate-gather of the global token tensor (8.6 GB/layer/device in
    # the olmoe dry-run).
    xt = rt.constrain(xt, ("tokens_batch", None))
    valid = jnp.ones((t,), bool)
    if pad:
        xt = jnp.concatenate([xt, jnp.zeros((pad, d), x.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
    xt = rt.constrain(xt, ("tokens", None))
    pipe_out = None
    if premat is not None and cfg.moe.rematerialize == "gather" \
            and rt.moe.mesh is not None:
        # true re-materialization: no chunk residuals, the backward
        # replays the SparseAllGather
        if pipe is not None:
            # explicit backward pipeline: this layer's backward consumes
            # slots gathered one backward step earlier and issues the
            # previous layer's re-gather ahead of its own kernels
            y, aux, pipe_out = moe_core.moe_layer_regather_pipelined(
                cfg, rt.moe, xt, wr, buf, pa, pa_prev, valid, premat,
                pipe, warm_start=warm_start)
        else:
            y, aux = moe_core.moe_layer_regather(cfg, rt.moe, xt, wr, buf,
                                                 pa, valid, premat)
    else:
        y, aux = moe_core.moe_layer(cfg, rt.moe, xt, wr, buf, pa, valid,
                                    premat=premat)
    y = rt.constrain(y, ("tokens", None))
    if pad:
        y = y[:t]
    y = rt.constrain(y, ("tokens_batch", None))
    return y.reshape(b, s, d), aux, pipe_out


# ---------------------------------------------------------------------------
# Superblock forward (full-sequence: train / prefill)
# ---------------------------------------------------------------------------
def _superblock(cfg: ModelConfig, rt: Runtime, params_sb, x, positions,
                moe_xs, enc_out=None, causal: bool = True,
                collect_cache: bool = False, prefetch=None,
                seg_remat: bool = False, premat_c=None, pipe=None,
                pa_prev0=None, tail: bool = False):
    """moe_xs: (routers:(c,d,E), plan arrays with leading c, buffer) or None.
    collect_cache: also return the per-sublayer decode cache (prefill).

    prefetch: None (serial path — each layer materializes its own chunks
    inside moe_layer), or ``(chunks_in, pa_next)`` enabling the one-layer-
    ahead materialization pipeline: ``chunks_in`` is the (M, K, chunk_len)
    compute slots for this block's FIRST MoE layer, built one step earlier;
    ``pa_next`` is the PlanArrays slice (leading dim removed) of the NEXT
    block's first MoE layer, or None for the last block.  Each MoE position
    issues the NEXT layer's SparseAllGather immediately BEFORE its own
    grouped-GEMM consumer, so the collectives overlap all the compute in
    between (§4.2).

    premat_c: (c, M, K, chunk_len) STEP-HOISTED compute slots for this
    block's MoE layers (``moe_core.materialize_stack`` built all L layers'
    slots once, before the gradient-accumulation loop) — each layer
    consumes its slice directly and NO materialization collectives are
    issued anywhere in the forward.  Mutually exclusive with prefetch's
    gather issuing.

    pipe / pa_prev0 / tail: the explicit BACKWARD re-gather pipeline
    (gather mode with ``cfg.moe.bwd_prefetch``): ``pipe`` is the
    chunk-shaped channel whose cotangent transports each layer's
    re-gathered slots backward; ``pa_prev0`` is the plan slice of the MoE
    layer preceding this block's first (the backward prefetch target at
    the block boundary); ``tail`` marks the LAST superblock, whose final
    MoE layer self-gathers at the head of the backward (warm start).

    With prefetch or premat_c the return is
    ``(x, ys, chunks_out, pipe_out)``.

    seg_remat: checkpoint the attention/mamba and dense-FFN SEGMENTS
    individually (rematerialize="gather": a block-level ``jax.checkpoint``
    would store the prefetched chunks as an input per scan step — the MoE
    consume stays outside any checkpoint because its custom VJP remats
    the layer interior itself)."""
    moe_pos = _moe_positions(cfg) if cfg.moe.enabled else ()
    aux_list = []
    cache = {}
    mi = 0
    cur_chunks = prefetch[0] if prefetch is not None else None
    for j, kind in enumerate(cfg.layer_pattern):
        p = params_sb[f"l{j}"]

        def mix_seg(p_, x_, enc_out_):
            h = ly.apply_norm(p_["ln1"], x_, cfg.norm)
            c = None
            if kind == "mamba":
                y = mb.mamba_forward(p_["mamba"], cfg, h,
                                     return_state=collect_cache)
                if collect_cache:
                    y, c = y
                x2 = x_ + y
            else:
                with jax.named_scope("attention"):
                    y = attn.attention(p_["attn"], cfg, h, positions,
                                       kind=kind, causal=causal,
                                       use_pallas=rt.use_pallas,
                                       return_kv=collect_cache,
                                       mesh=rt.mesh, rules=rt.rules)
                    if collect_cache:
                        y, c = y
                    x2 = x_ + y
                    if enc_out_ is not None:
                        hx = ly.apply_norm(p_["lnx"], x2, cfg.norm)
                        x2 = x2 + attn.attention(p_["xattn"], cfg, hx,
                                                 positions, causal=False,
                                                 xa=enc_out_)
            return x2, c

        if seg_remat:
            mix_seg = jax.checkpoint(mix_seg)
        x, c = mix_seg(p, x, enc_out)
        if collect_cache and c is not None:
            cache[f"l{j}"] = c
        x = rt.constrain(x, ("batch", None, None))
        if j in moe_pos:
            routers, pa_c, buf = moe_xs
            pa_j = jax.tree.map(lambda a: a[mi], pa_c)
            if premat_c is not None:
                # step-hoisted slots: slice, don't gather
                cur_chunks = premat_c[mi]
            nxt = None
            if prefetch is not None and premat_c is None:
                if mi + 1 < len(moe_pos):
                    pa_n = jax.tree.map(lambda a: a[mi + 1], pa_c)
                else:
                    pa_n = prefetch[1]
                if pa_n is not None:
                    # the pipeline: issue layer l+1's SparseAllGather HERE,
                    # before layer l's consumer below
                    nxt = moe_core.materialize_layer(
                        cfg, rt.moe, buf, pa_n, dtype=jnp.dtype(cfg.dtype))
                    if cfg.moe.rematerialize == "gather":
                        # the regather VJP computes the buffer grad by
                        # replaying the gather in the backward; detaching
                        # the prefetch at its producer keeps the carried
                        # chunks out of the differentiated scan state (no
                        # dead cotangent carry, no transposed producer)
                        nxt = jax.lax.stop_gradient(nxt)
            pa_prev = None
            if pipe is not None:
                pa_prev = (jax.tree.map(lambda a: a[mi - 1], pa_c)
                           if mi > 0 else pa_prev0)
            h = ly.apply_norm(p["ln2"], x, cfg.norm)
            y, aux, pipe_out = _moe_ffn(
                cfg, rt, h, routers[mi], buf, pa_j, premat=cur_chunks,
                pipe=pipe, pa_prev=pa_prev,
                warm_start=tail and mi == len(moe_pos) - 1)
            if pipe is not None:
                pipe = pipe_out
            cur_chunks = nxt
            x = x + y
            aux_list.append(aux)
            mi += 1
        elif kind != "mamba":
            def ffn_seg(p_, x_):
                h = ly.apply_norm(p_["ln2"], x_, cfg.norm)
                return x_ + ly.apply_mlp(p_["mlp"], h, cfg.act)
            if seg_remat:
                ffn_seg = jax.checkpoint(ffn_seg)
            x = ffn_seg(p, x)
        x = rt.constrain(x, ("batch", None, None))
    aux_acc = (jax.tree.map(lambda *xs: jnp.stack(xs), *aux_list)
               if aux_list else None)
    out_ys = (aux_acc, cache) if collect_cache else aux_acc
    if prefetch is not None or premat_c is not None:
        return x, out_ys, (None if premat_c is not None else cur_chunks), \
            pipe
    return x, out_ys


def _reshape_moe_xs(cfg: ModelConfig, routers, pa: PlanArrays):
    """(L_moe, ...) -> (n_sb, c, ...) for scanning."""
    n_sb = cfg.num_superblocks
    c = moe_core.num_moe_layers(cfg) // n_sb
    r = routers.reshape(n_sb, c, *routers.shape[1:])
    pa_r = PlanArrays(*[a.reshape(n_sb, c, *a.shape[1:]) for a in pa])
    return r, pa_r


def _remat_policy(cfg: ModelConfig):
    """Checkpoint policy per ``cfg.moe.rematerialize`` (repro.core.moe).

    save   — keep only the named materialized chunks; the block re-runs
             everything else in the backward.
    block  — recompute the whole superblock; pipeline forced off.
    gather — no BLOCK-level checkpoint at all (``jax.checkpoint`` always
             stores its inputs, which would pin the pipeline's carried
             chunks per scan step): the pipelined path checkpoints the
             attention/MLP SEGMENTS inside ``_superblock`` instead, and
             the consume custom VJP remats the MoE layer interior and
             re-gathers the chunks itself.
    """
    cp = jax.checkpoint_policies
    mode = cfg.moe.rematerialize if cfg.moe.enabled else "save"
    if mode == "block":
        return cp.nothing_saveable
    return cp.save_only_these_names("moe_materialized")


def _use_pipeline(cfg: ModelConfig, rt: Runtime) -> bool:
    """Cross-layer materialization prefetch: needs a mesh (the serial
    single-device oracle never materializes) and is forced off under
    rematerialize="block" (the carried chunks would become scan residuals,
    defeating nothing_saveable)."""
    return (cfg.moe.enabled and cfg.moe.pipeline
            and rt.moe.mesh is not None
            and cfg.moe.rematerialize != "block")


def _use_bwd_pipe(cfg: ModelConfig, rt: Runtime) -> bool:
    """Explicit backward re-gather pipeline: gather mode + bwd_prefetch
    (the pipe channel only exists where the regather VJP consumes it)."""
    return (cfg.moe.enabled and cfg.moe.rematerialize == "gather"
            and cfg.moe.bwd_prefetch and rt.moe.mesh is not None)


def _pipelined_blocks(cfg: ModelConfig, rt: Runtime, params, x, positions,
                      moe_xs, enc_out, causal: bool, collect_cache: bool,
                      premat=None):
    """Superblock stack with the one-layer-ahead SparseAllGather pipeline.

    A warm-up ``materialize_layer`` builds MoE layer 0's compute slots
    before the scan; the scan then carries ``(hidden, prefetched_chunks)``
    — each step consumes its first MoE layer's prefetched slots and issues
    the next block's first-layer SparseAllGather (within-block layers
    prefetch inside ``_superblock``).  The LAST superblock runs outside
    the scan so no dangling prefetch is issued: exactly ONE SparseAllGather
    per MoE layer per step, at the price of the block body appearing twice
    in the HLO.  The dry-run's depth extrapolation stays exact — the
    marginal block is the scan body.  Peak slot memory is two layers'
    (M, K, chunk_len) chunks instead of one.

    premat: optional STEP-HOISTED (L_moe, M, K, chunk_len) compute slots
    (``moe_core.materialize_stack``) — every layer consumes its slice and
    the forward issues NO materialization collectives at all (the train
    step built them once, before the gradient-accumulation loop).

    In gather mode with ``cfg.moe.bwd_prefetch`` the blocks additionally
    thread the backward pipe channel: a chunk-shaped zeros value chained
    through every MoE consume whose COTANGENT transports each layer's
    backward re-gather one layer ahead of its dgrad/wgrad consumer (see
    ``moe_core.moe_layer_regather_pipelined``).  The last block runs
    outside the scan, so its final MoE layer statically knows it heads
    the backward and self-gathers (warm start).
    """
    routers_r, pa_r, buf = moe_xs
    n_sb = cfg.num_superblocks
    policy = _remat_policy(cfg)
    dt = jnp.dtype(cfg.dtype)
    gather = cfg.moe.rematerialize == "gather"

    premat_r = None
    if premat is not None:
        c = moe_core.num_moe_layers(cfg) // n_sb
        premat_r = premat.reshape(n_sb, c, *premat.shape[1:])
        ch = None
    else:
        ch = moe_core.materialize_layer(
            cfg, rt.moe, buf, jax.tree.map(lambda a: a[0, 0], pa_r),
            dtype=dt)
        if gather:
            ch = jax.lax.stop_gradient(ch)   # see _superblock: the regather
            # VJP owns the buffer grad; the prefetch chain stays
            # undifferentiated

    pipe = None
    pa_prev_r = None
    if _use_bwd_pipe(cfg, rt):
        shape = premat.shape[1:] if premat is not None else ch.shape
        pipe = jnp.zeros(shape, dt)
        # plan slice of the MoE layer PRECEDING each block's first: block s
        # gets block s-1's last layer; block 0 gets its own first layer
        # (its emitted backward prefetch heads the chain — dead, DCE'd)
        pa_prev_r = jax.tree.map(
            lambda a: jnp.concatenate([a[0:1, 0], a[:-1, -1]], axis=0),
            pa_r)

    def run_block(x_, ch_, pipe_, params_sb, routers_c, pa_c, pa_nx,
                  premat_c, pa_p0, tail):
        def blk(params_sb_, x2, ch2, pipe2, routers2, pa2, pa_nx2,
                premat2, pa_p2, buf2, enc2):
            return _superblock(cfg, rt, params_sb_, x2, positions,
                               (routers2, pa2, buf2), enc2, causal,
                               collect_cache,
                               prefetch=(None if premat2 is not None
                                         else (ch2, pa_nx2)),
                               seg_remat=cfg.remat and gather,
                               premat_c=premat2, pipe=pipe2,
                               pa_prev0=pa_p2, tail=tail)
        if cfg.remat and not gather:
            # gather mode must NOT checkpoint the whole block: checkpoint
            # stores its inputs, which would pin the carried (M, K, chunk)
            # prefetch per scan step.  _superblock checkpoints the
            # attention/FFN segments instead (seg_remat above).
            blk = jax.checkpoint(blk, policy=policy)
        return blk(params_sb, x_, ch_, pipe_, routers_c, pa_c, pa_nx,
                   premat_c, pa_p0, buf, enc_out)

    def slice_s(s):
        return (jax.tree.map(lambda a: a[s], params["blocks"]),
                routers_r[s], jax.tree.map(lambda a: a[s], pa_r),
                None if premat_r is None else premat_r[s],
                None if pa_prev_r is None else jax.tree.map(
                    lambda a: a[s], pa_prev_r))

    if rt.unroll:
        ys_list = []
        for s in range(n_sb):
            params_sb, routers_c, pa_c, premat_c, pa_p0 = slice_s(s)
            pa_nx = (jax.tree.map(lambda a: a[s + 1, 0], pa_r)
                     if s + 1 < n_sb and premat_r is None else None)
            x, ys_s, ch, pipe = run_block(x, ch, pipe, params_sb,
                                          routers_c, pa_c, pa_nx,
                                          premat_c, pa_p0,
                                          tail=s == n_sb - 1)
            ys_list.append(ys_s)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *ys_list)

    ys_head = None
    if n_sb > 1:
        head = lambda a: a[:-1]
        xs = (jax.tree.map(head, params["blocks"]),
              (routers_r[:-1], jax.tree.map(head, pa_r),
               (None if premat_r is not None
                else jax.tree.map(lambda a: a[1:, 0], pa_r)),
               None if premat_r is None else premat_r[:-1],
               None if pa_prev_r is None else jax.tree.map(head,
                                                           pa_prev_r)))

        def body(carry, xs_s):
            x_c, ch_c, pipe_c = carry
            params_sb, (routers_c, pa_c, pa_nx, premat_c, pa_p0) = xs_s
            x2, ys_s, ch2, pipe2 = run_block(x_c, ch_c, pipe_c, params_sb,
                                             routers_c, pa_c, pa_nx,
                                             premat_c, pa_p0, tail=False)
            return (x2, ch2, pipe2), ys_s

        (x, ch, pipe), ys_head = jax.lax.scan(body, (x, ch, pipe), xs)
    params_sb, routers_c, pa_c, premat_c, pa_p0 = slice_s(-1)
    x, ys_last, _, _ = run_block(x, ch, pipe, params_sb, routers_c, pa_c,
                                 None, premat_c, pa_p0, tail=True)
    if ys_head is None:
        return x, jax.tree.map(lambda a: a[None], ys_last)
    return x, jax.tree.map(lambda h, t: jnp.concatenate([h, t[None]], 0),
                           ys_head, ys_last)


def forward(cfg: ModelConfig, rt: Runtime, params, tokens=None, *,
            embeds=None, positions=None, pa: Optional[PlanArrays] = None,
            encoder_input=None, causal: bool = True,
            collect_cache: bool = False, return_hidden: bool = False,
            premat=None):
    """Returns (logits, aux_tree) — or (logits, aux, cache) when
    ``collect_cache`` (prefill: the cache holds rotated K/V per layer, SSM
    states, and cross-attention K/V for enc-dec models).

    tokens: (B, S) int32 — or embeds: (B, S, D) for frontend-stub archs.
    encoder_input: (B, S_enc, D) frame/patch embeddings (whisper).
    pa: stacked PlanArrays (L_moe leading dim) for MoE archs.
    premat: optional stacked (L_moe, M, K, chunk_len) pre-materialized
    compute slots (``moe_core.materialize_stack``) — the train step builds
    every layer's slots ONCE (before its gradient-accumulation loop) and
    each MoE layer consumes its slice, so the forward issues no
    materialization collectives.  Requires the pipeline path (a mesh,
    ``cfg.moe.pipeline``, rematerialize != "block").
    """
    dt = jnp.dtype(cfg.dtype)
    if embeds is None:
        with jax.named_scope("lm_head"):
            x = ly.embed(params["embed"], tokens, dt)
            x = x * math.sqrt(cfg.d_model)
    else:
        x = embeds.astype(dt)
    b, s = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[..., None], (b, s, 3))
    x = rt.constrain(x, ("batch", None, None))

    enc_out = None
    if cfg.is_encoder_decoder:
        assert encoder_input is not None
        enc_out = _encode(cfg, rt, params["encoder"], encoder_input.astype(dt))

    moe_xs = None
    if cfg.moe.enabled:
        assert pa is not None, "MoE arch needs PlanArrays"
        routers_r, pa_r = _reshape_moe_xs(cfg, params["router"], pa)
        moe_xs = (routers_r, pa_r, params["moe_buffer"])

    def body(carry, xs):
        params_sb = xs[0]
        m_xs = None
        if moe_xs is not None:
            m_xs = (xs[1][0], xs[1][1], moe_xs[2])
        def blk(params_sb_, x_, positions_, m_xs_, enc_out_):
            return _superblock(cfg, rt, params_sb_, x_, positions_, m_xs_,
                               enc_out_, causal, collect_cache)
        if cfg.remat:
            blk = jax.checkpoint(blk, policy=_remat_policy(cfg))
        x, ys = blk(params_sb, carry, positions, m_xs, enc_out)
        return x, ys

    if premat is not None:
        assert moe_xs is not None and _use_pipeline(cfg, rt), (
            "forward(premat=...) needs the pipelined MoE path (a mesh, "
            "moe.pipeline=True, rematerialize != 'block')")
    if moe_xs is not None and _use_pipeline(cfg, rt):
        x, ys = _pipelined_blocks(cfg, rt, params, x, positions, moe_xs,
                                  enc_out, causal, collect_cache,
                                  premat=premat)
    else:
        xs = (params["blocks"],)
        if moe_xs is not None:
            xs = (params["blocks"], (moe_xs[0], moe_xs[1]))
        x, ys = _scan(rt, body, x, xs)
    with jax.named_scope("lm_head"):
        x = ly.apply_norm(params["final_norm"], x, cfg.norm)
    if return_hidden:
        # loss is computed chunked from the hidden states (train path):
        # materializing full (B, S, V) f32 logits costs tens of GB/device
        # for 150k-vocab models (seen in the qwen-110b dry-run).
        return x, ys
    with jax.named_scope("lm_head"):
        logits = ly.unembed(params["embed"], x, cfg.final_logit_softcap)
    if collect_cache:
        aux_stack, cache = ys if ys is not None else (None, {})
        if cfg.is_encoder_decoder:
            cache = dict(cache)
            cache["xk"], cache["xv"] = precompute_cross_kv(cfg, params,
                                                           enc_out)
        return logits, aux_stack, cache
    return logits, ys


def _encode(cfg: ModelConfig, rt: Runtime, enc_params, enc_in):
    b, s = enc_in.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    enc_cfg = cfg  # same dims

    def body(carry, params_sb):
        def blk(params_sb_, x_):
            return _superblock(enc_cfg, rt, params_sb_, x_, positions,
                               None, None, False)
        if cfg.remat:
            blk = jax.checkpoint(blk)
        x, _ = blk(params_sb, carry)
        return x, None

    x, _ = _scan(rt, body, enc_in, enc_params["blocks"])
    return ly.apply_norm(enc_params["final_norm"], x, cfg.norm)


# ---------------------------------------------------------------------------
# Decode (one token against a cache)
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               abstract: bool = False, mesh_batch: int = 1):
    """Stacked cache pytree with leading num_superblocks axis per sublayer."""
    dt = jnp.dtype(cfg.dtype)
    n_sb = cfg.num_superblocks

    def one(kind):
        if kind == "mamba":
            c = (mb.abstract_mamba_cache(cfg, batch, dt) if abstract
                 else mb.init_mamba_cache(cfg, batch, dt))
        else:
            c = (attn.abstract_kv_cache(cfg, batch, max_len, dt) if abstract
                 else attn.init_kv_cache(cfg, batch, max_len, dt))
        return c

    def stack(c):
        if abstract:
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                (n_sb,) + a.shape, a.dtype), c)
        return jax.tree.map(lambda a: jnp.broadcast_to(
            a[None], (n_sb,) + a.shape).copy(), c)

    cache = {f"l{j}": stack(one(kind))
             for j, kind in enumerate(cfg.layer_pattern)}
    if cfg.is_encoder_decoder:
        # cached encoder output + per-layer cross K/V
        nkv, hd = cfg.num_kv_heads, cfg.head_dim
        se = cfg.encoder_seq_len
        shp = (n_sb, batch, se, nkv, hd)
        if abstract:
            cache["xk"] = jax.ShapeDtypeStruct(shp, dt)
            cache["xv"] = jax.ShapeDtypeStruct(shp, dt)
        else:
            cache["xk"] = jnp.zeros(shp, dt)
            cache["xv"] = jnp.zeros(shp, dt)
    return cache


def init_paged_cache(cfg: ModelConfig, num_slots: int, num_rows: int):
    """Block-paged decode cache: attention sublayers share a flat pool of
    ``num_rows`` token rows (``attn.init_paged_kv_cache``; ownership is
    page-table metadata, see ``repro.serve.kv_pool``), while O(1)-state
    sublayers (mamba, whose state does not grow with sequence length) keep
    one dense state per scheduler SLOT.  Leading ``num_superblocks`` axis
    per sublayer, exactly like :func:`init_cache`.  Encoder-decoder
    caches are not paged (no continuous-batching path for them yet)."""
    assert not cfg.is_encoder_decoder, (
        "paged decode does not support encoder-decoder caches")
    dt = jnp.dtype(cfg.dtype)
    n_sb = cfg.num_superblocks

    def one(kind):
        if kind == "mamba":
            return mb.init_mamba_cache(cfg, num_slots, dt)
        return attn.init_paged_kv_cache(cfg, num_rows, dt)

    def stack(c):
        return jax.tree.map(lambda a: jnp.broadcast_to(
            a[None], (n_sb,) + a.shape).copy(), c)

    return {f"l{j}": stack(one(kind))
            for j, kind in enumerate(cfg.layer_pattern)}


def cache_logical_axes(cfg: ModelConfig, batch: int, mesh_batch: int):
    ax = {}
    for j, kind in enumerate(cfg.layer_pattern):
        if kind == "mamba":
            a = mb.mamba_cache_axes()
        else:
            a = attn.kv_cache_axes(batch, mesh_batch)
        ax[f"l{j}"] = jax.tree.map(lambda t: ("layers",) + t, a,
                                   is_leaf=lambda t: isinstance(t, tuple))
    if cfg.is_encoder_decoder:
        ax["xk"] = ("layers", "batch", None, "kv_heads", None)
        ax["xv"] = ("layers", "batch", None, "kv_heads", None)
    return ax


def decode_step(cfg: ModelConfig, rt: Runtime, params, cache, tokens, pos,
                pa: Optional[PlanArrays] = None, premat=None, *,
                row_idx=None, page_size=None):
    """tokens: (B, 1) int32; pos: scalar — position being written.
    premat: optional stacked (L_moe, M, K, chunk_len) pre-materialized
    compute slots (``moe_core.materialize_chunks``) — each MoE layer then
    skips its SparseAllGather (the plan/buffer are static across decode
    steps).  Returns (logits: (B,1,V), new_cache).

    row_idx: optional (B, max_kv) int32 — switches the attention layers
    to the BLOCK-PAGED cache (``init_paged_cache`` layout; each row maps
    a sequence token to its pool row).  In paged mode ``pos`` must be a
    (B,) int32 vector of per-sequence positions: B independent sequences
    decode one token each at independent lengths (continuous batching —
    see ``repro.serve.scheduler``).  ``page_size`` (a static Python int —
    constant per scheduler, so the jitted paged step compiles once)
    routes the paged attention through the Pallas paged-decode kernel
    (``repro.kernels.paged_attention``; pure-XLA gather without it or
    with ``cfg.paged_attn_kernel=False``).  Everything outside the
    attention cache read/write — MoE premat reuse included — is
    identical, so the paged step obeys the same collective law (zero
    SparseAllGathers with a fresh slot cache; jaxpr-asserted in
    tests/test_serve_batching.py).
    """
    if row_idx is not None:
        assert not cfg.is_encoder_decoder, (
            "paged decode does not support encoder-decoder models")
    dt = jnp.dtype(cfg.dtype)
    x = ly.embed(params["embed"], tokens, dt) * math.sqrt(cfg.d_model)
    x = rt.constrain(x, ("batch", None, None))

    moe_xs = None
    premat_r = None
    if cfg.moe.enabled:
        assert pa is not None
        routers_r, pa_r = _reshape_moe_xs(cfg, params["router"], pa)
        moe_xs = (routers_r, pa_r, params["moe_buffer"])
        if premat is not None:
            n_sb = cfg.num_superblocks
            c = moe_core.num_moe_layers(cfg) // n_sb
            premat_r = premat.reshape(n_sb, c, *premat.shape[1:])

    moe_pos = _moe_positions(cfg) if cfg.moe.enabled else ()

    def body(x, xs):
        premat_c = None
        if moe_xs is not None:
            if premat_r is not None:
                params_sb, cache_sb, (routers_c, pa_c, premat_c) = xs
            else:
                params_sb, cache_sb, (routers_c, pa_c) = xs
        else:
            params_sb, cache_sb = xs
        new_cache = dict(cache_sb)
        mi = 0
        for j, kind in enumerate(cfg.layer_pattern):
            p = params_sb[f"l{j}"]
            h = ly.apply_norm(p["ln1"], x, cfg.norm)
            if kind == "mamba":
                y, nc = mb.mamba_decode_step(p["mamba"], cfg, h,
                                             cache_sb[f"l{j}"])
                x = x + y
                new_cache[f"l{j}"] = nc
            elif row_idx is not None:
                y, nc = attn.decode_attention_paged(p["attn"], cfg, h,
                                                    cache_sb[f"l{j}"], pos,
                                                    row_idx, kind=kind,
                                                    page_size=page_size)
                x = x + y
                new_cache[f"l{j}"] = nc
            else:
                y, nc = attn.decode_attention(p["attn"], cfg, h,
                                              cache_sb[f"l{j}"], pos,
                                              kind=kind)
                x = x + y
                new_cache[f"l{j}"] = nc
                if cfg.is_encoder_decoder:
                    hx = ly.apply_norm(p["lnx"], x, cfg.norm)
                    y = _cross_decode(p["xattn"], cfg, hx,
                                      cache_sb["xk"], cache_sb["xv"])
                    x = x + y
            if j in moe_pos:
                h = ly.apply_norm(p["ln2"], x, cfg.norm)
                pa_j = jax.tree.map(lambda a: a[mi], pa_c)
                y, _, _ = _moe_ffn(cfg, rt, h, routers_c[mi], moe_xs[2],
                                   pa_j, premat=None if premat_c is None
                                   else premat_c[mi])
                x = x + y
                mi += 1
            elif kind != "mamba":
                h = ly.apply_norm(p["ln2"], x, cfg.norm)
                x = x + ly.apply_mlp(p["mlp"], h, cfg.act)
        return x, new_cache

    xs = [params["blocks"],
          {k: v for k, v in cache.items() if k.startswith("l")}]
    if moe_xs is not None:
        xs.append((moe_xs[0], moe_xs[1]) if premat_r is None
                  else (moe_xs[0], moe_xs[1], premat_r))
    if cfg.is_encoder_decoder:
        xs[1] = dict(xs[1], xk=cache["xk"], xv=cache["xv"])
    x, new_cache = _scan(rt, body, x, tuple(xs))
    x = ly.apply_norm(params["final_norm"], x, cfg.norm)
    logits = ly.unembed(params["embed"], x, cfg.final_logit_softcap)
    out_cache = dict(new_cache)
    if cfg.is_encoder_decoder:  # static across steps
        out_cache["xk"], out_cache["xv"] = cache["xk"], cache["xv"]
    return logits, out_cache


def _cross_decode(p, cfg: ModelConfig, x, xk, xv):
    """Cross-attention against precomputed encoder K/V."""
    dt = x.dtype
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    out = attn._sdpa(q, xk, xv, None, cfg.attn_logit_softcap, cfg.head_dim)
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt))


def precompute_cross_kv(cfg: ModelConfig, params, enc_out):
    """Fill the xk/xv cache entries from encoder output (per decoder layer)."""
    def one(p_attn):
        dt = enc_out.dtype
        k = jnp.einsum("bsd,dnh->bsnh", enc_out, p_attn["wk"].astype(dt))
        v = jnp.einsum("bsd,dnh->bsnh", enc_out, p_attn["wv"].astype(dt))
        return k, v
    return jax.vmap(one)(params["blocks"]["l0"]["xattn"])

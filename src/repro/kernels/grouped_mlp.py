"""Pallas TPU kernels: grouped expert FFN, forward AND backward
(MegaBlocks-style, arXiv:2211.15841).

The MoE hot spot: after dispatch, each materialized expert slot holds a
padded group of tokens — ``x: (K, T, D)`` with only some rows valid.  A
dense batched matmul wastes FLOPs on padding; these kernels **skip whole
token tiles that contain no valid row** (the TPU analogue of MegaBlocks'
block-sparse GEMM — no token dropping, no padded compute), in the forward
and in both backward passes.

Validity comes in two interchangeable forms:

* ``group_sizes (K,)`` — the valid rows of slot k are the prefix
  ``[0, group_sizes[k])`` (the classic grouped-GEMM contract);
* ``row_valid (K, T)`` — arbitrary per-row validity.  This is the **fused
  dispatch layout**: the FSSDP dispatch (``core/moe.py``) lands each source
  device's kept tokens in a valid *segment prefix* of its capacity stripe,
  so validity is scattered across the buffer.  Previously the caller
  compacted those segments into one prefix with a ``take_along_axis``
  gather before the kernel and scattered back after it — two full
  ``(K, T, D)`` copies per direction.  With ``row_valid`` the permutation
  disappears entirely: it becomes *metadata*.  A per-tile valid-row count
  (``tile_n``, shape ``(K, T/BT)``) rides the scalar-prefetch operand and
  drives ``pl.when`` tile skipping; a per-row mask rides a tiny
  ``(K, T, 1)`` int32 input (a ``(1, BT, 1)`` block: the TPU lowering
  needs the last two block dims to be multiples of (8, 128) or the whole
  array dim, which a ``(1, BT)`` block over ``(K, T)`` is not).  All
  loads/stores stay block-aligned (a ``BlockSpec`` index map addresses
  whole tiles, so an exact row gather
  cannot be expressed there — tile-granular skipping plus in-tile masking
  is the lowering-friendly equivalent and costs at most one partial tile
  per source segment).

Kernel layout:

* **forward** — grid ``(K, T/BT, F/BF)``, F innermost so the fused
  ``y += act(x@wi [* x@wg]) @ wo`` accumulates into a VMEM f32 scratch
  tile and writes once.  In training mode it also streams out the
  pre-activation hiddens ``h1 = x@wi`` (and ``h2 = x@wg``) as residuals,
  so the backward never re-runs the forward matmuls over padded buffers.
* **dgrad** — same ``(K, T/BT, F/BF)`` tiling and the same tile skipping:
  ``dh = dy@woᵀ``; ``dx += dh1@wiᵀ [+ dh2@wgᵀ]`` accumulates in VMEM f32.
  It additionally writes the per-tile ``dh1``/``dh2`` and the
  post-activation hidden ``h`` (all elementwise from the saved residuals)
  that the wgrad kernel consumes — no recomputation, no extra matmuls.
* **wgrad** — grid ``(K, D/BD, F/BF, T/BT)`` with the token dimension
  innermost as a *reduction*: only valid token tiles are accumulated into
  three VMEM f32 accumulators (``dwi``, ``dwg``, ``dwo``), written once
  per (k, d, f) cell.

Tiles are (128x128)-aligned for the MXU; T, F (and D for the wgrad) are
padded up to tile multiples — padded rows/columns are invalid everywhere,
so they cost no compute.  All accumulation is f32 regardless of the
operand dtype (bf16 in, f32 accumulate, bf16 out).

The public op carries a custom VJP wiring the three kernels together; it
matches ``repro.kernels.ref.grouped_mlp_ref`` under ``jax.grad`` for both
validity forms (padded rows contribute exactly zero to every gradient).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BT = 128   # token tile
BF = 128   # ffn tile
BD = 128   # model-dim tile (wgrad only)


def act_fn(act: str):
    """The kernel's activation — single source of truth shared by the
    Pallas kernels, the custom VJP, and the jnp oracle in ref.py."""
    return jax.nn.silu if act.startswith("silu") else jax.nn.gelu


def _pad_to(a, axis: int, mult: int):
    n = a.shape[axis]
    p = -n % mult
    if p == 0:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, p)
    return jnp.pad(a, pads)


def _tile_counts(mask, bt: int):
    """mask: (K, Tp) int32 with Tp % bt == 0 -> (K * Tp/bt,) valid rows per
    token tile — the scalar-prefetch skip table."""
    k_, tp = mask.shape
    return mask.reshape(k_, tp // bt, bt).sum(-1).reshape(-1).astype(jnp.int32)


def _row_mask(t_, group_sizes, row_valid):
    """Canonical (K, t_) int32 validity from either form (row_valid wins)."""
    if row_valid is not None:
        return row_valid.astype(jnp.int32)
    return (jnp.arange(t_)[None, :]
            < group_sizes[:, None]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------
def _fwd_kernel(tn_ref, x_ref, mask_ref, wi_ref, wg_ref, wo_ref, *rest,
                act: str, has_gate: bool, nt: int, save: bool):
    if save:
        if has_gate:
            y_ref, h1_ref, h2_ref, acc_ref = rest
        else:
            y_ref, h1_ref, acc_ref = rest
    else:
        y_ref, acc_ref = rest
    k = pl.program_id(0)
    t = pl.program_id(1)
    f = pl.program_id(2)
    nf = pl.num_programs(2)
    n = tn_ref[k * nt + t]                # valid rows in this token tile

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n > 0)                       # skip tiles with no valid row
    def _compute():
        m = mask_ref[0] > 0                           # (BT, 1)
        x = jnp.where(m, x_ref[0], 0)                 # (BT, D)
        h1 = jnp.dot(x, wi_ref[0], preferred_element_type=jnp.float32)
        if has_gate:
            h2 = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
            h = act_fn(act)(h1) * h2
        else:
            h = act_fn(act)(h1)
        if save:
            h1_ref[0] = h1.astype(h1_ref.dtype)
            if has_gate:
                h2_ref[0] = h2.astype(h2_ref.dtype)
        acc_ref[...] += jnp.dot(h.astype(x_ref.dtype), wo_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _write():
        m = mask_ref[0] > 0
        y_ref[0] = jnp.where(m, acc_ref[...], 0.0).astype(y_ref.dtype)
        # (n == 0 tiles write zeros: acc was only ever initialized)


def _forward(x, wi, wg, wo, mask, *, act: str, interpret: bool,
             save_residuals: bool):
    """mask: (K, t_) int32.  Returns y, or (y, h1[, h2]) with the padded
    (K, Tp, Fp) pre-activation residuals when ``save_residuals``."""
    k_, t_, d = x.shape
    f_ = wi.shape[-1]
    has_gate = wg is not None
    # Pad T and F up to tile multiples rather than shrinking tiles (group
    # buffers are (M·capacity) rows — often odd/prime; a shrunken tile
    # explodes the grid and loses MXU alignment).  Padded token rows are
    # invalid (mask 0) so the kernel never computes them; padded F columns
    # produce act(0)[*0] @ 0 = 0 and are sliced off below.
    bt = min(BT, t_)
    bf = min(BF, f_)
    x = _pad_to(x, 1, bt)
    mask = _pad_to(mask, 1, bt)
    wi = _pad_to(wi, 2, bf)
    if has_gate:
        wg = _pad_to(wg, 2, bf)
    else:
        wg = wi                                      # placeholder operand
    wo = _pad_to(wo, 1, bf)
    tp, fp = x.shape[1], wi.shape[2]
    nt, nf = tp // bt, fp // bf
    tile_n = _tile_counts(mask, bt)

    grid = (k_, nt, nf)
    kern = functools.partial(_fwd_kernel, act=act, has_gate=has_gate,
                             nt=nt, save=save_residuals)
    out_shape = [jax.ShapeDtypeStruct((k_, tp, d), x.dtype)]
    out_specs = [pl.BlockSpec((1, bt, d), lambda k, t, f, tn: (k, t, 0))]
    if save_residuals:
        out_shape.append(jax.ShapeDtypeStruct((k_, tp, fp), x.dtype))
        out_specs.append(
            pl.BlockSpec((1, bt, bf), lambda k, t, f, tn: (k, t, f)))
        if has_gate:
            out_shape.append(jax.ShapeDtypeStruct((k_, tp, fp), x.dtype))
            out_specs.append(
                pl.BlockSpec((1, bt, bf), lambda k, t, f, tn: (k, t, f)))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bt, d), lambda k, t, f, tn: (k, t, 0)),
                pl.BlockSpec((1, bt, 1), lambda k, t, f, tn: (k, t, 0)),
                pl.BlockSpec((1, d, bf), lambda k, t, f, tn: (k, 0, f)),
                pl.BlockSpec((1, d, bf), lambda k, t, f, tn: (k, 0, f)),
                pl.BlockSpec((1, bf, d), lambda k, t, f, tn: (k, f, 0)),
            ],
            out_specs=tuple(out_specs),
            scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        ),
        out_shape=tuple(out_shape),
        interpret=interpret,
        name="grouped_mlp_fwd",
    )(tile_n, x, mask[..., None], wi, wg, wo)
    y = out[0][:, :t_] if tp != t_ else out[0]
    if not save_residuals:
        return y
    return (y,) + tuple(out[1:])


# ---------------------------------------------------------------------------
# Backward: dgrad kernel (dx + the elementwise tiles wgrad consumes)
# ---------------------------------------------------------------------------
def _dgrad_kernel(tn_ref, dy_ref, mask_ref, h1_ref, h2_ref, wi_ref, wg_ref,
                  wo_ref, *rest, act: str, has_gate: bool, nt: int):
    if has_gate:
        dx_ref, dh1_ref, dh2_ref, h_ref, acc_ref = rest
    else:
        dx_ref, dh1_ref, h_ref, acc_ref = rest
    k = pl.program_id(0)
    t = pl.program_id(1)
    f = pl.program_id(2)
    nf = pl.num_programs(2)
    n = tn_ref[k * nt + t]

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n > 0)
    def _compute():
        m = mask_ref[0] > 0
        g = jnp.where(m, dy_ref[0], 0).astype(jnp.float32)    # (BT, D)
        # dh = g @ wo^T : contract the model dim of both operands
        dh = jax.lax.dot_general(
            g, wo_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (BT, BF)
        h1 = h1_ref[0].astype(jnp.float32)
        a, avjp = jax.vjp(act_fn(act), h1)
        if has_gate:
            h2 = h2_ref[0].astype(jnp.float32)
            dh1 = avjp(dh * h2)[0]
            dh2 = dh * a
            h = a * h2
        else:
            dh1 = avjp(dh)[0]
            h = a
        dh1_ref[0] = dh1.astype(dh1_ref.dtype)
        h_ref[0] = h.astype(h_ref.dtype)
        # dx += dh1 @ wi^T [+ dh2 @ wg^T] : contract the F dim
        dx = jax.lax.dot_general(
            dh1, wi_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if has_gate:
            dh2_ref[0] = dh2.astype(dh2_ref.dtype)
            dx += jax.lax.dot_general(
                dh2, wg_ref[0].astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc_ref[...] += dx

    @pl.when(n == 0)
    def _zero_tiles():
        # skipped tiles: the wgrad kernel skips them too, but keep the
        # streamed tiles defined (cheap VPU writes, no matmul)
        dh1_ref[0] = jnp.zeros_like(dh1_ref[0])
        h_ref[0] = jnp.zeros_like(h_ref[0])
        if has_gate:
            dh2_ref[0] = jnp.zeros_like(dh2_ref[0])

    @pl.when(f == nf - 1)
    def _write():
        m = mask_ref[0] > 0
        dx_ref[0] = jnp.where(m, acc_ref[...], 0.0).astype(dx_ref.dtype)


def _dgrad(dy, mask, h1, h2, wi, wg, wo, tile_n, *, act: str,
           interpret: bool, bt: int, bf: int):
    """dy: (K, Tp, D) padded cotangent; h1/h2: (K, Tp, Fp) residuals.
    Returns (dx, dh1[, dh2], h) — all padded; dh*/h in dy.dtype."""
    k_, tp, d = dy.shape
    fp = h1.shape[2]
    has_gate = wg is not None
    nt, nf = tp // bt, fp // bf
    if not has_gate:
        wg, h2 = wi, h1                              # placeholder operands
    grid = (k_, nt, nf)
    kern = functools.partial(_dgrad_kernel, act=act, has_gate=has_gate,
                             nt=nt)
    n_res = 3 if has_gate else 2                     # dh1[, dh2], h
    out_shape = [jax.ShapeDtypeStruct((k_, tp, d), dy.dtype)] + \
        [jax.ShapeDtypeStruct((k_, tp, fp), dy.dtype)] * n_res
    res_spec = pl.BlockSpec((1, bt, bf), lambda k, t, f, tn: (k, t, f))
    out_specs = [pl.BlockSpec((1, bt, d), lambda k, t, f, tn: (k, t, 0))] + \
        [res_spec] * n_res
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bt, d), lambda k, t, f, tn: (k, t, 0)),
                pl.BlockSpec((1, bt, 1), lambda k, t, f, tn: (k, t, 0)),
                res_spec,                                       # h1
                res_spec,                                       # h2
                pl.BlockSpec((1, d, bf), lambda k, t, f, tn: (k, 0, f)),
                pl.BlockSpec((1, d, bf), lambda k, t, f, tn: (k, 0, f)),
                pl.BlockSpec((1, bf, d), lambda k, t, f, tn: (k, f, 0)),
            ],
            out_specs=tuple(out_specs),
            scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        ),
        out_shape=tuple(out_shape),
        interpret=interpret,
        name="grouped_mlp_dgrad",
    )(tile_n, dy, mask[..., None], h1, h2, wi, wg, wo)


# ---------------------------------------------------------------------------
# Backward: wgrad kernel (dwi/dwg/dwo via token-tile reduction)
# ---------------------------------------------------------------------------
def _wgrad_kernel(tn_ref, x_ref, dy_ref, mask_ref, dh1_ref, dh2_ref, h_ref,
                  *rest, has_gate: bool, nt: int):
    if has_gate:
        dwi_ref, dwg_ref, dwo_ref, acc_i, acc_g, acc_o = rest
    else:
        dwi_ref, dwo_ref, acc_i, acc_o = rest
        acc_g = None
    k = pl.program_id(0)
    t = pl.program_id(3)                  # token tiles: innermost reduction
    n = tn_ref[k * nt + t]

    @pl.when(t == 0)
    def _init():
        acc_i[...] = jnp.zeros_like(acc_i)
        acc_o[...] = jnp.zeros_like(acc_o)
        if has_gate:
            acc_g[...] = jnp.zeros_like(acc_g)

    @pl.when(n > 0)                       # reduce only valid token tiles
    def _accum():
        m = mask_ref[0] > 0
        xm = jnp.where(m, x_ref[0], 0)                        # (BT, BD)
        g = jnp.where(m, dy_ref[0], 0)                        # (BT, BD)
        cn = (((0,), (0,)), ((), ()))     # contract the token dim
        acc_i[...] += jax.lax.dot_general(
            xm, dh1_ref[0], dimension_numbers=cn,
            preferred_element_type=jnp.float32)               # (BD, BF)
        if has_gate:
            acc_g[...] += jax.lax.dot_general(
                xm, dh2_ref[0], dimension_numbers=cn,
                preferred_element_type=jnp.float32)
        acc_o[...] += jax.lax.dot_general(
            h_ref[0], g, dimension_numbers=cn,
            preferred_element_type=jnp.float32)               # (BF, BD)

    @pl.when(t == nt - 1)
    def _write():
        dwi_ref[0] = acc_i[...].astype(dwi_ref.dtype)
        dwo_ref[0] = acc_o[...].astype(dwo_ref.dtype)
        if has_gate:
            dwg_ref[0] = acc_g[...].astype(dwg_ref.dtype)


def _wgrad(x, dy, mask, dh1, dh2, h, tile_n, wdtype, *, interpret: bool,
           bt: int, bf: int):
    """x/dy: (K, Tp, Dp); dh1/dh2/h: (K, Tp, Fp).
    Returns (dwi, dwg | None, dwo) padded, in ``wdtype``."""
    k_, tp, dp = x.shape
    fp = dh1.shape[2]
    has_gate = dh2 is not None
    bd = min(BD, dp)
    nt, nf, nd = tp // bt, fp // bf, dp // bd
    if not has_gate:
        dh2 = dh1                                    # placeholder operand
    grid = (k_, nd, nf, nt)
    kern = functools.partial(_wgrad_kernel, has_gate=has_gate, nt=nt)
    dwi_spec = pl.BlockSpec((1, bd, bf), lambda k, d, f, t, tn: (k, d, f))
    dwo_spec = pl.BlockSpec((1, bf, bd), lambda k, d, f, t, tn: (k, f, d))
    out_shape = [jax.ShapeDtypeStruct((k_, dp, fp), wdtype)]
    out_specs = [dwi_spec]
    if has_gate:
        out_shape.append(jax.ShapeDtypeStruct((k_, dp, fp), wdtype))
        out_specs.append(dwi_spec)
    out_shape.append(jax.ShapeDtypeStruct((k_, fp, dp), wdtype))
    out_specs.append(dwo_spec)
    scratch = [pltpu.VMEM((bd, bf), jnp.float32)]
    if has_gate:
        scratch.append(pltpu.VMEM((bd, bf), jnp.float32))
    scratch.append(pltpu.VMEM((bf, bd), jnp.float32))
    res_spec = pl.BlockSpec((1, bt, bf), lambda k, d, f, t, tn: (k, t, f))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bt, bd), lambda k, d, f, t, tn: (k, t, d)),
                pl.BlockSpec((1, bt, bd), lambda k, d, f, t, tn: (k, t, d)),
                pl.BlockSpec((1, bt, 1), lambda k, d, f, t, tn: (k, t, 0)),
                res_spec,                                       # dh1
                res_spec,                                       # dh2
                res_spec,                                       # h
            ],
            out_specs=tuple(out_specs),
            scratch_shapes=scratch,
        ),
        out_shape=tuple(out_shape),
        interpret=interpret,
        name="grouped_mlp_wgrad",
    )(tile_n, x, dy, mask[..., None], dh1, dh2, h)
    if has_gate:
        return out[0], out[1], out[2]
    return out[0], None, out[1]


def _bwd_pallas(x, wi, wg, wo, mask, h1, h2, dy, *, act: str,
                interpret: bool):
    """Wire dgrad + wgrad over the padded buffers; slice back to the
    caller's shapes."""
    k_, t_, d = x.shape
    f_ = wi.shape[-1]
    bt, bf = min(BT, t_), min(BF, f_)
    tp, fp = h1.shape[1], h1.shape[2]
    maskp = _pad_to(mask, 1, bt)
    tile_n = _tile_counts(maskp, bt)
    dyp = _pad_to(dy, 1, bt)
    wip = _pad_to(wi, 2, bf)
    wgp = None if wg is None else _pad_to(wg, 2, bf)
    wop = _pad_to(wo, 1, bf)

    out = _dgrad(dyp, maskp, h1, h2, wip, wgp, wop, tile_n,
                 act=act, interpret=interpret, bt=bt, bf=bf)
    if wg is not None:
        dx, dh1, dh2, h = out
    else:
        dx, dh1, h = out
        dh2 = None

    # wgrad blocks the model dim too — pad D if needed
    bd = min(BD, d)
    xw = _pad_to(_pad_to(x, 1, bt), 2, bd)
    dyw = _pad_to(dyp, 2, bd)
    dwi, dwg, dwo = _wgrad(xw, dyw, maskp, dh1, dh2, h, tile_n, wi.dtype,
                           interpret=interpret, bt=bt, bf=bf)
    dx = dx[:, :t_]
    dwi = dwi[:, :d, :f_]
    dwo = dwo[:, :f_, :d]
    if wg is not None:
        dwg = dwg[:, :d, :f_]
    return (dx.astype(x.dtype), dwi.astype(wi.dtype),
            None if wg is None else dwg.astype(wg.dtype),
            dwo.astype(wo.dtype))


# ---------------------------------------------------------------------------
# custom_vjp assembly
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _make_grouped_mlp(act: str, has_gate: bool, interpret: bool):
    """custom_vjp wrapper per static config: Pallas forward saves the
    pre-activation residuals; Pallas dgrad/wgrad kernels run the backward
    with the same tile skipping.  ``mask`` is the (K, T) int32 validity
    (non-differentiable)."""
    fwd = functools.partial(_forward, act=act, interpret=interpret)
    bwd = functools.partial(_bwd_pallas, act=act, interpret=interpret)
    if has_gate:
        @jax.custom_vjp
        def f(x, wi, wg, wo, mask):
            return fwd(x, wi, wg, wo, mask, save_residuals=False)

        def f_fwd(x, wi, wg, wo, mask):
            y, h1, h2 = fwd(x, wi, wg, wo, mask, save_residuals=True)
            # F-padded weights are re-derived in the backward; saving the
            # unpadded operands keeps residual memory at h1/h2 only.
            return y, (x, wi, wg, wo, mask, h1, h2)

        def f_bwd(res, dy):
            x, wi, wg, wo, mask, h1, h2 = res
            dx, dwi, dwg, dwo = bwd(x, wi, wg, wo, mask, h1, h2, dy)
            return dx, dwi, dwg, dwo, None
    else:
        @jax.custom_vjp
        def f(x, wi, wo, mask):
            return fwd(x, wi, None, wo, mask, save_residuals=False)

        def f_fwd(x, wi, wo, mask):
            y, h1 = fwd(x, wi, None, wo, mask, save_residuals=True)
            return y, (x, wi, wo, mask, h1)

        def f_bwd(res, dy):
            x, wi, wo, mask, h1 = res
            dx, dwi, _, dwo = bwd(x, wi, None, wo, mask, h1, None, dy)
            return dx, dwi, dwo, None
    f.defvjp(f_fwd, f_bwd)
    return f


def grouped_mlp(x, wi, wg, wo, group_sizes=None, *, row_valid=None,
                act: str = "silu_glu", interpret: bool = False):
    """x: (K,T,D); wi/wg: (K,D,F); wo: (K,F,D).

    Validity (either form; ``row_valid`` wins when both are given):
      group_sizes: (K,) int32 — valid rows are the prefix [0, size_k);
      row_valid:   (K,T) bool/int — arbitrary per-row validity (the fused
                   dispatch layout — no compaction copy needed).

    Returns (K,T,D).  Invalid rows are zero; token tiles with no valid row
    are skipped entirely — forward, dgrad and wgrad — and the custom VJP
    keeps invalid rows at exactly zero gradient.
    """
    k_, t_, _ = x.shape
    if row_valid is None and group_sizes is None:
        group_sizes = jnp.full((k_,), t_, jnp.int32)
    mask = _row_mask(t_, group_sizes, row_valid)
    fn = _make_grouped_mlp(act, wg is not None, interpret)
    if wg is not None:
        return fn(x, wi, wg, wo, mask)
    return fn(x, wi, wo, mask)

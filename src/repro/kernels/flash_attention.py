"""Pallas TPU kernel: blockwise flash attention (online softmax).

Used for the attention layers whose forward latency hides the FSSDP
SparseAllGather (paper Fig. 1c) — the faster the attention, the tighter the
overlap budget `t`, so this kernel matters to the system even though the
paper's contribution is the MoE side.

Grid (B, N/HB, Sq/BQ, Skv/BK), KV innermost; m/l/acc live in VMEM scratch.
``tile_sizes`` picks BQ, BK and the heads per program HB from the shapes:
blocks of up to ``MAX_BLOCK`` rows and several heads a program, so that
the fixed cost of a program is paid a few hundred times a call, not tens
of thousands.  Each q block needs one band of KV blocks (``kv_band``:
those that reach the causal diagonal and are not wholly older than the
sliding window).  Tiles outside it run no compute, and the K/V index maps
clamp to the band, so the pipeline fetches no block it does not use.  Only
tiles that straddle the diagonal or the window edge build the mask.

A pallas_call has no reverse-mode rule, so ``flash_attention_trainable``
wraps the kernel in a custom VJP: the forward is the kernel, the backward
recomputes the attention of one batch row at a time with the jnp oracle
(``repro.kernels.ref.flash_attention_ref``) and pulls the cotangent
through it, so its (N, S, S) f32 logits are live for one row only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_BLOCK = 1024        # rows of a q or kv block
LANE = 128              # a block under MAX_BLOCK rows is a multiple of this
VMEM_BYTES = 16 << 20   # the chip's default scoped VMEM a kernel may use
NEG_INF = -1e30


def _seq_block(s: int) -> int:
    """The whole sequence if it fits one block, else the largest
    multiple of ``LANE`` up to ``MAX_BLOCK`` that divides it."""
    if s <= MAX_BLOCK:
        return s
    for b in range(MAX_BLOCK, LANE - 1, -LANE):
        if s % b == 0:
            return b
    raise ValueError(f"sequence {s} has no block that divides it")


def _vmem_bytes(bq: int, bk: int, hb: int, h: int, itemsize: int) -> int:
    """VMEM of one program, minor dims padded to 128 lanes: q, k, v and o
    blocks double-buffered, the f32 m, l and acc scratch, and the f32
    scores and probabilities.  Of 16 geometries compiled for a v5e, those
    this puts within ``VMEM_BYTES`` compiled under the default limit and
    those it puts above did not."""
    hp = max(h, LANE)
    blocks = 2 * itemsize * hb * 2 * (bq + bk) * hp
    scratch = 4 * hb * bq * (2 * LANE + hp)
    scores = 2 * 4 * hb * bq * bk
    return blocks + scratch + scores


def tile_sizes(sq: int, skv: int, n: int, h: int,
               itemsize: int) -> tuple[int, int, int]:
    """(BQ, BK, HB): q rows, kv rows and heads of one program.  Blocks as
    large as the sequences allow, then as many heads (a divisor of ``n``)
    as fit ``VMEM_BYTES``.  Block size comes first: on a v5e, at
    (8, 2048, 12, 64) bf16 causal, (1024, 1024, 1) took 1.21 ms a call and
    (512, 512, 4) 1.63.  That shape gets (1024, 1024, 1), 11.5 MiB of
    VMEM a program."""
    bq, bk = _seq_block(sq), _seq_block(skv)
    hb = max(d for d in range(1, n + 1) if n % d == 0 and (
        d == 1 or _vmem_bytes(bq, bk, d, h, itemsize) <= VMEM_BYTES))
    return bq, bk, hb


def kv_band(qi, *, bq: int, bk: int, nk: int, causal: bool, window: int):
    """First and last KV block that q block ``qi`` needs: from the one
    holding the oldest key inside the window of the block's first query
    to the one holding the block's last query.  Works on Python ints and
    on traced scalars alike."""
    lo, hi = 0, nk - 1
    if causal:
        hi = jnp.minimum(((qi + 1) * bq - 1) // bk, nk - 1)
    if window > 0:
        lo = jnp.maximum(qi * bq - window + 1, 0) // bk
    return lo, hi


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, causal: bool, window: int, bq: int, bk: int, nk: int,
            scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    lo, hi = kv_band(qi, bq=bq, bk=bk, nk=nk, causal=causal, window=window)
    run = (ki >= lo) & (ki <= hi)
    whole = jnp.bool_(True)          # no key of the tile is masked
    if causal:
        whole &= k_start + bk - 1 <= q_start
    if window > 0:
        whole &= k_start > q_start + bq - 1 - window

    def step(masked: bool):
        q = q_ref[0] * scale                          # (HB, BQ, H)
        s = jnp.einsum("nqh,nkh->nqk", q, k_ref[0],
                       preferred_element_type=jnp.float32)  # (HB, BQ, BK)
        if masked:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            mask = jnp.ones(s.shape, jnp.bool_)
            if causal:
                mask &= kpos <= qpos
            if window > 0:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "nqk,nkh->nqh", p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    pl.when(run & whole)(lambda: step(False))
    pl.when(run & ~whole)(lambda: step(True))

    @pl.when(ki == nk - 1)
    def _write():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def kv_index_map(*, bq: int, bk: int, nk: int, causal: bool, window: int):
    """The K/V block index map over the grid (b, head block, qi, ki).  It
    clamps ``ki`` into the q block's band: a skipped tile names the block
    its neighbour inside the band already holds, so the pipeline starts
    no copy for it."""
    def kv(b, n, qi, ki):
        lo, hi = kv_band(qi, bq=bq, bk=bk, nk=nk, causal=causal,
                         window=window)
        return b, n, jnp.minimum(jnp.maximum(ki, lo), hi), 0

    return kv


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: bool = False):
    """q/k/v: (B, S, N, H) with equal N (GQA pre-expanded in ops.py)."""
    b, sq, n, h = q.shape
    skv = k.shape[1]
    bq, bk, hb = tile_sizes(sq, skv, n, h, q.dtype.itemsize)
    nk = skv // bk
    assert sq % bq == 0 and skv % bk == 0 and n % hb == 0
    scale = 1.0 / (h ** 0.5)
    # layout (B, N, S, H) for clean tiling
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qo_map = lambda b, n, qi, ki: (b, n, qi, 0)
    kv_map = kv_index_map(bq=bq, bk=bk, nk=nk, causal=causal, window=window)
    kern = functools.partial(_kernel, causal=causal, window=window,
                             bq=bq, bk=bk, nk=nk, scale=scale)
    out = pl.pallas_call(
        kern,
        grid=(b, n // hb, sq // bq, nk),
        in_specs=[pl.BlockSpec((1, hb, bq, h), qo_map),
                  pl.BlockSpec((1, hb, bk, h), kv_map),
                  pl.BlockSpec((1, hb, bk, h), kv_map)],
        out_specs=pl.BlockSpec((1, hb, bq, h), qo_map),
        scratch_shapes=[pltpu.VMEM((hb, bq, 1), jnp.float32),
                        pltpu.VMEM((hb, bq, 1), jnp.float32),
                        pltpu.VMEM((hb, bq, h), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, n, sq, h), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_trainable(q, k, v, causal: bool = True, window: int = 0,
                              interpret: bool = False):
    """``flash_attention`` with a gradient (see the module docstring)."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=interpret)


def _trainable_fwd(q, k, v, causal, window, interpret):
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=interpret)
    return out, (q, k, v)


def _trainable_bwd(causal, window, interpret, res, do):
    from repro.kernels.ref import flash_attention_ref

    def row(args):
        qb, kb, vb, dob = args
        _, vjp = jax.vjp(
            lambda a, b_, c: flash_attention_ref(
                a[None], b_[None], c[None], causal=causal,
                window=window)[0], qb, kb, vb)
        return vjp(dob)

    return jax.lax.map(row, res + (do,))


flash_attention_trainable.defvjp(_trainable_fwd, _trainable_bwd)

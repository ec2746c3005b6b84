"""Pallas TPU kernel: block-paged decode attention (PagedAttention-style).

One decode token per sequence against the flat block-paged KV pool
(``repro.models.attention.init_paged_kv_cache``: head-major
``(nkv, num_rows, hd)`` token rows, no batch dimension).  The pre-kernel
path gathered every sequence's rows into a ``(B, max_kv, nkv, hd)`` copy
per sublayer per step (``k[row_idx]``) and blew GQA K/V up to ``nq``
heads — this kernel
reads the pool IN PLACE through the page table and consumes the ``nkv``
KV heads natively.

Grid and page-table addressing
------------------------------
Grid is ``(B, nkv, max_kv / page_size)`` with the KV-page axis innermost.
The page table arrives as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``): ``block_tbl[b, i]`` is the POOL PAGE
holding sequence ``b``'s tokens ``[i*page_size, (i+1)*page_size)``, so
the K/V BlockSpec index map is ``(head, block_tbl[b, i], 0)`` — the pool
row axis is blocked at page granularity and each program DMAs exactly
one page of one KV head from the flat pool.  The pool is head-major so
that the block's last two dims are ``(page_size, hd)``: the TPU lowering
requires them to be multiples of (8, 128) or whole array dims, which a
``(page_size, 1, hd)`` block over a row-major ``(num_rows, nkv, hd)``
pool is not.  No per-sequence KV copy is ever materialized; unallocated tail pages point at the reserved trash
page 0 and are skipped by the position mask below.  Q is reshaped to
``(B, nkv, group, hd)`` so a program's ``group = nq // nkv`` query heads
share its KV head (native GQA — no ``jnp.repeat`` expansion anywhere).

Masking contract (must match ``attention._sdpa`` + the decode mask)
-------------------------------------------------------------------
``positions[b]`` is sequence ``b``'s write position (= current length):
token ``t`` participates iff ``t <= positions[b]`` and, with a sliding
window, ``t > positions[b] - window``.  Tiles wholly outside that range
are skipped BEFORE their compute (the grid still visits them — skipping
is a ``pl.when`` predicate, free on TPU).  Logit soft-capping
(``tanh(s / cap) * cap``) is applied before the mask, exactly where the
XLA path applies it.  A sequence parked on the trash page (idle slot:
``block_tbl`` all zeros, position 0) reduces over exactly one masked-in
row — same garbage-in/garbage-out as the XLA gather path, never read by
a live sequence.  Accumulation runs online-softmax in f32 VMEM scratch
(m/l/acc), so kernel-vs-XLA parity is reduction-order-limited: ≤1e-6
absolute in f32, bf16 inputs accumulate in f32.

Interpret mode
--------------
On the CPU backend ``repro.kernels.ops._interpret()`` switches
``interpret=True`` and the kernel body runs as traced Python — bitwise
the math above, minus the DMA pipeline.  The pure-XLA gather fallback
stays available behind ``ModelConfig.paged_attn_kernel = False``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, bk: int, window: int, softcap: float, scale: float):
    b = pl.program_id(0)
    i = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    t0 = i * bk
    run = t0 <= pos                      # page intersects [0, pos]
    if window > 0:                       # ... and is not wholly pre-window
        run &= t0 + bk - 1 > pos - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (G, H)
        k = k_ref[0].astype(jnp.float32)                # (BK, H)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        g = q.shape[0]
        tpos = t0 + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        mask = tpos <= pos
        if window > 0:
            mask &= tpos > pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _write():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tbl, positions, *,
                           page_size: int, window: int = 0,
                           softcap: float = 0.0, interpret: bool = False):
    """q: (B, nq, hd); k/v_pool: (nkv, num_rows, hd) flat page pool;
    block_tbl: (B, max_kv/page_size) int32 pool-page ids; positions: (B,)
    int32 per-sequence write positions.  Returns (B, nq, hd) in q.dtype
    with f32 accumulation.  See the module docstring for the contract."""
    b, nq, h = q.shape
    nkv, num_rows, _ = k_pool.shape
    assert nq % nkv == 0, (nq, nkv)
    assert num_rows % page_size == 0, (num_rows, page_size)
    group = nq // nkv
    n_blk = block_tbl.shape[1]
    scale = 1.0 / (h ** 0.5)
    qg = q.reshape(b, nkv, group, h)
    kern = functools.partial(_kernel, bk=page_size, window=window,
                             softcap=softcap, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nkv, n_blk),
        in_specs=[
            pl.BlockSpec((1, 1, group, h),
                         lambda b_, n_, i_, tbl, pos: (b_, n_, 0, 0)),
            pl.BlockSpec((1, page_size, h),
                         lambda b_, n_, i_, tbl, pos: (n_, tbl[b_, i_], 0)),
            pl.BlockSpec((1, page_size, h),
                         lambda b_, n_, i_, tbl, pos: (n_, tbl[b_, i_], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, h),
                               lambda b_, n_, i_, tbl, pos: (b_, n_, 0, 0)),
        scratch_shapes=[pltpu.VMEM((group, 1), jnp.float32),
                        pltpu.VMEM((group, 1), jnp.float32),
                        pltpu.VMEM((group, h), jnp.float32)],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, group, h), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tbl.astype(jnp.int32), positions.astype(jnp.int32),
      qg, k_pool, v_pool)
    return out.reshape(b, nq, h)

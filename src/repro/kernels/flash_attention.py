"""Pallas TPU kernel: blockwise flash attention (online softmax).

Used for the attention layers whose forward latency hides the FSSDP
SparseAllGather (paper Fig. 1c) — the faster the attention, the tighter the
overlap budget `t`, so this kernel matters to the system even though the
paper's contribution is the MoE side.

Grid (B, N, Sq/BQ, Skv/BK), KV innermost; m/l/acc live in VMEM scratch;
causal and sliding-window tiles outside the mask are skipped entirely.

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

BQ = 128
BK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, causal: bool, window: int, bq: int, bk: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    run = jnp.bool_(True)
    if causal:                       # tile intersects the lower triangle
        run &= k_start <= q_start + bq - 1
    if window > 0:                   # tile not wholly older than the window
        run &= k_start + bk - 1 > q_start - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0] * scale                       # (BQ, H)
        k = k_ref[0, 0]                               # (BK, H)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (BQ, BK)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _write():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: bool = False):
    """q/k/v: (B, S, N, H) with equal N (GQA pre-expanded in ops.py)."""
    b, sq, n, h = q.shape
    skv = k.shape[1]
    bq = min(BQ, sq)
    bk = min(BK, skv)
    assert sq % bq == 0 and skv % bk == 0
    scale = 1.0 / (h ** 0.5)
    # layout (B, N, S, H) for clean tiling
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (b, n, sq // bq, skv // bk)
    kern = functools.partial(_kernel, causal=causal, window=window,
                             bq=bq, bk=bk, scale=scale)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, h), lambda b, n, q_, k_: (b, n, q_, 0)),
            pl.BlockSpec((1, 1, bk, h), lambda b, n, q_, k_: (b, n, k_, 0)),
            pl.BlockSpec((1, 1, bk, h), lambda b, n, q_, k_: (b, n, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, h), lambda b, n, q_, k_: (b, n, q_, 0)),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, h), jnp.float32)],
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

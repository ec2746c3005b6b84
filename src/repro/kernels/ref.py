"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.grouped_mlp import act_fn


def grouped_mlp_ref(x, wi, wg, wo, act: str = "silu_glu",
                    group_sizes=None, row_valid=None):
    """x: (K, T, D); wi/wg: (K, D, F); wo: (K, F, D).

    Per-slot FFN.  Validity comes as ``group_sizes`` (K,) — rows
    t >= size are the padded tail of each expert group — or as
    ``row_valid`` (K, T) bool for arbitrary per-row validity (the fused
    dispatch layout); the kernel skips token tiles with no valid row.
    The mask is applied on BOTH sides (input and output) so autodiff
    through this reference also respects validity exactly: invalid rows
    get zero cotangent and contribute zero to every weight gradient,
    matching the kernel's custom VJP.
    """
    mask = None
    if row_valid is not None:
        mask = row_valid.astype(bool)[..., None]
        x = x * mask.astype(x.dtype)
    elif group_sizes is not None:
        t = x.shape[1]
        mask = (jnp.arange(t)[None, :] < group_sizes[:, None])[..., None]
        x = x * mask.astype(x.dtype)
    h = jnp.einsum("ktd,kdf->ktf", x, wi)
    if wg is not None:
        g = jnp.einsum("ktd,kdf->ktf", x, wg)
        h = act_fn(act)(h) * g
    else:
        h = act_fn(act)(h)          # same source of truth as the kernels
    y = jnp.einsum("ktf,kfd->ktd", h, wo)
    if mask is not None:
        y = y * mask.astype(y.dtype)
    return y


def paged_decode_attention_ref(q, k_pool, v_pool, row_idx, positions, *,
                               window: int = 0, softcap: float = 0.0):
    """q: (B, nq, hd); k/v_pool: (nkv, num_rows, hd); row_idx: (B, max_kv)
    int32 pool rows; positions: (B,) int32 write positions.

    The pre-kernel XLA path, kept as the oracle: gather every sequence's
    rows into a (nkv, B, max_kv, hd) view, mask ``t <= positions[b]``
    (windowed, soft-capped), softmax in f32.  Masked tokens — including
    every trash-page row past a sequence's allocation — get EXACTLY zero
    probability (exp(-1e30 - m) underflows to 0.0), so the unallocated
    tail contributes no mass here or in the kernel.
    """
    kb = k_pool[:, row_idx].astype(jnp.float32)     # (nkv, B, max_kv, hd)
    vb = v_pool[:, row_idx].astype(jnp.float32)
    b, nq, h = q.shape
    nkv = k_pool.shape[0]
    g = nq // nkv
    qg = q.reshape(b, nkv, g, h).astype(jnp.float32)
    s = jnp.einsum("bkgh,kbsh->bkgs", qg, kb) / jnp.sqrt(h)
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    kpos = jnp.arange(row_idx.shape[1])
    valid = kpos[None, :] <= positions[:, None]
    if window > 0:
        valid &= kpos[None, :] > positions[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,kbsh->bkgh", p, vb)
    return out.reshape(b, nq, h).astype(q.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q/k/v: (B, S, N, H) (same N — GQA expansion happens in ops.py).

    Standard softmax attention with optional causal + sliding-window mask.
    """
    b, s, n, h = q.shape
    logits = jnp.einsum("bqnh,bknh->bnqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(h)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((s, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqk,bknh->bqnh", p, v.astype(jnp.float32))
    return out.astype(q.dtype)

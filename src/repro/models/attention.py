"""GQA attention with RoPE / M-RoPE, softcap, sliding window, KV cache.

Reference implementation is einsum-based (XLA path used by the distributed
dry-run); the Pallas flash-attention kernel in ``repro.kernels`` is switched
in for train/prefill when ``use_pallas=True``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common import sharding as shd
from repro.common.config import ModelConfig
from repro.common.params import Param
from repro.models.layers import apply_rope, default_mrope_sections

NEG_INF = -1e30


def attn_params(cfg: ModelConfig, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    p = {
        "wq": Param((d, nq, hd), ("embed", "heads", "head_dim"), init="scaled"),
        "wk": Param((d, nkv, hd), ("embed", "kv_heads", "head_dim"), init="scaled"),
        "wv": Param((d, nkv, hd), ("embed", "kv_heads", "head_dim"), init="scaled"),
        "wo": Param((nq, hd, d), ("heads", "head_dim", "embed"), init="scaled"),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = Param((nq, hd), ("heads", "head_dim"), init="zeros")
        p["bk"] = Param((nkv, hd), ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = Param((nkv, hd), ("kv_heads", "head_dim"), init="zeros")
    return p


def _project_qkv(p, x, xa=None):
    """xa: cross-attention source (encoder states); else self-attention."""
    dt = x.dtype
    src = x if xa is None else xa
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bsnh", src, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", src, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def _sdpa(q, k, v, mask, softcap: float, head_dim: int):
    """q: (B,Sq,Nq,hd)  k,v: (B,Skv,Nkv,hd)  mask: (B,1,Sq,Skv) bool or None."""
    nq, nkv = q.shape[2], k.shape[2]
    group = nq // nkv
    b, sq = q.shape[0], q.shape[1]
    qg = q.reshape(b, sq, nkv, group, head_dim)
    logits = jnp.einsum("bqkgh,bskh->bkgqs",
                        qg.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits / jnp.sqrt(head_dim).astype(jnp.float32)
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    if mask is not None:
        logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, nq, head_dim).astype(q.dtype)


def make_mask(sq: int, skv: int, *, causal: bool, window: int = 0,
              q_offset=0):
    """(1, 1, Sq, Skv) boolean mask. q_offset: absolute position of q[0]."""
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(skv)
    m = jnp.ones((sq, skv), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        m &= kpos[None, :] > qpos[:, None] - window
    return m[None, None]


def _flash(q, k, v, *, causal: bool, window: int, mesh=None, rules=None):
    """The Pallas flash kernel.  GSPMD cannot partition a Mosaic kernel,
    so on a mesh it runs per shard under ``shard_map``: batch over the
    batch axes, heads over the model axis when both the query and the KV
    head counts divide it (else every shard holds all heads)."""
    from repro.kernels import ops as kops
    fn = partial(kops.flash_attention, causal=causal, window=window)
    if mesh is None:
        return fn(q, k, v)
    qs = shd.shape_aware_pspec(q.shape, ("batch", None, "heads", None),
                               rules, mesh)
    ks = shd.shape_aware_pspec(k.shape, ("batch", None, "kv_heads", None),
                               rules, mesh)
    if qs[2] != ks[2]:
        qs = ks = P(qs[0], None, None, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(qs, ks, ks),
                         out_specs=qs, check_vma=False)(q, k, v)


def attention(p, cfg: ModelConfig, x, positions, *, kind: str = "attn",
              causal: bool = True, xa=None, use_pallas: bool = False,
              return_kv: bool = False, mesh=None, rules=None):
    """Full-sequence attention (train / prefill). Returns (B,S,D)
    (and the rotated (k, v) when ``return_kv`` — prefill cache fill).
    ``mesh``/``rules``: the model's mesh and logical-axis rules, which
    place the Pallas kernel's shards (``use_pallas``)."""
    q, k, v = _project_qkv(p, x, xa=xa)
    mr = default_mrope_sections(cfg.head_dim) if cfg.mrope else None
    if xa is None:
        q = apply_rope(q, positions, cfg.rope_theta, mr)
        k = apply_rope(k, positions, cfg.rope_theta, mr)
    window = cfg.sliding_window if kind == "local" else 0
    mask = None
    if causal or window:
        mask = make_mask(q.shape[1], k.shape[1], causal=causal, window=window)
        mask = jnp.broadcast_to(mask, (q.shape[0], 1, q.shape[1], k.shape[1]))
    if use_pallas and mask is not None and xa is None and cfg.attn_logit_softcap == 0.0:
        out = _flash(q, k, v, causal=causal, window=window, mesh=mesh,
                     rules=rules)
    else:
        out = _sdpa(q, k, v, mask, cfg.attn_logit_softcap, cfg.head_dim)
    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))
    if return_kv:
        return out, {"k": k, "v": v}
    return out


# ------------------------------------------------------------------ decode
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, nkv, hd), dtype),
        "v": jnp.zeros((batch, max_len, nkv, hd), dtype),
    }


def abstract_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    sds = jax.ShapeDtypeStruct
    return {"k": sds((batch, max_len, nkv, hd), dtype),
            "v": sds((batch, max_len, nkv, hd), dtype)}


def kv_cache_axes(batch: int, mesh_batch: int):
    """Logical axes for the cache: shard batch if it covers the batch axes,
    else shard the sequence dim (long-context decode, batch=1)."""
    if batch >= mesh_batch:
        return {"k": ("batch", None, "kv_heads", None),
                "v": ("batch", None, "kv_heads", None)}
    return {"k": (None, "seq_shard", "kv_heads", None),
            "v": (None, "seq_shard", "kv_heads", None)}


def init_paged_kv_cache(cfg: ModelConfig, num_rows: int, dtype):
    """Block-paged KV cache for ONE sublayer: a flat pool of
    ``num_rows = num_pages * page_size`` token rows shared by every
    sequence.  Which rows belong to which sequence is pure metadata (the
    scheduler's page tables — see ``repro.serve.kv_pool``); the device
    arrays carry no batch dimension at all.  The pool is head-major,
    ``(nkv, num_rows, hd)``, so one page of one KV head is a contiguous
    ``(page_size, hd)`` tile — the block the paged kernel DMAs."""
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((nkv, num_rows, hd), dtype),
        "v": jnp.zeros((nkv, num_rows, hd), dtype),
    }


def decode_attention_paged(p, cfg: ModelConfig, x, cache, positions,
                           row_idx, *, kind="attn", page_size=None):
    """One-token decode for B sequences at INDEPENDENT positions against a
    block-paged KV pool.

    x: (B, 1, D); positions: (B,) int32 — each sequence's write position
    (= its current length); row_idx: (B, max_kv) int32 — the page tables
    flattened to per-token pool rows: ``row_idx[b, t]`` is the pool row
    holding sequence b's token t (rows past the allocated pages point at
    the reserved trash page 0, which no live sequence owns).

    The new K/V is scattered to ``row_idx[b, positions[b]]``; attention
    then masks ``t <= positions[b]`` (windowed for ``kind="local"``) over
    each sequence's rows.  With ``page_size`` set and
    ``cfg.paged_attn_kernel`` (default), the reduction runs in the Pallas
    paged kernel (``repro.kernels.paged_attention``): each program reads
    its KV pages straight from the flat pool through the page table —
    no ``(B, max_kv, nkv, hd)`` gather copy, native GQA, online softmax
    in f32 (paged-vs-dense parity ≤1e-6 in f32; reduction order is the
    only difference).  Without ``page_size`` (or with the config flag
    off) the pure-XLA fallback gathers ``k[:, row_idx]`` and reuses
    ``_sdpa`` — identical math to the dense path, BIT-exact with a
    dense-cache trace of the same sequence.  Both laws are asserted in
    tests/test_serve_batching.py.  Returns (out, new_cache).
    """
    q, k_new, v_new = _project_qkv(p, x)
    mr = default_mrope_sections(cfg.head_dim) if cfg.mrope else None
    posb = positions[:, None]                       # (B, 1)
    if cfg.mrope:
        posb = jnp.broadcast_to(posb[..., None], posb.shape + (3,))
    q = apply_rope(q, posb, cfg.rope_theta, mr)
    k_new = apply_rope(k_new, posb, cfg.rope_theta, mr)
    write_rows = jnp.take_along_axis(row_idx, positions[:, None],
                                     axis=1)[:, 0]  # (B,)
    # slots parked on the trash page collide at row 0 — harmless, nothing
    # live ever reads it; live sequences own disjoint rows by construction
    k = cache["k"].at[:, write_rows].set(k_new[:, 0].swapaxes(0, 1))
    v = cache["v"].at[:, write_rows].set(v_new[:, 0].swapaxes(0, 1))
    window = cfg.sliding_window if kind == "local" else 0
    if page_size is not None and cfg.paged_attn_kernel:
        from repro.kernels import ops as kops
        out = kops.paged_decode_attention(
            q[:, 0], k, v, row_idx, positions, page_size=page_size,
            window=window, softcap=cfg.attn_logit_softcap)[:, None]
    else:
        # (nkv, B, max_kv, hd) -> (B, max_kv, nkv, hd)
        kb = k[:, row_idx].transpose(1, 2, 0, 3)
        vb = v[:, row_idx].transpose(1, 2, 0, 3)
        kpos = jnp.arange(row_idx.shape[1])
        valid = kpos[None, :] <= positions[:, None]
        if window > 0:
            valid &= kpos[None, :] > positions[:, None] - window
        mask = valid[:, None, None, :]              # (B, 1, 1, max_kv)
        out = _sdpa(q, kb, vb, mask, cfg.attn_logit_softcap, cfg.head_dim)
    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))
    return out, {"k": k, "v": v}


def decode_attention(p, cfg: ModelConfig, x, cache, pos, *, kind="attn",
                     xa=None, update_cache: bool = True):
    """One-token decode. x: (B,1,D); pos: scalar int32 current position.

    Returns (out, new_cache).  The new K/V is written at ``pos``; attention
    spans cache[0..pos] (optionally windowed).  For a seq-sharded cache the
    einsum + softmax reduce over the sharded axis and GSPMD inserts the
    required AllReduce (flash-decoding-style combine).
    """
    q, k_new, v_new = _project_qkv(p, x, xa=xa)
    mr = default_mrope_sections(cfg.head_dim) if cfg.mrope else None
    if xa is None:
        posb = jnp.full((x.shape[0], 1), pos)
        if cfg.mrope:
            posb = jnp.broadcast_to(posb[..., None], posb.shape + (3,))
        q = apply_rope(q, posb, cfg.rope_theta, mr)
        k_new = apply_rope(k_new, posb, cfg.rope_theta, mr)
        if update_cache:
            cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, pos, 1),
                "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, pos, 1),
            }
        k, v = cache["k"], cache["v"]
        skv = k.shape[1]
        kpos = jnp.arange(skv)
        valid = kpos <= pos
        if kind == "local" and cfg.sliding_window > 0:
            valid &= kpos > pos - cfg.sliding_window
        mask = jnp.broadcast_to(valid[None, None, None, :],
                                (x.shape[0], 1, 1, skv))
    else:  # cross-attention: static encoder KV, no cache update needed
        k, v, mask = k_new, v_new, None
    out = _sdpa(q, k, v, mask, cfg.attn_logit_softcap, cfg.head_dim)
    out = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(x.dtype))
    return out, cache

"""Jitted public wrappers around the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode (the
kernel body runs as traced Python — numerically identical to the TPU
lowering); on a TPU they compile to Mosaic.  Any other backend is an
error: there is no silent fallback that would hide which device ran.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import grouped_mlp as _gm
from repro.kernels import paged_attention as _pa


# the ``name=`` of every pallas_call; a TPU compile names the kernel's
# custom call after it, so these find a kernel in compiled HLO text
KERNEL_NAMES = ("grouped_mlp_fwd", "grouped_mlp_dgrad", "grouped_mlp_wgrad",
                "flash_attention", "paged_decode_attention")


def compiled_kernels(hlo_text: str) -> frozenset:
    """Names (from ``KERNEL_NAMES``) of the Pallas kernels that a compiled
    program's text carries as ``tpu_custom_call`` ops.  Interpret-mode
    (CPU) programs carry none."""
    found = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            found.update(n for n in KERNEL_NAMES if n in line)
    return frozenset(found)


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"Pallas kernels run on TPU (compiled) or CPU (interpret mode); "
            f"backend {backend!r} is neither")
    return backend == "cpu"


@partial(jax.jit, static_argnames=("act",))
def grouped_mlp(x, wi, wg, wo, group_sizes=None, row_valid=None, *,
                act: str = "silu_glu"):
    """Grouped expert FFN: x (K,T,D) -> (K,T,D).

    Validity marks the real tokens the MoE dispatch routed to each slot —
    either ``group_sizes`` (K,) int32 (valid-row prefix, the grouped-GEMM
    contract) or ``row_valid`` (K,T) bool (arbitrary rows — the fused
    dispatch layout, no compaction copy).  The kernels skip token tiles
    with no valid row in the forward AND both backward passes (Pallas
    dgrad/wgrad), and the custom VJP keeps invalid rows at exactly zero
    gradient, so padded capacity costs neither forward nor backward FLOPs.
    None = all rows valid.
    """
    return _gm.grouped_mlp(x, wi, wg, wo, group_sizes, row_valid=row_valid,
                           act=act, interpret=_interpret())


@partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """Flash attention, q/k/v (B,S,N,H).  Differentiable: the Pallas
    kernel runs the forward, the backward recomputes in XLA (see
    ``repro.kernels.flash_attention``).

    The PREFILL kernel still tiles over ``nq`` equal heads, so GQA K/V are
    expanded here — prefill-only cost, paid once per sequence.  The decode
    paths must NOT come through this expansion: ``paged_decode_attention``
    reads the ``nkv`` heads natively, and the dense decode path uses the
    grouped-einsum ``_sdpa`` (jaxpr-asserted in tests/test_serve_batching).
    """
    nq, nkv = q.shape[2], k.shape[2]
    if nq != nkv:
        rep = nq // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return _fa.flash_attention_trainable(q, k, v, causal, window,
                                         _interpret())


@partial(jax.jit, static_argnames=("page_size", "window", "softcap"))
def paged_decode_attention(q, k_pool, v_pool, row_idx, positions, *,
                           page_size: int, window: int = 0,
                           softcap: float = 0.0):
    """Block-paged decode attention over the flat KV pool.

    q: (B, nq, hd); k/v_pool: (nkv, num_rows, hd); row_idx: (B, max_kv)
    int32 per-token pool rows (page-aligned — the kernel consumes the
    page-granular table ``row_idx[:, ::page_size] // page_size``);
    positions: (B,) int32 write positions.  Native GQA: the kernel reads
    the ``nkv`` KV heads directly, with NO ``jnp.repeat`` head expansion
    and NO ``(B, max_kv, ...)`` gather materialization (contrast
    ``flash_attention`` above, whose prefill kernel still expands).
    """
    assert row_idx.shape[1] % page_size == 0, (row_idx.shape, page_size)
    block_tbl = row_idx[:, ::page_size] // page_size
    return _pa.paged_decode_attention(
        q, k_pool, v_pool, block_tbl, positions, page_size=page_size,
        window=window, softcap=softcap, interpret=_interpret())

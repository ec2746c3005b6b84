"""Compile every main-path Pallas kernel for a described TPU v5e.

Interpret mode runs a kernel body as Python and so accepts tiles the TPU
compiler refuses (block dims that are not multiples of the (8, 128)
tiling) and scratch that overflows VMEM.  These tests hand the TPU
compiler the kernels at published widths, with no chip attached: the
topology is described, not opened.  Each asserts that the compiled
program carries the kernel as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the suite runs
under several workers that each import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import grouped_mlp as gm
from repro.kernels import paged_attention as pa
from repro.kernels.ops import compiled_kernels

HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-device compile cannot read back a persistent-cache
    # entry; keep these compiles out of any cache the process has set
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled_kernels(compiled.as_text())


# (d_model, expert d_ff, activation): gpt-moe-s and olmoe-1b-7b
MLP_WIDTHS = {"gpt-moe-s": (768, 1536, "gelu"),
              "olmoe-1b-7b": (2048, 1024, "silu_glu")}
K_SLOTS, T_ROWS = 16, 1000        # T not a tile multiple: exercises padding


def _mlp_shapes(sharding, width):
    d, f, act = MLP_WIDTHS[width]
    sds = lambda shp, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shp, dt, sharding=sharding)
    x = sds((K_SLOTS, T_ROWS, d))
    wi = sds((K_SLOTS, d, f))
    wg = sds((K_SLOTS, d, f)) if act.endswith("_glu") else None
    wo = sds((K_SLOTS, f, d))
    mask = sds((K_SLOTS, T_ROWS), jnp.bool_)
    return act, (x, wi, wg, wo, mask)


@pytest.mark.parametrize("width", sorted(MLP_WIDTHS))
def test_grouped_mlp_fwd_compiles(one_chip, width):
    act, (x, wi, wg, wo, mask) = _mlp_shapes(one_chip, width)

    def fwd(x, wi, wg, wo, mask):
        return gm.grouped_mlp(x, wi, wg, wo, row_valid=mask, act=act)

    assert "grouped_mlp_fwd" in _compile(fwd, x, wi, wg, wo, mask)


@pytest.mark.parametrize("kernel", ["grouped_mlp_dgrad", "grouped_mlp_wgrad"])
@pytest.mark.parametrize("width", sorted(MLP_WIDTHS))
def test_grouped_mlp_bwd_compiles(one_chip, width, kernel):
    act, (x, wi, wg, wo, mask) = _mlp_shapes(one_chip, width)

    def loss(x, wi, wg, wo, mask):
        y = gm.grouped_mlp(x, wi, wg, wo, row_valid=mask, act=act)
        return y.astype(jnp.float32).sum()

    grad = jax.grad(loss, argnums=(0, 1, 3))
    assert kernel in _compile(grad, x, wi, wg, wo, mask)


@pytest.mark.parametrize("nq,nkv,hd", [(12, 12, 64), (32, 8, 128)])
def test_paged_decode_attention_compiles(one_chip, nq, nkv, hd):
    b, page, max_kv = 8, 16, 2048
    num_rows = (b * max_kv // page + 1) * page
    sds = lambda shp, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shp, dt, sharding=one_chip)

    def decode(q, k, v, tbl, pos):
        return pa.paged_decode_attention(q, k, v, tbl, pos, page_size=page)

    assert "paged_decode_attention" in _compile(
        decode, sds((b, nq, hd)), sds((nkv, num_rows, hd)),
        sds((nkv, num_rows, hd)), sds((b, max_kv // page), jnp.int32),
        sds((b,), jnp.int32))


@pytest.mark.parametrize("shape,window", [
    ((8, 2048, 12, 64), 0),        # gpt-moe-s at the benchmark cell's batch
    ((1, 4096, 16, 128), 0),       # olmoe-1b-7b widths
    ((1, 8192, 16, 256), 4096)])   # a Gemma-2 local (sliding-window) layer
def test_flash_attention_compiles(one_chip, shape, window):
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window)

    assert "flash_attention" in _compile(fwd, qkv, qkv, qkv)

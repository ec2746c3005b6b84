"""Paged decode-attention microbenchmark: Pallas kernel vs XLA gather.

What this measures (results to ``BENCH_paged_attn.json``), across a
``(B, max_kv, page_size)`` sweep on the CPU mirror:

* **Parity** — max |kernel - gather| per shape (the kernel's online
  softmax only reorders the f32 reduction; acceptance asserts <= 1e-6).
* **Traffic model** — the XLA fallback materializes a
  ``(B, max_kv, nkv, hd)`` K and V copy EVERY step (``k[row_idx]``);
  the kernel DMAs pages straight from the flat pool and skips every
  tile past a sequence's position, so its traffic is
  ``sum_b ceil((pos_b+1)/ps)`` pages.  ``bytes_ratio`` (gather/kernel)
  is the portable signal: it grows with table slack (ragged sequences
  padded to max_kv) and is what a TPU run converts into HBM-bandwidth
  headroom.
* **Wall clock** — per-step latency of both jitted paths.  CAVEAT:
  host-only container runs the kernel in Pallas INTERPRET mode (a
  Python grid loop), so kernel wall-clock is mock-latency only —
  gather wall-clock is real XLA-CPU, the bytes model is the portable
  comparison.

Run: ``PYTHONPATH=src python benchmarks/paged_attn_microbench.py``
Smoke (CI): ``... paged_attn_microbench.py --smoke`` — one tiny shape,
parity + trash-page checks only, no JSON write.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "src"))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from repro.kernels import ops                           # noqa: E402
from repro.kernels.ref import paged_decode_attention_ref  # noqa: E402
from repro.serve.kv_pool import PageTable               # noqa: E402

OUT_PATH = os.path.join(HERE, "..", "BENCH_paged_attn.json")
NQ, NKV, HD = 8, 2, 64                  # GQA 4:1, f32


def make_case(seed, b, max_kv, ps):
    """Ragged positions (uniform in [0, max_kv)), shuffled page tables."""
    rng = np.random.default_rng(seed)
    positions = rng.integers(0, max_kv, size=b)
    num_pages = b * (max_kv // ps) + 1          # worst case + trash page
    avail = list(range(1, num_pages))
    rng.shuffle(avail)
    rows = []
    for pos in positions:
        pages = [avail.pop() for _ in range(int(pos) // ps + 1)]
        rows.append(PageTable(ps, max_kv, pages).row_idx())
    q = jnp.asarray(rng.standard_normal((b, NQ, HD)) * 0.4, jnp.float32)
    # head-major pool (nkv, num_rows, hd)
    k = jnp.asarray(rng.standard_normal((num_pages * ps, NKV, HD)) * 0.4,
                    jnp.float32).swapaxes(0, 1)
    v = jnp.asarray(rng.standard_normal((num_pages * ps, NKV, HD)) * 0.6,
                    jnp.float32).swapaxes(0, 1)
    return (q, k, v, jnp.asarray(np.stack(rows)),
            jnp.asarray(positions, jnp.int32))


@jax.jit
def xla_gather(q, k_pool, v_pool, row_idx, positions):
    """The pre-kernel decode path: materialize the per-sequence KV view,
    then masked softmax — same math as the ref oracle, jitted whole."""
    return paged_decode_attention_ref(q, k_pool, v_pool, row_idx, positions)


def time_fn(fn, *args, reps=5):
    fn(*args).block_until_ready()               # compile / warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def bench_shape(b, max_kv, ps, seed):
    q, k, v, row_idx, positions = make_case(seed, b, max_kv, ps)
    kern = jax.jit(lambda *a: ops.paged_decode_attention(
        *a, page_size=ps))
    out_k = kern(q, k, v, row_idx, positions)
    out_x = xla_gather(q, k, v, row_idx, positions)
    max_err = float(np.abs(np.asarray(out_k) - np.asarray(out_x)).max())

    itm = np.dtype(np.float32).itemsize
    gather_bytes = 2 * b * max_kv * NKV * HD * itm      # the (B,max_kv,..) copy
    live_rows = int(sum((int(p) // ps + 1) * ps for p in positions))
    kernel_bytes = 2 * live_rows * NKV * HD * itm       # pages actually read
    row = {
        "B": b, "max_kv": max_kv, "page_size": ps,
        "nq": NQ, "nkv": NKV, "head_dim": HD,
        "max_err": max_err,
        "kernel_ms_interpret": round(time_fn(kern, q, k, v, row_idx,
                                             positions), 3),
        "xla_gather_ms": round(time_fn(xla_gather, q, k, v, row_idx,
                                       positions), 3),
        "gather_bytes": gather_bytes,
        "kernel_bytes": kernel_bytes,
        "bytes_ratio": round(gather_bytes / kernel_bytes, 2),
    }
    print(f"  B={b:2d} max_kv={max_kv:4d} ps={ps:2d}: "
          f"err {max_err:.2e}, bytes ratio {row['bytes_ratio']:.2f}x "
          f"(kernel-interpret {row['kernel_ms_interpret']:.1f}ms, "
          f"gather {row['xla_gather_ms']:.1f}ms)")
    return row


def run():
    print("paged decode attention: kernel vs XLA gather")
    rows = []
    seed = 0
    for b in (1, 4, 8):
        for max_kv in (64, 128):
            for ps in (8, 16):
                seed += 1
                rows.append(bench_shape(b, max_kv, ps, seed))
    worst = max(r["max_err"] for r in rows)
    assert worst <= 1e-6, worst             # reduction-order noise only
    ratios = [r["bytes_ratio"] for r in rows]
    return {
        "backend": jax.default_backend(),
        "sweep": rows,
        "acceptance": {"max_err": worst, "bound": "<= 1e-6 (f32)"},
        "bytes_ratio_range": [min(ratios), max(ratios)],
        "note": ("CPU mirror: the kernel runs in Pallas interpret mode "
                 "(Python grid loop), so kernel_ms_interpret is mock "
                 "latency — bytes_ratio (gather copy traffic / pages the "
                 "kernel actually reads) is the portable signal."),
    }


def smoke():
    """CI: one tiny shape — parity + trash-page immutability only."""
    b, max_kv, ps = 2, 16, 4
    q, k, v, row_idx, positions = make_case(0, b, max_kv, ps)
    out_k = ops.paged_decode_attention(q, k, v, row_idx, positions,
                                       page_size=ps)
    out_x = xla_gather(q, k, v, row_idx, positions)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               atol=1e-6, rtol=1e-6)
    poisoned = ops.paged_decode_attention(
        q, k.at[:, :ps].set(1e4), v.at[:, :ps].set(1e4), row_idx, positions,
        page_size=ps)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(poisoned))
    print("SMOKE PASSED")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny shape, parity checks only, no JSON")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        sys.exit(0)
    out = run()
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out, indent=2))

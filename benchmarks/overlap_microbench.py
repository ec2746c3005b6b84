"""Pipelined-materialization microbenchmark: prefetch schedule + remat modes.

What this measures (results to ``BENCH_overlap.json``), on an 8-host-device
(2 data x 4 expert) mesh over gpt_moe_s-mirror shapes:

* **Pipelined vs serial materialization** — full train fwd+bwd step time
  with the one-layer-ahead SparseAllGather prefetch
  (``cfg.moe.pipeline``) on and off, plus a jaxpr audit of the schedule
  (standalone materialization shard_maps per layer, issued before the
  previous layer's FFN consumer).
* **save vs gather vs block backward** — step time AND compiled temp
  memory (``Compiled.memory_analysis().temp_size_in_bytes``) at two
  depths, so the JSON records the MARGINAL per-layer residual footprint of
  each ``cfg.moe.rematerialize`` mode.  ``gather`` re-gathers the chunks
  in the backward (collective count (3·L+1)·m pipelined / 3·m·L legacy vs
  save's 2·m·L, also recorded) instead of storing them: its marginal
  footprint sits strictly between ``save`` (stores every layer's chunks)
  and ``block`` (stores nothing, recomputes the whole block).
* **Backward schedule (gather mode)** — marginal save-vs-gather step time
  with the EXPLICIT backward re-gather pipeline
  (``cfg.moe.bwd_prefetch``) on vs off.  With it on, layer l−1's
  re-gather is issued (jaxpr-ordered) before layer l's backward FFN
  kernels instead of at the head of layer l−1's own VJP, so an async
  collective scheduler overlaps each re-gather with a whole layer's
  backward compute — on CPU only the schedule itself (issue order +
  collective counts, recorded) is portable signal.

CAVEAT on wall-clock here: this container has no accelerator — collectives
run through XLA's CPU host emulation and there is no async collective
scheduler, so the OVERLAP the pipeline creates cannot show up as CPU
wall-clock; the schedule (issue order) and the memory numbers are the
portable signal.  Re-run on a TPU/GPU backend for real step-time ratios
(the JSON records backend + mode).

Run: ``PYTHONPATH=src python benchmarks/overlap_microbench.py``
Smoke (CI): ``... overlap_microbench.py --smoke`` — tiny shapes, mode
parity + run-to-completion only, no JSON write.
"""
import argparse
import json
import os
import sys
import time

N_DEV, EP = 8, 4
os.environ.setdefault(
    "XLA_FLAGS",
    f"--xla_force_host_platform_device_count={N_DEV}")

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "src"))


import dataclasses                                      # noqa: E402
from repro.common.config import ModelConfig, MoEConfig  # noqa: E402
from repro.core import moe as moe_core                  # noqa: E402
from repro.core.placement import homogeneous_sharding   # noqa: E402
from repro.core.schedule import sparse_materialization  # noqa: E402
from repro.models import model as mdl                   # noqa: E402

OUT_PATH = os.path.join(HERE, "..", "BENCH_overlap.json")

# gpt_moe_s mirror, reduced for CPU: gelu experts (2 mats), d_ffn=2*d_model,
# top-2 of E experts, m=1 extra slot — the sweep varies depth and d_model
SHAPES = [
    ("sweep_small", dict(d_model=128, d_ff=256, experts=8, seq=16, batch=8)),
    ("gpt_moe_s_mirror",
     dict(d_model=256, d_ff=512, experts=16, seq=32, batch=8)),
]
DEPTHS = (2, 6)


def build(name, d_model, d_ff, experts, seq, batch, num_layers, mode,
          pipe, remat=True, bwd_prefetch=True):
    cfg = ModelConfig(
        name=name, arch_type="moe", num_layers=num_layers,
        d_model=d_model, num_heads=4, num_kv_heads=4, head_dim=d_model // 4,
        d_ff=d_ff, vocab_size=512,
        moe=MoEConfig(num_experts=experts, experts_per_token=2, d_ff=d_ff,
                      slots_per_device=2, rematerialize=mode, pipeline=pipe,
                      bwd_prefetch=bwd_prefetch),
        act="gelu", norm="ln", remat=remat, dtype="float32")
    mesh = jax.make_mesh((N_DEV // EP, EP), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    L = moe_core.num_moe_layers(cfg)
    sh = homogeneous_sharding(L, experts, EP)
    plan = sparse_materialization(sh, np.ones((L, experts)), t=4, m=1,
                                  impl="ring")
    pa = moe_core.plan_to_arrays(plan)
    rt = mdl.Runtime(mesh=mesh, moe=moe_core.MoERuntime(
        mesh=mesh, batch_axes=("data",), impl="ring", m=1, capacity=16,
        use_pallas=False))
    params = mdl.init_params(cfg, jax.random.PRNGKey(0), ep=EP)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)), jnp.int32)

    def loss(buf):
        p = dict(params, moe_buffer=buf)
        logits, aux = mdl.forward(cfg, rt, p, toks, pa=pa)
        aux = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), aux)
        return (jnp.sum(logits.astype(jnp.float32) ** 2) * 1e-3
                + aux.aux_loss.sum() + aux.z_loss.sum())

    return cfg, loss, params["moe_buffer"], L


def _bench(fn, *args, reps=3, iters=2):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def _ppermutes(fn, *args):
    from repro.common.jaxprs import count_prims
    return count_prims(fn, *args, prims={"ppermute"})


def run():
    rows = []
    for name, kw in SHAPES:
        # --- pipelined vs serial schedule, save mode, depth = max sweep ---
        for pipe in (False, True):
            cfg, loss, buf, L = build(name, num_layers=DEPTHS[-1],
                                      mode="save", pipe=pipe, **kw)
            g = jax.jit(jax.grad(loss))
            t = _bench(g, buf)
            comp = g.lower(buf).compile()
            rows.append({
                "shape": name, "kind": "schedule", "L": L,
                "pipeline": pipe, "rematerialize": "save",
                "step_ms": round(t, 2),
                "temp_bytes": comp.memory_analysis().temp_size_in_bytes,
            })
            print(f"{name} schedule pipe={pipe}: {t:.1f} ms")
        # --- remat modes: step time + marginal per-layer temp memory ---
        for mode in ("save", "gather", "block"):
            temps, times, pperms = {}, {}, {}
            for nl in DEPTHS:
                cfg, loss, buf, L = build(name, num_layers=nl, mode=mode,
                                          pipe=True, **kw)
                g = jax.jit(jax.grad(loss))
                times[nl] = _bench(g, buf)
                temps[nl] = g.lower(buf).compile().memory_analysis() \
                    .temp_size_in_bytes
                pperms[nl] = _ppermutes(jax.grad(loss), buf)
            d_layers = DEPTHS[-1] - DEPTHS[0]
            chunk_b = moe_core.chunk_len(cfg) * 4
            rows.append({
                "shape": name, "kind": "remat", "rematerialize": mode,
                "pipeline": mode != "block",   # block forces serial
                "step_ms_L2": round(times[DEPTHS[0]], 2),
                "step_ms_L6": round(times[DEPTHS[-1]], 2),
                "temp_bytes_L2": temps[DEPTHS[0]],
                "temp_bytes_L6": temps[DEPTHS[-1]],
                "marginal_temp_per_layer": int(
                    (temps[DEPTHS[-1]] - temps[DEPTHS[0]]) / d_layers),
                # jaxpr-level count: the scan body is traced ONCE, so this
                # is per-trace (warmup + scan body + final block), not xL;
                # the unrolled per-layer law (save 2mL, gather 3mL) is
                # asserted in tests/test_pipeline_remat.py
                "grad_ppermutes_jaxpr": pperms[DEPTHS[-1]],
                "chunk_bytes": chunk_b,
            })
            print(f"{name} remat={mode}: marginal temp/layer "
                  f"{(temps[DEPTHS[-1]] - temps[DEPTHS[0]]) / d_layers / 1e6:.3f} MB"
                  f"  jaxpr ppermutes {pperms[DEPTHS[-1]]}")
        # --- backward schedule: explicit backward re-gather prefetch ---
        # marginal step time of gather over save, with the backward
        # pipeline on/off.  On CPU the collectives cannot overlap, so the
        # marginal-time delta is noise-level by construction — the
        # recorded jaxpr collective counts + the ordering asserted in
        # tests/test_pipeline_remat.py are the portable signal.
        cfg_s, loss_s, buf_s, L = build(name, num_layers=DEPTHS[-1],
                                        mode="save", pipe=True, **kw)
        t_save = _bench(jax.jit(jax.grad(loss_s)), buf_s)
        for bp in (False, True):
            cfg_g, loss_g, buf_g, L = build(name, num_layers=DEPTHS[-1],
                                            mode="gather", pipe=True,
                                            bwd_prefetch=bp, **kw)
            g = jax.jit(jax.grad(loss_g))
            t_gather = _bench(g, buf_g)
            rows.append({
                "shape": name, "kind": "bwd_schedule", "L": L,
                "bwd_prefetch": bp,
                "step_ms_save": round(t_save, 2),
                "step_ms_gather": round(t_gather, 2),
                "marginal_gather_over_save_ms": round(t_gather - t_save, 2),
                "grad_ppermutes_jaxpr": _ppermutes(jax.grad(loss_g),
                                                   buf_g),
            })
            print(f"{name} bwd_schedule prefetch={bp}: gather-save "
                  f"{t_gather - t_save:+.1f} ms")
    res = {
        "backend": jax.default_backend(),
        "devices": N_DEV, "ep": EP, "depths": list(DEPTHS),
        "rows": rows,
        "note": ("schedule rows: train fwd+bwd step time with the one-layer"
                 "-ahead SparseAllGather prefetch on/off (CPU host-emulated "
                 "collectives cannot overlap, so wall-clock parity is the "
                 "expected CPU result — the schedule and memory numbers are "
                 "the portable signal; re-run on an accelerator for real "
                 "ratios).  remat rows: marginal per-layer temp bytes of "
                 "the compiled step — save stores every layer's (K, chunk) "
                 "slots, gather re-gathers them in the backward "
                 "(collective law (3L+1)m with the explicit backward "
                 "pipeline / 3mL legacy vs save's 2mL, asserted on the "
                 "unrolled jaxpr in tests/test_pipeline_remat.py), block "
                 "recomputes the whole superblock.  bwd_schedule rows: "
                 "marginal gather-over-save step time with the explicit "
                 "backward re-gather prefetch (cfg.moe.bwd_prefetch) "
                 "off/on — on CPU the delta is noise (host collectives "
                 "cannot overlap); the issue ORDER (re-gather l-1 before "
                 "layer l's backward kernels, spRS trailing) is the "
                 "portable signal, jaxpr-asserted in the tests."),
    }
    for name, _ in SHAPES:
        r = {row["rematerialize"]: row for row in rows
             if row["shape"] == name and row["kind"] == "remat"}
        res[f"{name}_marginal_temp_save_over_gather"] = round(
            r["save"]["marginal_temp_per_layer"]
            / max(r["gather"]["marginal_temp_per_layer"], 1), 2)
        assert (r["save"]["marginal_temp_per_layer"]
                > r["gather"]["marginal_temp_per_layer"]
                > r["block"]["marginal_temp_per_layer"]), r
    return res


def smoke():
    """CI: tiny shape — mode parity + run-to-completion, no JSON."""
    name, kw = SHAPES[0]
    grads = {}
    for mode, pipe, bp in [("save", True, True), ("gather", True, True),
                           ("gather", True, False), ("save", False, True),
                           ("block", True, True)]:
        cfg, loss, buf, L = build(name, num_layers=2, mode=mode, pipe=pipe,
                                  remat=False, bwd_prefetch=bp, **kw)
        grads[(mode, pipe, bp)] = jax.jit(jax.grad(loss))(buf)
    base = grads[("save", True, True)]
    scale = float(jnp.abs(base).max())
    for k, g in grads.items():
        err = float(jnp.abs(g - base).max()) / scale
        assert err < 1e-4, (k, err)
        print(f"smoke {k}: grad parity {err:.1e}")
    print("SMOKE PASSED")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny correctness-only run, no JSON write")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        sys.exit(0)
    out = run()
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps({k: v for k, v in out.items() if k != "rows"},
                     indent=2))

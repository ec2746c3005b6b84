"""Smoke test of the training and serving main paths on a TPU.

  python chip_smoke.py               # one chip: train, then serve
  python chip_smoke.py --four-chips  # four chips: FSSDP (ring) vs EP

One chip: ``gpt_moe_s`` at its published widths (d_model 768, 12 heads
of 64, 64 experts top-2 of d_ff 1536, vocab 50304, seq 2048) with its
depth cut to ``TRAIN_DEPTH`` layers, so that the train step fits 16 GB.
The model trains a few steps through ``repro.launch.train``'s path (a 1x1
``(data, model)`` mesh, the sparse FSSDP MoE layer, the Pallas kernels),
then the trained parameters serve a few requests through
``RequestScheduler`` with the paged decode kernel.

Four chips: the full 12-layer ``gpt_moe_s`` on a ``(1, 4)`` mesh, one
train step with ``impl="ring"`` (FSSDP) and one with ``impl="ep"`` from
the same seed and batch.  The state is created sharded; the per-device
peak memory shows that no chip holds the whole model.

Every phase prints one JSON line (compile seconds, step or tick times,
losses, skipped steps, planner fallbacks, dropped fraction, peak device
memory, and which Pallas kernels the compiled program carries).  The
script exits non-zero, with no success line, when the device is not a
TPU, a loss is not finite, a step was skipped, the planner fell back, a
request did not finish, or an expected kernel is missing.  Its last line
on success is ``{"ok": true, "device": {...}}``.  Weights are random,
made from ``SEED``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
ARCH = "gpt-moe-s"
SEQ = 2048
# the largest depth whose compiled step fits 16 GB: at 3 layers the
# compiled step needs 13.34 GiB (5.58 GiB of state in, 5.58 out, 2.19
# temporary; the step does not donate its input state); at 4 it would need
# about 17.4 GiB
TRAIN_DEPTH = 3
TRAIN_BATCH = 8             # sequences per step, one chip
TRAIN_STEPS = 4
FOUR_CHIP_BATCH = 4         # sequences per step on the (1, 4) mesh
# first-step loss agreement, ring vs ep: both run the same math on the same
# params and batch; only the grouping of tokens into bf16 expert tiles and
# the order of the f32 reductions differ
LOSS_ATOL = 2e-2
SERVE_PROMPTS = (128, 100, 77, 120)    # one prefill bucket (128)
SERVE_NEW_TOKENS = 16
PAGE_SIZE = 16

TRAIN_KERNELS = ("grouped_mlp_fwd", "grouped_mlp_dgrad", "grouped_mlp_wgrad",
                 "flash_attention")
SERVE_KERNELS = ("paged_decode_attention",)


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def _peak_bytes(devices):
    """``peak_bytes_in_use`` per device (None where not reported)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def train_phase(cfg, argv):
    """Train ``cfg`` through ``repro.launch.train``'s path (its argument
    parser, mesh, runtime, scheduler and ``train_loop``).  The train step
    is compiled ahead of time so that its compile time and program text
    are seen; ``train_loop`` then runs exactly that executable."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import compiled_kernels
    from repro.launch import inputs as inp
    from repro.launch import train as train_launch
    from repro.train import step as step_lib
    from repro.train.trainer import jit_train_step, train_loop

    args = train_launch.parse_args(argv)
    s = train_launch.build(cfg, args)
    ep = s.mesh.shape["model"]
    # the state train_loop will create, as shapes: a live copy held here
    # would not fit beside the (undonated) step's input and output
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(lambda k: step_lib.init_state(cfg, k, ep),
                       jax.random.PRNGKey(s.tc.seed)),
        step_lib.state_shardings(cfg, s.mesh))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (args.global_batch, args.seq_len + 1), jnp.int32)}
    pa = inp.concrete_plan(cfg, ep, args.impl)
    t0 = time.perf_counter()
    compiled = jit_train_step(cfg, s.rt, s.tc).lower(state, batch,
                                                     pa).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled_kernels(compiled.as_text())

    state, hist = train_loop(
        cfg, s.rt, s.tc, s.stream, scheduler=s.scheduler,
        train_step_fn=compiled, num_steps=args.steps, log_every=0,
        callback=lambda i, st, metrics: jax.block_until_ready(st))
    return {
        "phase": "train", "arch": cfg.name, "layers": cfg.num_layers,
        "batch": args.global_batch, "seq": args.seq_len, "impl": args.impl,
        "mesh": dict(s.mesh.shape), "compile_s": compile_s,
        # host time from dispatch to the step's metrics read back (one
        # executable: its state is then ready too)
        "step_s": [h["time_s"] for h in hist],
        "losses": [h["loss"] for h in hist],
        "skipped_steps": hist[-1]["skipped_steps"],
        "plan_fallbacks": hist[-1]["plan_fallbacks"],
        "dropped_frac": [h.get("dropped_frac") for h in hist],
        "peak_bytes_in_use": _peak_bytes(s.mesh.devices.flat),
        "kernels": sorted(kernels),
    }, state, s


def serve_phase(cfg, rt, params, *, impl, prompts=SERVE_PROMPTS,
                new_tokens=SERVE_NEW_TOKENS, page_size=PAGE_SIZE):
    """Serve a few requests with ``params`` through ``RequestScheduler`` on
    the training runtime ``rt``.  The paged decode tick is compiled ahead
    of time once to read its program; the scheduler then runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import compiled_kernels
    from repro.launch import inputs as inp
    from repro.serve.engine import Engine, build_paged_serve_step
    from repro.serve.scheduler import DONE, RequestScheduler

    ep = rt.mesh.shape["model"]
    pa = inp.concrete_plan(cfg, ep, impl)
    max_kv = -(-(max(prompts) + new_tokens) // page_size) * page_size
    slots = len(prompts)
    num_pages = slots * (max_kv // page_size) + 1
    rng = np.random.default_rng(SEED)
    with Engine(cfg, rt, params, max_len=max_kv, pa=pa) as eng, \
            RequestScheduler(eng, max_slots=slots, num_pages=num_pages,
                             page_size=page_size, max_kv=max_kv,
                             default_ttl_s=3600.0) as rs:
        tick = jax.jit(build_paged_serve_step(cfg, rt, page_size=page_size))
        t0 = time.perf_counter()
        compiled = tick.lower(
            params, rs.cache, jnp.zeros((slots, 1), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots, max_kv), jnp.int32), pa,
            eng._materialized()).compile()
        compile_s = time.perf_counter() - t0
        kernels = compiled_kernels(compiled.as_text())
        reqs = [rs.submit(rng.integers(1, cfg.vocab_size, n),
                          max_new_tokens=new_tokens) for n in prompts]
        tick_s = []
        while any(not r.done for r in reqs) and len(tick_s) < 10 * new_tokens:
            t0 = time.perf_counter()
            rs.step()           # ends in a host read of the tick's logits
            tick_s.append(time.perf_counter() - t0)
        states = [r.state for r in reqs]
        ok = all(s == DONE for s in states)
        generated = [len(r.generated) for r in reqs]
    return {
        "phase": "serve", "arch": cfg.name, "layers": cfg.num_layers,
        "requests": len(reqs), "prompt_lens": list(prompts),
        "new_tokens": new_tokens, "page_size": page_size,
        "tick_compile_s": compile_s,
        # the first ticks admit and prefill (and compile the prefill)
        "tick_s": tick_s, "states": states, "all_done": ok,
        "generated": generated,
        "peak_bytes_in_use": _peak_bytes(rt.mesh.devices.flat),
        "kernels": sorted(kernels),
    }


def four_chip_phase(cfg, *, batch=FOUR_CHIP_BATCH, seq=SEQ, ep=4):
    """One train step of ``cfg`` with ring (FSSDP) and with ep on a
    ``(1, ep)`` mesh, each from the same seed and the same batch.  The
    dispatch capacity is the tokens per device, which no (source, slot)
    cell can exceed, so no token is dropped."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.config import TrainConfig
    from repro.data.pipeline import make_stream
    from repro.kernels.ops import compiled_kernels
    from repro.launch import inputs as inp
    from repro.launch.mesh import make_debug_mesh
    from repro.train import step as step_lib

    mesh = make_debug_mesh(1, ep)
    tc = TrainConfig(seed=SEED, warmup_steps=1, total_steps=1)
    tokens = jnp.asarray(make_stream(cfg.vocab_size, seq, batch,
                                     seed=SEED).next_batch()["tokens"])
    capacity = batch * seq // mesh.size
    shardings = step_lib.state_shardings(cfg, mesh)
    out = {"phase": "four_chips", "arch": cfg.name, "layers": cfg.num_layers,
           "batch": batch, "seq": seq, "mesh": dict(mesh.shape),
           "capacity": capacity}
    for impl in ("ring", "ep"):
        rt = inp.make_runtime(cfg, mesh, impl=impl, use_pallas=True,
                              capacity=capacity)
        state = step_lib.init_state(cfg, jax.random.PRNGKey(SEED), ep,
                                    mesh=mesh)
        pa = inp.concrete_plan(cfg, ep, impl)
        step = jax.jit(step_lib.build_train_step(cfg, rt, tc),
                       donate_argnums=0, out_shardings=(shardings, None))
        t0 = time.perf_counter()
        compiled = step.lower(state, {"tokens": tokens}, pa).compile()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        state, metrics = compiled(state, {"tokens": tokens}, pa)
        jax.block_until_ready(state)
        step_s = time.perf_counter() - t0
        metrics = jax.tree.map(np.asarray, metrics)
        del state
        out[impl] = {
            "compile_s": compile_s, "step_s": step_s,
            "loss": float(metrics["loss"]),
            "step_ok": float(metrics["step_ok"]),
            "dropped_frac": float(metrics["dropped_frac"]),
            "peak_bytes_in_use": _peak_bytes(mesh.devices.flat),
            "kernels": sorted(compiled_kernels(compiled.as_text())),
        }
    out["loss_diff"] = abs(out["ring"]["loss"] - out["ep"]["loss"])
    out["loss_atol"] = LOSS_ATOL
    return out


def check_train(res):
    if not all(math.isfinite(x) for x in res["losses"]):
        raise SmokeFailure(f"non-finite loss: {res['losses']}")
    if res["skipped_steps"]:
        raise SmokeFailure(f"step guard skipped {res['skipped_steps']} "
                           f"step(s)")
    if res["plan_fallbacks"]:
        raise SmokeFailure(f"{res['plan_fallbacks']} planner fallback(s)")


def check_serve(res):
    if not res["all_done"]:
        raise SmokeFailure(f"requests did not finish: {res['states']}")


def check_four_chips(res):
    for impl in ("ring", "ep"):
        r = res[impl]
        if not math.isfinite(r["loss"]) or r["step_ok"] < 0.5:
            raise SmokeFailure(f"{impl}: bad first step {r}")
        if r["dropped_frac"] != 0.0:
            raise SmokeFailure(f"{impl}: dropped {r['dropped_frac']}")
    if res["loss_diff"] > res["loss_atol"]:
        raise SmokeFailure(f"ring and ep first-step losses differ by "
                           f"{res['loss_diff']} > {res['loss_atol']}")


def check_kernels(res, expected):
    missing = sorted(set(expected) - set(res["kernels"]))
    if missing:
        raise SmokeFailure(f"{res['phase']}: kernels missing from the "
                           f"compiled program: {missing}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip ring-vs-ep phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import repro.configs as configs

    cfg = configs.get(ARCH)
    try:
        if args.four_chips:
            res = four_chip_phase(cfg)
            print(json.dumps(res), flush=True)
            check_four_chips(res)
            for impl in ("ring", "ep"):
                check_kernels(dict(res[impl], phase=f"four_chips/{impl}"),
                              TRAIN_KERNELS)
        else:
            cfg = cfg.replace(num_layers=TRAIN_DEPTH)
            res, state, s = train_phase(cfg, [
                "--arch", ARCH, "--steps", str(TRAIN_STEPS),
                "--global-batch", str(TRAIN_BATCH), "--seq-len", str(SEQ),
                "--seed", str(SEED)])
            print(json.dumps(res), flush=True)
            check_train(res)
            check_kernels(res, TRAIN_KERNELS)
            params = state.params
            del state
            res = serve_phase(cfg, s.rt, params, impl="ring")
            print(json.dumps(res), flush=True)
            check_serve(res)
            check_kernels(res, SERVE_KERNELS)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

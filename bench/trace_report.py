"""Where a training cell's traced window goes, by the program's own span
and scope names.

  python3 bench/trace_report.py --workload <cell> --seed <n> \
      [--seconds 10] [--hlo <path>] [--fixture <path> --steps 2]

It sets the cell up as a run of ``bench/run.py`` does, traces a window of
``--seconds`` and prints one JSON line per phase:

- ``program``: the compiled step's bytes and kernels;
- ``scope_ms``: device ms a step per named scope and phase
  (``bench.scopes``), with the largest unscoped operations;
- ``idle_by_span`` and ``span_coverage`` (``bench.spans``): the device's
  idle ms a step by the loop span over it, and the shares the loop's
  phase spans cover;
- ``layers``: ms a step of the host loop's gap between steps, of the
  scheduler (with its split and its counters over the window), and of
  the device work of the MoE dispatch, FSSDP materialization (spAG and
  spRS apart) and the optimizer;
- ``throughput``: the window's tokens/s;
- ``window``: steps, busy and idle time, and the share of the busy time
  under a named scope.

A program without the spans or scopes reads as empty there.  ``--hlo``
writes the compiled step's HLO text (gzip), to compare two programs;
``--fixture`` keeps ``--steps`` steps of the window after its first, cut
at their ``hecate.step`` spans, as a test fixture: the device
operations, the benchmark's host spans and Python frames (under a
``bench.window`` span over the cut), the program's spans, and under
``scopes`` the scope and phase of every operation in it that has one.
"""
import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEDULER_COUNTERS = ("plan_ahead_hits", "calibration_events",
                      "plan_fallbacks")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def cut(ev, start: float, end: float):
    """The events of ``ev`` (``spans.Traced``) that overlap [start, end),
    under a window span over that interval."""
    from bench import spans
    from bench import tracereduce as tr

    def inside(s, d):
        return s < end and s + d > start
    devices = {k: [e for e in v if inside(e[1], e[2])]
               for k, v in ev.devices.items()}
    host = [(tr.WINDOW_SPAN, start, end - start)] + [
        e for e in ev.host if e[0] != tr.WINDOW_SPAN and inside(e[1], e[2])]
    return spans.Traced(devices, host,
                        [sp for sp in ev.spans if inside(sp[1], sp[2])])


def report(ev, names, smap, n: int, counters) -> None:
    """Print the phase lines of a window of ``n`` steps."""
    from bench import scopes
    per_op = scopes.op_ms(ev, n)
    ms = scopes.device_ms(per_op, smap)
    rest = sorted(((op, t) for op, t in per_op.items() if op not in smap),
                  key=lambda kv: -kv[1])[:8]
    emit("scope_ms", **dict(sorted(ms.items())),
         top_unscoped=[[op, t, names.get(op)] for op, t in rest])
    idle = ev.idle_by_span()
    emit("idle_by_span", **{k: v * 1e3 / n for k, v in
                            sorted(idle.items(), key=lambda kv: -kv[1])})
    emit("span_coverage", **ev.span_coverage())
    sched, split = ev.scheduler_ms()
    layers = {f"{k}_ms": scopes.scope_sum(ms, *v) if smap else None
              for k, v in scopes.LAYERS.items()}
    emit("layers", host_gap_ms=ev.host_gap_ms(), scheduler_ms=sched,
         **layers, sprs_ms=scopes.sprs_ms(ms) if smap else None,
         scheduler_split=split, scheduler_counters=counters)
    busy = sum(ms.values())
    emit("window", steps=n, busy_s=ev.busy_s(), window_s=ev.window_s(),
         busy_ms_per_step=busy,
         scoped_share=1 - ms.get(scopes.UNSCOPED, 0.0) / busy,
         idle_ms_per_step=(ev.window_s() - ev.busy_s()) * 1e3 / n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--hlo")
    ap.add_argument("--fixture")
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)

    from bench import harness, scopes, spans, train_cell
    cell = harness.Cell(harness.load_benchmark(), args.workload)
    devices, _ = harness.check_devices(cell.chips)
    harness.enable_compile_cache()
    setup = train_cell.Setup(cell, args.seed, devices)
    setup.check_steps()
    text = setup.step.as_text()
    emit("program", program_bytes=setup.program_bytes,
         kernels=setup.kernels)
    if args.hlo:
        with gzip.open(args.hlo, "wt") as f:
            f.write(text)
    sch = setup.s.scheduler
    before = {k: getattr(sch, k) for k in SCHEDULER_COUNTERS
              if hasattr(sch, k)}
    trace_dir = tempfile.mkdtemp(prefix="trace_report_")
    try:
        win = train_cell.run_window(setup, args.seconds, trace_dir)
        ev = spans.load_dir(trace_dir, len(devices))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    n = len(win["steps"])
    mix = cell.traffic
    emit("throughput", tokens_per_s=n * mix["global_batch"]
         * mix["seq_len"] / win["seconds"])
    names = scopes.op_names(text)
    smap = scopes.scope_map(names)
    report(ev, names, smap, n,
           {k: getattr(sch, k) - v for k, v in before.items()})
    if not args.fixture:
        return 0
    steps = [st[spans.STEP_SPAN][0] for st in ev.dispatched_steps()]
    if len(steps) < args.steps + 1:
        print(f"the window completed {len(steps)} steps", file=sys.stderr)
        return 1
    kept = cut(ev, steps[1][0], steps[args.steps][1])
    present = {e[0] for v in kept.devices.values() for e in v}
    kept.to_json(args.fixture, steps=args.steps,
                 scopes={op: smap[op] for op in sorted(present & set(smap))})
    emit("fixture", steps=args.steps, ops=len(present),
         busy_s=kept.busy_s(), window_s=kept.window_s())
    return 0


if __name__ == "__main__":
    # the checkout heads the path, as in bench/run.py
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())

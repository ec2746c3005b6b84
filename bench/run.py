"""Run one cell of the benchmark on the chip it is started on.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything it
names is found by that name (see ``bench/harness.py``).  The last line
of standard output is the result, one JSON object; the numbers the
correctness check compared, each beside its limit, are the last lines
of standard error.  With no accelerator, or fewer chips than the cell
asks for, the run prints no result and exits with code 2.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout, not bench/, heads the path: bench's modules are imported
# as the package ``bench`` and the program from ``src``
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    try:
        cell = harness.Cell(harness.load_benchmark(), args.workload)
        runner = cell.runner()
        devices, peaks = harness.check_devices(cell.chips)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    result, checks = runner.run(cell, devices=devices, peaks=peaks,
                                seed=args.seed, seconds=args.seconds,
                                trace=bool(args.trace), t0=T0)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())

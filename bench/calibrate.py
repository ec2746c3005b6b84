"""Readings that the correctness limits of a training cell are set from.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--stand-ins]

In one process, for each seed: the program's first steps exactly as a
run of the cell makes them (``bench.train_cell.Setup.check_steps``) and
the reference's, compared as a run compares them.  With ``--stand-ins``
the same comparison is made for stand-ins put in the program's place:

- ``control``: the reference computed with float8 matmul operands, the
  next precision below the configuration's bfloat16;
- ``half_batch``: the reference on half of each batch's rows, the mean
  taken over those (the fault of a step that leaves half the batch out).

A step that returns its state unchanged reads 1 on ``grad_gap`` and
``delta_gap`` by their definition and needs no run.  One JSON line per
seed; exits 2 without a chip, as a run does.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def readings(cell, devices, seeds, stand_ins: bool, out=sys.stdout):
    """Yields one dict of readings per seed."""
    from bench import generator, train_cell as tc
    setup = None
    for seed in seeds:
        t = time.perf_counter()
        if setup is None:
            setup = tc.Setup(cell, seed, devices)
        else:
            setup.state.clear()
            setup.seed, setup.key = seed, tc.seed_key(seed)
            setup.state.append(setup.make_state(setup.key))
            setup.feed = tc.Feed(generator.TopicStream(
                cell.traffic, cell.config["vocab_size"], seed))
        prog = setup.check_steps()
        setup.state.clear()
        cap = tc.capacity_of(setup)
        on = {"groups": cell.chips, "devices": devices}
        ref = tc.reference_readings(cell.config, setup.opt, cap, setup.key,
                                    prog["batches"], **on)
        row = {"seed": seed, "program": tc.gaps(prog, ref),
               "losses": prog["losses"], "ref_losses": ref["losses"]}
        if stand_ins:
            ctl = tc.reference_readings(cell.config, setup.opt, cap,
                                        setup.key, prog["batches"],
                                        quant="fp8", **on)
            half = [b[: b.shape[0] // 2] for b in prog["batches"]]
            hb = tc.reference_readings(cell.config, setup.opt, cap,
                                       setup.key, half, **on)
            row["control"] = tc.gaps(ctl, ref)
            row["half_batch"] = tc.gaps(hb, ref)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), file=out, flush=True)
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--stand-ins", action="store_true")
    args = ap.parse_args(argv)
    from bench import harness
    try:
        cell = harness.Cell(harness.load_benchmark(), args.workload)
        devices, _ = harness.check_devices(cell.chips)
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    for _ in readings(cell, devices, seeds, args.stand_ins):
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())

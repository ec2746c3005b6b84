"""Roofline share of the grouped-MLP kernels (forward, dgrad, wgrad):
the least time their work can take on the chip over their summed device
time in the trace.

The work is what the algorithm needs: per step and MoE layer, the kept
(token, expert) rows, each expert's count capped at its capacity, over
the experts that received any (``bench.work.grouped_mlp``), never the
kernel's padded tiles.  Each call in the trace is charged its layer's
work; calls per layer and step are the trace's calls over steps x layers.
Prints which roof bounds each kernel."""
import json

import numpy as np

from bench import work

KINDS = ("fwd", "dgrad", "wgrad")


def read(ctx):
    ev, cfg, peaks = ctx["events"], ctx["config"], ctx["peaks"]
    steps = [s for s in ctx["steps"] if "expert_counts" in s]
    if not steps:
        return None
    counts = np.stack([np.asarray(s["expert_counts"]) for s in steps])
    n_steps, n_layers = counts.shape[0], counts.shape[1]
    kept = np.minimum(counts, ctx["capacity"]).sum(-1)        # (steps, L)
    used = (counts > 0).sum(-1)
    least = dev = 0.0
    bounds = {}
    for kind in KINDS:
        secs, calls = ev.kernel(f"grouped_mlp_{kind}")
        if calls == 0:
            return None
        per = calls / (n_steps * n_layers)
        for r, u in zip(kept.reshape(-1), used.reshape(-1)):
            w = work.grouped_mlp(cfg, float(r) / ctx["chips"], int(u), kind)
            least += per * work.least_seconds(w, peaks)
        bounds[kind] = work.bound(
            work.grouped_mlp(cfg, float(kept.mean()) / ctx["chips"],
                             int(used.max()), kind), peaks)
        dev += secs
    print(json.dumps({"phase": "grouped_mlp_roofline", "bound": bounds,
                      "least_s": least, "device_s": dev}), flush=True)
    return 100.0 * least / dev

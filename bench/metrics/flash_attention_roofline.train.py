"""Roofline share of the flash-attention forward kernel: the least time
of its causal work (``bench.work.flash_attention_fwd`` over the cell's
batch, sequence and heads, a chip's share of them) over its summed
device time in the trace.  Every call in the trace is one layer's
attention forward.  The backward is an XLA recompute, not this kernel,
and is not counted.  Prints which roof bounds it."""
import json

from bench import work


def read(ctx):
    ev, cfg, mix = ctx["events"], ctx["config"], ctx["traffic"]
    secs, calls = ev.kernel("flash_attention")
    if calls == 0:
        return None
    w = work.flash_attention_fwd(mix["global_batch"], mix["seq_len"],
                                 cfg["num_heads"], cfg["head_dim"])
    w = {k: v / ctx["chips"] for k, v in w.items()}
    least = calls * work.least_seconds(w, ctx["peaks"])
    print(json.dumps({"phase": "flash_attention_roofline",
                      "bound": work.bound(w, ctx["peaks"]),
                      "least_s": least, "device_s": secs}), flush=True)
    return 100.0 * least / secs

"""Share of the grouped MLP's compute rows that are padding: the step's
own ``pad_frac`` counter, the mean over the traced window's steps."""


def read(ctx):
    vals = [float(s["pad_frac"]) for s in ctx["steps"] if "pad_frac" in s]
    if not vals:
        return None
    return 100.0 * sum(vals) / len(vals)

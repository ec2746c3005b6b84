"""Share of the traced training window in which no operation ran on the
device: 1 - (union of the device's operation intervals) / window, the
mean over the devices."""


def read(ctx):
    ev = ctx["events"]
    return 100.0 * (1.0 - ev.busy_s() / ev.window_s())

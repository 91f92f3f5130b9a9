"""Share of the traced training window in which no operation runs on a
device, averaged over the cell's chips (:mod:`chipbench.trace`)."""


def read(ctx):
    s = ctx["summary"]
    return 100.0 * (1.0 - s.busy_s / s.window_s)

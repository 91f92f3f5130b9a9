"""Share of the traced training window in which a collective runs on a
chip and no other operation of that chip covers it, averaged over the
cell's chips (:mod:`chipbench.trace`): the communication the step does
not hide."""


def read(ctx):
    s = ctx["summary"]
    return 100.0 * s.collective_exposed_s / s.window_s

"""outer.syncs_per_solve (syncs): blocking host readbacks per solve, read
from the program's own spans: the ``lqcd.sync`` spans of the traced
window over its ``lqcd.solve`` spans (``repro.lqcd.cg.solve_wilson_eo``
opens one ``lqcd.sync`` around each readback).  Like the other trace
readers it reads only a trace that holds the cell's devices, that is, a
run on the chip."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    solves = sum(n == "lqcd.solve" for n, _, _ in tr.host)
    if not solves:
        return None
    return sum(n == "lqcd.sync" for n, _, _ in tr.host) / solves

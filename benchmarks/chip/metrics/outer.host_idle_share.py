"""outer.host_idle_share (%): the share of the traced window in which a
device ran no operation while the host was inside the solver's own work:
inside an ``lqcd.solve`` span but not inside one of its ``lqcd.sync``
readbacks (dispatch, operator construction, program loads).  Measured by
the overlap of intervals, averaged over the cell's chips."""
from benchmarks.chip.trace import gaps, merge, overlap_length


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    solves = merge((s, e) for n, s, e in tr.host if n == "lqcd.solve")
    if not solves:
        return None
    syncs = [(s, e) for n, s, e in tr.host if n == "lqcd.sync"]
    host_work = [g for span in solves for g in gaps(syncs, span)]
    idle = [overlap_length(gaps(((s, e) for _, s, e in d.ops), tr.window),
                           host_work) for d in tr.devices.values()]
    return 100.0 * sum(idle) / len(idle) / (tr.window[1] - tr.window[0])

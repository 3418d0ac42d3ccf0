"""solve_roofline (%): the whole solve's share of the chip's roofline.

The least time the cell's chips could take for the work each solve of the
window needed (``benchmarks.chip.work.solve``: declared precisions, the
solver's reported iterations), over the solves' time on the host clock.
It bounds every kernel's share: a layer taken off the path leaves this
number standing."""
from benchmarks.chip import work


def read(ctx):
    if ctx.peaks is None or not ctx.solves:
        return None
    lat, prec = ctx.cell.config["lattice"], ctx.cell.config["precision"]
    total = work.Work(0.0, 0.0)
    for s in ctx.solves:
        total = total + work.solve(lat, s.counters["iters"],
                                   s.counters["outer_iters"],
                                   prec["inner"], prec["outer"])
    least = total.seconds_at(ctx.chips * ctx.peaks["bf16_flops_per_s"],
                             ctx.chips * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(s.seconds for s in ctx.solves)

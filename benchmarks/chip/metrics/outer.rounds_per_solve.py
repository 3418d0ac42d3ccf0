"""outer.rounds_per_solve (rounds): defect-correction rounds of the host
loop in ``solve_wilson_eo`` per solve (``EOCGResult.outer_iters``)."""


def read(ctx):
    if not ctx.solves:
        return None
    return (sum(s.counters["outer_iters"] for s in ctx.solves)
            / len(ctx.solves))

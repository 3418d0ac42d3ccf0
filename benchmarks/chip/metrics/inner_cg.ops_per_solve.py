"""inner_cg.ops_per_solve (ops): inner normal-op applications per solve,
as the solver reports them (``EOCGResult.iters``), over the window."""


def read(ctx):
    if not ctx.solves:
        return None
    return sum(s.counters["iters"] for s in ctx.solves) / len(ctx.solves)

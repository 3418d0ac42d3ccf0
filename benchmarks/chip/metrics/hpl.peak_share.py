"""hpl.peak_share (%): HPL's useful work over the solves' time on the host
clock, as a share of the chip's highest peak (bf16).  The work is HPL's
own count (``benchmarks.chip.work.hpl``), whatever the program computes
besides, so the share cannot pass 100%."""
from benchmarks.chip import work


def read(ctx):
    if ctx.peaks is None or not ctx.solves:
        return None
    flops = work.hpl(ctx.cell.config["n"]) * len(ctx.solves)
    seconds = sum(s.seconds for s in ctx.solves)
    return 100.0 * flops / (ctx.chips * ctx.peaks["bf16_flops_per_s"]
                            * seconds)

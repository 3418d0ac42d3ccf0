"""inner_cg_roofline (%): the inner CG's share of its roofline.

The least time for the inner iterations of the traced solves
(``benchmarks.chip.work.inner_cg`` at the declared inner precision), over
the device time of the inner-CG programs in the trace, both per chip.
The programs are found by name and by the loop they run: the
single-device ``_eo_inner`` (``repro.lqcd.cg``) and the sharded
``cg_normal`` (``repro.lqcd.multichip_eo``), a jitted ``shard_map`` of a
function named ``body`` whose other programs run no loop."""
import re

from benchmarks.chip import work

PROGRAMS = re.compile(r"^jit__eo_inner\(|^jit_body\(")
LOOP = re.compile(r"^while\.")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.traced:
        return None
    busy = ctx.trace.module_s(PROGRAMS, holding=LOOP)
    if busy <= 0:
        return None
    lat, prec = ctx.cell.config["lattice"], ctx.cell.config["precision"]
    iters = sum(s.counters["iters"] for s in ctx.traced)
    total = work.inner_cg(lat, iters, prec["inner"])
    least = total.seconds_at(ctx.chips * ctx.peaks["bf16_flops_per_s"],
                             ctx.chips * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy

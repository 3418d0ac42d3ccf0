"""Pluggable cost models for the autotuner.

A cost model is any callable ``evaluate(point) -> (perf_gflops,
power_w)``.  Two families ship here:

* **Analytic** — queries the unified power engine
  (:mod:`repro.power.engine`) at the point's operating settings.  Fast,
  deterministic, CI-safe; this is how the paper's published operating
  point (774 MHz, 40% fan, efficiency-mode blocking) is *rediscovered*
  rather than hard-coded.
* **Measured** — timed execution of the real code path (``linpack_run``
  or the Pallas kernels in interpret mode on CPU).  Wall-clock is
  measured; power still comes from the engine (CI hosts have no power
  meter) — the ranking between candidates is what matters.

This module carries **no power model of its own**: the calibrated
fan→temperature, blocking→utilization and node-power curves it once
duplicated now live in :mod:`repro.power.model` /
:mod:`repro.power.layers`, and the node cost model is a thin wrapper
over :func:`repro.power.evaluate_operating_point` (the dedup test in
``tests/test_power_dedup.py`` keeps it that way).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro.power.engine import evaluate_operating_point
from repro.power.layers import NodeModel
from repro.power.model import (OperatingPoint, temp_from_fan,  # noqa: F401
                               tpu_chip_power, uniform_vids)
from repro.roofline import hw

Point = Dict[str, Any]

INFEASIBLE: Tuple[float, float] = (0.0, float("inf"))


# ---------------------------------------------------------------------------
# Analytic node model (the paper's GPU cluster) — a view over the engine
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AnalyticNodeHPLModel:
    """Node Linpack (perf, power) at an operating point, queried from the
    power engine's layered node model.  Points are dicts with keys
    ``f_mhz, vid, fan, nb, lookahead`` (see ``space.operating_space``).
    """

    n_gpus: int = 4

    def __call__(self, point: Point) -> Tuple[float, float]:
        return self.evaluate(point)

    def evaluate(self, point: Point) -> Tuple[float, float]:
        op = OperatingPoint.from_point(point)
        node = NodeModel.from_vids(uniform_vids(self.n_gpus, op.vid))
        return evaluate_operating_point(op, node)


# Process-level cache for the scheduler's placement-time consult: the
# coordinate-descent search over the analytic node model is deterministic
# (it rediscovers the paper's 774 MHz / VID-floor / 40%-fan Green500
# point), so one search amortizes over every schedule() call.
_RECOMMENDED_OP: Optional[OperatingPoint] = None


def recommended_operating_point() -> OperatingPoint:
    """The autotuner cost model's operating-point pick, as an
    :class:`~repro.power.model.OperatingPoint`.

    This is what :meth:`repro.cluster.scheduler.Scheduler.schedule`
    consults at placement time for jobs that carry no ``preferred_op``:
    a coordinate-descent search of :class:`AnalyticNodeHPLModel` under
    the published perf floor — the same search
    ``benchmarks/paper_tables.py::autotune_operating_point`` gates, so
    the recommendation *is* the Green500 record point rather than a
    hard-coded constant.  Cached per process (the search is ~0.3 s)."""
    global _RECOMMENDED_OP
    if _RECOMMENDED_OP is None:
        from repro.autotune import tune_operating_point
        res = tune_operating_point(method="coordinate")
        _RECOMMENDED_OP = OperatingPoint.from_point(res.best.point)
    return _RECOMMENDED_OP


@dataclass(frozen=True)
class AnalyticHPLBlockingModel:
    """Blocking/lookahead tuning for an actual ``linpack_run`` problem
    size ``n``, at a fixed electrical operating point.

    CPU-scale blocks are mapped onto the paper-scale NB axis by the
    block *fraction* of the matrix (``block · 2048 / n``), so a 1024²
    problem with block 256 sits where NB 512 sits for the paper's run —
    the same knee, floor and utilization trade apply at every scale, and
    ``HPLConfig.efficiency()``'s halved block falls out as the winner.
    """

    n: int
    f_mhz: float = 774.0
    vid: float = 1.1425
    fan: float = 0.40
    node: AnalyticNodeHPLModel = AnalyticNodeHPLModel()

    def __call__(self, point: Point) -> Tuple[float, float]:
        return self.evaluate(point)

    def evaluate(self, point: Point) -> Tuple[float, float]:
        block = int(point["block"])
        if block < 1 or self.n % block:
            return INFEASIBLE
        nb_equiv = float(np.clip(block * 2048.0 / self.n, 64.0, 4096.0))
        return self.node.evaluate({
            "f_mhz": self.f_mhz, "vid": self.vid, "fan": self.fan,
            "nb": nb_equiv, "lookahead": int(point.get("lookahead", 1))})


# ---------------------------------------------------------------------------
# Analytic Pallas-kernel tile models (TPU roofline + TPU power model)
# ---------------------------------------------------------------------------

# Fixed cost per grid step (DMA issue + pipeline refill); pushes the
# tuner toward bigger tiles until VMEM pushes back.
GRID_STEP_OVERHEAD_S = 1.0e-6
# Inputs are double-buffered (see the Pallas guide's pipelining pattern),
# and the budget leaves headroom for the compiler's own allocations.
VMEM_BUDGET = 0.8 * hw.VMEM_PER_CORE


@dataclass(frozen=True)
class AnalyticDgemmModel:
    """(perf, power) of the tiled-matmul kernel for tile point
    ``{bm, bn, bk}`` on an (m, k) @ (k, n) problem."""

    m: int
    k: int
    n: int
    itemsize: int = 4              # float32 operands

    def __call__(self, point: Point) -> Tuple[float, float]:
        return self.evaluate(point)

    def evaluate(self, point: Point) -> Tuple[float, float]:
        bm, bn, bk = int(point["bm"]), int(point["bn"]), int(point["bk"])
        if self.m % bm or self.n % bn or self.k % bk:
            return INFEASIBLE
        vmem = (2 * (bm * bk + bk * bn) * self.itemsize   # double-buffered in
                + bm * bn * 4                             # f32 accumulator
                + bm * bn * self.itemsize)                # out tile
        if vmem > VMEM_BUDGET:
            return INFEASIBLE
        flops = 2.0 * self.m * self.n * self.k
        # each k-strip of x re-streams once per N-tile (and y per M-tile)
        hbm = (self.m * self.k * (self.n // bn)
               + self.k * self.n * (self.m // bm)
               + self.m * self.n) * self.itemsize
        # MXU is 128x128: sub-128 tiles underfill the systolic array
        mxu_eff = min(bm, 128) * min(bn, 128) / (128.0 * 128.0)
        compute_s = flops / (hw.PEAK_BF16_FLOPS * mxu_eff)
        memory_s = hbm / hw.HBM_BW
        steps = (self.m // bm) * (self.n // bn) * (self.k // bk)
        t = max(compute_s, memory_s) + steps * GRID_STEP_OVERHEAD_S
        power = tpu_chip_power(1.0, compute_s / t, memory_s / t)
        return flops / t / 1e9, power


@dataclass(frozen=True)
class AnalyticDslashModel:
    """(perf, power) of the T-blocked D-slash kernel for ``{t_block}``.

    Memory-bound (the paper's thesis): time is streaming traffic over
    HBM bandwidth plus per-grid-step overhead.  Bytes are the kernels'
    site-minor layout as the TPU tiles it: (X, Y*Z) planes padded to
    (8, 128).  A grid step's blocks must fit the kernels' scoped-VMEM
    cap (``kernels.dslash.kernel.t_block_fits``)."""

    lat: Tuple[int, int, int, int]
    real_bytes: int = 4            # float32 split re/im on TPU

    def __call__(self, point: Point) -> Tuple[float, float]:
        return self.evaluate(point)

    def evaluate(self, point: Point) -> Tuple[float, float]:
        from repro.kernels.dslash.kernel import _plane_bytes, t_block_fits
        from repro.lqcd.dirac import (dslash_bytes_per_site,
                                      dslash_flops_per_site)
        tb = int(point["t_block"])
        X, Y, Z, T = self.lat
        if not t_block_fits(self.lat, tb):
            return INFEASIBLE
        vol = X * Y * Z * T
        plane = _plane_bytes(X, Y * Z)            # one real per site, tiled
        pad = plane / (X * Y * Z * 4)
        flops = vol * dslash_flops_per_site()
        hbm = vol * dslash_bytes_per_site(self.real_bytes,
                                          compressed_links=False) * pad
        # per grid step the halos re-fetch two half spinors and a t-link
        hbm += (T // tb) * (12 + 12 + 18) * plane
        memory_s = hbm / hw.HBM_BW
        compute_s = flops / hw.PEAK_BF16_FLOPS
        t = max(memory_s, compute_s) + (T // tb) * GRID_STEP_OVERHEAD_S
        power = tpu_chip_power(1.0, compute_s / t, memory_s / t)
        return flops / t / 1e9, power


# ---------------------------------------------------------------------------
# Measured cost models (timed execution of the real code paths)
# ---------------------------------------------------------------------------

def _timeit(fn, reps: int = 2) -> float:
    fn()                           # compile / warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


@dataclass
class MeasuredDgemmModel:
    """Times the actual Pallas ``dgemm`` (interpret mode off-TPU); power
    from the TPU chip model at the analytic utilization split."""

    m: int
    k: int
    n: int
    reps: int = 2
    _xy: Optional[tuple] = field(default=None, repr=False)

    def _operands(self):
        if self._xy is None:
            import jax
            kx, ky = jax.random.split(jax.random.PRNGKey(0))
            import jax.numpy as jnp
            self._xy = (jax.random.normal(kx, (self.m, self.k), jnp.float32),
                        jax.random.normal(ky, (self.k, self.n), jnp.float32))
        return self._xy

    def __call__(self, point: Point) -> Tuple[float, float]:
        return self.evaluate(point)

    def evaluate(self, point: Point) -> Tuple[float, float]:
        analytic = AnalyticDgemmModel(self.m, self.k, self.n)
        model = analytic.evaluate(point)     # feasibility + power, once
        if model == INFEASIBLE:
            return INFEASIBLE
        import jax
        from repro.kernels.dgemm.ops import dgemm
        x, y = self._operands()
        bm, bn, bk = int(point["bm"]), int(point["bn"]), int(point["bk"])
        t = _timeit(lambda: jax.block_until_ready(
            dgemm(x, y, bm=bm, bn=bn, bk=bk)), self.reps)
        flops = 2.0 * self.m * self.n * self.k
        return flops / t / 1e9, model[1]


@dataclass
class MeasuredHPLModel:
    """Times ``linpack_run`` at the point's blocking; node power from the
    engine at the point's electrical settings (defaults: the paper's
    efficiency clock/fan).  Power uses the same block → NB-axis mapping
    as :class:`AnalyticHPLBlockingModel`, so bigger blocks cost watts
    here too — otherwise the efficiency trade could never pick a
    smaller block."""

    n: int = 192
    f_mhz: float = 774.0
    vid: float = 1.1425
    fan: float = 0.40

    def __call__(self, point: Point) -> Tuple[float, float]:
        return self.evaluate(point)

    def evaluate(self, point: Point) -> Tuple[float, float]:
        from repro.configs.hpl import HPLConfig
        from repro.hpl.linpack import linpack_run
        block = int(point["block"])
        la = int(point.get("lookahead", 1))
        if block < 1 or self.n % block:
            return INFEASIBLE
        cfg = HPLConfig(n=self.n, block=block, lookahead=la)
        res = linpack_run(cfg)
        if not res.passed:
            return INFEASIBLE
        nb_equiv = float(np.clip(block * 2048.0 / self.n, 64.0, 4096.0))
        node = AnalyticNodeHPLModel()
        _, power = node.evaluate({"f_mhz": self.f_mhz, "vid": self.vid,
                                  "fan": self.fan, "nb": nb_equiv,
                                  "lookahead": la})
        return res.gflops, power

"""The Wilson-Dirac solve as a system under test:
``repro.lqcd.cg.solve_dirac`` as the configuration states it.

Inputs (``benchmarks.chip.fields``): a Haar-random gauge field, shared by
every solve, and the traffic's point sources, the requests the window
cycles through.  The check is the plain reference's true relative
residual ``||b - M x|| / ||b||`` (``benchmarks.chip.reference``) of every
solve, held to the configuration's ``solver.tol``.  The control is the
reference CG one precision lower (``reference.control_solve``).
``validate`` holds a cell to the configuration as the paper runs it.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

# normal ops of the control's CG (no early exit)
CONTROL_ITERS = 300
PRECISION = {"outer": "float32", "inner": "bfloat16"}


def validate(config, traffic, chips: int) -> None:
    """Raises ``BenchError`` naming each rule the cell breaks: nothing of
    the lattice cut, float32 outer over a bfloat16 inner CG, the T axis
    divided evenly over ``t_shards`` chips, one chip to a shard, and the
    point sources of a propagator at a positive kappa for one caller."""
    from benchmarks.chip.harness import require
    shards = int(config["t_shards"])
    require(f"{config['name']} on {chips} chip(s), lqcd_wilson's rules", {
        "reduced == []": config["reduced"] == [],
        "precision float32 outer, bfloat16 inner":
            config["precision"] == PRECISION,
        "lattice[3] % t_shards == 0": config["lattice"][3] % shards == 0,
        "chips == t_shards": chips == shards,
        "kappa > 0": float(traffic.get("kappa", 0)) > 0,
        "point sources": traffic.get("sources") == "point",
        "one client": traffic.get("clients") == 1,
    })


def _mesh(config: Dict[str, Any]):
    """The T-axis mesh of a sharded configuration, or None."""
    from repro.distributed.sharding import lattice_mesh
    shards = int(config["t_shards"])
    if shards == 1:
        return None
    mesh = lattice_mesh(config["lattice"][3], shards)
    if mesh.size != shards:
        from benchmarks.chip.harness import BenchError
        raise BenchError(f"mesh of {mesh.size} devices, configuration asks "
                         f"for {shards}")
    return mesh


def _shardings(mesh):
    """(gauge, spinor) placements: T-sharded over the mesh, or None."""
    if mesh is None:
        return None, None
    from jax.sharding import NamedSharding, PartitionSpec as P
    t = mesh.axis_names[0]
    return (NamedSharding(mesh, P(None, None, None, None, t)),
            NamedSharding(mesh, P(None, None, None, t)))


def make_inputs(seed: int, config, traffic, devices):
    """(gauge field, point sources), made on the device."""
    from benchmarks.chip import fields
    return fields.make_inputs(seed, config["lattice"], traffic,
                              *_shardings(_mesh(config)))


class Solver:
    """One call solves one source on the shared gauge field."""

    def __init__(self, config, traffic, devices):
        from repro.config import SolverConfig
        self.kappa = float(traffic["kappa"])
        self.cfg = SolverConfig(**config["solver"])
        self.kw = dict(mesh=_mesh(config), backend=config["backend"],
                       overlap=bool(config["overlap"]))

    def _solve(self, U, b):
        import jax
        from repro.lqcd import cg
        res = cg.solve_dirac(U, b, self.kappa, self.cfg, **self.kw)
        jax.block_until_ready(res.x)
        return res

    def warm_up(self, U, b) -> str:
        """One solve, which compiles or loads every program a solve
        runs."""
        res = self._solve(U, b)
        return (f"one solve, {res.iters}+{res.outer_iters} ops, residual "
                f"{res.rel_residual:.3e}")

    def __call__(self, U, b):
        res = self._solve(U, b)
        return res.x, {"iters": int(res.iters),
                       "outer_iters": int(res.outer_iters)}


def check(solves: List, U, sources, config, traffic, device):
    """The true relative residual of every solve of the window, by the
    plain reference on one device: (solves over the limit, checks)."""
    import jax
    from benchmarks.chip.reference import relative_residual
    kappa = float(traffic["kappa"])
    limit = float(config["solver"]["tol"])
    # every solution but the last is on the host already (the harness
    # moves each as the window runs); the last follows, and its device
    # copy (sharded, in the program's padded layout) is freed before the
    # reference needs one chip's memory
    xs = jax.device_get([s.x for s in solves])
    for s in solves:
        s.x = None
    U1 = jax.device_put(U, device)
    rs = []
    for s, x in zip(solves, xs):
        x, b = jax.device_put((x, sources[s.source]), device)
        rs.append(float(relative_residual(U1, x, b, kappa)))
    failed = sum(not r <= limit for r in rs)          # NaN fails too
    worst = float("nan") if any(math.isnan(r) for r in rs) else max(rs)
    return failed, {"residual_max": {"value": worst, "limit": limit}}


def control(U, b, config, traffic):
    """The answer to source ``b`` by the reference CG with every stored
    field rounded through bfloat16."""
    from benchmarks.chip.reference import control_solve
    return control_solve(U, b, float(traffic["kappa"]), CONTROL_ITERS)

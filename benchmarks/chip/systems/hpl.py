"""HPL as a system under test: one dense system ``a x = b`` factored by
the program's blocked LU with partial pivoting (``repro.hpl.lu``:
``blocked_lu``, then ``lu_solve``), the two jitted as one program and
timed as one solve.

Inputs: the traffic's ``systems`` pairs (A, b) of order ``n``, entries
uniform in [-0.5, 0.5) as in HPL's generator, made on the device from the
seed in one jitted call; nothing is shared between solves.  The program
runs with JAX's default matmul precision set to the configuration's
``precision.products``: ``blocked_lu`` and ``lu_solve`` state no
precision of their own, and on the TPU a float32 product left at the
default precision multiplies bfloat16-rounded operands.

The check is HPL 2.3's scaled residual of every answer
(``benchmarks.chip.hpl_reference``), held to the configuration's
``scaled_residual_limit``.  The control is the reference's LU with its
trailing updates one precision lower.  ``validate`` holds a cell to
HPL as the configuration states it.
"""
from __future__ import annotations

import math
import sys
from typing import List

PRECISION = {"storage": "float32", "products": "float32"}


def validate(config, traffic, chips: int) -> None:
    """Raises ``BenchError`` naming each rule the cell breaks: only N cut,
    float32 storage and products, N a whole number of NB blocks, and at
    least two systems, so that no two consecutive solves share a matrix,
    for one caller."""
    from benchmarks.chip.harness import require
    require(f"{config['name']} on {chips} chip(s), hpl's rules", {
        "reduced == ['n']": config["reduced"] == ["n"],
        "precision float32 storage and products":
            config["precision"] == PRECISION,
        "n % nb == 0": int(config["n"]) % int(config["nb"]) == 0,
        "systems >= 2": int(traffic.get("systems", 0)) >= 2,
        "one client": traffic.get("clients") == 1,
    })


def make_inputs(seed: int, config, traffic, devices):
    """(None, the traffic's (A, b) pairs), made on the device."""
    import jax
    import jax.numpy as jnp
    from benchmarks.chip.fields import base_key
    if int(traffic["clients"]) != 1:
        raise ValueError("the generator makes systems for one caller; "
                         f"traffic asks for {traffic['clients']} callers")
    n, count = int(config["n"]), int(traffic["systems"])
    dtype = jnp.dtype(config["precision"]["storage"])

    def build(key):
        pairs = []
        for k in jax.random.split(key, count):
            ka, kb = jax.random.split(k)
            pairs.append((jax.random.uniform(ka, (n, n), dtype, -0.5, 0.5),
                          jax.random.uniform(kb, (n,), dtype, -0.5, 0.5)))
        return tuple(pairs)

    return None, jax.block_until_ready(jax.jit(build)(base_key(seed)))


class Solver:
    """One call factors one matrix anew and solves its system."""

    def __init__(self, config, traffic, devices):
        # imported here and not while ``_solve`` is traced: the package's
        # first import builds module-level arrays (``repro.lqcd.dirac``'s
        # among them), which inside a trace would be left as tracers
        from repro.hpl import lu
        self.lu = lu
        self.nb = int(config["nb"])
        self.lookahead = int(config["lookahead"])
        self.precision = config["precision"]["products"]
        self.compiled = None

    def _solve(self, a, b):
        res = self.lu.blocked_lu(a, self.nb, lookahead=self.lookahead)
        return self.lu.lu_solve(res, b, self.nb)

    def lower(self, request):
        """The solve as one program, lowered with its products at the
        configuration's precision."""
        import jax
        with jax.default_matmul_precision(self.precision):
            return jax.jit(self._solve).lower(*request)

    def warm_up(self, shared, request) -> str:
        """Compiles the solve, or loads it from the cache; runs nothing."""
        self.compiled = self.lower(request).compile()
        return f"the solve program ready, products at {self.precision}"

    def __call__(self, shared, request):
        x = self.compiled(*request)
        x.block_until_ready()
        return x, {}


def check(solves: List, shared, requests, config, traffic, device):
    """HPL's scaled residual of every answer of the window, in float64 on
    the host: (answers over the limit, checks)."""
    import jax
    import numpy as np
    from benchmarks.chip.hpl_reference import scaled_residual
    limit = float(config["scaled_residual_limit"])
    xs = [np.asarray(x) for x in jax.device_get([s.x for s in solves])]
    for s in solves:
        s.x = None
    used = sorted({s.source for s in solves})
    systems = dict(zip(used, jax.device_get([requests[i] for i in used])))
    rs = [scaled_residual(*systems[s.source], x) for s, x in zip(solves, xs)]
    failed = sum(not r <= limit for r in rs)          # NaN fails too
    worst = float("nan") if any(math.isnan(r) for r in rs) else max(rs)
    print(f"[check] scaled residuals {' '.join(f'{r:.4e}' for r in rs)}; "
          f"HPL 2.3 would pass each below 16", file=sys.stderr, flush=True)
    return failed, {"scaled_residual_max": {"value": worst, "limit": limit}}


def control(shared, request, config, traffic):
    """The answer by the reference LU with bfloat16-rounded trailing
    updates, on the host."""
    import jax
    import jax.numpy as jnp
    from benchmarks.chip.hpl_reference import control_solve
    a, b = jax.device_get(request)
    return jnp.asarray(control_solve(a, b, int(config["nb"])))

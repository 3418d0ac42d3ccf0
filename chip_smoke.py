#!/usr/bin/env python3
"""Chip smoke test: the paper's main path, once, on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # the T-sharded solve, 4 chips

One process runs every phase; fields and matrices come from ``--seed``.

1. device   - refuse anything but a TPU (no CPU fallback).
2. D-slash  - the Pallas kernels at the thermal lattice (32^3 x 8), both
              even-odd parities and the full lattice, against the jnp
              operators (elementwise f32); the compiled programs must
              hold the kernel (``tpu_custom_call``).
3. solve    - ``solve_dirac`` with the even-odd mixed-precision solver on
              that lattice: converged, true residual <= 1e-6.
4. HPL      - ``linpack_run`` at N=8192, NB=256: scaled residual < 16.

``--four-chips`` runs only the T-sharded solve and what it is compared
with: the thermal lattice over four chips (jnp and Pallas backends) against
the one-chip solve in this process, then 32^3 x 32 (a thermal lattice per
chip) and the cold lattice 32^3 x 64 on the Pallas backend.  Times are one
cold run each, not a benchmark.

The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``; a failing phase exits non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DSLASH_RTOL = 1e-5       # f32 roundoff of a sum of 8 hops, max-norm relative
SOLVE_TOL = 1e-6         # true ||b - M x|| / ||b||
SHARDED_X_RTOL = 1e-4    # two solves to 1e-6 of a well-conditioned M
HPL_N, HPL_NB = 8192, 256


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")


def phase_device(n_chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    require(d.platform == "tpu", f"needs a TPU, JAX found {d.platform}")
    require(len(devs) >= n_chips, f"needs {n_chips} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def make_fields(seed: int, lat):
    import jax
    import jax.numpy as jnp
    from repro.lqcd.su3 import random_su3_field

    ku, kr, ki = jax.random.split(jax.random.PRNGKey(seed), 3)

    @jax.jit
    def source(kr, ki):
        shape = tuple(lat) + (4, 3)
        return (jax.random.normal(kr, shape)
                + 1j * jax.random.normal(ki, shape)).astype(jnp.complex64)

    U = random_su3_field(ku, tuple(lat))
    return jax.block_until_ready((U, source(kr, ki)))


def _rel_max(got, ref) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def _compiled_kernel(fn, *args):
    """Compile ``fn`` for ``args``; require the Pallas kernel in it."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    require("tpu_custom_call" in compiled.as_text(),
            f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in the "
            "compiled program")
    return compiled


def phase_dslash(U, psi) -> None:
    import jax
    from functools import partial
    from repro.kernels.dslash import (dslash_half_pallas, dslash_pallas,
                                      dslash_ref)
    from repro.lqcd.eo import dslash_half, eo_pack, pack_gauge

    U_e, U_o = jax.jit(pack_gauge)(U)
    ref_half = jax.jit(dslash_half, static_argnums=3)
    for p in (0, 1):
        half = jax.jit(eo_pack, static_argnums=1)(psi, p)
        u_out, u_src = (U_o, U_e) if p == 0 else (U_e, U_o)
        kern = _compiled_kernel(
            partial(dslash_half_pallas, src_parity=p), U_e, U_o, half)
        err = _rel_max(kern(U_e, U_o, half), ref_half(u_out, u_src, half, p))
        log(f"[dslash] even-odd src_parity={p}: max rel err {err:.3e} "
            f"(bound {DSLASH_RTOL:g}), tpu_custom_call present")
        require(err <= DSLASH_RTOL, f"dslash_half_pallas parity {p}")
    kern = _compiled_kernel(dslash_pallas, U, psi)
    err = _rel_max(kern(U, psi), jax.jit(dslash_ref)(U, psi))
    log(f"[dslash] full lattice: max rel err {err:.3e} "
        f"(bound {DSLASH_RTOL:g}), tpu_custom_call present")
    require(err <= DSLASH_RTOL, "dslash_pallas")


def true_residual(U, x, b, kappa, mesh=None) -> float:
    """‖b − M x‖ / ‖b‖ with the full-lattice operator (T-sharded over
    ``mesh`` when given), which shares no code with the even-odd solver."""
    import jax
    import jax.numpy as jnp
    from repro.lqcd.dirac import dslash
    from repro.lqcd.multichip import dslash_sharded

    @jax.jit
    def rel(U, x, b):
        d = dslash(U, x) if mesh is None else dslash_sharded(U, x, mesh)
        return jnp.linalg.norm(b - (x - kappa * d)) / jnp.linalg.norm(b)

    return float(rel(U, x, b))


def solve(U, b, kappa, label: str, **kw):
    """One EO mixed-precision solve, checked; returns the result."""
    from repro.configs.lcsc_lqcd import EO_MIXED_SOLVER
    from repro.lqcd.cg import solve_dirac

    t0 = time.perf_counter()
    res = solve_dirac(U, b, kappa, EO_MIXED_SOLVER, **kw)
    res.x.block_until_ready()
    secs = time.perf_counter() - t0
    check = true_residual(U, res.x, b, kappa, kw.get("mesh"))
    log(f"[{label}] converged={res.converged} inner={res.iters} "
        f"outer={res.outer_iters} solver residual={res.rel_residual:.3e} "
        f"recomputed residual={check:.3e} ({secs:.2f} s, one run)")
    require(res.converged and res.rel_residual <= SOLVE_TOL
            and check <= SOLVE_TOL, f"{label}: residual above {SOLVE_TOL:g}")
    return res, secs


def phase_solve(U, b, kappa) -> None:
    import jax
    res, first = solve(U, b, kappa, "solve")
    _, second = solve(U, b, kappa, "solve again")
    peak = jax.devices()[0].memory_stats() or {}
    log(f"[solve] first call {first:.2f} s (compiles and runs), second "
        f"{second:.2f} s (runs): about {first - second:.1f} s of compile; "
        f"peak device memory {peak.get('peak_bytes_in_use', 0) / 2**30:.2f}"
        " GiB")


def phase_hpl() -> None:
    from repro.configs.hpl import HPLConfig
    from repro.hpl.linpack import linpack_run

    t0 = time.perf_counter()
    r = linpack_run(HPLConfig(n=HPL_N, block=HPL_NB))
    log(f"[hpl] n={r.n} nb={r.block} scaled residual={r.residual:.3f} "
        f"(pass < 16) passed={r.passed}; factor {r.wall_s:.2f} s, "
        f"{r.gflops:.1f} GFLOP/s (the run after the compiling one; one "
        "run, not a benchmark); "
        f"phase {time.perf_counter() - t0:.1f} s")
    require(r.passed, f"HPL scaled residual {r.residual:.3f} >= 16")


def phase_sharded(seed: int) -> None:
    """The T-sharded EO solve over four chips against one chip."""
    import jax.numpy as jnp
    from repro.configs.lcsc_lqcd import COLD_LATTICE, THERMAL_LATTICE
    from repro.distributed.sharding import lattice_mesh

    kappa = THERMAL_LATTICE.kappa
    U, b = make_fields(seed, THERMAL_LATTICE.shape)
    ref, _ = solve(U, b, kappa, "one chip 32^3x8")
    mesh = lattice_mesh(THERMAL_LATTICE.shape[3], 4)
    for backend in ("jnp", "pallas"):
        got, _ = solve(U, b, kappa, f"4 chips 32^3x8 {backend}", mesh=mesh,
                       backend=backend)
        dx = float(jnp.linalg.norm(got.x - ref.x) / jnp.linalg.norm(ref.x))
        log(f"[sharded {backend}] iterations {got.iters}+{got.outer_iters} "
            f"vs one chip {ref.iters}+{ref.outer_iters}; "
            f"||x - x_1chip|| / ||x_1chip|| = {dx:.3e} "
            f"(bound {SHARDED_X_RTOL:g})")
        require((got.iters, got.outer_iters) == (ref.iters, ref.outer_iters),
                f"sharded {backend}: iteration counts differ from one chip")
        require(dx <= SHARDED_X_RTOL, f"sharded {backend}: solution differs")
    del U, b, ref, got
    # 32^3 x 32 (a thermal lattice per chip) and the cold lattice
    # (32^3 x 16 per chip), which fit four chips on the Pallas backend
    for lat in (THERMAL_LATTICE.shape[:3] + (32,), COLD_LATTICE.shape):
        U, b = make_fields(seed, lat)
        solve(U, b, kappa, "4 chips {}^3x{} pallas".format(lat[0], lat[3]),
              mesh=lattice_mesh(lat[3], 4), backend="pallas")
        del U, b


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the T-sharded solve on four chips")
    args = ap.parse_args()

    device = phase_device(4 if args.four_chips else 1)
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"[device] compile cache: {enable_compile_cache()}")

    if args.four_chips:
        phase_sharded(args.seed)
    else:
        from repro.configs.lcsc_lqcd import THERMAL_LATTICE
        U, psi = make_fields(args.seed, THERMAL_LATTICE.shape)
        phase_dslash(U, psi)
        phase_solve(U, psi, THERMAL_LATTICE.kappa)
        del U, psi
        phase_hpl()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The paper's workload: invert the Wilson-Dirac operator with CG on a
thermal lattice, using the Pallas D-slash kernel, with the energy plan the
framework derives for it (memory-bound -> deep clock derate, <1.5% loss),
and the plain-vs-even-odd mixed-precision energy-to-solution comparison.

  PYTHONPATH=src python examples/lqcd_cg.py
"""
import time

import jax
import jax.numpy as jnp

from repro.config import EnergyConfig
from repro.core.energy import solver_energy
from repro.core.energy.dvfs import plan_frequency
from repro.kernels.dslash import dslash_pallas, dslash_ref
from repro.lqcd import (dslash_bytes_per_site, dslash_flops_per_site,
                        random_su3_field, solve_wilson, solve_wilson_eo)
from repro.roofline import hw


def main() -> None:
    lattice = (8, 8, 8, 8)        # thermal (T > 0) smoke lattice
    kappa = 0.12
    key = jax.random.PRNGKey(0)
    U = random_su3_field(key, lattice)
    kr, ki = jax.random.split(key)
    b = (jax.random.normal(kr, lattice + (4, 3))
         + 1j * jax.random.normal(ki, lattice + (4, 3))
         ).astype(jnp.complex64)

    # Pallas kernel cross-check: compiled on a TPU, interpret mode elsewhere
    got = dslash_pallas(U, b, t_block=4)
    want = dslash_ref(U, b)
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"Pallas D-slash vs oracle: max err {err:.2e}")

    t0 = time.time()
    res = solve_wilson(U, b, kappa, tol=1e-6, max_iters=1000)
    dt = time.time() - t0
    vol = 8 ** 4
    platform = jax.devices()[0].platform
    # each CG iteration applies D-slash twice (M and M-dagger)
    gflops = 2 * int(res.iters) * vol * dslash_flops_per_site() / dt / 1e9
    print(f"CG converged={bool(res.converged)} iters={int(res.iters)} "
          f"rel_resid={float(res.rel_residual):.2e} ({dt:.1f}s, "
          f"{gflops:.2f} GFLOPS on {platform}, compile included)")

    # the paper's solver-level optimization: even-odd Schur CG with a
    # bf16 inner / f32 outer defect-correction loop (CL2QCD strategy)
    t0 = time.time()
    eo = solve_wilson_eo(U, b, kappa, tol=1e-6, max_iters=1000,
                         inner_dtype=jnp.bfloat16)
    dt_eo = time.time() - t0
    print(f"EO mixed CG converged={eo.converged} normal_ops={eo.iters}"
          f"+{eo.outer_iters} (plain: {int(res.iters)}) "
          f"rel_resid={eo.rel_residual:.2e} ({dt_eo:.1f}s)")
    e_plain = solver_energy("plain_f32", vol, int(res.iters))
    e_eo = solver_energy("eo_bf16", vol, eo.iters, outer_ops=eo.outer_iters,
                         inner_real_bytes=2, even_odd=True)
    print(f"energy-to-solution (S9150 model): plain={e_plain.energy_j:.3e} J"
          f" @ {e_plain.gflops_per_w:.2f} GFLOPS/W -> "
          f"eo_bf16={e_eo.energy_j:.3e} J @ {e_eo.gflops_per_w:.2f} GFLOPS/W"
          f" ({1 - e_eo.energy_j / e_plain.energy_j:.0%} saved)")

    # the paper's C5: D-slash is memory-bound -> the DVFS plan derates
    ai = dslash_flops_per_site() / dslash_bytes_per_site(4)
    compute_s = 1.0 / hw.PEAK_BF16_FLOPS
    memory_s = (1.0 / ai) / hw.HBM_BW
    plan = plan_frequency(compute_s, memory_s, 0.0, flops_per_step=1e12,
                          cfg=EnergyConfig(mode="efficiency"))
    print(f"energy plan: dominant={plan.dominant} freq={plan.freq_scale:.2f}"
          f" perf_loss={plan.perf_loss:.3%} (paper: <1.5%)")


if __name__ == "__main__":
    main()

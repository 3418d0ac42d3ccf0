"""Even-odd preconditioned / mixed-precision solver suite.

Covers the compact checkerboard decomposition (pack/unpack, hopping
operators), the Schur-complement solve against the full-lattice CGNE, the
bf16 defect-correction loop, the even-odd Pallas kernel, the config
dispatch, and the energy-to-solution accounting.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.lqcd import (dslash, random_su3_field, solve_dirac, solve_wilson,
                        solve_wilson_eo, wilson_matvec)
from repro.lqcd.dirac import eo_matvec, parity_mask
from repro.lqcd import eo as EO

SHAPE = (4, 4, 4, 4)


def _fields(shape=SHAPE, seed=0):
    ku, kr, ki = jax.random.split(jax.random.PRNGKey(seed), 3)
    U = random_su3_field(ku, shape)
    b = (jax.random.normal(kr, shape + (4, 3))
         + 1j * jax.random.normal(ki, shape + (4, 3))).astype(jnp.complex64)
    return U, b


def test_eo_pack_unpack_roundtrip():
    _, psi = _fields((4, 6, 4, 6))
    pe, po = EO.eo_pack(psi, 0), EO.eo_pack(psi, 1)
    assert pe.shape == (2, 6, 4, 6, 4, 3)
    back = EO.eo_unpack(pe, po)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(psi))


def test_eo_pack_selects_parities():
    """Packed halves hold exactly the (x+y+z+t) even / odd sites."""
    shape = (4, 4, 4, 4)
    x, y, z, t = np.indices(shape)
    par = ((x + y + z + t) % 2).astype(np.complex64)
    field = jnp.asarray(par)[..., None, None] * jnp.ones(shape + (4, 3),
                                                         jnp.complex64)
    assert float(jnp.max(jnp.abs(EO.eo_pack(field, 0)))) == 0.0
    assert float(jnp.min(jnp.abs(EO.eo_pack(field, 1)))) == 1.0


@pytest.mark.parametrize("src_parity", [0, 1])
@pytest.mark.parametrize("shape", [(4, 4, 4, 4), (4, 6, 4, 8)])
def test_dslash_half_matches_masked_full(shape, src_parity):
    """Compact hop == full-lattice D-slash on the masked field."""
    U, psi = _fields(shape, seed=1)
    mask_e = parity_mask(shape)
    U_e, U_o = EO.pack_gauge(U)
    src_mask = mask_e if src_parity == 0 else ~mask_e
    full_src = jnp.where(src_mask[..., None, None], psi, 0)
    want = EO.eo_pack(dslash(U, full_src), 1 - src_parity)
    half = EO.eo_pack(psi, src_parity)
    U_out, U_src = (U_o, U_e) if src_parity == 0 else (U_e, U_o)
    got = EO.dslash_half(U_out, U_src, half, src_parity=src_parity)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_schur_matches_masked_eo_operator():
    """Compact Schur A == the masked full-lattice A of dirac.eo_matvec."""
    U, psi = _fields(seed=2)
    kappa = 0.11
    mask_e = parity_mask(SHAPE)
    psi_e_full = jnp.where(mask_e[..., None, None], psi, 0)
    want_full = eo_matvec(U, psi_e_full, kappa, mask_e)
    U_e, U_o = EO.pack_gauge(U)
    got = EO.schur_matvec(U_e, U_o, EO.eo_pack(psi, 0), kappa)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(EO.eo_pack(want_full, 0)),
                               rtol=1e-5, atol=1e-5)


def test_eo_solution_matches_full_cgne():
    U, b = _fields(seed=3)
    kappa = 0.1
    full = solve_wilson(U, b, kappa, tol=1e-6, max_iters=600)
    eo = solve_wilson_eo(U, b, kappa, tol=1e-6, max_iters=600)
    assert bool(full.converged) and eo.converged
    # both solve the same (nonsingular) system -> same solution
    np.testing.assert_allclose(np.asarray(eo.x), np.asarray(full.x),
                               rtol=2e-4, atol=2e-4)
    # the residual the solver reports is the true one: ‖b − M x‖ of the
    # returned x, recomputed in float64 (an f32 recomputation carries
    # cancellation noise of the same order as the tolerance at 5e-7)
    with jax.enable_x64(True):
        U64, x64, b64 = (jnp.asarray(np.asarray(a), jnp.complex128)
                         for a in (U, eo.x, b))
        r = b64 - wilson_matvec(U64, x64, kappa)
        rel = float(jnp.linalg.norm(r.reshape(-1))
                    / jnp.linalg.norm(b64.reshape(-1)))
    assert rel == pytest.approx(eo.rel_residual, rel=1e-3)
    assert rel <= 1e-6


def test_preconditioning_cuts_iterations():
    """The Schur spectrum contracts quadratically: fewer normal ops."""
    U, b = _fields((8, 8, 8, 8), seed=0)
    kappa = 0.12
    full = solve_wilson(U, b, kappa, tol=1e-6, max_iters=1000)
    eo = solve_wilson_eo(U, b, kappa, tol=1e-6, max_iters=1000)
    assert bool(full.converged) and eo.converged
    assert eo.iters + eo.outer_iters < int(full.iters)


def test_mixed_precision_bf16_converges_to_tol():
    """bf16 inner + f32 reliable updates reaches the f32 tolerance on the
    acceptance lattice, in fewer normal ops than the plain solver."""
    U, b = _fields((8, 8, 8, 8), seed=0)
    kappa = 0.12
    plain = solve_wilson(U, b, kappa, tol=1e-6, max_iters=1000)
    eo = solve_wilson_eo(U, b, kappa, tol=1e-6, max_iters=1000,
                         inner_dtype=jnp.bfloat16)
    assert eo.converged and eo.rel_residual <= 1e-6
    assert eo.outer_iters > 1          # bf16 alone can't reach 1e-6
    assert eo.iters + eo.outer_iters < int(plain.iters)


def test_mixed_precision_inner_really_rounds():
    """The inner operator must quantize: bf16 path differs from f32 path
    on a single inner application (guards against a silent no-op cast)."""
    from repro.lqcd.cg import _round_complex
    v = (jnp.arange(1, 13, dtype=jnp.float32) / 7.0).astype(jnp.complex64)
    rounded = _round_complex(v, jnp.bfloat16)
    assert float(jnp.max(jnp.abs(rounded - v))) > 0
    assert float(jnp.max(jnp.abs(rounded - v))) < 1e-2


def test_eo_pallas_kernel_matches_reference():
    from repro.kernels.dslash import dslash_half_pallas
    U, psi = _fields((4, 6, 4, 8), seed=4)
    U_e, U_o = EO.pack_gauge(U)
    for p in (0, 1):
        half = EO.eo_pack(psi, p)
        U_out, U_src = (U_o, U_e) if p == 0 else (U_e, U_o)
        want = EO.dslash_half(U_out, U_src, half, src_parity=p)
        got = dslash_half_pallas(U_e, U_o, half, p, t_block=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_solve_dirac_config_dispatch():
    from repro.configs.lcsc_lqcd import (EO_MIXED_SOLVER, EO_SOLVER,
                                         PLAIN_SOLVER)
    U, b = _fields(seed=5)
    kappa = 0.1
    for cfg in (PLAIN_SOLVER, EO_SOLVER, EO_MIXED_SOLVER):
        res = solve_dirac(U, b, kappa, cfg)
        assert bool(res.converged), cfg
        r = b - wilson_matvec(U, res.x, kappa)
        rel = float(jnp.linalg.norm(r.reshape(-1))
                    / jnp.linalg.norm(b.reshape(-1)))
        assert rel < 1e-5, cfg


def test_solver_energy_accounting():
    from repro.core.energy import solver_energy
    vol = 8 ** 4
    plain = solver_energy("plain", vol, 27)
    eo = solver_energy("eo", vol, 15, outer_ops=3, inner_real_bytes=2,
                       even_odd=True)
    # fewer ops at half the bytes -> less energy, better GFLOPS/W
    assert eo.energy_j < plain.energy_j
    assert eo.gflops_per_w > plain.gflops_per_w
    # scale invariance: energy is linear in ops
    assert solver_energy("p2", vol, 54).energy_j == \
        pytest.approx(2 * plain.energy_j)

"""The plain reference operator against the program's, the benchmark's own
gauge field, and the control that the comparison has to fail."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_helpers  # noqa: F401  (puts the repository on the path)
from benchmarks.chip import fields, reference
from repro.lqcd.dirac import GAMMA, GAMMA5, wilson_matvec

TRAFFIC = {"sources": "point", "clients": 1, "spins": 4, "colours": 3}


def _spinor(key, lattice):
    kr, ki = jax.random.split(key)
    shape = tuple(lattice) + (4, 3)
    return (jax.random.normal(kr, shape)
            + 1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


def test_gamma_matrices_are_the_programs_basis():
    g = reference.gamma_matrices()
    np.testing.assert_array_equal(g, np.asarray(GAMMA))
    np.testing.assert_array_equal(g[3] @ g[0] @ g[1] @ g[2],
                                  np.asarray(GAMMA5))
    for mu in range(4):
        for nu in range(4):
            anti = g[mu] @ g[nu] + g[nu] @ g[mu]
            np.testing.assert_allclose(anti, 2 * np.eye(4) * (mu == nu))


@pytest.mark.parametrize("lattice", [(4, 4, 4, 4), (8, 8, 8, 8)])
def test_reference_matches_wilson_matvec(lattice):
    U, _ = fields.make_inputs(5, lattice, TRAFFIC)
    psi = _spinor(jax.random.key(6), lattice)
    for kappa in (0.137, 0.2):
        ref = reference.wilson(U, psi, kappa)
        got = wilson_matvec(U, psi, kappa)
        err = float(jnp.max(jnp.abs(ref - got)) / jnp.max(jnp.abs(ref)))
        assert err < 1e-6


def test_gauge_field_is_su3_and_seeded():
    U, sources = fields.make_inputs(2 ** 31 + 7, (4, 4, 4, 4), TRAFFIC)
    assert U.shape == (4, 4, 4, 4, 4, 3, 3) and U.dtype == jnp.complex64
    uu = jnp.einsum("...ab,...cb->...ac", U, jnp.conj(U),
                    precision="highest")
    assert float(jnp.max(jnp.abs(uu - jnp.eye(3)))) < 1e-5
    assert float(jnp.max(jnp.abs(jnp.linalg.det(U) - 1))) < 1e-5
    U2, _ = fields.make_inputs(2 ** 31 + 7, (4, 4, 4, 4), TRAFFIC)
    np.testing.assert_array_equal(np.asarray(U), np.asarray(U2))
    U3, _ = fields.make_inputs(2 ** 31 + 8, (4, 4, 4, 4), TRAFFIC)
    assert not np.array_equal(np.asarray(U), np.asarray(U3))
    assert len(sources) == 12
    site = fields.source_site(2 ** 31 + 7, (4, 4, 4, 4))
    for i, b in enumerate(sources):
        assert float(jnp.sum(jnp.abs(b))) == 1.0
        assert float(jnp.abs(b[site + divmod(i, 3)])) == 1.0


def test_residual_of_a_solve_and_of_its_control():
    """A float32 solve reaches the limit; the control, the reference put
    in its place one precision lower (bfloat16), stays far above it."""
    from repro.configs.lcsc_lqcd import EO_MIXED_SOLVER
    from repro.lqcd.cg import solve_dirac
    lattice, kappa, limit = (4, 4, 4, 4), 0.2, EO_MIXED_SOLVER.tol
    for seed in (1, 2, 3):
        U, sources = fields.make_inputs(seed, lattice, TRAFFIC)
        b = sources[seed]
        x = solve_dirac(U, b, kappa, EO_MIXED_SOLVER).x
        assert float(reference.relative_residual(U, x, b, kappa)) <= limit
        xc = reference.control_solve(U, b, kappa, 300)
        control = float(reference.relative_residual(U, xc, b, kappa))
        assert control > 30 * limit

"""The plain reference: the full-lattice Wilson-Dirac operator, and the
control that has to fail the comparison.

    (M psi)(x) = psi(x) - kappa sum_mu [ (1 - g_mu) U_mu(x) psi(x + mu)
                                       + (1 + g_mu) U_mu(x - mu)^H psi(x - mu) ]

with periodic boundaries and the Dirac basis of the gamma matrices
(g_t = diag(1, 1, -1, -1), g_k = [[0, -i s_k], [i s_k, 0]] for the Pauli
matrices s_k), the convention of the solver under test.  Written from the
definition: it imports nothing of the program.  Every contraction is an
elementwise multiply-add in float32, which is exact float32 on the TPU's
vector unit (a matrix unit would round f32 through bf16 at default
precision); the whole operator also runs under
``jax.default_matmul_precision("highest")``.

Fields: ``psi`` ``(X, Y, Z, T, 4, 3)`` and ``U`` ``(4, X, Y, Z, T, 3, 3)``,
complex64.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_PAULI = (np.array([[0, 1], [1, 0]]), np.array([[0, -1j], [1j, 0]]),
          np.array([[1, 0], [0, -1]]))


def gamma_matrices() -> np.ndarray:
    """(g_x, g_y, g_z, g_t) in the Dirac basis, shape (4, 4, 4)."""
    zero = np.zeros((2, 2))
    gs = [np.block([[zero, -1j * s], [1j * s, zero]]) for s in _PAULI]
    gs.append(np.diag([1.0, 1.0, -1.0, -1.0]))
    return np.stack(gs).astype(np.complex64)


def _spin(p: np.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """4x4 spin matrix ``p`` (static) on (..., 4, 3); zero entries skipped."""
    rows = []
    for s in range(4):
        acc = None
        for t in range(4):
            if p[s, t] != 0:
                term = complex(p[s, t]) * v[..., t, :]
                acc = term if acc is None else acc + term
        rows.append(jnp.zeros_like(v[..., 0, :]) if acc is None else acc)
    return jnp.stack(rows, axis=-2)


def _link(u: jnp.ndarray, v: jnp.ndarray, dagger: bool) -> jnp.ndarray:
    """U v (or U^H v) at every site for (..., 3, 3) links, (..., 4, 3)
    spinors."""
    if dagger:
        u = jnp.conj(jnp.swapaxes(u, -1, -2))
    return sum(u[..., None, :, b] * v[..., :, b, None] for b in range(3))


def wilson(U: jnp.ndarray, psi: jnp.ndarray, kappa) -> jnp.ndarray:
    """M psi = psi - kappa D psi."""
    g = gamma_matrices()
    one = np.eye(4, dtype=np.complex64)
    hop = jnp.zeros_like(psi)
    for mu in range(4):
        fwd = _link(U[mu], jnp.roll(psi, -1, axis=mu), dagger=False)
        bwd = _link(jnp.roll(U[mu], 1, axis=mu), jnp.roll(psi, 1, axis=mu),
                    dagger=True)
        hop = hop + _spin(one - g[mu], fwd) + _spin(one + g[mu], bwd)
    return psi - kappa * hop


def _norm(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.sum(jnp.real(v) ** 2 + jnp.imag(v) ** 2))


@jax.jit
def relative_residual(U, x, b, kappa) -> jnp.ndarray:
    """||b - M x|| / ||b||, in float32."""
    with jax.default_matmul_precision("highest"):
        return _norm(b - wilson(U, x, kappa)) / _norm(b)


# ---------------------------------------------------------------------------
# The control: the reference put in the solver's place one precision lower
# ---------------------------------------------------------------------------

def round_bf16(v: jnp.ndarray) -> jnp.ndarray:
    """A complex64 field rounded through bfloat16, plane by plane (JAX has
    no complex bfloat16)."""
    re = jnp.real(v).astype(jnp.bfloat16).astype(jnp.float32)
    im = jnp.imag(v).astype(jnp.bfloat16).astype(jnp.float32)
    return jax.lax.complex(re, im)


def _gamma5(v: jnp.ndarray) -> jnp.ndarray:
    g = gamma_matrices()
    return _spin(g[3] @ g[0] @ g[1] @ g[2], v)


@jax.jit
def control_solve(U, b, kappa, iters) -> jnp.ndarray:
    """CG on the normal equations M^H M x = M^H b, with the reference
    operator and every field it stores rounded through bfloat16: the solve
    the configuration states (outer residual in float32) computed one
    precision lower.  ``iters`` normal ops, no early exit."""
    U = round_bf16(U)

    def m(v):
        return round_bf16(wilson(U, round_bf16(v), kappa))

    def m_dag(v):  # M^H = g5 M g5
        return round_bf16(_gamma5(m(_gamma5(v))))

    def dot(a, c):
        return jnp.sum(jnp.real(a) * jnp.real(c) + jnp.imag(a) * jnp.imag(c))

    def body(_, state):
        x, r, p, rs = state
        ap = m_dag(m(p))
        alpha = rs / jnp.maximum(dot(p, ap), 1e-30)
        x = round_bf16(x + alpha * p)
        r = round_bf16(r - alpha * ap)
        rs_new = dot(r, r)
        p = round_bf16(r + (rs_new / jnp.maximum(rs, 1e-30)) * p)
        return x, r, p, rs_new

    rhs = m_dag(b)
    x0 = jnp.zeros_like(b)
    x, *_ = jax.lax.fori_loop(0, iters, body, (x0, rhs, rhs, dot(rhs, rhs)))
    return x

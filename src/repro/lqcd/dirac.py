"""Wilson-Dirac operator.

D-slash is the sparse stencil at the heart of LQCD (paper §Introduction):

  (D ψ)(x) = Σ_μ [ (1 − γ_μ) U_μ(x) ψ(x+μ̂) + (1 + γ_μ) U†_μ(x−μ̂) ψ(x−μ̂) ]

with periodic boundaries.  The full Wilson operator is M = 1 − κ D.
It is memory-bandwidth-bound: 1320 flops/site against ~1.4 KB/site of
streamed spinors+links in fp32 — exactly why L-CSC was built around GPU
memory bandwidth.

Fields:
  psi: (X, Y, Z, T, 4, 3) complex64   (spin, color)
  U:   (4, X, Y, Z, T, 3, 3) complex64 (direction-major)
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

# Dirac gamma matrices (Dirac basis), complex64
_g0 = np.array([[1, 0, 0, 0], [0, 1, 0, 0], [0, 0, -1, 0], [0, 0, 0, -1]],
               np.complex64)
_g1 = np.array([[0, 0, 0, -1j], [0, 0, -1j, 0], [0, 1j, 0, 0],
                [1j, 0, 0, 0]], np.complex64)
_g2 = np.array([[0, 0, 0, -1], [0, 0, 1, 0], [0, 1, 0, 0], [-1, 0, 0, 0]],
               np.complex64)
_g3 = np.array([[0, 0, -1j, 0], [0, 0, 0, 1j], [1j, 0, 0, 0],
                [0, -1j, 0, 0]], np.complex64)
GAMMA = jnp.stack([jnp.asarray(_g1), jnp.asarray(_g2), jnp.asarray(_g3),
                   jnp.asarray(_g0)])   # order: x, y, z, t
EYE4 = jnp.eye(4, dtype=jnp.complex64)


def dslash_flops_per_site() -> int:
    """Standard Wilson D-slash flop count (real ops) per lattice site."""
    return 1320


def dslash_bytes_per_site(real_bytes: int = 8,
                          compressed_links: bool = True) -> int:
    """Streaming traffic per site: 8 neighbor spinor loads + read/write of
    the output spinor (24 reals each) + 8 gauge links.

    CL2QCD stores links compressed to 8 reals and reconstructs SU(3) on the
    fly (Bach et al. [1]) — that compression is what puts the published
    135 GFLOPS at ~80% of the 320 GB/s S9150 bandwidth in fp64."""
    link_reals = 8 if compressed_links else 18
    reals = 8 * 24 + 24 + 24 + 8 * link_reals
    return reals * real_bytes


# The 3x3 colour and 4x4 spin contractions are written as elementwise
# multiply-adds, not einsums: on a TPU an f32 einsum runs on the MXU at
# bf16 accuracy by default, and at HIGHEST precision its padded temporaries
# do not fit one chip at 32^3 x 8.  Elementwise, they are exact f32 on the
# vector unit.

def mv(u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """U v per site: (..., 3, 3) links on (..., 4, 3) spinors."""
    return jnp.sum(u[..., None, :, :] * v[..., :, None, :], axis=-1)


def mv_dag(u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """U† v per site: (U†)_ab = conj(U_ba)."""
    return jnp.sum(jnp.conj(u)[..., None, :, :] * v[..., :, :, None],
                   axis=-2)


def spin(proj: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """A 4x4 spin matrix on (..., 4, 3) spinors."""
    return jnp.sum(proj[:, :, None] * v[..., None, :, :], axis=-2)


def dslash(U: jnp.ndarray, psi: jnp.ndarray) -> jnp.ndarray:
    """Apply D-slash with periodic boundaries via jnp.roll (reference)."""
    out = jnp.zeros_like(psi)
    for mu in range(4):
        axis = mu
        g = GAMMA[mu]
        u = U[mu]
        # forward: (1 - γ_mu) U_mu(x) psi(x+mu)
        psi_fwd = jnp.roll(psi, -1, axis=axis)
        out = out + spin(EYE4 - g, mv(u, psi_fwd))
        # backward: (1 + γ_mu) U†_mu(x-mu) psi(x-mu)
        u_bwd = jnp.roll(u, 1, axis=axis)
        psi_bwd = jnp.roll(psi, 1, axis=axis)
        out = out + spin(EYE4 + g, mv_dag(u_bwd, psi_bwd))
    return out


def wilson_matvec(U: jnp.ndarray, psi: jnp.ndarray,
                  kappa: float) -> jnp.ndarray:
    """M ψ = ψ − κ D ψ."""
    return psi - kappa * dslash(U, psi)


# γ5 = γ0 γ1 γ2 γ3 in the Dirac basis: off-diagonal identity blocks
GAMMA5 = jnp.asarray(np.array(
    [[0, 0, 1, 0], [0, 0, 0, 1], [1, 0, 0, 0], [0, 1, 0, 0]], np.complex64))


def wilson_matvec_dagger(U: jnp.ndarray, psi: jnp.ndarray,
                         kappa: float) -> jnp.ndarray:
    """M† ψ via γ5-hermiticity: M† = γ5 M γ5."""
    return spin(GAMMA5, wilson_matvec(U, spin(GAMMA5, psi), kappa))


# ---------------------------------------------------------------------------
# Even-odd (red-black) preconditioning (paper: CL2QCD uses it)
# ---------------------------------------------------------------------------

def parity_mask(shape: Tuple[int, int, int, int]) -> jnp.ndarray:
    """Boolean mask, True on even sites ((x+y+z+t) % 2 == 0)."""
    grids = jnp.meshgrid(*[jnp.arange(s) for s in shape], indexing="ij")
    return (sum(grids) % 2) == 0


def eo_matvec(U: jnp.ndarray, psi_e: jnp.ndarray, kappa: float,
              mask_e: jnp.ndarray) -> jnp.ndarray:
    """Even-odd preconditioned operator  A = 1 − κ² D_eo D_oe  acting on
    even-site spinors (odd entries of psi_e are kept zero)."""
    d1 = dslash(U, psi_e)
    d1 = jnp.where(mask_e[..., None, None], 0.0, d1)   # keep odd part
    d2 = dslash(U, d1)
    d2 = jnp.where(mask_e[..., None, None], d2, 0.0)   # back to even
    return psi_e - (kappa * kappa) * d2


# ---------------------------------------------------------------------------
# Dense cross-check helper (tiny lattices only)
# ---------------------------------------------------------------------------

def dslash_dense_matrix(U: jnp.ndarray) -> np.ndarray:
    """Build the explicit dense D-slash matrix by applying it to basis
    vectors — O((V·12)²) memory; use on ≤ 4⁴ lattices in tests."""
    shape = U.shape[1:5]
    vol = int(np.prod(shape)) * 12
    cols = []
    for i in range(vol):
        e = np.zeros((vol,), np.complex64)
        e[i] = 1.0
        psi = jnp.asarray(e.reshape(shape + (4, 3)))
        cols.append(np.asarray(dslash(U, psi)).reshape(-1))
    return np.stack(cols, axis=1)

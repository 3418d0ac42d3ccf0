"""Even-odd (red-black) site decomposition of the Wilson operator.

The paper's solver-level bandwidth optimization (§Introduction, CL2QCD):
color the lattice by site parity p = (x+y+z+t) mod 2.  D-slash only couples
opposite parities, so in the parity basis the Wilson operator is

    M = [[ 1,        -kappa D_eo ],
         [ -kappa D_oe,        1 ]]

and the Schur complement of the odd block,

    A = M_ee - M_eo M_oo^{-1} M_oe = 1 - kappa^2 D_eo D_oe ,

acts on even sites only.  Solving A x_e = b_e + kappa D_eo b_o and
reconstructing x_o = b_o + kappa D_oe x_e is exactly equivalent to solving
M x = b, but every CG vector is half as long (half the memory traffic of
the bandwidth-bound axpy/dot stream) and A is better conditioned than M,
so CG needs fewer iterations on top.

Compact storage ("checkerboard" layout along x, X even):

    half[i, y, z, t] = full[2*i + ((y + z + t + p) % 2), y, z, t]

i.e. each half-field has shape (X//2, Y, Z, T, ...).  With this layout the
hops of D-slash become:

    y/z/t hops : plain rolls along that axis (the compact x-index of the
                 neighbour is unchanged — see ``_hop_parity`` note);
    x hops     : a roll that applies only where s = (y+z+t+p) % 2 says the
                 neighbour wrapped past a cell boundary.

All functions below are jittable; parities are 0 = even, 1 = odd.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.lqcd.dirac import EYE4, GAMMA, GAMMA5, mv, mv_dag, spin

PROJ_M = jnp.stack([EYE4 - GAMMA[mu] for mu in range(4)])   # (1 - gamma_mu)
PROJ_P = jnp.stack([EYE4 + GAMMA[mu] for mu in range(4)])   # (1 + gamma_mu)


def _sublattice_offset(shape: Tuple[int, ...], parity: int) -> np.ndarray:
    """s(y,z,t) = (y+z+t+parity) % 2 — the x offset of the first site of
    ``parity`` on each (y,z,t) line.  Static numpy, shape (1, Y, Z, T)."""
    _, Y, Z, T = shape[:4]
    y, z, t = np.indices((Y, Z, T))
    return ((y + z + t + parity) % 2)[None]


def _offset_mask(shape: Tuple[int, ...], parity: int, ndim: int):
    """s(y,z,t) == 1 for ``parity``, shaped to broadcast against a compact
    field of ``ndim`` dims (site axes lead)."""
    s = _sublattice_offset(shape, parity)
    return (s == 1).reshape(s.shape + (1,) * (ndim - 4))


def eo_pack(field: jnp.ndarray, parity: int) -> jnp.ndarray:
    """Gather the ``parity`` sites of a full-lattice field (site axes lead)
    into the compact (X//2, Y, Z, T, ...) layout.

    Pure selection (reshape x into pairs, pick the pair member s), so a
    field sharded on T stays sharded."""
    X = field.shape[0]
    if X % 2:
        raise ValueError(
            f"even-odd packing needs an even x extent, got X={X}")
    pairs = field.reshape((X // 2, 2) + field.shape[1:])
    odd = _offset_mask(field.shape, parity, field.ndim)
    return jnp.where(odd, pairs[:, 1], pairs[:, 0])


def eo_unpack(half_e: jnp.ndarray, half_o: jnp.ndarray) -> jnp.ndarray:
    """Interleave compact even/odd half-fields back into a full field."""
    Xh = half_e.shape[0]
    full_shape = (2 * Xh,) + half_e.shape[1:]
    # where the even site comes second in its x pair, the odd one is first
    even_second = _offset_mask(full_shape, 0, half_e.ndim)
    first = jnp.where(even_second, half_o, half_e)
    second = jnp.where(even_second, half_e, half_o)
    return jnp.stack([first, second], axis=1).reshape(full_shape)


def pack_gauge(U: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split a (4, X, Y, Z, T, 3, 3) gauge field into per-parity halves of
    shape (4, X//2, Y, Z, T, 3, 3)."""
    Ue = jnp.stack([eo_pack(U[mu], 0) for mu in range(4)])
    Uo = jnp.stack([eo_pack(U[mu], 1) for mu in range(4)])
    return Ue, Uo


def _x_neighbors(src: jnp.ndarray, s_out: jnp.ndarray):
    """Compact +x / -x neighbours of the opposite-parity field ``src`` as
    seen from output sites with offset pattern ``s_out``.

    Output site x = 2i + s_out; its +x neighbour lives at compact index
    i + s_out in the source half-field, its -x neighbour at i + s_out - 1.
    """
    cond = s_out[..., None, None].astype(bool)
    fwd = jnp.where(cond, jnp.roll(src, -1, axis=0), src)
    bwd = jnp.where(cond, src, jnp.roll(src, 1, axis=0))
    return fwd, bwd


def hops_spatial(U_out: jnp.ndarray, U_src: jnp.ndarray, psi: jnp.ndarray,
                 s_out: jnp.ndarray) -> jnp.ndarray:
    """x/y/z hop contributions of one parity block (compact layout).

    ``s_out`` is the output-parity offset pattern: static numpy on the
    single-device path, a traced (global-t aware) array on the T-sharded
    path (:mod:`repro.lqcd.multichip_eo`) — x/y/z hops never cross the
    sharded T axis, so they are identical in both settings.
    """
    # x direction: s-conditional rolls for spinors and the backward link
    psi_fwd, psi_bwd = _x_neighbors(psi, s_out)
    # the -x link sits at the source site = the bwd neighbour's own site
    cond = s_out[..., None, None].astype(bool)
    u_bwd_x = jnp.where(cond, U_src[0], jnp.roll(U_src[0], 1, axis=0))
    out = spin(PROJ_M[0], mv(U_out[0], psi_fwd))
    out = out + spin(PROJ_P[0], mv_dag(u_bwd_x, psi_bwd))

    # y/z directions: plain rolls (axis 1..2 of the compact layout)
    for mu in (1, 2):
        psi_f = jnp.roll(psi, -1, axis=mu)
        psi_b = jnp.roll(psi, 1, axis=mu)
        u_b = jnp.roll(U_src[mu], 1, axis=mu)
        out = out + spin(PROJ_M[mu], mv(U_out[mu], psi_f))
        out = out + spin(PROJ_P[mu], mv_dag(u_b, psi_b))
    return out


def dslash_half(U_out: jnp.ndarray, U_src: jnp.ndarray, psi: jnp.ndarray,
                src_parity: int) -> jnp.ndarray:
    """One parity block of D-slash: input ``psi`` lives on ``src_parity``
    sites, output on the opposite parity.  ``U_out``/``U_src`` are the
    packed gauge halves of the output/source parity.

    y/z/t hops are plain rolls because a unit hop in those directions flips
    the parity but leaves the compact x-index unchanged (the offset pattern
    s absorbs the parity flip).  x hops use the s-conditional roll.
    """
    out_parity = 1 - src_parity
    s_out = jnp.asarray(_sublattice_offset(
        (2 * psi.shape[0],) + psi.shape[1:4], out_parity)[0])

    # named in the compiled program's op metadata, so a device trace can
    # tell the hop's operations from the rest (docs/solvers.md)
    with jax.named_scope("lqcd.hop"):
        out = hops_spatial(U_out, U_src, psi, s_out)

        # t direction: plain rolls (axis 3 of the compact layout)
        mu = 3
        psi_f = jnp.roll(psi, -1, axis=mu)
        psi_b = jnp.roll(psi, 1, axis=mu)
        u_b = jnp.roll(U_src[mu], 1, axis=mu)
        out = out + spin(PROJ_M[mu], mv(U_out[mu], psi_f))
        out = out + spin(PROJ_P[mu], mv_dag(u_b, psi_b))
    return out


def schur_matvec(U_e: jnp.ndarray, U_o: jnp.ndarray, psi_e: jnp.ndarray,
                 kappa: float) -> jnp.ndarray:
    """A psi_e = (1 - kappa^2 D_eo D_oe) psi_e on the even half-lattice."""
    d_oe = dslash_half(U_o, U_e, psi_e, src_parity=0)   # even -> odd
    d_eo = dslash_half(U_e, U_o, d_oe, src_parity=1)    # odd -> even
    return psi_e - (kappa * kappa) * d_eo


def schur_matvec_dagger(U_e: jnp.ndarray, U_o: jnp.ndarray,
                        psi_e: jnp.ndarray, kappa: float) -> jnp.ndarray:
    """A^dagger via gamma5-hermiticity: A^dagger = gamma5 A gamma5 (the
    parity projection commutes with gamma5, so the identity survives the
    Schur reduction)."""
    return spin(GAMMA5, schur_matvec(U_e, U_o, spin(GAMMA5, psi_e), kappa))


def eo_rhs(U_e: jnp.ndarray, U_o: jnp.ndarray, b_e: jnp.ndarray,
           b_o: jnp.ndarray, kappa: float) -> jnp.ndarray:
    """Even-system right-hand side b'_e = b_e + kappa D_eo b_o."""
    return b_e + kappa * dslash_half(U_e, U_o, b_o, src_parity=1)


def reconstruct_odd(U_e: jnp.ndarray, U_o: jnp.ndarray, x_e: jnp.ndarray,
                    b_o: jnp.ndarray, kappa: float) -> jnp.ndarray:
    """Back-substitute the odd sites: x_o = b_o + kappa D_oe x_e."""
    return b_o + kappa * dslash_half(U_o, U_e, x_e, src_parity=0)

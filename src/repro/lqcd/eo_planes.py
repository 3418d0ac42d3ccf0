"""The even-odd Schur operator on lane-dense real planes: the layout of
the single-device inner CG.

The complex layout of :mod:`repro.lqcd.eo` keeps spin and colour as the
minor dims, ``(..., 4, 3)`` and ``(..., 3, 3)``, which a TPU pads to its
(8, 128) tile, and JAX has no complex bfloat16.  Here a field is real,
with re/im, spin and colour as leading axes and the sites last:

    spinor  psi[c, s, a, t, z, y * Xh + i]     (2, 4, 3, T, Z, Y*Xh)
    links     U[c, mu, a, b, t, z, y * Xh + i]  (2, 4, 3, 3, T, Z, Y*Xh)

where ``c`` is re/im and ``i`` the compact x index of ``eo``'s
checkerboard (``half[i, y, z, t] = full[2i + (y+z+t+p) % 2, y, z, t]``).
At 32^3 x 8 every component is one (8, 32, 512) plane, which tiles a
bfloat16 (16, 128) or float32 (8, 128) vreg with no padding, so a field
can be stored at the inner CG's precision with no byte wasted.

The hops are ``eo.dslash_half``'s: t/z hops roll their axis, a y hop
rolls the merged axis by Xh (which wraps y by itself), and an x hop rolls
within each run of Xh where the offset pattern s = (y+z+t+p) % 2 says the
neighbour sits in the next x pair.  Each direction projects the spinor to
the two spin components ``1 -/+ gamma_mu`` keeps, multiplies them by the
link and lifts them back: the same operator, half the colour products.
Colour and spin products are elementwise multiply-adds on the vector
unit, like ``dirac.mv``; every product and sum runs in float32 (or wider)
whatever the storage dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.lqcd.dirac import EYE4, GAMMA, GAMMA5


def _slots(m: np.ndarray, rows) -> list:
    """Rows ``rows`` (a pair) of the constant complex matrix ``m`` as
    slots: each slot is (a pair of adjacent input indices, a pair of
    coefficients), one input per row, and a row is the sum over the
    slots.  The gamma basis of ``dirac`` gives this shape; another basis
    is refused here, at import."""
    terms = [[(j, complex(m[r, j])) for j in range(m.shape[1])
              if abs(m[r, j]) > 1e-6] for r in rows]
    slots = [tuple(zip(*slot)) for slot in zip(*terms)]
    if len(terms[0]) != len(terms[1]) or any(
            abs(a - b) != 1 for (a, b), _ in slots):
        raise ValueError("spin rows do not pair into adjacent slots")
    return slots


def _factor(proj: np.ndarray):
    """Split a rank-2 spin projector as ``proj = lift @ keep``: ``keep``
    (2x4) picks the two spin components the projector keeps, ``lift``
    (4x2) rebuilds the four.  Returned as slots: ``keep`` for the half
    spinor's two rows, and for each output spin pair that ``lift`` fills,
    ``(pair, slots)``."""
    rows = []
    for r in range(4):
        if np.linalg.matrix_rank(proj[rows + [r]]) > len(rows):
            rows.append(r)
    keep = proj[rows]
    lift = np.round(proj @ np.linalg.pinv(keep), 6)
    if not np.allclose(lift @ keep, proj):
        raise ValueError("spin projector does not factor at rank 2")
    pairs = [(pair, _slots(lift, pair)) for pair in ((0, 1), (2, 3))
             if np.abs(lift[list(pair)]).max() > 0]
    return _slots(keep, (0, 1)), pairs


_EYE, _GAMMA = np.asarray(EYE4), np.asarray(GAMMA)
# (keep, lift) of 1 - gamma_mu (forward hops) and 1 + gamma_mu (backward)
_FWD = [_factor(_EYE - _GAMMA[mu]) for mu in range(4)]
_BWD = [_factor(_EYE + _GAMMA[mu]) for mu in range(4)]
# gamma_5 is a spin permutation in this basis
_G5 = np.asarray(GAMMA5)
_G5_PERM = [int(np.flatnonzero(row)[0]) for row in _G5]
if not np.array_equal(_G5, np.eye(4)[_G5_PERM]):
    raise ValueError("gamma_5 is not a plain spin permutation")


# ---------------------------------------------------------------------------
# Conversions (complex compact layout <-> planes)
# ---------------------------------------------------------------------------

def spinor_planes(h: jnp.ndarray) -> jnp.ndarray:
    """(Xh, Y, Z, T, 4, 3) complex half-spinor -> float32 (2, 4, 3, T, Z,
    Y*Xh)."""
    Xh, Y, Z, T = h.shape[:4]
    p = jnp.stack([jnp.real(h), jnp.imag(h)]).astype(jnp.float32)
    return p.transpose(0, 5, 6, 4, 3, 2, 1).reshape(2, 4, 3, T, Z, Y * Xh)


def spinor_complex(p: jnp.ndarray, xh: int) -> jnp.ndarray:
    """Inverse of :func:`spinor_planes`: complex64 (Xh, Y, Z, T, 4, 3)."""
    T, Z, L = p.shape[3:]
    p = p.astype(jnp.float32).reshape(2, 4, 3, T, Z, L // xh, xh)
    p = p.transpose(0, 6, 5, 4, 3, 1, 2)
    return jax.lax.complex(p[0], p[1])


def link_planes(U: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """(4, Xh, Y, Z, T, 3, 3) complex gauge half -> (2, 4, 3, 3, T, Z,
    Y*Xh); already-real planes are only cast to ``dtype``."""
    if not jnp.iscomplexobj(U):
        return U.astype(dtype)
    _, Xh, Y, Z, T = U.shape[:5]
    p = jnp.stack([jnp.real(U), jnp.imag(U)]).astype(dtype)
    return p.transpose(0, 1, 6, 7, 5, 4, 3, 2).reshape(
        2, 4, 3, 3, T, Z, Y * Xh)


# ---------------------------------------------------------------------------
# Neighbours
# ---------------------------------------------------------------------------

def _offset(shape, xh: int, parity: int) -> np.ndarray:
    """s(y, z, t) = (y+z+t+parity) % 2 == 1 over a (T, Z, Y*Xh) plane."""
    T, Z, L = shape
    t, z, l = np.indices((T, Z, L))
    return (t + z + l // xh + parity) % 2 == 1


def _x_roll(f: jnp.ndarray, xh: int, step: int) -> jnp.ndarray:
    """f at compact x index i + step (step = +-1), wrapping inside each run
    of Xh along the merged y*Xh axis."""
    i = np.arange(f.shape[-1]) % xh
    edge = (i == xh - 1) if step > 0 else (i == 0)
    return jnp.where(edge, jnp.roll(f, step * (xh - 1), axis=-1),
                     jnp.roll(f, -step, axis=-1))


def _neighbour(f: jnp.ndarray, mu: int, step: int, xh: int,
               s_out: np.ndarray) -> jnp.ndarray:
    """f at the site ``step`` (+-1) along ``mu`` from each output site."""
    if mu == 0:
        # output x = 2i + s: +x lives at i + s, -x at i + s - 1
        if step > 0:
            return jnp.where(s_out, _x_roll(f, xh, 1), f)
        return jnp.where(s_out, f, _x_roll(f, xh, -1))
    if mu == 1:
        return jnp.roll(f, -step * xh, axis=-1)
    return jnp.roll(f, -step, axis=-2 if mu == 2 else -3)


# ---------------------------------------------------------------------------
# Complex arithmetic: re/im on axis 0 of every array
# ---------------------------------------------------------------------------

def _times(z: jnp.ndarray, coeffs) -> jnp.ndarray:
    """coeffs[k] * z[:, k] for a pair of complex constants (+-1, +-i or 2
    here); z is (2, 2, ...), and i z swaps re and im with a sign."""
    iz = jnp.stack([-z[1], z[0]])
    if coeffs[0] != coeffs[1]:
        ex = (None, slice(None)) + (None,) * (z.ndim - 2)
        re = jnp.asarray([c.real for c in coeffs], z.dtype)[ex]
        im = jnp.asarray([c.imag for c in coeffs], z.dtype)[ex]
        return re * z + im * iz
    (part, scale), = [(p, s) for p, s in ((z, coeffs[0].real),
                                          (iz, coeffs[0].imag)) if s]
    return part if scale == 1 else (-part if scale == -1 else scale * part)


def _pair(z: jnp.ndarray, idx) -> jnp.ndarray:
    """z[:, idx] for a pair of adjacent indices, as a slice."""
    a, b = idx
    return z[:, a:a + 2] if b > a else z[:, b:b + 2][:, ::-1]


def _apply(slots, z: jnp.ndarray) -> jnp.ndarray:
    """Two rows of a constant matrix (:func:`_slots`) applied to z."""
    out = None
    for idx, coeffs in slots:
        t = _times(_pair(z, idx), coeffs)
        out = t if out is None else out + t
    return out


def _mv(u: jnp.ndarray, h: jnp.ndarray, dagger: bool) -> jnp.ndarray:
    """U h (or U^dagger h) per site: u (2, 3, 3, ...) link planes, h a
    (2, 2, 3, ...) half spinor; returns the half spinor's shape."""
    ur, ui = u[0], u[1]
    re = im = None
    for b in range(3):
        if dagger:       # (U^dagger)_ab = conj(U_ba)
            a_r, a_i = ur[b][None], -ui[b][None]
        else:
            a_r, a_i = ur[:, b][None], ui[:, b][None]
        hr, hi = h[0][:, b][:, None], h[1][:, b][:, None]
        tr = a_r * hr - a_i * hi
        ti = a_r * hi + a_i * hr
        re = tr if re is None else re + tr
        im = ti if im is None else im + ti
    return jnp.stack([re, im])


# ---------------------------------------------------------------------------
# Operators
# ---------------------------------------------------------------------------

def hop(U_out: jnp.ndarray, U_src: jnp.ndarray, psi: jnp.ndarray,
        out_parity: int, xh: int) -> jnp.ndarray:
    """One parity block of D-slash on planes (``eo.dslash_half``): ``psi``
    lives on the sites of parity 1 - ``out_parity``; ``U_out``/``U_src``
    are the link planes of the output/source parity.  Computes in float32
    (or the wider of it and the inputs) and returns that dtype.

    A +mu hop moves the projected half spinor from x + mu and applies
    U_mu(x); a -mu hop applies U_mu(y)^dagger at the source site y first
    and moves the product from y = x - mu, so no link is ever moved."""
    dtype = jnp.promote_types(psi.dtype, jnp.float32)
    s_out = _offset(psi.shape[-3:], xh, out_parity)
    psi = psi.astype(dtype)
    out = {}
    with jax.named_scope("lqcd.hop"):
        for mu in range(4):
            def move(f, step):
                return _neighbour(f, mu, step, xh, s_out)

            (keep_f, lift_f), (keep_b, lift_b) = _FWD[mu], _BWD[mu]
            fwd = _mv(U_out[:, mu].astype(dtype),
                      move(_apply(keep_f, psi), 1), False)
            bwd = move(_mv(U_src[:, mu].astype(dtype), _apply(keep_b, psi),
                           True), -1)
            for half, lift in ((fwd, lift_f), (bwd, lift_b)):
                for pair, slots in lift:
                    t = _apply(slots, half)
                    out[pair] = t if pair not in out else out[pair] + t
        return jnp.concatenate([out[(0, 1)], out[(2, 3)]], axis=1)


def gamma5(psi: jnp.ndarray) -> jnp.ndarray:
    """gamma_5 on spinor planes: a permutation of the spin axis."""
    return jnp.stack([psi[:, s] for s in _G5_PERM], axis=1)


def schur(U_e: jnp.ndarray, U_o: jnp.ndarray, psi: jnp.ndarray, kappa,
          xh: int) -> jnp.ndarray:
    """A psi = (1 - kappa^2 D_eo D_oe) psi on even planes
    (``eo.schur_matvec``)."""
    d_oe = hop(U_o, U_e, psi, 1, xh)
    d_eo = hop(U_e, U_o, d_oe, 0, xh)
    return psi.astype(d_eo.dtype) - (kappa * kappa) * d_eo


def schur_dagger(U_e: jnp.ndarray, U_o: jnp.ndarray, psi: jnp.ndarray,
                 kappa, xh: int) -> jnp.ndarray:
    """A^dagger = gamma_5 A gamma_5 (``eo.schur_matvec_dagger``)."""
    return gamma5(schur(U_e, U_o, gamma5(psi), kappa, xh))


def normal(U_e: jnp.ndarray, U_o: jnp.ndarray, v: jnp.ndarray, kappa,
           xh: int) -> jnp.ndarray:
    """A^dagger A v with v, A v and A^dagger A v stored in the links'
    dtype: the inner CG's three rounding points."""
    store = U_e.dtype
    v = v.astype(store)
    av = schur(U_e, U_o, v, kappa, xh).astype(store)
    return schur_dagger(U_e, U_o, av, kappa, xh).astype(store)

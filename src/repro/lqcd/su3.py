"""SU(3) gauge-field helpers."""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


def random_su3(key, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Random SU(3) matrices of shape (*shape, 3, 3) complex64.

    Gram-Schmidt (QR) of a random complex matrix, phase-fixed to det=1.
    The projection runs in pieces over the two leading axes of ``shape``
    (``lax.map``), so its temporaries are one piece's, not the field's.
    """
    kr, ki = jax.random.split(key)
    m = (jax.random.normal(kr, shape + (3, 3))
         + 1j * jax.random.normal(ki, shape + (3, 3))).astype(jnp.complex64)
    lead = len(shape[:2])
    pieces = m.reshape((-1,) + m.shape[lead:])
    q = jax.lax.map(su3_project, pieces)
    return q.reshape(m.shape).astype(jnp.complex64)


@partial(jax.jit, static_argnums=1)
def random_su3_field(key, lattice_shape: Tuple[int, int, int, int],
                     ) -> jnp.ndarray:
    """Gauge field U_mu(x): shape (4, X, Y, Z, T, 3, 3)."""
    return random_su3(key, (4,) + tuple(lattice_shape))


def su3_project(m: jnp.ndarray) -> jnp.ndarray:
    """Project arbitrary 3x3 matrices onto SU(3): QR, R's diagonal made
    real-positive so Q is unique, then det fixed to 1."""
    q, r = jnp.linalg.qr(m)
    d = jnp.diagonal(r, axis1=-2, axis2=-1)
    ph = d / jnp.abs(d)
    q = q * jnp.conj(ph)[..., None, :]
    det = jnp.linalg.det(q)
    return q * (jnp.conj(det) ** (1.0 / 3.0))[..., None, None]


def unitarity_defect(u: jnp.ndarray) -> jnp.ndarray:
    """max |U U† − 1| — 0 for exact SU(3)."""
    eye = jnp.eye(3, dtype=u.dtype)
    uu = jnp.einsum("...ab,...cb->...ac", u, jnp.conj(u))
    return jnp.max(jnp.abs(uu - eye))

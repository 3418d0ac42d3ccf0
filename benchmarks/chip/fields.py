"""Inputs of a cell, made on the device from ``--seed``.

The gauge field is a hot start (beta = 0): every link an independent
Haar-random SU(3) matrix, built here by Gram-Schmidt on two Gaussian
colour vectors and the conjugated cross product of the two, which has
determinant 1 exactly.  It shares no code with the program's
``repro.lqcd.su3``, so the plain reference never sees a field the program
made.  The sources are the spin-colour point sources of one propagator
at one site drawn from the seed, as the traffic file asks.

Layouts are the program's: gauge ``(4, X, Y, Z, T, 3, 3)`` and spinors
``(X, Y, Z, T, 4, 3)``, complex64.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed below 2**62."""
    seed = int(seed) % 2 ** 62
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def source_site(seed: int, lattice: Sequence[int]) -> Tuple[int, ...]:
    """The site of the point sources: one site per seed."""
    rng = np.random.default_rng(int(seed) % 2 ** 62)
    return tuple(int(rng.integers(n)) for n in lattice)


def _cdot(a, b):
    """sum_c conj(a_c) b_c over the last axis."""
    return jnp.sum(jnp.conj(a) * b, axis=-1, keepdims=True)


def _normalise(v):
    return v * jax.lax.rsqrt(jnp.sum(jnp.abs(v) ** 2, axis=-1, keepdims=True))


def haar_su3(key, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Haar-random SU(3) matrices, shape ``shape + (3, 3)``: rows u, v, w
    with u, v orthonormal and w = conj(u x v)."""
    ka, kb, kc, kd = jax.random.split(key, 4)
    a = jax.random.normal(ka, shape + (3,)) + 1j * jax.random.normal(
        kb, shape + (3,))
    b = jax.random.normal(kc, shape + (3,)) + 1j * jax.random.normal(
        kd, shape + (3,))
    u = _normalise(a)
    v = _normalise(b - _cdot(u, b) * u)
    w = jnp.conj(jnp.stack([
        u[..., 1] * v[..., 2] - u[..., 2] * v[..., 1],
        u[..., 2] * v[..., 0] - u[..., 0] * v[..., 2],
        u[..., 0] * v[..., 1] - u[..., 1] * v[..., 0]], axis=-1))
    return jnp.stack([u, v, w], axis=-2).astype(jnp.complex64)


def make_inputs(seed: int, lattice: Sequence[int], traffic: Dict[str, Any],
                sharding=None, field_sharding=None):
    """The gauge field and the traffic's point sources (``spins`` x
    ``colours`` at one site), in one jitted call on the device.
    ``sharding``/``field_sharding`` place the gauge field and each source
    (None: the default device)."""
    if traffic["sources"] != "point" or int(traffic["clients"]) != 1:
        raise ValueError("the generator makes point sources for one caller; "
                         f"traffic asks for {traffic['sources']!r} sources "
                         f"and {traffic['clients']} callers")
    lattice = tuple(int(n) for n in lattice)
    spins, colours = int(traffic["spins"]), int(traffic["colours"])
    shardings: Optional[tuple] = None
    if sharding is not None:
        shardings = (sharding, (field_sharding,) * (spins * colours))

    # the site is an argument, not a constant: one program for every seed
    def build(key, site):
        U = haar_su3(key, (4,) + lattice)
        sources = []
        for s in range(spins):
            for c in range(colours):
                b = jnp.zeros(lattice + (4, 3), jnp.complex64)
                sources.append(b.at[tuple(site) + (s, c)].set(1.0))
        return U, tuple(sources)

    fn = jax.jit(build, out_shardings=shardings)
    site = jnp.asarray(source_site(seed, lattice), jnp.int32)
    return jax.block_until_ready(fn(base_key(seed), site))

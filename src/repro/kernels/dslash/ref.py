"""Pure-jnp oracle (the complex reference D-slash from repro.lqcd) and the
conversions between complex fields and the kernels' split layout."""
import jax.numpy as jnp

from repro.lqcd.dirac import dslash


def to_split(x: jnp.ndarray) -> jnp.ndarray:
    """Complex field -> the kernels' site-minor f32 layout.

    Spinor (X, Y, Z, T, 4, 3) -> (T, 4, 3, 2, X, Y*Z); gauge
    (4, X, Y, Z, T, 3, 3) -> (T, 4, 3, 3, 2, X, Y*Z).  The trailing 2 of
    the component axes is re/im."""
    s = jnp.stack([jnp.real(x), jnp.imag(x)], axis=-1).astype(jnp.float32)
    if x.ndim == 6:
        X, Y, Z, T = x.shape[:4]
        s = jnp.transpose(s, (3, 4, 5, 6, 0, 1, 2))
        return s.reshape(T, 4, 3, 2, X, Y * Z)
    if x.ndim == 7:
        _, X, Y, Z, T = x.shape[:5]
        s = jnp.transpose(s, (4, 0, 5, 6, 7, 1, 2, 3))
        return s.reshape(T, 4, 3, 3, 2, X, Y * Z)
    raise ValueError(f"not a spinor or gauge field: shape {x.shape}")


def from_split(x_s: jnp.ndarray, z_extent: int) -> jnp.ndarray:
    """Inverse of :func:`to_split`; ``z_extent`` unmerges the Y*Z lanes."""
    lanes = x_s.shape[-1]
    s = x_s.reshape(x_s.shape[:-1] + (lanes // z_extent, z_extent))
    if x_s.ndim == 6:                     # (T, 4, 3, 2, X, Y, Z)
        s = jnp.transpose(s, (4, 5, 6, 0, 1, 2, 3))
    elif x_s.ndim == 7:                   # (T, 4, 3, 3, 2, X, Y, Z)
        s = jnp.transpose(s, (1, 5, 6, 7, 0, 2, 3, 4))
    else:
        raise ValueError(f"not a split field: shape {x_s.shape}")
    return (s[..., 0] + 1j * s[..., 1]).astype(jnp.complex64)


def dslash_ref(U: jnp.ndarray, psi: jnp.ndarray) -> jnp.ndarray:
    """Complex-field reference."""
    return dslash(U, psi)

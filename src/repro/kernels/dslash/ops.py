"""Public jit'd wrappers: complex-field D-slash backed by the Pallas
kernels.

``tuned=True`` resolves ``t_block`` from the autotune cache for this
lattice and backend (``repro.autotune``; analytic roofline tuner on a
cache miss) instead of the static default of 1.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.dslash.kernel import dslash_eo_split, dslash_split
from repro.kernels.dslash.ref import from_split, to_split

DEFAULT_T_BLOCK = 1


def _resolve_t_block(t_block: int | None, tuned: bool,
                     lat: tuple) -> int:
    if t_block is not None:
        return t_block
    if tuned:
        from repro.autotune import tuned_config
        return int(tuned_config("dslash", lat)["t_block"])
    return DEFAULT_T_BLOCK


def sharded_t_block(local_lat: tuple) -> int:
    """T-block for a T-sharded local volume, resolved through the
    autotune cache so sharded local volumes (including their ±1 halo
    pad) get their own entries — the multi-chip even-odd path
    (``repro.lqcd.multichip_eo``) calls this once per gauge binding."""
    from repro.autotune import tuned_config
    lat = tuple(int(d) for d in local_lat)
    return int(tuned_config("dslash", lat)["t_block"])


def _interpret_default(interpret: bool | None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


@partial(jax.jit, static_argnames=("t_block", "interpret"))
def _dslash_call(U: jnp.ndarray, psi: jnp.ndarray, *, t_block: int,
                 interpret: bool) -> jnp.ndarray:
    Z = psi.shape[2]
    out_s = dslash_split(to_split(U), to_split(psi), Z, t_block=t_block,
                         interpret=interpret)
    return from_split(out_s, Z)


def dslash_pallas(U: jnp.ndarray, psi: jnp.ndarray, *,
                  t_block: int | None = None, tuned: bool = False,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Complex-in/complex-out D-slash via the split-field Pallas kernel."""
    # gauge layout is (4, X, Y, Z, T, 3, 3): direction axis leads
    t_block = _resolve_t_block(t_block, tuned, tuple(U.shape[1:5]))
    return _dslash_call(U, psi, t_block=t_block,
                        interpret=_interpret_default(interpret))


def dslash_half_split(U_out: jnp.ndarray, U_src: jnp.ndarray,
                      psi: jnp.ndarray, src_parity: int, *, t_block: int,
                      interpret: bool) -> jnp.ndarray:
    """Complex compact half-fields through the even-odd kernel (traceable;
    the sharded path calls it per shard on halo-padded blocks)."""
    Z = psi.shape[2]
    out_s = dslash_eo_split(to_split(U_out), to_split(U_src), to_split(psi),
                            src_parity, Z, t_block=t_block,
                            interpret=interpret)
    return from_split(out_s, Z)


@partial(jax.jit, static_argnames=("src_parity", "t_block", "interpret"))
def _dslash_half_call(U_e: jnp.ndarray, U_o: jnp.ndarray, psi: jnp.ndarray,
                      src_parity: int, *, t_block: int,
                      interpret: bool) -> jnp.ndarray:
    U_out, U_src = (U_o, U_e) if src_parity == 0 else (U_e, U_o)
    return dslash_half_split(U_out, U_src, psi, src_parity, t_block=t_block,
                             interpret=interpret)


def dslash_half_pallas(U_e: jnp.ndarray, U_o: jnp.ndarray, psi: jnp.ndarray,
                       src_parity: int, *, t_block: int | None = None,
                       tuned: bool = False,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Even-odd hop on complex compact half-fields via the Pallas kernel.

    Same contract as ``repro.lqcd.eo.dslash_half``: ``psi`` lives on
    ``src_parity`` sites (compact layout), the result on the opposite
    parity.  ``U_e``/``U_o`` are the packed gauge halves from
    ``repro.lqcd.eo.pack_gauge``.
    """
    # the packed half-lattice keeps the full T extent (X is halved)
    t_block = _resolve_t_block(t_block, tuned, tuple(U_e.shape[1:5]))
    return _dslash_half_call(U_e, U_o, psi, src_parity, t_block=t_block,
                             interpret=_interpret_default(interpret))

"""Multi-chip D-slash: lattice time-axis sharded over the model axis with
halo exchange via ``collective_permute`` (the paper's multi-GPU lattice mode;
published observation: ~20% slowdown vs single-GPU — our ICI roofline model
re-derives that in ``benchmarks/paper_tables.py::dslash_bw``).

Wire-traffic optimization (CL2QCD does the same on PCIe): the Wilson
projector ``(1 ∓ γ_t)`` in the Dirac basis is ``diag(0,0,2,2)`` /
``diag(2,2,0,0)``, so only two of the four spin components of a halo
slice ever enter the t-direction hop.  With ``compress=True`` (default)
only those two components cross the wire — half the spinor halo bytes —
and the result is **bit-identical** in f32, because the dropped terms
were exact zero-adds.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.lqcd.dirac import EYE4, GAMMA, mv, mv_dag, spin

T_AX = 3


@lru_cache(maxsize=None)
def halo_perms(n: int):
    """Static ``ppermute`` permutation tables for a ring of ``n`` shards.

    ``fwd`` sends each shard's first T-slice to its predecessor (so every
    shard *receives from its successor*); ``bwd`` the reverse.  Cached per
    axis size so the traced halo exchange stays allocation-free instead of
    rebuilding the Python pair lists on every call.
    """
    fwd = tuple((i, (i - 1) % n) for i in range(n))   # to prev
    bwd = tuple((i, (i + 1) % n) for i in range(n))   # to next
    return fwd, bwd


def _halo_exchange(x: jnp.ndarray, axis_name: str, t_axis: int):
    """Returns (from_next_first_slice, from_prev_last_slice)."""
    fwd_perm, bwd_perm = halo_perms(jax.lax.axis_size(axis_name))
    first = jax.lax.slice_in_dim(x, 0, 1, axis=t_axis)
    last = jax.lax.slice_in_dim(x, x.shape[t_axis] - 1, x.shape[t_axis],
                                axis=t_axis)
    from_next = jax.lax.ppermute(first, axis_name, fwd_perm)
    from_prev = jax.lax.ppermute(last, axis_name, bwd_perm)
    return from_next, from_prev


def scatter_spin(v: jnp.ndarray, lo: int) -> jnp.ndarray:
    """Expand a 2-spin-component field ``(..., 2, 3)`` back to 4 spin
    components, placing it at spin positions ``lo:lo+2`` (zeros elsewhere)."""
    z = jnp.zeros(v.shape[:-2] + (4,) + v.shape[-1:], v.dtype)
    return jax.lax.dynamic_update_slice_in_dim(z, v, lo, axis=-2)


def _t_slice(x: jnp.ndarray, start: int, stop: int):
    """T rows [start, stop) of a local block, or ``None`` when empty
    (T_local == 1 leaves no interior rows)."""
    return (jax.lax.slice_in_dim(x, start, stop, axis=T_AX)
            if stop > start else None)


def _t_join(a, b) -> jnp.ndarray:
    """Concatenate along T, skipping an empty (``None``) side."""
    if a is None or b is None:
        return b if a is None else a
    return jnp.concatenate([a, b], axis=T_AX)


def _dslash_local(U_loc: jnp.ndarray, psi_loc: jnp.ndarray,
                  axis_name: str, compress: bool) -> jnp.ndarray:
    """D-slash body on a T-sharded block: x/y/z via local rolls; T via halos."""
    out = jnp.zeros_like(psi_loc)
    # spatial directions: fully local (periodic within the global lattice —
    # x/y/z are unsharded)
    for mu in range(3):
        g = GAMMA[mu]
        u = U_loc[mu]
        psi_f = jnp.roll(psi_loc, -1, axis=mu)
        out = out + spin(EYE4 - g, mv(u, psi_f))
        u_b = jnp.roll(u, 1, axis=mu)
        psi_b = jnp.roll(psi_loc, 1, axis=mu)
        out = out + spin(EYE4 + g, mv_dag(u_b, psi_b))
    # time direction: halo exchange over the mesh axis
    g = GAMMA[3]
    u_t = U_loc[3]
    Tl = psi_loc.shape[T_AX]

    if compress:
        # spin-projected halos: the +t hop applies (1 - γ_t) = diag(0,0,2,2)
        # so the neighbour slice only contributes spin components 2,3; the
        # -t hop applies (1 + γ_t) = diag(2,2,0,0) → components 0,1.  Send
        # exactly those (half the spinor wire bytes), zero-fill the dropped
        # components on arrival, and run the *identical* hop assembly below
        # — the projector annihilates the zero-filled components, so the
        # result is bit-compatible with the full-slice exchange.  Bonus:
        # only one gauge ppermute (the -t hop's last link slice) instead of
        # the uncompressed path's two.
        fwd_perm, bwd_perm = halo_perms(jax.lax.axis_size(axis_name))
        send_f = jax.lax.slice_in_dim(psi_loc, 0, 1, axis=T_AX)[..., 2:4, :]
        send_b = jax.lax.slice_in_dim(psi_loc, Tl - 1, Tl,
                                      axis=T_AX)[..., 0:2, :]
        psi_next = scatter_spin(
            jax.lax.ppermute(send_f, axis_name, fwd_perm), 2)
        psi_prev = scatter_spin(
            jax.lax.ppermute(send_b, axis_name, bwd_perm), 0)
        u_last = jax.lax.slice_in_dim(u_t, Tl - 1, Tl, axis=T_AX)
        u_prev_last = jax.lax.ppermute(u_last, axis_name, bwd_perm)
    else:
        psi_next, psi_prev = _halo_exchange(psi_loc, axis_name, T_AX)
        u_prev_last = _halo_exchange(u_t, axis_name, T_AX)[1]
    psi_f = _t_join(_t_slice(psi_loc, 1, Tl), psi_next)
    out = out + spin(EYE4 - g, mv(u_t, psi_f))
    psi_b = _t_join(psi_prev, _t_slice(psi_loc, 0, Tl - 1))
    u_b = _t_join(u_prev_last, _t_slice(u_t, 0, Tl - 1))
    out = out + spin(EYE4 + g, mv_dag(u_b, psi_b))
    return out


def dslash_sharded(U: jnp.ndarray, psi: jnp.ndarray, mesh,
                   axis_name: str = "model",
                   compress: bool = True) -> jnp.ndarray:
    """D-slash with the lattice T axis sharded over ``axis_name``.

    ``compress=False`` keeps the full-4-spinor halo exchange (reference
    for the bit-compatibility test); the default sends the two
    spin-projected components only.
    """
    u_spec = P(None, None, None, None, axis_name, None, None)
    psi_spec = P(None, None, None, axis_name, None, None)
    return jax.shard_map(
        partial(_dslash_local, axis_name=axis_name, compress=compress),
        mesh=mesh, in_specs=(u_spec, psi_spec), out_specs=psi_spec,
        check_vma=False)(U, psi)

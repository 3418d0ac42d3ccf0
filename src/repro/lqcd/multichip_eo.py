"""Multi-chip even-odd D-slash and CG: the compact checkerboarded
half-lattices T-sharded over the device mesh, with the halo exchange
*overlapped* against interior compute.

This is the paper's production configuration — multi-GPU LQCD chosen for
memory bandwidth — applied to the even-odd solver of :mod:`repro.lqcd.eo`:

  * Each T-shard owns a ``(X/2, Y, Z, T/n)`` block of both parity
    half-fields.  x/y/z hops never cross the shard boundary (those axes
    are unsharded), so they are **interior** work; only the ±t hops touch
    neighbour shards.
  * Per half-hop, exactly two ``ppermute`` messages cross the wire — the
    two *spin-projected* components the Wilson projector keeps
    (``(1 ∓ γ_t)`` is ``diag(0,0,2,2)`` / ``diag(2,2,0,0)`` in the Dirac
    basis), i.e. half a spinor slice each way and **no gauge traffic**:
    the neighbour's last +t link slice is loop-invariant and gathered
    host-side once per gauge field (``_prev_t_links``).
  * With ``overlap=True`` (default) the ``ppermute``\\ s are issued first,
    the interior terms (x/y/z hops plus the on-shard part of the t hops)
    are computed while the halos are in flight, and the two boundary
    T-rows are filled in when the results land.  ``overlap=False`` is the
    halo-then-compute baseline: full-spinor halos, an
    ``optimization_barrier`` pinning all compute behind the exchange, and
    concat-assembled neighbour arrays — the shape QCDOC
    (hep-lat/0306023) and Ibrahim et al. (arXiv:0808.0391) show you must
    *not* ship at scale.  The boundary rows re-apply the identical
    projector∘link composition on the zero-filled halo, so both variants
    agree to f32 roundoff (bitwise, in practice, on the CPU test mesh).

The inner CG runs **fully sharded**: the entire ``while_loop`` executes
inside one ``shard_map``, with ``psum`` only for the reduction scalars
(dot products and norms) — vectors never leave their shards.

``measured_lqcd_calibration`` closes the loop with the cluster layer:
it times the executed sharded normal op, emits the run onto the PR-3
telemetry bus, and returns an :class:`LQCDCalibration` that
``repro.cluster.workload.LQCDSolveWorkload`` can consume in place of the
analytic S9150 roofline.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.lqcd.cg import CGResult, _round_complex
from repro.lqcd.dirac import (GAMMA5, dslash_bytes_per_site,
                              dslash_flops_per_site)
from repro.lqcd.eo import (PROJ_M, PROJ_P, _sublattice_offset, hops_spatial,
                           mv, mv_dag, spin)
from repro.lqcd.multichip import T_AX, halo_perms, scatter_spin

__all__ = [
    "LQCDCalibration",
    "ShardedWilsonEO",
    "analytic_lqcd_calibration",
    "dslash_half_sharded",
    "measured_lqcd_calibration",
]


# ---------------------------------------------------------------------------
# Host-side, loop-invariant preparation
# ---------------------------------------------------------------------------

def _prev_t_links(U_half: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Per-shard copy of the *previous* shard's last +t link slice.

    The -t hop at a shard's first T-row needs the source-parity gauge link
    at global ``t = j*T_local - 1``.  The gauge field is constant across a
    solve, so this is a host-side gather of shape ``(Xh, Y, Z, n, 3, 3)``
    (sharded over its n axis) — no gauge ``ppermute`` per matvec, unlike
    the full-lattice path in :mod:`repro.lqcd.multichip`.
    """
    T = U_half.shape[4]
    t_local = T // n_shards
    idx = (np.arange(n_shards) * t_local - 1) % T
    return U_half[3][:, :, :, idx]


def _padded_gauge(U_half: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Halo-padded gauge half for the Pallas backend: each shard's local
    T extent grows to ``T_local + 2`` with the periodic neighbour slices
    baked in (global shape ``(4, Xh, Y, Z, n*(T_local+2), 3, 3)``)."""
    T = U_half.shape[4]
    t_local = T // n_shards
    idx = np.concatenate(
        [np.r_[(s - 1) % T, np.arange(s, s + t_local), (s + t_local) % T]
         for s in np.arange(n_shards) * t_local])
    return U_half[:, :, :, :, idx]


# ---------------------------------------------------------------------------
# Local (per-shard) hop bodies
# ---------------------------------------------------------------------------

def _half_hop_local(U_out: jnp.ndarray, U_src: jnp.ndarray,
                    u_prev: jnp.ndarray, psi: jnp.ndarray, *,
                    out_parity: int, axis_name: str, n_shards: int,
                    overlap: bool) -> jnp.ndarray:
    """One parity block of D-slash on a T-shard (compact layout).

    ``u_prev`` is the precomputed previous-shard last +t link slice of the
    *source* parity, local shape ``(Xh, Y, Z, 1, 3, 3)``.
    """
    Xh, Y, Z, Tl = psi.shape[:4]
    # the parity offset pattern s = (y+z+t+parity) % 2 depends on *global*
    # t; shift the local pattern by this shard's T offset (traced — shards
    # with odd T_local alternate patterns, e.g. 8^4 over 8 devices)
    s_base = jnp.asarray(_sublattice_offset((2 * Xh, Y, Z, Tl),
                                            out_parity)[0])
    t0 = jax.lax.axis_index(axis_name) * Tl
    s_out = (s_base + t0) % 2

    fwd_perm, bwd_perm = halo_perms(n_shards)

    if overlap:
        # launch the wire traffic first: spin-projected half-spinor slices
        send_f = jax.lax.slice_in_dim(psi, 0, 1, axis=T_AX)[..., 2:4, :]
        send_b = jax.lax.slice_in_dim(psi, Tl - 1, Tl, axis=T_AX)[..., 0:2, :]
        from_next = jax.lax.ppermute(send_f, axis_name, fwd_perm)
        from_prev = jax.lax.ppermute(send_b, axis_name, bwd_perm)

        with jax.named_scope("lqcd.hop"):
            # interior: x/y/z hops and the on-shard t hops, while halos fly
            u_t = U_out[3]
            u_last = jax.lax.slice_in_dim(u_t, Tl - 1, Tl, axis=T_AX)
            out = hops_spatial(U_out, U_src, psi, s_out)
            f_int = spin(PROJ_M[3], mv(
                jax.lax.slice_in_dim(u_t, 0, Tl - 1, axis=T_AX),
                jax.lax.slice_in_dim(psi, 1, Tl, axis=T_AX)))
            b_int = spin(PROJ_P[3], mv_dag(
                jax.lax.slice_in_dim(U_src[3], 0, Tl - 1, axis=T_AX),
                jax.lax.slice_in_dim(psi, 0, Tl - 1, axis=T_AX)))

            # boundary rows as the halos land: zero-fill the dropped spin
            # components and apply the same projector∘link composition as
            # the interior — the projector annihilates the zero fill
            # exactly
            f_bnd = spin(PROJ_M[3], mv(u_last, scatter_spin(from_next, 2)))
            b_bnd = spin(PROJ_P[3], mv_dag(u_prev,
                                           scatter_spin(from_prev, 0)))
            out = out + jnp.concatenate([f_int, f_bnd], axis=T_AX)
            out = out + jnp.concatenate([b_bnd, b_int], axis=T_AX)
        return out

    # halo-then-compute baseline: full-spinor halos, everything serialized
    # behind the exchange, neighbour arrays materialized by concat
    first = jax.lax.slice_in_dim(psi, 0, 1, axis=T_AX)
    last = jax.lax.slice_in_dim(psi, Tl - 1, Tl, axis=T_AX)
    from_next = jax.lax.ppermute(first, axis_name, fwd_perm)
    from_prev = jax.lax.ppermute(last, axis_name, bwd_perm)
    psi, from_next, from_prev, U_out, U_src, u_prev = \
        jax.lax.optimization_barrier(
            (psi, from_next, from_prev, U_out, U_src, u_prev))
    with jax.named_scope("lqcd.hop"):
        u_t = U_out[3]
        out = hops_spatial(U_out, U_src, psi, s_out)
        psi_f = jnp.concatenate(
            [jax.lax.slice_in_dim(psi, 1, Tl, axis=T_AX), from_next],
            axis=T_AX)
        out = out + spin(PROJ_M[3], mv(u_t, psi_f))
        psi_b = jnp.concatenate(
            [from_prev, jax.lax.slice_in_dim(psi, 0, Tl - 1, axis=T_AX)],
            axis=T_AX)
        u_b = jnp.concatenate(
            [u_prev, jax.lax.slice_in_dim(U_src[3], 0, Tl - 1, axis=T_AX)],
            axis=T_AX)
        out = out + spin(PROJ_P[3], mv_dag(u_b, psi_b))
    return out


def _half_hop_pallas_local(U_out_pad: jnp.ndarray, U_src_pad: jnp.ndarray,
                           psi: jnp.ndarray, *, src_parity_eff: int,
                           t_block: int, interpret: bool, axis_name: str,
                           n_shards: int) -> jnp.ndarray:
    """Per-shard hop through the Pallas EO kernel on halo-padded fields.

    The spinor halos still cross the wire spin-projected (half slices);
    the dropped components are zero-filled before padding — exact, since
    the kernel's t-projectors annihilate them.  The kernel's periodic
    halo index maps only wrap on the pad rows, which are cropped.
    ``src_parity_eff`` absorbs the pad's t-shift of 1 (requires even
    ``T_local`` so every shard sees the same static parity).
    """
    from repro.kernels.dslash.ops import dslash_half_split

    Tl = psi.shape[T_AX]
    fwd_perm, bwd_perm = halo_perms(n_shards)
    send_f = jax.lax.slice_in_dim(psi, 0, 1, axis=T_AX)[..., 2:4, :]
    send_b = jax.lax.slice_in_dim(psi, Tl - 1, Tl, axis=T_AX)[..., 0:2, :]
    from_next = jax.lax.ppermute(send_f, axis_name, fwd_perm)
    from_prev = jax.lax.ppermute(send_b, axis_name, bwd_perm)
    psi_pad = jnp.concatenate(
        [scatter_spin(from_prev, 0), psi, scatter_spin(from_next, 2)],
        axis=T_AX)
    with jax.named_scope("lqcd.hop"):
        out_pad = dslash_half_split(U_out_pad, U_src_pad, psi_pad,
                                    src_parity_eff, t_block=t_block,
                                    interpret=interpret)
    return jax.lax.slice_in_dim(out_pad, 1, Tl + 1, axis=T_AX)


# ---------------------------------------------------------------------------
# The gauge-bound sharded operator set
# ---------------------------------------------------------------------------

class ShardedWilsonEO:
    """T-sharded even-odd Wilson operator set, bound to one gauge field.

    Construction precomputes everything loop-invariant: the
    previous-shard +t link slices (jnp backend) or the halo-padded gauge
    halves plus the autotuned ``t_block`` for the padded local volume
    (``backend="pallas"``).  All public methods take and return *global*
    compact arrays; the inner CG (:meth:`cg_normal`) runs its whole
    ``while_loop`` inside one ``shard_map`` with ``psum`` reductions.
    """

    def __init__(self, U_e: jnp.ndarray, U_o: jnp.ndarray, kappa: float,
                 mesh, *, axis_name: str = "model", overlap: bool = True,
                 backend: str = "jnp"):
        if backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        self.mesh, self.axis_name = mesh, axis_name
        self.kappa = float(kappa)
        self.overlap = bool(overlap)
        self.backend = backend
        self.n = int(np.prod(mesh.shape[axis_name]))
        T = int(U_e.shape[4])
        if T % self.n:
            raise ValueError(
                f"lattice T extent {T} is not divisible by the "
                f"{self.n}-way mesh axis {axis_name!r}")
        self.t_local = T // self.n
        self.U_e, self.U_o = U_e, U_o

        from repro.distributed.sharding import lattice_eo_specs
        self._u_spec, self._p_spec = lattice_eo_specs(axis_name)
        if backend == "pallas":
            if self.t_local % 2:
                raise ValueError(
                    "backend='pallas' needs an even local T extent (the "
                    f"halo pad shifts parity per shard); got T_local="
                    f"{self.t_local}")
            from repro.kernels.dslash.ops import sharded_t_block
            self._t_block = sharded_t_block(
                tuple(U_e.shape[1:4]) + (self.t_local + 2,))
            self._interpret = jax.default_backend() != "tpu"
            self._gauge_args = (_padded_gauge(U_e, self.n),
                                _padded_gauge(U_o, self.n))
            self._gauge_specs = (self._u_spec, self._u_spec)
        else:
            self._gauge_args = (U_e, U_o,
                                _prev_t_links(U_e, self.n),
                                _prev_t_links(U_o, self.n))
            self._gauge_specs = (self._u_spec, self._u_spec,
                                 self._p_spec, self._p_spec)
        self._jit_cache: dict = {}

    # -- local-body plumbing ------------------------------------------------

    def _make_hop(self, gauge_local):
        """Per-shard ``hop(v, src_parity)`` closure over local gauge."""
        if self.backend == "pallas":
            U_e_pad, U_o_pad = gauge_local

            def hop(v, src_parity):
                u_out, u_src = ((U_o_pad, U_e_pad) if src_parity == 0
                                else (U_e_pad, U_o_pad))
                return _half_hop_pallas_local(
                    u_out, u_src, v, src_parity_eff=1 - src_parity,
                    t_block=self._t_block, interpret=self._interpret,
                    axis_name=self.axis_name, n_shards=self.n)
            return hop

        U_e, U_o, up_e, up_o = gauge_local

        def hop(v, src_parity):
            u_out, u_src, u_prev = ((U_o, U_e, up_e) if src_parity == 0
                                    else (U_e, U_o, up_o))
            return _half_hop_local(
                u_out, u_src, u_prev, v, out_parity=1 - src_parity,
                axis_name=self.axis_name, n_shards=self.n,
                overlap=self.overlap)
        return hop

    def _schur_from_hop(self, hop, v):
        d = hop(v, 0)                        # even -> odd
        d = hop(d, 1)                        # odd -> even
        return v - (self.kappa * self.kappa) * d

    def _shmap(self, f, in_specs, out_specs):
        return jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _jitted(self, key, build):
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = self._jit_cache[key] = build()
        return fn

    def _vec_fn(self, kind: str):
        """Jitted shard_map for one of the vector→vector operators; its
        program is named ``jit_eo_<kind>`` in a trace."""
        def build():
            ng = len(self._gauge_specs)

            def body(*args):
                hop = self._make_hop(args[:ng])
                v = args[ng]
                g5 = lambda w: spin(GAMMA5, w)              # noqa: E731
                if kind == "hop_e":
                    return hop(v, 0)
                if kind == "hop_o":
                    return hop(v, 1)
                if kind == "schur":
                    return self._schur_from_hop(hop, v)
                if kind == "schur_dagger":
                    return g5(self._schur_from_hop(hop, g5(v)))
                # normal op A†A — the unit the calibration times
                av = self._schur_from_hop(hop, v)
                return g5(self._schur_from_hop(hop, g5(av)))

            body.__name__ = body.__qualname__ = f"eo_{kind}"
            return jax.jit(self._shmap(
                body, in_specs=self._gauge_specs + (self._p_spec,),
                out_specs=self._p_spec))
        return self._jitted(kind, build)

    # -- public operators (global compact arrays) ---------------------------

    def dslash_half(self, psi: jnp.ndarray, src_parity: int) -> jnp.ndarray:
        """Sharded equivalent of :func:`repro.lqcd.eo.dslash_half` (with
        the gauge halves bound at construction)."""
        kind = "hop_e" if src_parity == 0 else "hop_o"
        return self._vec_fn(kind)(*self._gauge_args, psi)

    def schur(self, psi_e: jnp.ndarray) -> jnp.ndarray:
        return self._vec_fn("schur")(*self._gauge_args, psi_e)

    def schur_dagger(self, psi_e: jnp.ndarray) -> jnp.ndarray:
        return self._vec_fn("schur_dagger")(*self._gauge_args, psi_e)

    def normal(self, psi_e: jnp.ndarray) -> jnp.ndarray:
        """A†A in one fused sharded call (calibration/benchmark unit)."""
        return self._vec_fn("normal")(*self._gauge_args, psi_e)

    def rhs(self, b_e: jnp.ndarray, b_o: jnp.ndarray) -> jnp.ndarray:
        """Even-system right-hand side b'_e = b_e + κ D_eo b_o."""
        return b_e + self.kappa * self.dslash_half(b_o, 1)

    def reconstruct(self, x_e: jnp.ndarray, b_o: jnp.ndarray) -> jnp.ndarray:
        """Back-substitute the odd sites: x_o = b_o + κ D_oe x_e."""
        return b_o + self.kappa * self.dslash_half(x_e, 0)

    # -- fully-sharded inner CG --------------------------------------------

    def cg_normal(self, b: jnp.ndarray, *, tol: float, max_iters: int,
                  inner_dtype=None) -> CGResult:
        """CGNE on A†A with the entire iteration inside one ``shard_map``:
        vectors stay sharded for the whole ``while_loop``; only the
        reduction scalars cross the mesh (``psum``).  ``inner_dtype``
        rounds fields exactly like the single-device ``normal_lo`` path.
        """
        dt_key = None if inner_dtype is None else jnp.dtype(inner_dtype).name

        def build():
            ng = len(self._gauge_specs)
            ax = self.axis_name

            def body(*args):
                hop = self._make_hop(args[:ng])
                b_loc, tol_a, cap_a = args[ng:]
                g5 = lambda w: spin(GAMMA5, w)              # noqa: E731

                def schur(v):
                    return self._schur_from_hop(hop, v)

                def normal(v):
                    if inner_dtype is None:
                        return g5(schur(g5(schur(v))))
                    v = _round_complex(v, inner_dtype)
                    av = _round_complex(schur(v), inner_dtype)
                    out = g5(schur(g5(av)))
                    return _round_complex(out, inner_dtype)

                def pdot(a, c):
                    return jax.lax.psum(jnp.sum(jnp.conj(a) * c).real, ax)

                b_norm = jnp.sqrt(pdot(b_loc, b_loc))
                x0 = jnp.zeros_like(b_loc)
                rs0 = pdot(b_loc, b_loc)

                def cond(state):
                    _, _, _, rs, it = state
                    return (jnp.sqrt(rs) > tol_a * b_norm) & (it < cap_a)

                def loop(state):
                    x, r, p, rs, it = state
                    ap = normal(p)
                    alpha = rs / jnp.maximum(pdot(p, ap), 1e-30)
                    x = x + alpha * p
                    r = r - alpha * ap
                    rs_new = pdot(r, r)
                    beta = rs_new / jnp.maximum(rs, 1e-30)
                    p = r + beta * p
                    return x, r, p, rs_new, it + 1

                x, r, p, rs, it = jax.lax.while_loop(
                    cond, loop, (x0, b_loc, b_loc, rs0,
                                 jnp.zeros((), jnp.int32)))
                rel = jnp.sqrt(rs) / jnp.maximum(b_norm, 1e-30)
                return x, it, rel

            return jax.jit(self._shmap(
                body,
                in_specs=self._gauge_specs + (self._p_spec, P(), P()),
                out_specs=(self._p_spec, P(), P())))

        fn = self._jitted(("cg", dt_key), build)
        x, it, rel = fn(*self._gauge_args, b, jnp.float32(tol),
                        jnp.int32(max_iters))
        return CGResult(x, it, rel, rel <= tol)


def dslash_half_sharded(U_e: jnp.ndarray, U_o: jnp.ndarray,
                        psi: jnp.ndarray, src_parity: int, mesh, *,
                        axis_name: str = "model", overlap: bool = True,
                        backend: str = "jnp") -> jnp.ndarray:
    """One-shot sharded EO hop on global compact arrays (test/bench entry
    point; for repeated application build a :class:`ShardedWilsonEO`)."""
    ops = ShardedWilsonEO(U_e, U_o, 0.0, mesh, axis_name=axis_name,
                          overlap=overlap, backend=backend)
    return ops.dslash_half(psi, src_parity)


# ---------------------------------------------------------------------------
# Measured calibration — executed multi-chip GFLOPS/W on the telemetry bus
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LQCDCalibration:
    """Multi-chip LQCD operating figures for the cluster layer.

    ``source="measured"`` entries come from timing the executed sharded
    normal op (:func:`measured_lqcd_calibration`); ``source="analytic"``
    restates the S9150 roofline (:func:`analytic_lqcd_calibration`) in
    the same shape so :class:`~repro.cluster.workload.LQCDSolveWorkload`
    can consume either and report the delta.
    """

    lattice: Tuple[int, int, int, int]
    n_devices: int
    gflops: float                # sustained over the timed normal ops
    eff_bw_gbs: float            # executed aggregate streaming bandwidth
    busy_w: float                # aggregate device power at the op point
    wall_s: float
    energy_j: float              # integrated from the telemetry bus
    source: str = "measured"
    trace: Optional[Any] = field(default=None, repr=False, compare=False)

    @property
    def gflops_per_w(self) -> float:
        return self.gflops / max(self.busy_w, 1e-9)


def _busy_watts(op=None, n_devices: int = 1) -> float:
    from repro.power.model import OperatingPoint, gpu_power_throttled
    op = op or OperatingPoint.green500()
    return n_devices * gpu_power_throttled(op.f_mhz, op.vid,
                                           temp_c=op.temperature(), util=1.0)


def analytic_lqcd_calibration(lattice: Tuple[int, int, int, int],
                              n_devices: int = 1, op=None,
                              ) -> LQCDCalibration:
    """The S9150 roofline restated as a calibration (fallback path)."""
    from repro.configs.lcsc_lqcd import (DSLASH_BW_FRACTION,
                                         MULTI_GPU_SLOWDOWN, S9150_BW_GBS)
    volume = int(np.prod(lattice))
    slowdown = 1.0 - (MULTI_GPU_SLOWDOWN if n_devices > 1 else 0.0)
    eff_bw = S9150_BW_GBS * DSLASH_BW_FRACTION * n_devices * slowdown
    bytes_op = 2 * volume * dslash_bytes_per_site(4)
    flops_op = 2 * volume * dslash_flops_per_site()
    wall = bytes_op / (eff_bw * 1e9)
    busy_w = _busy_watts(op, n_devices)
    return LQCDCalibration(tuple(lattice), n_devices, flops_op / wall / 1e9,
                           eff_bw, busy_w, wall, busy_w * wall,
                           source="analytic")


def measured_lqcd_calibration(lattice: Tuple[int, int, int, int] = (8, 8, 8, 16),
                              *, kappa: float = 0.12, mesh=None,
                              axis_name: str = "model", reps: int = 5,
                              op=None, recorder=None, overlap: bool = True,
                              backend: str = "jnp", seed: int = 0,
                              ) -> LQCDCalibration:
    """Time the executed sharded normal op and put it on the telemetry bus.

    Runs ``reps`` applications of the fused A†A on the real device mesh
    (all local devices by default), converts wall time into sustained
    multi-chip GFLOPS and effective streaming bandwidth, takes busy watts
    from the power model at ``op`` (Green500 point by default), emits the
    run into ``recorder`` (or a private bus) exactly like
    ``solver_energy`` does, and integrates joules from the trace.
    """
    from repro.distributed.sharding import lattice_mesh
    from repro.lqcd.eo import eo_pack, pack_gauge
    from repro.lqcd.su3 import random_su3_field
    from repro.power.trace import TraceRecorder

    if mesh is None:
        mesh = lattice_mesh(lattice[3], axis_name=axis_name)
    n_dev = int(np.prod(mesh.shape[axis_name]))

    ku, kr, ki = jax.random.split(jax.random.PRNGKey(seed), 3)
    U = random_su3_field(ku, tuple(lattice))
    b = (jax.random.normal(kr, tuple(lattice) + (4, 3))
         + 1j * jax.random.normal(ki, tuple(lattice) + (4, 3))
         ).astype(jnp.complex64)
    U_e, U_o = pack_gauge(U)
    b_e = eo_pack(b, 0)
    ops = ShardedWilsonEO(U_e, U_o, kappa, mesh, axis_name=axis_name,
                          overlap=overlap, backend=backend)

    v = ops.normal(b_e)                      # compile + warm
    jax.block_until_ready(v)
    t_start = time.perf_counter()
    for _ in range(reps):
        v = ops.normal(v)
    jax.block_until_ready(v)
    wall = max(time.perf_counter() - t_start, 1e-9)

    volume = int(np.prod(lattice))
    flops = reps * 2 * volume * dslash_flops_per_site()
    streamed = reps * 2 * volume * dslash_bytes_per_site(4)
    gflops = flops / wall / 1e9
    busy_w = _busy_watts(op, n_dev)

    rec = recorder if recorder is not None \
        else TraceRecorder(source="lqcd-calibration")
    t0 = rec.t_last
    for t in (t0, t0 + wall):
        rec.emit(t, {"gpu": busy_w}, flops_rate=gflops, util=1.0)
    trace = rec.trace()
    energy_j = trace.energy_j(t0=t0, t1=t0 + wall)
    return LQCDCalibration(tuple(lattice), n_dev, gflops,
                           streamed / wall / 1e9, busy_w, wall, energy_j,
                           source="measured", trace=trace)

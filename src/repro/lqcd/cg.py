"""Conjugate-gradient inversion of the Dirac operator (paper §Introduction:
'inversion of the Dirac operator ... usually performed by a conjugate
gradient algorithm, which involves a sparse matrix-vector-multiplication
called D-slash').

CGNE on the normal equations M†M x = M† b (M is not hermitian), with the
γ5-hermitian adjoint.  ``jax.lax.while_loop`` keeps it jittable.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation, annotate_function

from repro.lqcd.dirac import wilson_matvec, wilson_matvec_dagger


class CGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray
    rel_residual: jnp.ndarray
    converged: jnp.ndarray


def _dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(jnp.conj(a) * b).real


def cg_solve(matvec: Callable[[jnp.ndarray], jnp.ndarray], b: jnp.ndarray,
             *, tol: float = 1e-6, max_iters: int = 1000) -> CGResult:
    """CG for hermitian positive-definite ``matvec``."""
    b_norm = jnp.sqrt(_dot(b, b))
    x0 = jnp.zeros_like(b)
    r0 = b
    p0 = r0
    rs0 = _dot(r0, r0)

    def cond(state):
        _, _, _, rs, it = state
        return (jnp.sqrt(rs) > tol * b_norm) & (it < max_iters)

    def body(state):
        x, r, p, rs, it = state
        ap = matvec(p)
        alpha = rs / jnp.maximum(_dot(p, ap), 1e-30)
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = _dot(r, r)
        beta = rs_new / jnp.maximum(rs, 1e-30)
        p = r + beta * p
        return x, r, p, rs_new, it + 1

    x, r, p, rs, it = jax.lax.while_loop(
        cond, body, (x0, r0, p0, rs0, jnp.zeros((), jnp.int32)))
    rel = jnp.sqrt(rs) / jnp.maximum(b_norm, 1e-30)
    return CGResult(x, it, rel, rel <= tol)


def solve_wilson(U: jnp.ndarray, b: jnp.ndarray, kappa: float, *,
                 tol: float = 1e-6, max_iters: int = 1000) -> CGResult:
    """Solve M x = b for the Wilson operator via CGNE (M†M x = M† b)."""

    def normal_op(v):
        return wilson_matvec_dagger(U, wilson_matvec(U, v, kappa), kappa)

    rhs = wilson_matvec_dagger(U, b, kappa)
    res = cg_solve(normal_op, rhs, tol=tol, max_iters=max_iters)
    # report the true residual of M x = b
    true_r = b - wilson_matvec(U, res.x, kappa)
    rel = jnp.sqrt(_dot(true_r, true_r)) / jnp.sqrt(_dot(b, b))
    return CGResult(res.x, res.iters, rel, rel <= tol * 10)


# ---------------------------------------------------------------------------
# Even-odd preconditioned, mixed-precision solver (paper: CL2QCD strategy)
# ---------------------------------------------------------------------------

class EOCGResult(NamedTuple):
    """Result of the even-odd / mixed-precision solve.

    ``iters`` counts normal-op (A†A) applications — directly comparable to
    ``CGResult.iters`` of the unpreconditioned CGNE, since one Schur normal
    op costs the same D-slash traffic as one full-lattice normal op (two
    half-lattice hops ≡ one full hop, applied twice)."""

    x: jnp.ndarray
    iters: int                   # inner normal-op applications (total)
    outer_iters: int             # defect-correction (reliable-update) steps
    rel_residual: float          # true ‖b − M x‖ / ‖b‖
    converged: bool


def _round_complex(v: jnp.ndarray, dtype) -> jnp.ndarray:
    """Round a complex field through a reduced-precision real dtype.

    JAX has no complex bfloat16, so reduced precision is emulated by
    rounding the re/im planes through ``dtype`` — the storage/traffic model
    of CL2QCD's low-precision inner solver — while arithmetic stays f32."""
    if dtype is None:
        return v
    if jnp.issubdtype(dtype, jnp.complexfloating):
        return v.astype(dtype)
    re = jnp.real(v).astype(dtype).astype(jnp.float32)
    im = jnp.imag(v).astype(dtype).astype(jnp.float32)
    return (re + 1j * im).astype(jnp.complex64)


@jax.jit
def _eo_setup(U: jnp.ndarray, b: jnp.ndarray):
    """Gauge halves, source halves and ‖b‖ for the even-odd solve."""
    from repro.lqcd.eo import eo_pack, pack_gauge
    U_e, U_o = pack_gauge(U)
    return U_e, U_o, eo_pack(b, 0), eo_pack(b, 1), jnp.sqrt(_dot(b, b))


def _plane_dtype(inner_dtype):
    """What the inner CG stores its operator's fields in."""
    return jnp.float32 if inner_dtype is None else inner_dtype


@partial(jax.jit, static_argnames=("inner_dtype",))
def _eo_system(U_e, U_o, b_e, b_o, kappa, *, inner_dtype):
    """Schur right-hand side b_e + κ D_eo b_o, and the gauge halves the
    inner CG streams: real planes stored in ``inner_dtype``
    (:mod:`repro.lqcd.eo_planes`)."""
    from repro.lqcd.eo import eo_rhs
    from repro.lqcd.eo_planes import link_planes
    store = _plane_dtype(inner_dtype)
    return (eo_rhs(U_e, U_o, b_e, b_o, kappa),
            link_planes(U_e, store), link_planes(U_o, store))


@jax.jit
def _eo_defect_rhs(U_e, U_o, r_s, kappa):
    """A† r_s: right-hand side of the defect normal equations."""
    from repro.lqcd.eo import schur_matvec_dagger
    return schur_matvec_dagger(U_e, U_o, r_s, kappa)


@partial(jax.jit, static_argnames=("inner_dtype",))
def _eo_inner(U_e, U_o, rhs_n, kappa, eta, cap, *, inner_dtype):
    """Inner CG on A†A e = rhs_n, at most ``cap`` normal ops, on real
    planes (:mod:`repro.lqcd.eo_planes`): the links (``U_e``/``U_o``, as
    ``_eo_system`` makes them) and the normal op's input and outputs are
    stored in ``inner_dtype``, the CG vectors in float32.  ``rhs_n`` and
    the correction come and go in the complex layout."""
    from repro.lqcd import eo_planes

    xh = rhs_n.shape[0]
    store = _plane_dtype(inner_dtype)
    U_e, U_o = (eo_planes.link_planes(U, store) for U in (U_e, U_o))
    inner = cg_solve(lambda v: eo_planes.normal(U_e, U_o, v, kappa, xh),
                     eo_planes.spinor_planes(rhs_n), tol=eta, max_iters=cap)
    return eo_planes.spinor_complex(inner.x, xh), inner.iters


@jax.jit
def _eo_update(U_e, U_o, rhs_e, x_e, e, kappa):
    """x_e += e and the f32 Schur residual r_s = rhs_e − A x_e."""
    from repro.lqcd.eo import schur_matvec
    x_e = x_e + e
    r_s = rhs_e - schur_matvec(U_e, U_o, x_e, kappa)
    return x_e, r_s, jnp.sqrt(_dot(r_s, r_s))


@jax.jit
def _eo_finish(U, U_e, U_o, x_e, b, b_o, kappa):
    """Back-substitute the odd sites and take the true ‖b − M x‖ with the
    full-lattice operator, which shares no code with the even-odd one."""
    from repro.lqcd.eo import eo_unpack, reconstruct_odd
    x = eo_unpack(x_e, reconstruct_odd(U_e, U_o, x_e, b_o, kappa))
    true_r = b - wilson_matvec(U, x, kappa)
    return x, jnp.sqrt(_dot(true_r, true_r))


def _read(x, what: str, cast=float):
    """Bring one device value to the host inside an ``lqcd.sync`` span.

    Every blocking readback of the even-odd solve goes through here, so
    the number of ``lqcd.sync`` spans in a traced solve is its count of
    host syncs."""
    with TraceAnnotation("lqcd.sync", what=what):
        return cast(x)


@partial(annotate_function, name="lqcd.solve")
def solve_wilson_eo(U: jnp.ndarray, b: jnp.ndarray, kappa: float, *,
                    tol: float = 1e-6, max_iters: int = 1000,
                    inner_dtype=None, inner_tol: float = 1e-2,
                    max_outer: int = 30, mesh=None,
                    axis_name: str = "model", overlap: bool = True,
                    backend: str = "jnp") -> EOCGResult:
    """Solve M x = b via the even-odd Schur complement with an (optionally
    mixed-precision) defect-correction CG.

    The Schur system A x_e = b_e + κ D_eo b_o (A = 1 − κ² D_eo D_oe) is
    solved by CGNE on the even half-lattice; odd sites are reconstructed
    exactly as x_o = b_o + κ D_oe x_e, so the full-lattice residual equals
    the even-system residual.  With ``inner_dtype`` set (e.g.
    ``jnp.bfloat16``), the inner CG streams fields rounded through that
    dtype and the outer loop re-computes the residual in f32 and restarts —
    the reliable-update scheme the paper's single/double CG uses.  The
    round cap, κ and the inner tolerance are traced, so the outer rounds
    reuse one set of compiled programs per lattice shape.

    With ``mesh`` set, the Schur operators and the whole inner CG run
    T-sharded over the mesh's ``axis_name`` axis
    (:class:`repro.lqcd.multichip_eo.ShardedWilsonEO`): halos overlap
    interior compute (``overlap``), the inner ``while_loop`` stays inside
    one ``shard_map`` with ``psum`` reductions only, and
    ``backend="pallas"`` routes local hops through the autotuned Pallas
    kernel on halo-padded blocks.  Back-substitution and the true residual
    stay T-sharded too (no field is gathered onto one device).
    """
    # spans on the profiler's clock: lqcd.setup, one lqcd.round per round
    # and lqcd.finish never overlap; between them only the loop's own
    # bookkeeping runs
    with TraceAnnotation("lqcd.setup"):
        U_e, U_o, b_e, b_o, b_norm = _eo_setup(U, b)
        b_norm = _read(b_norm, "b_norm")
        # no low-precision pass gets below its own roundoff; full
        # precision drives straight to tol in one outer sweep
        eta = inner_tol if inner_dtype is not None else tol

        if mesh is not None:
            from repro.lqcd.eo import eo_unpack
            from repro.lqcd.multichip import dslash_sharded
            from repro.lqcd.multichip_eo import ShardedWilsonEO
            hi = ShardedWilsonEO(U_e, U_o, kappa, mesh, axis_name=axis_name,
                                 overlap=overlap, backend=backend)
            # the inner CG streams the *rounded* gauge field, like the
            # single-device normal_lo path
            lo = hi if inner_dtype is None else ShardedWilsonEO(
                _round_complex(U_e, inner_dtype),
                _round_complex(U_o, inner_dtype), kappa, mesh,
                axis_name=axis_name, overlap=overlap, backend=backend)
            rhs_e = hi.rhs(b_e, b_o)

            def run_round(x_e, r_s, cap):
                inner = lo.cg_normal(hi.schur_dagger(r_s), tol=eta,
                                     max_iters=cap, inner_dtype=inner_dtype)
                x_e = x_e + inner.x
                r_s = rhs_e - hi.schur(x_e)
                return x_e, r_s, jnp.sqrt(_dot(r_s, r_s)), inner.iters

            def finish(x_e):
                x = eo_unpack(x_e, hi.reconstruct(x_e, b_o))
                true_r = b - (x - kappa * dslash_sharded(U, x, mesh,
                                                          axis_name))
                return x, jnp.sqrt(_dot(true_r, true_r))
        else:
            rhs_e, *U_lo = _eo_system(U_e, U_o, b_e, b_o, kappa,
                                      inner_dtype=inner_dtype)

            def run_round(x_e, r_s, cap):
                # three programs, not one: XLA would keep the Schur
                # operators' temporaries beside the inner loop's
                rhs_n = _eo_defect_rhs(U_e, U_o, r_s, kappa)
                e, iters = _eo_inner(*U_lo, rhs_n, kappa, eta, cap,
                                     inner_dtype=inner_dtype)
                return _eo_update(U_e, U_o, rhs_e, x_e, e, kappa) + (iters,)

            def finish(x_e):
                return _eo_finish(U, U_e, U_o, x_e, b, b_o, kappa)

        x_e = jnp.zeros_like(rhs_e)
        r_s = rhs_e                          # Schur-system residual
        r_norm = _read(jnp.sqrt(_dot(r_s, r_s)), "r_norm0")

    total_inner = 0
    outer = 0
    while outer < max_outer and total_inner < max_iters:
        if r_norm / max(b_norm, 1e-30) <= tol:
            break
        # inner CG on the defect equation A†A e = A† r_s, reduced precision.
        # Cap each low-precision restart so a stalled inner solve (roundoff
        # plateau above inner_tol) can't eat the whole budget in one round.
        remaining = max_iters - total_inner
        round_cap = (remaining if inner_dtype is None
                     else min(remaining, max(10, max_iters // 5)))
        with TraceAnnotation("lqcd.round", round=outer, cap=round_cap):
            x_e, r_s, r_norm, iters = run_round(x_e, r_s,
                                                jnp.int32(round_cap))
            total_inner += _read(iters, "iters", int)
            r_norm = _read(r_norm, "r_norm")
        outer += 1

    with TraceAnnotation("lqcd.finish"):
        x, true_norm = finish(x_e)
        rel = _read(true_norm, "true_norm") / max(b_norm, 1e-30)
        return EOCGResult(x, total_inner, outer, rel, rel <= tol)


def solve_dirac(U: jnp.ndarray, b: jnp.ndarray, kappa: float, cfg, *,
                mesh=None, axis_name: str = "model", overlap: bool = True,
                backend: str = "jnp"):
    """Config-driven entry point: dispatch on a ``repro.config.SolverConfig``.

    Returns a ``CGResult`` for the plain path and an ``EOCGResult`` for the
    even-odd paths (both expose ``.x``, ``.iters``, ``.rel_residual``,
    ``.converged``).  ``mesh`` routes the even-odd paths through the
    T-sharded multi-chip solver.
    """
    if cfg.preconditioner == "none":
        if mesh is not None:
            raise ValueError("mesh= requires an even-odd preconditioner "
                             "(cfg.preconditioner != 'none')")
        return solve_wilson(U, b, kappa, tol=cfg.tol,
                            max_iters=cfg.max_iters)
    # float32 inner == working precision: not a mixed-precision solve
    inner = None if not cfg.mixed_precision else jnp.dtype(cfg.inner_dtype)
    return solve_wilson_eo(U, b, kappa, tol=cfg.tol,
                           max_iters=cfg.max_iters, inner_dtype=inner,
                           inner_tol=cfg.inner_tol, max_outer=cfg.max_outer,
                           mesh=mesh, axis_name=axis_name, overlap=overlap,
                           backend=backend)

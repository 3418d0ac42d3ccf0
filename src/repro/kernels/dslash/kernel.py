"""Wilson D-slash Pallas kernels — the paper's memory-bound hotspot (C1),
laid out for the TPU's vector tiles.

GPU original (CL2QCD): one thread per site, LDS-staged links.  TPU version:
the lattice is blocked along T; each grid step keeps ``t_block`` time
slices of spinors and links in VMEM.  Spatial (x/y/z) neighbours are
in-block rotations; the T-boundary halos arrive as single-slice blocks
through overlapping BlockSpec index maps ((i·Tb ± 1) mod T).

Layout ("split" fields, made by :func:`repro.kernels.dslash.ref.to_split`):

    spinor  (T, 4, 3, 2, X, Y*Z)      t, spin, colour, re/im, sites
    gauge   (T, 4, 3, 3, 2, X, Y*Z)   t, direction, row, col, re/im, sites

Component axes lead and the two minor dims are site axes — X on sublanes,
Y and Z merged on lanes — so every (8, 128) vector tile holds 1024 sites
of one real component.  All arithmetic is elementwise f32 on the VPU:
3×3 complex multiply-adds, unrolled (the MXU has no use for 3×3).

Each hop projects first: ``(1 ∓ γ_μ)`` has rank 2, so the kernel forms
the two-component half spinor, multiplies it by the link and rebuilds the
four components (the standard Wilson trick: half the SU(3) work and half
the rotations of the naive hop).  Backward hops multiply at the source
site and rotate the product, so links are never rotated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# gamma matrices (Dirac basis); order x, y, z, t
_g = np.zeros((4, 4, 4), np.complex128)
_g[0] = [[0, 0, 0, -1j], [0, 0, -1j, 0], [0, 1j, 0, 0], [1j, 0, 0, 0]]
_g[1] = [[0, 0, 0, -1], [0, 0, 1, 0], [0, 1, 0, 0], [-1, 0, 0, 0]]
_g[2] = [[0, 0, -1j, 0], [0, 0, 0, 1j], [1j, 0, 0, 0], [0, -1j, 0, 0]]
_g[3] = [[1, 0, 0, 0], [0, 1, 0, 0], [0, 0, -1, 0], [0, 0, 0, -1]]
_eye = np.eye(4)
PROJ_M = np.stack([_eye - _g[mu] for mu in range(4)])   # (1 - gamma_mu)
PROJ_P = np.stack([_eye + _g[mu] for mu in range(4)])   # (1 + gamma_mu)


def _rank2_split(proj: np.ndarray):
    """Write a rank-2 projector as ``proj = R @ Q``: ``Q`` (2×4) is two
    independent rows of ``proj`` (they form the half spinor) and ``R``
    (4×2) rebuilds all four rows from them."""
    rows = [i for i in range(4) if np.any(proj[i])]
    q_rows = [rows[0]] + [i for i in rows[1:]
                          if np.linalg.matrix_rank(proj[[rows[0], i]]) == 2][:1]
    Q = proj[q_rows]
    R = np.linalg.lstsq(Q.T, proj.T, rcond=None)[0].T
    R = np.round(R.real) + 1j * np.round(R.imag)
    assert np.allclose(R @ Q, proj)
    return Q, R


# (Q, R) per direction for the forward (1 - γ) and backward (1 + γ) hops
FWD = [_rank2_split(PROJ_M[mu]) for mu in range(4)]
BWD = [_rank2_split(PROJ_P[mu]) for mu in range(4)]


def _halo_spins(Q: np.ndarray) -> int:
    """First of the two consecutive spin components a t-halo must carry."""
    used = [s for s in range(4) if np.any(Q[:, s])]
    # the halo BlockSpec selects spin block used[0] // 2 of size 2
    assert len(used) == 2 and used[1] == used[0] + 1 and used[0] % 2 == 0
    return used[0]


HALO_NEXT_SPIN = _halo_spins(FWD[3][0])    # +t hop reads these of t+1
HALO_PREV_SPIN = _halo_spins(BWD[3][0])    # -t hop reads these of t-1

# Scoped-VMEM limits: the compiler's default, and the most the kernels
# request (v5e has 128 MiB of VMEM per core)
DEFAULT_SCOPED_VMEM_BYTES = 16 * 2**20
VMEM_CAP_BYTES = 100 * 2**20


# ---------------------------------------------------------------------------
# Elementwise complex helpers.  A colour vector is a (re, im) pair of
# (tb, 3, X, Y*Z) f32 arrays — the block's time slices and colour on
# leading axes; a spinor is a list of four colour vectors; a link is a list
# of three (re, im) pairs of such arrays — its columns (for U v) or its
# rows (for U† v).
# ---------------------------------------------------------------------------

def _cscale(c: complex, v):
    """Complex literal times a complex array (literals are 0, ±1, ±i, ±2)."""
    cr, ci = float(c.real), float(c.imag)
    re, im = v
    if ci == 0.0:
        if cr == 1.0:
            return re, im
        if cr == -1.0:
            return -re, -im
        return cr * re, cr * im
    if cr == 0.0:
        if ci == 1.0:
            return -im, re
        if ci == -1.0:
            return im, -re
        return -ci * im, ci * re
    return cr * re - ci * im, cr * im + ci * re


def _cadd(a, b):
    if a is None:
        return b
    return a[0] + b[0], a[1] + b[1]


def _lincomb(coefs, vecs):
    """Σ_k coefs[k] · vecs[k], skipping zero terms (``None`` if all are)."""
    out = None
    for c, v in zip(coefs, vecs):
        if c == 0:
            continue
        assert v is not None, "projection reads a spin the halo lacks"
        out = _cadd(out, _cscale(c, v))
    return out


def _mv(cols, v):
    """U v: Σ_b U[:, b] v_b, with ``cols[b]`` the b-th column of U."""
    vr, vi = v
    re = im = None
    for b, (ur, ui) in enumerate(cols):
        vbr, vbi = vr[:, b:b + 1], vi[:, b:b + 1]
        pr = ur * vbr - ui * vbi
        pi = ur * vbi + ui * vbr
        re = pr if re is None else re + pr
        im = pi if im is None else im + pi
    return re, im


def _mv_dag(rows, v):
    """U† v: (U†)_ab = conj(U_ba), so Σ_b conj(U[b, :]) v_b."""
    vr, vi = v
    re = im = None
    for b, (ur, ui) in enumerate(rows):
        vbr, vbi = vr[:, b:b + 1], vi[:, b:b + 1]
        pr = ur * vbr + ui * vbi
        pi = ur * vbi - ui * vbr
        re = pr if re is None else re + pr
        im = pi if im is None else im + pi
    return re, im


def _project(Q, spinor):
    return [_lincomb(Q[j], spinor) for j in range(2)]


def _accumulate(out, R, half):
    """out[s] += Σ_j R[s, j] · half[j]."""
    for s in range(4):
        add = _lincomb(R[s], half)
        if add is not None:
            out[s] = _cadd(out[s], add)


def _map_half(fn, half):
    return [(fn(re), fn(im)) for re, im in half]


def _spins(ref, t, first_spin=0, n_spins=4):
    """Colour vectors of time slices ``t`` (a slice) of a split spinor ref;
    spins outside the block are ``None``."""
    sp = [None] * 4
    for k in range(n_spins):
        sp[first_spin + k] = (ref[t, k, :, 0], ref[t, k, :, 1])
    return sp


def _cols(ref, t, mu):
    return [(ref[t, mu, :, b, 0], ref[t, mu, :, b, 1]) for b in range(3)]


def _rows(ref, t, mu):
    return [(ref[t, mu, b, :, 0], ref[t, mu, b, :, 1]) for b in range(3)]


def _cat_t(first, rest):
    """Join complex arrays along the block's time axis (``rest`` may be
    ``None`` when t_block is 1)."""
    if rest is None:
        return first
    return tuple(jnp.concatenate([a, b], axis=0) for a, b in zip(first, rest))


class _Shifts:
    """Neighbour access on (tb, 3, X, Y*Z) arrays with periodic boundaries.

    ``fwd(mu, p)`` gives p(site + μ̂), ``bwd(mu, p)`` gives p(site − μ̂) for
    μ = x (sublane rotation), y (lane rotation by Z) and z (lane rotation
    by 1, patched where z wraps inside its row of Y*Z lanes)."""

    def __init__(self, X, YZ, Z, z_last, z_first):
        self.X, self.YZ, self.Z = X, YZ, Z
        self.z_last, self.z_first = z_last, z_first

    @staticmethod
    def _roll(p, shift, axis, n):
        shift %= n
        return p if shift == 0 else pltpu.roll(p, shift, axis)

    def fwd(self, mu, p):
        X, YZ, Z = self.X, self.YZ, self.Z
        if mu == 0:
            return self._roll(p, X - 1, 2, X)
        if mu == 1:
            return self._roll(p, YZ - Z, 3, YZ)
        return jnp.where(self.z_last, self._roll(p, Z - 1, 3, YZ),
                         self._roll(p, YZ - 1, 3, YZ))

    def bwd(self, mu, p):
        X, YZ, Z = self.X, self.YZ, self.Z
        if mu == 0:
            return self._roll(p, 1, 2, X)
        if mu == 1:
            return self._roll(p, Z, 3, YZ)
        return jnp.where(self.z_first, self._roll(p, YZ - Z + 1, 3, YZ),
                         self._roll(p, 1, 3, YZ))


def _hop_fwd(out, mu, shift, cols, psi):
    """out += (1 − γ_μ) U_μ(x) ψ(x + μ̂): project, shift, multiply."""
    Q, R = FWD[mu]
    half = _map_half(shift, _project(Q, psi))
    _accumulate(out, R, [_mv(cols, h) for h in half])


def _hop_bwd(out, mu, shift, rows, psi):
    """out += (1 + γ_μ) U_μ†(x − μ̂) ψ(x − μ̂): project, multiply at the
    source site, shift the product."""
    Q, R = BWD[mu]
    w = [_mv_dag(rows, h) for h in _project(Q, psi)]
    _accumulate(out, R, _map_half(shift, w))


def _t_hops(out, tb, psi_ref, nxt_ref, prv_ref, u_fwd_ref, u_bwd_ref,
            u_bwd_prev_ref):
    """±t hops of the whole block: slice t reads slice t ± 1, or the halo
    slice at the block's edge (with t_block=1, only the halos)."""
    same = lambda p: p                                      # noqa: E731
    inner = tb > 1
    nxt = _spins(nxt_ref, slice(None), HALO_NEXT_SPIN, 2)
    mid = _spins(psi_ref, slice(1, tb)) if inner else [None] * 4
    src = [None if v is None else _cat_t(mid[s], v) if inner else v
           for s, v in enumerate(nxt)]
    _hop_fwd(out, 3, same, _cols(u_fwd_ref, slice(None), 3), src)

    prv = _spins(prv_ref, slice(None), HALO_PREV_SPIN, 2)
    mid = _spins(psi_ref, slice(0, tb - 1)) if inner else [None] * 4
    src = [None if v is None else _cat_t(v, mid[s] if inner else None)
           for s, v in enumerate(prv)]
    rows = _rows(u_bwd_prev_ref, slice(None), 0)
    if inner:
        rows = [_cat_t(r, m) for r, m in
                zip(rows, _rows(u_bwd_ref, slice(0, tb - 1), 3))]
    _hop_bwd(out, 3, same, rows, src)


def _store(o_ref, out):
    for s in range(4):
        o_ref[:, s, :, 0] = out[s][0]
        o_ref[:, s, :, 1] = out[s][1]


def _masks(mask_ref, tb):
    """(y+z)%2 and the z-wrap masks, broadcast to (tb, 3, X, Y*Z)."""
    m = mask_ref[...]
    shape = (tb, 3) + m.shape[1:]
    rows = [jnp.broadcast_to(m[k][None, None], shape) for k in range(3)]
    return rows[0], rows[1] == 1, rows[2] == 1


def _dslash_kernel(Z, psi_ref, nxt_ref, prv_ref, u_ref, u_prev_ref,
                   mask_ref, o_ref):
    tb, _, _, _, X, YZ = psi_ref.shape
    _, z_last, z_first = _masks(mask_ref, tb)
    sh = _Shifts(X, YZ, Z, z_last, z_first)
    every = slice(None)
    psi = _spins(psi_ref, every)
    out = [None] * 4
    for mu in range(3):
        _hop_fwd(out, mu, functools.partial(sh.fwd, mu),
                 _cols(u_ref, every, mu), psi)
        _hop_bwd(out, mu, functools.partial(sh.bwd, mu),
                 _rows(u_ref, every, mu), psi)
    _t_hops(out, tb, psi_ref, nxt_ref, prv_ref, u_ref, u_ref, u_prev_ref)
    _store(o_ref, out)


def _dslash_eo_kernel(Z, out_parity, psi_ref, nxt_ref, prv_ref, uout_ref,
                      usrc_ref, usrc_prev_ref, mask_ref, o_ref):
    """One parity block of D-slash on the compact (checkerboard) layout.

    Input spinors live on the opposite parity of the output; both are
    half-lattices (X//2 on sublanes), so each grid step streams only
    same-parity blocks through VMEM — half the spinor traffic of the full
    kernel per output site, which is the CL2QCD bandwidth trick.

    Compact-layout hop rules (derivation in ``repro.lqcd.eo``):
      y/z hops: in-block rotations;  t hops: neighbour slices or halos;
      x hops:  rotation applied only where s = (y+z+t+parity) % 2 == 1.
    """
    tb, _, _, _, Xh, YZ = psi_ref.shape
    yz_par, z_last, z_first = _masks(mask_ref, tb)
    sh = _Shifts(Xh, YZ, Z, z_last, z_first)
    # output sites with s_out = 1 take their x neighbours one compact
    # index over: +x at i + 1, -x (and its link) at i - 1
    t0 = pl.program_id(0) * tb + out_parity
    s_out = jnp.concatenate(
        [yz_par[j:j + 1] != jnp.bitwise_and(t0 + j, 1) for j in range(tb)],
        axis=0)
    every = slice(None)
    psi = _spins(psi_ref, every)
    out = [None] * 4
    _hop_fwd(out, 0, lambda p: jnp.where(s_out, sh.fwd(0, p), p),
             _cols(uout_ref, every, 0), psi)
    _hop_bwd(out, 0, lambda p: jnp.where(s_out, p, sh.bwd(0, p)),
             _rows(usrc_ref, every, 0), psi)
    for mu in (1, 2):
        _hop_fwd(out, mu, functools.partial(sh.fwd, mu),
                 _cols(uout_ref, every, mu), psi)
        _hop_bwd(out, mu, functools.partial(sh.bwd, mu),
                 _rows(usrc_ref, every, mu), psi)
    _t_hops(out, tb, psi_ref, nxt_ref, prv_ref, uout_ref, usrc_ref,
            usrc_prev_ref)
    _store(o_ref, out)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _plane_bytes(X: int, YZ: int) -> int:
    """Bytes of one (X, Y*Z) f32 plane once tiled as (8, 128)."""
    return (-(-X // 8) * 8) * (-(-YZ // 128) * 128) * 4


def vmem_bytes(lat, t_block: int, *, even_odd: bool = True) -> int:
    """Double-buffered VMEM of one grid step's blocks, in tiled bytes.

    ``lat`` is the kernel's own (X, Y, Z, T): the compact half-lattice
    (X//2 leading) for the even-odd kernel.  Per time slice a block holds
    24 spinor planes in, 24 out and 72 gauge planes per gauge operand (two
    for even-odd); the halos add two half spinors (12 planes each) and one
    t-link (18 planes), and the site masks 3 planes."""
    X, Y, Z, _ = lat
    per_t = 24 + 24 + 72 * (2 if even_odd else 1)
    planes = t_block * per_t + 12 + 12 + 18 + 3
    return 2 * planes * _plane_bytes(X, Y * Z)


def vmem_need(lat, t_block: int, *, even_odd: bool = True) -> int:
    """Scoped VMEM a grid step needs: the blocks plus 25% for the body's
    own stack.  On a v5e the even-odd kernel on 16 x 32 x 32 x T half
    lattices (53.6 MiB of blocks at t_block=2) needed 55.6 MiB at T=8 and
    64.6 MiB at T=18; t_block=4 needs 108.7 MiB (101.6 MiB of blocks),
    over the cap."""
    return int(1.25 * vmem_bytes(lat, t_block, even_odd=even_odd))


def t_block_fits(lat, t_block: int, *, even_odd: bool = True) -> bool:
    """Whether ``t_block`` divides T and its blocks fit the VMEM cap."""
    return (lat[3] % t_block == 0
            and vmem_need(lat, t_block, even_odd=even_odd) <= VMEM_CAP_BYTES)


def _vmem_limit(lat, tb: int, even_odd: bool) -> int:
    """The compiler's default when a step fits it, else the cap: the
    body's stack grows with T by more than a compile for a described chip
    shows, so no tighter limit is safe."""
    if vmem_need(lat, tb, even_odd=even_odd) <= DEFAULT_SCOPED_VMEM_BYTES:
        return DEFAULT_SCOPED_VMEM_BYTES
    return VMEM_CAP_BYTES


def site_masks(X: int, Y: int, Z: int) -> jnp.ndarray:
    """(3, X, Y*Z) int32 lane patterns: (y+z)%2, z==Z-1, z==0."""
    y, z = np.divmod(np.arange(Y * Z), Z)
    rows = np.stack([(y + z) % 2, z == Z - 1, z == 0]).astype(np.int32)
    return jnp.asarray(np.broadcast_to(rows[:, None, :], (3, X, Y * Z)))


def _specs(X, YZ, T, tb, n_gauge):
    psi = pl.BlockSpec((tb, 4, 3, 2, X, YZ), lambda i: (i, 0, 0, 0, 0, 0))
    nxt = pl.BlockSpec((1, 2, 3, 2, X, YZ),
                       lambda i: ((i * tb + tb) % T, HALO_NEXT_SPIN // 2,
                                  0, 0, 0, 0))
    prv = pl.BlockSpec((1, 2, 3, 2, X, YZ),
                       lambda i: ((i * tb - 1) % T, HALO_PREV_SPIN // 2,
                                  0, 0, 0, 0))
    u = pl.BlockSpec((tb, 4, 3, 3, 2, X, YZ),
                     lambda i: (i, 0, 0, 0, 0, 0, 0))
    u_prev = pl.BlockSpec((1, 1, 3, 3, 2, X, YZ),
                          lambda i: ((i * tb - 1) % T, 3, 0, 0, 0, 0, 0))
    mask = pl.BlockSpec((3, X, YZ), lambda i: (0, 0, 0))
    return psi, [psi, nxt, prv] + [u] * n_gauge + [u_prev, mask]


def _check_t_block(T: int, t_block: int) -> int:
    tb = min(t_block, T)
    if T % tb:
        raise ValueError(f"t_block={t_block} must divide T={T}")
    return tb


def dslash_eo_split(U_out_s: jnp.ndarray, U_src_s: jnp.ndarray,
                    psi_s: jnp.ndarray, src_parity: int, z_extent: int, *,
                    t_block: int = 1, interpret: bool = False) -> jnp.ndarray:
    """Half-lattice D-slash hop on split compact fields.

    U_out_s/U_src_s: (T, 4, 3, 3, 2, X//2, Y*Z) f32 packed at the
    output/source parity; psi_s: (T, 4, 3, 2, X//2, Y*Z) f32 on
    ``src_parity`` sites.  Returns the opposite-parity half-field.
    """
    T, _, _, _, Xh, YZ = psi_s.shape
    tb = _check_t_block(T, t_block)
    out_spec, in_specs = _specs(Xh, YZ, T, tb, n_gauge=2)
    lat = (Xh, YZ // z_extent, z_extent, T)
    return pl.pallas_call(
        functools.partial(_dslash_eo_kernel, z_extent, 1 - src_parity),
        grid=(T // tb,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(psi_s.shape, psi_s.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(lat, tb, True)),
        interpret=interpret,
        name="dslash_eo",
    )(psi_s, psi_s, psi_s, U_out_s, U_src_s, U_src_s,
      site_masks(Xh, YZ // z_extent, z_extent))


def dslash_split(U_s: jnp.ndarray, psi_s: jnp.ndarray, z_extent: int, *,
                 t_block: int = 1, interpret: bool = False) -> jnp.ndarray:
    """D-slash on split fields.

    U_s: (T, 4, 3, 3, 2, X, Y*Z) f32; psi_s: (T, 4, 3, 2, X, Y*Z) f32.
    """
    T, _, _, _, X, YZ = psi_s.shape
    tb = _check_t_block(T, t_block)
    out_spec, in_specs = _specs(X, YZ, T, tb, n_gauge=1)
    lat = (X, YZ // z_extent, z_extent, T)
    return pl.pallas_call(
        functools.partial(_dslash_kernel, z_extent),
        grid=(T // tb,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(psi_s.shape, psi_s.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(lat, tb, False)),
        interpret=interpret,
        name="dslash",
    )(psi_s, psi_s, psi_s, U_s, U_s, site_masks(X, YZ // z_extent, z_extent))

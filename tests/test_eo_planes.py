"""The even-odd Schur operator on real planes (``repro.lqcd.eo_planes``),
the layout the single-device inner CG runs on, against the complex
operator of ``repro.lqcd.eo`` and the complex inner CG it replaced."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.lqcd import cg
from repro.lqcd import eo as EO
from repro.lqcd import eo_planes as P
from repro.lqcd.su3 import random_su3_field

SHAPES = [(4, 4, 4, 4), (8, 8, 8, 8), (4, 4, 4, 8)]
KAPPA = 0.13
BF16 = jnp.bfloat16


def _fields(shape, seed=0):
    ku, kr, ki = jax.random.split(jax.random.PRNGKey(seed), 3)
    U = random_su3_field(ku, shape)
    b = (jax.random.normal(kr, shape + (4, 3))
         + 1j * jax.random.normal(ki, shape + (4, 3))).astype(jnp.complex64)
    return U, b


def _link_complex(p, xh):
    """Inverse of ``link_planes``: complex64 (4, Xh, Y, Z, T, 3, 3)."""
    T, Z, L = p.shape[4:]
    p = p.astype(jnp.float32).reshape(2, 4, 3, 3, T, Z, L // xh, xh)
    p = p.transpose(0, 1, 7, 6, 5, 4, 2, 3)
    return jax.lax.complex(p[0], p[1])


def _planes(v):
    return np.asarray(P.spinor_planes(v), np.float32)


def _assert_f32_close(got, want):
    """Equal up to float32 rounding of a sum of O(100) terms."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("shape", SHAPES)
def test_conversions_round_trip_exactly(shape):
    U, b = _fields(shape)
    xh = shape[0] // 2
    U_e, U_o = EO.pack_gauge(U)
    for half in (EO.eo_pack(b, 0), EO.eo_pack(b, 1)):
        p = P.spinor_planes(half)
        assert p.shape[-1] == shape[1] * xh and p.shape[:3] == (2, 4, 3)
        np.testing.assert_array_equal(np.asarray(P.spinor_complex(p, xh)),
                                      np.asarray(half))
    for links in (U_e, U_o):
        p = P.link_planes(links)
        assert p.shape[:4] == (2, 4, 3, 3)
        np.testing.assert_array_equal(np.asarray(_link_complex(p, xh)),
                                      np.asarray(links))
        # real planes pass through, cast only
        assert P.link_planes(p, BF16).dtype == BF16


@pytest.mark.parametrize("src_parity", [0, 1])
@pytest.mark.parametrize("shape", SHAPES + [(2, 4, 4, 4), (4, 6, 4, 8)])
def test_hop_matches_complex_hop(shape, src_parity):
    U, b = _fields(shape, seed=1)
    xh = shape[0] // 2
    U_e, U_o = EO.pack_gauge(U)
    U_out, U_src = (U_o, U_e) if src_parity == 0 else (U_e, U_o)
    half = EO.eo_pack(b, src_parity)
    want = EO.dslash_half(U_out, U_src, half, src_parity=src_parity)
    got = P.hop(P.link_planes(U_out), P.link_planes(U_src),
                P.spinor_planes(half), 1 - src_parity, xh)
    _assert_f32_close(got, _planes(want))


@pytest.mark.parametrize("dagger", [False, True], ids=["A", "A_dagger"])
@pytest.mark.parametrize("data_parity", [0, 1])
@pytest.mark.parametrize("shape", SHAPES)
def test_schur_matches_complex_schur(shape, data_parity, dagger):
    """On the even- and the odd-site data of one field."""
    U, b = _fields(shape, seed=2)
    xh = shape[0] // 2
    U_e, U_o = EO.pack_gauge(U)
    psi = EO.eo_pack(b, data_parity)
    ref, planes = ((EO.schur_matvec_dagger, P.schur_dagger) if dagger
                   else (EO.schur_matvec, P.schur))
    want = ref(U_e, U_o, psi, KAPPA)
    got = planes(P.link_planes(U_e), P.link_planes(U_o),
                 P.spinor_planes(psi), KAPPA, xh)
    _assert_f32_close(got, _planes(want))


def _parent_normal(U_e, U_o, v, kappa, inner_dtype):
    """The complex normal op the planes replaced: fields rounded through
    ``inner_dtype`` and stored complex64 (``U_e``/``U_o`` rounded)."""
    v = cg._round_complex(v, inner_dtype)
    av = cg._round_complex(EO.schur_matvec(U_e, U_o, v, kappa), inner_dtype)
    return cg._round_complex(EO.schur_matvec_dagger(U_e, U_o, av, kappa),
                             inner_dtype)


def _bf16_half_ulp(x):
    """Half a bfloat16 ulp (8 significant bits) of each entry of x."""
    x = np.abs(np.asarray(x, np.float64))
    return np.exp2(np.floor(np.log2(np.where(x > 0, x, 1.0))) - 8)


def _assert_rounded_from(got, unrounded):
    """``got`` is ``unrounded`` rounded to bfloat16: within half an ulp,
    plus the float32 noise of two orders of summation."""
    got = np.asarray(got, np.float32)
    unrounded = np.asarray(unrounded, np.float32)
    noise = 1e-6 * np.abs(unrounded).max()
    assert (np.abs(got - unrounded)
            <= _bf16_half_ulp(unrounded) * (1 + 1e-6) + noise).all()


@pytest.mark.parametrize("shape", SHAPES)
def test_bf16_normal_rounds_where_the_complex_one_did(shape):
    """v, A v and A^dagger A v are each rounded to bf16, nothing else."""
    U, b = _fields(shape, seed=3)
    xh = shape[0] // 2
    U_e, U_o = EO.pack_gauge(U)
    Ue_r, Uo_r = (cg._round_complex(u, BF16) for u in (U_e, U_o))
    Le, Lo = P.link_planes(U_e, BF16), P.link_planes(U_o, BF16)
    np.testing.assert_array_equal(np.asarray(_link_complex(Le, xh)),
                                  np.asarray(Ue_r))
    v = EO.eo_pack(b, 0)
    v16 = cg._round_complex(v, BF16)

    out = P.normal(Le, Lo, P.spinor_planes(v), KAPPA, xh)
    assert out.dtype == BF16
    # first rounding point: A v from the rounded v
    av = P.schur(Le, Lo, P.spinor_planes(v16).astype(BF16), KAPPA,
                 xh).astype(BF16)
    _assert_rounded_from(av, _planes(EO.schur_matvec(Ue_r, Uo_r, v16,
                                                     KAPPA)))
    # second: A^dagger of that A v
    want = EO.schur_matvec_dagger(Ue_r, Uo_r, P.spinor_complex(av, xh),
                                  KAPPA)
    _assert_rounded_from(out, _planes(want))
    # and the whole op against the complex formula: mostly equal, and
    # within two bf16 ulps of the output's scale where an earlier
    # rounding fell the other way
    parent = _planes(_parent_normal(Ue_r, Uo_r, v, KAPPA, BF16))
    got = np.asarray(out, np.float32)
    assert np.abs(got - parent).max() <= 4 * _bf16_half_ulp(
        np.abs(parent).max())
    assert np.mean(got == parent) > 0.9


@partial(jax.jit, static_argnames=("inner_dtype",))
def _parent_eo_inner(U_e, U_o, rhs_n, kappa, eta, cap, *, inner_dtype):
    """The complex inner CG the planes replaced, fed the planes links."""
    xh = rhs_n.shape[0]
    U_e, U_o = (_link_complex(u, xh) for u in (U_e, U_o))
    inner = cg.cg_solve(
        lambda v: _parent_normal(U_e, U_o, v, kappa, inner_dtype),
        rhs_n, tol=eta, max_iters=cap)
    return inner.x, inner.iters


def test_bf16_solve_converges_like_the_complex_inner_cg(monkeypatch):
    U, b = _fields((8, 8, 8, 8))
    kappa = 0.12
    planes = cg.solve_wilson_eo(U, b, kappa, tol=1e-6, max_iters=1000,
                                inner_dtype=BF16)
    monkeypatch.setattr(cg, "_eo_inner", _parent_eo_inner)
    parent = cg.solve_wilson_eo(U, b, kappa, tol=1e-6, max_iters=1000,
                                inner_dtype=BF16)
    assert planes.converged and planes.rel_residual <= 1e-6
    assert parent.converged
    assert abs(planes.iters - parent.iters) <= 2
    assert planes.outer_iters == parent.outer_iters


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


def test_inner_loop_is_real_and_stores_bf16():
    """Inside ``_eo_inner``'s while loop nothing is complex; the links
    come in as bf16 planes and the loop rounds three fields to bf16 a
    normal op (v, A v, A^dagger A v)."""
    shape = (4, 4, 4, 8)
    U, b = _fields(shape)
    U_e, U_o = EO.pack_gauge(U)
    _, Le, Lo = cg._eo_system(U_e, U_o, EO.eo_pack(b, 0), EO.eo_pack(b, 1),
                              KAPPA, inner_dtype=BF16)
    assert Le.dtype == Lo.dtype == BF16 and Le.shape[:4] == (2, 4, 3, 3)
    closed = jax.make_jaxpr(partial(cg._eo_inner, inner_dtype=BF16))(
        Le, Lo, EO.eo_pack(b, 0), KAPPA, 1e-2, jnp.int32(10))
    (loop,) = [e for e in _eqns(closed.jaxpr) if e.primitive.name == "while"]
    body = loop.params["body_jaxpr"].jaxpr
    avals = [v.aval for e in _eqns(body) for v in e.invars + e.outvars
             if hasattr(v, "aval")]
    avals += [v.aval for v in body.invars]
    assert not any(jnp.issubdtype(a.dtype, jnp.complexfloating)
                   for a in avals if hasattr(a, "dtype"))
    links = [v.aval for v in body.invars if v.aval.shape == Le.shape]
    assert links and all(a.dtype == BF16 for a in links)
    rounds = [e for e in _eqns(body)
              if e.primitive.name == "convert_element_type"
              and e.params["new_dtype"] == BF16]
    assert len(rounds) == 3
    assert all(e.outvars[0].aval.shape == (2, 4, 3) + Le.shape[4:]
               for e in rounds)

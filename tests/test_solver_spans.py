"""The even-odd solver's own spans and names in the JAX profiler's trace.

``solve_wilson_eo`` opens one ``lqcd.solve`` span, the phase spans
``lqcd.setup``, ``lqcd.round`` (one per defect-correction round) and
``lqcd.finish`` inside it, and one ``lqcd.sync`` around each blocking
host readback; the hop arithmetic carries the ``lqcd.hop`` named scope
into the compiled programs, and the sharded vector programs have names
of their own."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from conftest import need_devices
from repro.distributed.sharding import lattice_mesh
from repro.lqcd import cg
from repro.lqcd.eo import eo_pack, pack_gauge
from repro.lqcd.multichip_eo import ShardedWilsonEO
from repro.lqcd.su3 import random_su3_field

LAT = (4, 4, 4, 8)
KAPPA = 0.12
PHASES = ("lqcd.setup", "lqcd.round", "lqcd.finish")


def _fields():
    U = random_su3_field(jax.random.PRNGKey(0), LAT)
    b = jnp.zeros(LAT + (4, 3), jnp.complex64).at[1, 2, 0, 3, 0, 1].set(1.0)
    return U, b


def _host_spans(path):
    """(name, start, end, stats) of every ``lqcd.*`` host event."""
    files = sorted(path.rglob("*.xplane.pb"))
    profile = ProfileData.from_file(str(files[-1]))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("lqcd.")]


@pytest.fixture(scope="module", params=[1, 4], ids=["one_device",
                                                    "four_devices"])
def traced(request, tmp_path_factory):
    """One mixed-precision solve under the profiler, after a warm-up solve
    that compiles: (result, its spans)."""
    mesh = None
    if request.param > 1:
        need_devices(request.param)
        mesh = lattice_mesh(LAT[3], request.param)
        assert mesh.size == request.param
    U, b = _fields()
    kw = dict(inner_dtype=jnp.bfloat16, mesh=mesh)
    cg.solve_wilson_eo(U, b, KAPPA, **kw)
    path = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(path))
    try:
        res = cg.solve_wilson_eo(U, b, KAPPA, **kw)
    finally:
        jax.profiler.stop_trace()
    return res, _host_spans(path)


def test_one_solve_span_and_phases_cover_it(traced):
    res, spans = traced
    (solve,) = [s for s in spans if s[0] == "lqcd.solve"]
    phases = sorted((s, e, n) for n, s, e, _ in spans if n in PHASES)
    assert [n for _, _, n in phases] == (
        ["lqcd.setup"] + ["lqcd.round"] * res.outer_iters + ["lqcd.finish"])
    # inside the solve, in order, never overlapping
    assert solve[1] <= phases[0][0] and phases[-1][1] <= solve[2]
    for (_, end, _), (start, _, _) in zip(phases, phases[1:]):
        assert end <= start
    # what falls between them is the loop's own bookkeeping
    uncovered = (solve[2] - solve[1]) - sum(e - s for s, e, _ in phases)
    assert uncovered < 2e6, f"{uncovered} ns outside the phase spans"


def test_rounds_and_syncs_are_counted(traced):
    res, spans = traced
    rounds = [st for n, _, _, st in spans if n == "lqcd.round"]
    assert len(rounds) == res.outer_iters >= 2
    assert [r["round"] for r in rounds] == list(range(res.outer_iters))
    assert all(r["cap"] > 0 for r in rounds)
    syncs = [st["what"] for n, _, _, st in spans if n == "lqcd.sync"]
    assert len(syncs) == 2 * res.outer_iters + 3
    assert syncs == (["b_norm", "r_norm0"]
                     + ["iters", "r_norm"] * res.outer_iters + ["true_norm"])


def test_each_sync_lies_in_a_phase(traced):
    _, spans = traced
    phases = [(s, e) for n, s, e, _ in spans if n in PHASES]
    for n, s, e, _ in spans:
        if n == "lqcd.sync":
            assert any(ps <= s and e <= pe for ps, pe in phases)


def _op_names(compiled_text):
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def test_single_device_inner_cg_carries_the_hop_scope():
    U, b = _fields()
    U_e, U_o = pack_gauge(U)
    b_e = eo_pack(b, 0)
    lowered = cg._eo_inner.lower(U_e, U_o, b_e, KAPPA, 1e-2, jnp.int32(10),
                                 inner_dtype=jnp.bfloat16)
    assert lowered.as_text().startswith("module @jit__eo_inner")
    names = _op_names(lowered.compile().as_text())
    assert any("lqcd.hop" in n for n in names)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_sharded_programs_are_named(backend):
    """The vector programs lower as ``jit_eo_<kind>``, their Schur program
    carries the hop scope, and the sharded inner CG keeps ``jit_body``."""
    need_devices(4)
    U, b = _fields()
    U_e, U_o = pack_gauge(U)
    b_e = eo_pack(b, 0)
    ops = ShardedWilsonEO(U_e, U_o, KAPPA, lattice_mesh(LAT[3], 4),
                          backend=backend)
    for kind in ("hop_e", "hop_o", "schur", "schur_dagger", "normal"):
        lowered = ops._vec_fn(kind).lower(*ops._gauge_args, b_e)
        assert lowered.as_text().startswith(f"module @jit_eo_{kind} ")
    schur = ops._vec_fn("schur").lower(*ops._gauge_args, b_e).compile()
    assert any("lqcd.hop" in n for n in _op_names(schur.as_text()))
    ops.cg_normal(b_e, tol=1e-2, max_iters=2)
    cg_fn = ops._jit_cache[("cg", None)]
    lowered = cg_fn.lower(*ops._gauge_args, b_e, jnp.float32(1e-2),
                          jnp.int32(2))
    assert lowered.as_text().startswith("module @jit_body ")

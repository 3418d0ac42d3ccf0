"""Autotuner: cache round-trip, perf-floor contract (property), the
paper's operating point, and the tuned=True consumer paths."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:               # deterministic grid fallback
    from _hypothesis_fallback import given, settings, strategies as st

from repro.autotune import (AnalyticDgemmModel, CacheEntry, NB_EFFICIENCY,
                            Space, TuneCache, coordinate_descent,
                            default_cache, grid_search, set_default_cache,
                            tune_operating_point, tuned_config)


# -- cache ---------------------------------------------------------------

def test_cache_roundtrip(tmp_path):
    """save -> load -> identical entries (the satellite requirement)."""
    path = tmp_path / "autotune.json"
    c = TuneCache(path)
    e1 = CacheEntry(config={"bm": 256, "bn": 512, "bk": 128},
                    perf_gflops=123.4, power_w=150.0, mflops_per_w=822.7,
                    model="analytic", perf_loss=0.02)
    e2 = CacheEntry(config={"block": 64, "lookahead": 1})
    c.put("dgemm", (1024, 1024, 1024), "cpu", e1)
    c.put("hpl", (256,), "tpu", e2)
    assert path.exists()

    c2 = TuneCache(path)                   # fresh load from disk
    assert len(c2) == 2
    assert c2.get("dgemm", (1024, 1024, 1024), "cpu") == e1
    assert c2.get("hpl", (256,), "tpu") == e2
    assert c2.to_dict() == c.to_dict()
    # the file itself is versioned, sorted JSON
    raw = json.loads(path.read_text())
    assert raw["version"] == 1
    assert sorted(raw["entries"]) == list(raw["entries"])


def test_cache_rejects_unknown_version(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"version": 99, "entries": {}}))
    with pytest.raises(ValueError):
        TuneCache(path)


def test_tuned_config_memoizes(tmp_path):
    cache = TuneCache(tmp_path / "c.json")
    got = tuned_config("hpl", (256,), device="cpu", cache=cache)
    assert 256 % got["block"] == 0
    # second call is a pure cache hit (identical dict, file unchanged)
    before = (tmp_path / "c.json").read_text()
    again = tuned_config("hpl", (256,), device="cpu", cache=cache)
    assert again == got
    assert (tmp_path / "c.json").read_text() == before


def test_default_cache_env_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "env.json"))
    set_default_cache(None)                # re-resolve from env
    try:
        assert default_cache().path == tmp_path / "env.json"
    finally:
        set_default_cache(None)            # don't leak into other tests


# -- searchers -----------------------------------------------------------

def _toy_space():
    return Space({"x": tuple(range(1, 8)), "y": tuple(range(1, 6))})


@settings(max_examples=12, deadline=None)
@given(loss=st.floats(0.0, 0.45), a=st.integers(1, 7), b=st.integers(1, 5))
def test_searchers_respect_perf_floor(loss, a, b):
    """Property: neither searcher ever returns a point below its perf
    floor, even with infeasible holes in the space."""
    space = _toy_space()

    def ev(p):
        if p["x"] == a and p["y"] == min(b, 5):     # infeasible hole
            return 0.0, float("inf")
        perf = 10.0 * p["x"] + a * p["y"]
        power = 5.0 + (p["x"] - 3) ** 2 + b * p["y"]
        return perf, power

    for search in (grid_search, coordinate_descent):
        res = search(space, ev, max_perf_loss=loss)
        assert res.best.perf_gflops >= res.perf_floor_gflops - 1e-9
        assert res.perf_floor_gflops == pytest.approx(
            (1.0 - loss) * res.peak_perf_gflops)
        assert res.best.power_w < float("inf")

    # the grid's peak is the true feasible max
    gres = grid_search(space, ev, max_perf_loss=loss)
    true_peak = max(ev(p)[0] for p in space.points())
    assert gres.peak_perf_gflops == pytest.approx(true_peak)


def test_grid_search_skips_infeasible_and_is_deterministic():
    space = Space({"x": (1, 2, 3)})

    def ev(p):
        if p["x"] == 2:
            return 0.0, float("inf")
        return 10.0, 10.0 / p["x"]         # x=3 most efficient

    r1 = grid_search(space, ev, max_perf_loss=0.5)
    r2 = grid_search(space, ev, max_perf_loss=0.5)
    assert r1.best.point == r2.best.point == {"x": 3}
    assert r1.evaluations == 3


def test_grid_search_raises_when_nothing_feasible():
    space = Space({"x": (1, 2)})
    with pytest.raises(ValueError):
        grid_search(space, lambda p: (0.0, float("inf")))


# -- the paper's operating point ----------------------------------------

def test_operating_point_matches_paper():
    """The analytic searcher rediscovers §2–4's published settings."""
    res = tune_operating_point()
    best = res.best.point
    assert best["f_mhz"] == 774.0
    assert best["fan"] == pytest.approx(0.40, abs=0.051)
    assert best["nb"] == NB_EFFICIENCY
    assert abs(res.best.mflops_per_w - 5271.8) / 5271.8 < 0.02
    cd = tune_operating_point(method="coordinate")
    assert cd.best.point == best
    assert cd.evaluations < res.evaluations


# -- analytic kernel model feasibility ----------------------------------

def test_dgemm_model_rejects_nondividing_and_oversized_tiles():
    m = AnalyticDgemmModel(512, 512, 512)
    perf, power = m.evaluate({"bm": 384, "bn": 128, "bk": 128})
    assert perf == 0.0 and power == float("inf")     # 512 % 384 != 0
    perf, _ = m.evaluate({"bm": 512, "bn": 512, "bk": 512})
    assert perf > 0.0
    huge = AnalyticDgemmModel(1 << 16, 1 << 16, 1 << 16)
    perf, _ = huge.evaluate({"bm": 1 << 16, "bn": 1 << 16, "bk": 256})
    assert perf == 0.0                               # blows the VMEM budget


def test_dslash_space_and_model_offer_only_compiling_t_blocks():
    """At the thermal half-lattice the v5e compiler takes t_block 1 and 2
    and refuses 4 (scoped VMEM); space and model agree with it."""
    from repro.autotune import AnalyticDslashModel
    from repro.autotune.space import dslash_tile_space
    half = (16, 32, 32, 8)
    assert dslash_tile_space(half).axes["t_block"] == (1, 2)
    model = AnalyticDslashModel(half)
    assert model.evaluate({"t_block": 4}) == (0.0, float("inf"))
    assert all(model.evaluate({"t_block": tb})[0] > 0.0 for tb in (1, 2))
    # a small lattice keeps the whole range, t_block=1 included
    assert dslash_tile_space((4, 8, 8, 8)).axes["t_block"] == (1, 2, 4)


# -- tuned=True consumer paths ------------------------------------------

def test_dgemm_tuned_path_matches_ref(tmp_path):
    from repro.kernels.dgemm import dgemm
    from repro.kernels.dgemm.ref import dgemm_ref
    cache = TuneCache(tmp_path / "k.json")
    set_default_cache(cache)
    try:
        x = jax.random.normal(jax.random.PRNGKey(0), (256, 256), jnp.float32)
        y = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
        got = dgemm(x, y, tuned=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(dgemm_ref(x, y)),
                                   rtol=2e-4, atol=2e-4)
        assert cache.get("dgemm", (256, 256, 256), "cpu") is not None
    finally:
        set_default_cache(None)


def test_linpack_tuned_path(tmp_path):
    from repro.configs.hpl import HPLConfig
    from repro.hpl import linpack_run
    set_default_cache(TuneCache(tmp_path / "h.json"))
    try:
        r = linpack_run(HPLConfig(n=192, block=96, mode="efficiency"),
                        tuned=True)
        assert r.passed
        assert r.mode == "efficiency"      # caller's mode is preserved
        assert 192 % r.block == 0
        assert r.block < 96                # tuned blocking, not the input
    finally:
        set_default_cache(None)


def test_recommended_operating_point_is_green500_and_cached():
    # the scheduler's placement-time consult: the coordinate-descent
    # search over the analytic node model rediscovers the paper's
    # Green500 record point, and the result is cached per process
    from repro.autotune.measure import recommended_operating_point
    from repro.power.model import OperatingPoint
    op = recommended_operating_point()
    assert op == OperatingPoint.green500()
    assert recommended_operating_point() is op

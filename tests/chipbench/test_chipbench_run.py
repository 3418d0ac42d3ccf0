"""The harness end to end on the CPU: it refuses to measure without a TPU,
fails where the system under test is absent, and otherwise drives a whole
run (set-up, window, check, result line) on a small cell."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_helpers import (REPO, SPEC, close_window_after, on_cpu,
                               patch_system, run_small, small_root)


@pytest.fixture
def harness_env(monkeypatch):
    restore = on_cpu(monkeypatch)
    yield
    restore()


def _command(cwd, workload="thermal_32x8.light"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", workload,
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_platform_other_than_tpu():
    p = _command(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU; JAX found cpu" in p.stderr


def test_fails_without_the_system_under_test(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    paths has no program to measure: non-zero exit and no result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_small_cell_runs_and_is_correct(tmp_path, harness_env):
    root = small_root(tmp_path)
    out = run_small(root, "small.cell", seconds=0.5)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"solve_s", "setup_s"}
    assert out["metrics"]["solve_s"]["unit"] == "s"
    assert 0 < out["metrics"]["solve_s"]["value"]
    check = out["checks"]["residual_max"]
    assert check["limit"] == 1e-6 and 0 < check["value"] <= 1e-6
    assert out["device"]["count"] == 1
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics(tmp_path, harness_env):
    """With --trace 1 the line carries the per-layer metrics that have
    something to read: on the CPU the counters, not the device trace."""
    root = small_root(tmp_path)
    out = run_small(root, "small.cell", seconds=0.0, trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"inner_cg.ops_per_solve",
                                   "outer.rounds_per_solve"}
    assert out["metrics"]["outer.rounds_per_solve"]["value"] >= 1
    assert "window_s" in out["device"] and "busy_s" in out["device"]
    assert list(out)[-1] == "checks" and "breakdown" in out
    assert not (root / ".bench_out" / "trace").exists()


def test_window_keeps_at_most_two_solutions_on_the_device(
        tmp_path, monkeypatch, harness_env):
    """Each solution of the window leaves the device while the next solve
    runs: when a solve returns, it and at most the one before it are
    device arrays, whatever the window's length, and every solve of the
    window still reaches the check."""
    import jax
    import repro.lqcd.cg as cg
    made = close_window_after(monkeypatch, 5)
    real_solve = cg.solve_dirac
    on_device = []            # device solutions when each solve returns
    checked = []              # (solve, on the device) as the check gets it

    def solve(*args, **kw):
        res = real_solve(*args, **kw)
        on_device.append(1 + sum(isinstance(s.x, jax.Array) for s in made))
        return res

    def watch_check(monkeypatch, system):
        real_check = system.check

        def check(solves, *args, **kw):
            checked.extend((s, isinstance(s.x, jax.Array)) for s in solves)
            return real_check(solves, *args, **kw)
        monkeypatch.setattr(system, "check", check)
    monkeypatch.setattr(cg, "solve_dirac", solve)
    patch_system(monkeypatch, watch_check)
    out = run_small(small_root(tmp_path), "small.cell", seconds=3600.0)
    assert out["correct"] is True and out["attempted"] == 5
    # the warm-up solve, then the window's five
    assert on_device == [1, 1, 2, 2, 2, 2]
    assert [id(s) for s, _ in checked] == [id(s) for s in made]
    assert [on for _, on in checked] == [False] * 4 + [True]

"""The comparison that decides ``correct`` catches a broken timed path.

Each test drives a whole run of a small cell on the CPU with the solver
broken underneath the harness, and sees ``correct`` come out false: a
solve that hands back its starting state, the odd half of the lattice
left out of the answer, an answer altered where it is produced (every
answer, or the window's first alone), and the control (the reference
solve one precision lower) in the solver's place.
The exchange between chips left out is in ``test_chipbench_sharded.py``."""
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from chipbench_helpers import (close_window_after, on_cpu, run_small,
                               small_root)

import repro.lqcd.cg as cg


@pytest.fixture
def harness_env(monkeypatch):
    restore = on_cpu(monkeypatch)
    yield
    restore()


def _break_solution(monkeypatch, breaker):
    real = cg.solve_dirac

    def broken(U, b, kappa, cfg, **kw):
        res = real(U, b, kappa, cfg, **kw)
        return res._replace(x=breaker(res.x, b))
    monkeypatch.setattr(cg, "solve_dirac", broken)


def _odd_sites_zero(x, b):
    t = sum(jnp.arange(n).reshape((-1,) + (1,) * (3 - a))
            for a, n in enumerate(x.shape[:4]))
    return jnp.where((t % 2 == 1)[..., None, None], 0, x)


def _one_entry_altered(x, b):
    return x.at[0, 0, 0, 0, 0, 0].add(1e-3 * jnp.max(jnp.abs(x)))


@pytest.mark.parametrize("breaker", [
    lambda x, b: jnp.zeros_like(x),        # state returned unchanged
    _odd_sites_zero,                       # half the lattice left out
    _one_entry_altered,                    # an answer altered
], ids=["state_unchanged", "odd_half_left_out", "answer_altered"])
def test_broken_solution_is_not_correct(tmp_path, monkeypatch, harness_env,
                                        breaker):
    root = small_root(tmp_path)
    _break_solution(monkeypatch, breaker)
    out = run_small(root, "small.cell", seconds=0.0)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert out["checks"]["residual_max"]["value"] > 1e-6


def test_first_solution_of_the_window_altered_is_not_correct(
        tmp_path, monkeypatch, harness_env):
    """Only the window's first answer is altered; it has left the device
    long before the check, and its host copy still carries the fault
    there: one failed solve of four."""
    close_window_after(monkeypatch, 4)
    calls = []

    def first_of_window(x, b):
        calls.append(None)
        # the first call is the warm-up solve, outside the window
        return _one_entry_altered(x, b) if len(calls) == 2 else x
    root = small_root(tmp_path)
    _break_solution(monkeypatch, first_of_window)
    out = run_small(root, "small.cell", seconds=3600.0)
    assert out["correct"] is False
    assert out["attempted"] == 4 and out["failed"] == 1
    assert out["checks"]["residual_max"]["value"] > 1e-6


def test_bf16_control_in_the_solvers_place_is_not_correct(
        tmp_path, monkeypatch, harness_env):
    """The control of the comparison, ``reference.control_solve`` (every
    stored field rounded through bfloat16), answers every solve of a run
    and the harness's own check finds each answer wrong."""
    from benchmarks.chip import reference

    def control(U, b, kappa, cfg, **kw):
        x = reference.control_solve(U, b, kappa, 300)
        return SimpleNamespace(x=x, iters=300, outer_iters=0,
                               rel_residual=float("nan"))
    monkeypatch.setattr(cg, "solve_dirac", control)
    root = small_root(tmp_path)
    out = run_small(root, "small.cell", seconds=0.0)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert out["checks"]["residual_max"]["value"] > 30 * 1e-6

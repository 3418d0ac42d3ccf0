"""The system under test is named by the configuration: a system added as
new files is found by name, passes the benchmark's spec checks and is
driven by the harness; each system refuses a cell that breaks one of its
rules before any input is made; and the HPL system runs on the CPU, is
correct, and fails the comparison that decides ``correct`` when its timed
path is broken underneath it."""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_helpers import (BENCH, check_spec, close_window_after, on_cpu,
                               patch_system, root_with_cell, run_small,
                               small_hpl_root, small_root)

from benchmarks.chip import control, harness, hpl_reference, work

TOY_SYSTEM = '''
import jax
import numpy as np


def validate(config, traffic, chips):
    from benchmarks.chip.harness import require
    require("toy_scale", {"n >= 1": config["n"] >= 1,
                          "requests >= 1": traffic.get("requests", 0) >= 1,
                          "one chip": chips == 1})


def make_inputs(seed, config, traffic, devices):
    v = jax.random.uniform(jax.random.key(seed % 2 ** 31), (config["n"],))
    return v, tuple(range(1, traffic["requests"] + 1))


class Solver:
    def __init__(self, config, traffic, devices):
        self.f = jax.jit(lambda v, k: v * k)

    def warm_up(self, shared, request):
        self.f(shared, request).block_until_ready()
        return "toy"

    def __call__(self, shared, request):
        x = self.f(shared, request)
        x.block_until_ready()
        return x, {"scale": request}


def check(solves, shared, requests, config, traffic, device):
    v = np.asarray(shared)
    errs = [float(np.max(np.abs(np.asarray(s.x) - v * requests[s.source])))
            for s in solves]
    return (sum(e > 0 for e in errs),
            {"error_max": {"value": max(errs), "limit": 0.0}})


def control(shared, request, config, traffic):
    return shared * request + 1.0
'''


@pytest.fixture
def harness_env(monkeypatch):
    restore = on_cpu(monkeypatch)
    yield
    restore()


def test_toy_system_added_as_files_is_found_and_run(tmp_path, harness_env):
    """A system, its configuration, traffic, a reader and a cell added to
    a copy of the benchmark as new files and entries only: the copy passes
    the same spec checks as the repository, and the harness loads the
    system by the configuration's name and runs it end to end; every
    committed file of the copy is byte-for-byte the original."""
    root = small_root(tmp_path)
    chip = root / "benchmarks" / "chip"
    (chip / "systems" / "toy_scale.py").write_text(TOY_SYSTEM)
    (chip / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "system": "toy_scale", "n": 8, "reduced": []}))
    (chip / "traffic" / "three.json").write_text(json.dumps(
        {"requests": 3}))
    (chip / "metrics" / "toy.mean_scale.py").write_text(
        "def read(ctx):\n"
        "    return sum(s.counters['scale'] for s in ctx.solves) / "
        "len(ctx.solves)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "toy-test",
                            "file": "benchmarks/chip/configs/toy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy.three", "config": "toy",
                              "traffic": "three", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "toy.mean_scale", "unit": "x",
                              "better": "lower", "source": "program_counter",
                              "layer": "toy", "moves": "solve_s",
                              "workloads": ["toy.three"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for p in BENCH.rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            assert (chip / p.relative_to(BENCH)).read_bytes() == p.read_bytes()
    check_spec(root)
    out = run_small(root, "toy.three", seconds=0.2)
    assert out["correct"] is True and out["attempted"] >= 1
    assert out["checks"] == {"error_max": {"value": 0.0, "limit": 0.0}}
    assert set(out["metrics"]) == {"solve_s", "setup_s"}
    traced = run_small(root, "toy.three", seconds=0.0, trace=True)
    assert traced["metrics"]["toy.mean_scale"]["value"] == pytest.approx(
        1.5)                            # the two traced solves: 1, then 2


@pytest.mark.parametrize("name", ["harness.py", "control.py"])
def test_harness_and_control_name_no_system(name):
    """What belongs to one system lives in its own files: the harness and
    the control name none of the systems found under ``systems/``, nor
    any piece of the Wilson solve or of HPL."""
    systems = [p.stem for p in (BENCH / "systems").glob("*.py")]
    assert len(systems) >= 2
    text = (BENCH / name).read_text()
    for word in systems + ["kappa", "solve_dirac", "relative_residual",
                           "fields", "lqcd", "hpl", "blocked_lu",
                           "scaled_residual"]:
        assert word not in text, (name, word)


# one case for each rule a system holds its cells to: (the configuration
# and traffic of a repository cell, what breaks the rule, the rule)
BROKEN_RULES = {
    "wilson_reduced": ("thermal_32x8", "light", {"reduced": ["lattice"]},
                       {}, 1, "reduced == []"),
    "wilson_precision": ("thermal_32x8", "light",
                         {"precision": {"outer": "float32",
                                        "inner": "float32"}},
                         {}, 1, "precision float32 outer, bfloat16 inner"),
    "wilson_t_split": ("thermal_32x8", "light",
                       {"lattice": [32, 32, 32, 6], "t_shards": 4}, {}, 4,
                       "lattice[3] % t_shards == 0"),
    "wilson_chips": ("thermal_32x8", "light", {}, {}, 4,
                     "chips == t_shards"),
    "wilson_kappa": ("thermal_32x8", "light", {}, {"kappa": 0.0}, 1,
                     "kappa > 0"),
    "wilson_point_sources": ("thermal_32x8", "light", {},
                             {"sources": "volume"}, 1, "point sources"),
    "wilson_one_client": ("thermal_32x8", "light", {}, {"clients": 2}, 1,
                          "one client"),
    "hpl_reduced": ("hpl_n8192", "factor", {"reduced": []}, {}, 1,
                    "reduced == ['n']"),
    "hpl_precision": ("hpl_n8192", "factor",
                      {"precision": {"storage": "float32",
                                     "products": "bfloat16"}},
                      {}, 1, "precision float32 storage and products"),
    "hpl_whole_blocks": ("hpl_n8192", "factor", {"n": 8000}, {}, 1,
                         "n % nb == 0"),
    "hpl_systems": ("hpl_n8192", "factor", {}, {"systems": 1}, 1,
                    "systems >= 2"),
    "hpl_one_client": ("hpl_n8192", "factor", {}, {"clients": 2}, 1,
                       "one client"),
}


def _broken_root(tmp_path, base, mix, cfg_changes, traffic_changes, chips):
    """A copy of the benchmark with a cell ``broken.cell`` on ``base``
    changed by ``cfg_changes`` and a traffic mix ``broken``, ``mix``
    changed by ``traffic_changes``; and the two as the system gets them."""
    traffic = dict(json.loads((BENCH / "traffic" / f"{mix}.json")
                              .read_text()), **traffic_changes)
    root = root_with_cell(tmp_path, base, "broken", chips, name="broken",
                          **cfg_changes)
    (root / "benchmarks/chip/traffic/broken.json").write_text(
        json.dumps(traffic))
    config = json.loads((root / "benchmarks/chip/configs/broken.json")
                        .read_text())
    return root, config, traffic


@pytest.mark.parametrize("case", list(BROKEN_RULES))
def test_cell_that_breaks_a_rule_of_its_system_is_refused(
        tmp_path, monkeypatch, harness_env, case):
    """The system's ``validate`` names the broken rule, the spec checks
    refuse the copy that holds the cell, and the harness refuses the cell
    with ``BenchError`` before its system makes any input; the cell as
    the repository has it is accepted."""
    base, mix, cfg_changes, traffic_changes, chips, rule = BROKEN_RULES[case]
    config = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    system = harness.load_system(BENCH.parents[1], config["system"])
    system.validate(config, json.loads(
        (BENCH / "traffic" / f"{mix}.json").read_text()), 1)
    root, config, traffic = _broken_root(tmp_path, base, mix, cfg_changes,
                                         traffic_changes, chips)
    match = re.escape(rule)
    with pytest.raises(harness.BenchError, match=match):
        system.validate(config, traffic, chips)
    with pytest.raises(harness.BenchError, match=match):
        check_spec(root)

    def no_inputs(monkeypatch, module):
        def make_inputs(*args, **kw):
            raise AssertionError("inputs made for a refused cell")
        monkeypatch.setattr(module, "make_inputs", make_inputs)
    patch_system(monkeypatch, no_inputs)
    with pytest.raises(harness.BenchError, match=match):
        run_small(root, "broken.cell")


def test_control_refuses_a_cell_that_breaks_its_systems_rules(
        tmp_path, monkeypatch):
    """``control.py`` holds a cell to its system's rules before it looks
    for a chip."""
    root, _, _ = _broken_root(tmp_path, "thermal_32x8", "light", {},
                              {"clients": 2}, 1)
    monkeypatch.setattr(control, "ROOT", root)
    with pytest.raises(harness.BenchError, match="one client"):
        control.main(["--workload", "broken.cell", "--seeds", "1"])


def test_unknown_system_is_an_error(tmp_path, harness_env):
    root = small_root(tmp_path)
    cfg_file = root / "benchmarks/chip/configs/small.json"
    cfg = json.loads(cfg_file.read_text())
    cfg_file.write_text(json.dumps(dict(cfg, system="no_such_system")))
    with pytest.raises(harness.BenchError, match="no system"):
        run_small(root, "small.cell")


def test_small_hpl_cell_runs_and_is_correct(tmp_path, harness_env):
    root = small_hpl_root(tmp_path)
    out = run_small(root, "small_hpl.cell", seed=2 ** 31 + 11, seconds=0.3)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True and out["attempted"] >= 1
    assert set(out["metrics"]) == {"solve_s", "setup_s"}
    check = out["checks"]["scaled_residual_max"]
    assert check["limit"] == 0.1 and 0 < check["value"] <= 0.1
    traced = run_small(root, "small_hpl.cell", seconds=0.0, trace=True)
    assert traced["correct"] is True
    # on the CPU: no chip's peaks and no TPU in the trace, nothing to read
    assert traced["metrics"] == {}


def test_hpl_inputs_are_seeded_and_uniform():
    from benchmarks.chip.systems import hpl
    cfg = {"n": 64, "precision": {"storage": "float32"}}
    traffic = {"systems": 4, "clients": 1}
    _, one = hpl.make_inputs(2 ** 31 + 3, cfg, traffic, jax.devices()[:1])
    _, two = hpl.make_inputs(2 ** 31 + 3, cfg, traffic, jax.devices()[:1])
    _, other = hpl.make_inputs(2 ** 31 + 4, cfg, traffic, jax.devices()[:1])
    assert len(one) == 4
    for (a, b), (a2, b2), (a3, _) in zip(one, two, other):
        assert a.shape == (64, 64) and b.shape == (64,)
        assert a.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a2))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(b2))
        assert not np.array_equal(np.asarray(a), np.asarray(a3))
        assert -0.5 <= float(jnp.min(a)) and float(jnp.max(a)) < 0.5
    assert not np.array_equal(np.asarray(one[0][0]), np.asarray(one[1][0]))


def test_hpl_products_are_float32_in_the_solve_program():
    """Every product of the timed program is lowered at HIGHEST precision,
    the float32 the configuration states: on the TPU a product at the
    default precision rounds its operands through bfloat16, which no CPU
    run can show."""
    from benchmarks.chip.systems import hpl
    cfg = json.loads((BENCH / "configs" / "hpl_n8192.json").read_text())
    cfg.update(n=256, nb=32)
    solver = hpl.Solver(cfg, {}, jax.devices()[:1])
    request = (jnp.zeros((256, 256), jnp.float32),
               jnp.zeros((256,), jnp.float32))
    text = solver.lower(request).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == 2                # the two trailing updates
    for line in dots:
        assert "precision = [HIGHEST, HIGHEST]" in line, line


def test_scaled_residual_is_hpls_formula():
    a = np.array([[2.0, 1.0], [-1.0, 3.0]], np.float32)
    b = np.array([1.0, 0.5], np.float32)
    x = np.array([0.25, 0.25], np.float32)
    # A x - b = (-0.25, 0), ||A||_inf = 4, ||x||_inf = 0.25, ||b||_inf = 1
    want = 0.25 / (2.0 ** -24 * (4 * 0.25 + 1.0) * 2)
    assert hpl_reference.scaled_residual(a, b, x) == pytest.approx(want)
    exact = np.linalg.solve(a.astype(np.float64), b.astype(np.float64))
    assert hpl_reference.scaled_residual(a, b, exact) < 1e-6


def _hpl_break(monkeypatch, breaker):
    """The program's ``lu_solve`` hands ``breaker`` of its answer back."""
    import repro.hpl.lu as lu
    real = lu.lu_solve
    monkeypatch.setattr(lu, "lu_solve",
                        lambda res, b, nb: breaker(real(res, b, nb)))


def _half_left_out(x):
    return x.at[x.shape[0] // 2:].set(0.0)


def _one_entry_altered(x):
    return x.at[0].add(1e-2 * jnp.max(jnp.abs(x)))


@pytest.mark.parametrize("breaker", [
    jnp.zeros_like,                     # state returned unchanged
    _half_left_out,                     # half of the answer left out
    _one_entry_altered,                 # an answer altered
], ids=["state_unchanged", "half_left_out", "answer_altered"])
def test_broken_hpl_solve_is_not_correct(tmp_path, monkeypatch, harness_env,
                                         breaker):
    root = small_hpl_root(tmp_path)
    _hpl_break(monkeypatch, breaker)
    out = run_small(root, "small_hpl.cell", seconds=0.0)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert out["checks"]["scaled_residual_max"]["value"] > 0.1


def test_first_hpl_answer_of_the_window_altered_is_not_correct(
        tmp_path, monkeypatch, harness_env):
    """Only the window's first answer is altered, as the solver hands it
    back: one failed solve of four."""
    close_window_after(monkeypatch, 4)

    def alter_first(monkeypatch, system):
        real = system.Solver.__call__
        calls = []

        def call(self, shared, request):
            x, counters = real(self, shared, request)
            calls.append(None)
            return (_one_entry_altered(x) if len(calls) == 1 else x), counters
        monkeypatch.setattr(system.Solver, "__call__", call)
    patch_system(monkeypatch, alter_first)
    out = run_small(small_hpl_root(tmp_path), "small_hpl.cell",
                    seconds=3600.0)
    assert out["correct"] is False
    assert out["attempted"] == 4 and out["failed"] == 1
    assert out["checks"]["scaled_residual_max"]["value"] > 0.1


def test_bf16_control_in_the_hpl_solvers_place_is_not_correct(
        tmp_path, monkeypatch, harness_env):
    """The control, the reference LU with its trailing updates rounded
    through bfloat16 (``hpl.control``), answers every solve of a run, and
    the system's own check finds each answer wrong, by 100 times the
    limit or more."""
    root = small_hpl_root(tmp_path)
    config = harness.load_cell(root, "small_hpl.cell").config

    def control_in_place(monkeypatch, system):
        def call(self, shared, request):
            return system.control(shared, request, config, {}), {}
        monkeypatch.setattr(system.Solver, "__call__", call)
    patch_system(monkeypatch, control_in_place)
    out = run_small(root, "small_hpl.cell", seconds=0.0)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert out["checks"]["scaled_residual_max"]["value"] > 100 * 0.1


@pytest.mark.parametrize("n,nb", [(256, 32), (512, 64)])
def test_hpl_control_reads_far_above_a_float32_lu(n, nb):
    """On the host: a float32 LU (LAPACK) reads under the small limit,
    the bf16-product control two orders of magnitude above it."""
    import scipy.linalg
    rng = np.random.default_rng(n)
    a = rng.uniform(-0.5, 0.5, (n, n)).astype(np.float32)
    b = rng.uniform(-0.5, 0.5, n).astype(np.float32)
    sound = scipy.linalg.lu_solve(scipy.linalg.lu_factor(a), b)
    control = hpl_reference.control_solve(a, b, nb)
    assert hpl_reference.scaled_residual(a, b, sound) < 0.1
    assert hpl_reference.scaled_residual(a, b, control) > 10.0


def test_hpl_peak_share_reader():
    cfg = {"n": 8192}
    cell = harness.Cell("hpl", 1, cfg, {}, [], [])
    peaks = {"bf16_flops_per_s": 1.97e14}
    solves = [harness.Solve(0, 13.7, {}, None), harness.Solve(1, 13.7, {},
                                                              None)]
    read = harness.load_reader(BENCH.parents[1], "hpl.peak_share")
    share = read(harness.Context(cell, solves, [], None, peaks, 1))
    assert work.hpl(8192) == 2 / 3 * 8192 ** 3 + 1.5 * 8192 ** 2
    assert share == pytest.approx(100 * work.hpl(8192) / 13.7 / 1.97e14)
    assert read(harness.Context(cell, solves, [], None, None, 1)) is None

"""BENCHMARK.json and the files it names: every configuration, traffic mix
and metric reader loads under its name, the file keeps to the limits on
names, units and bounds, each cell keeps the rules of its system, and a
cell or metric is added by files and entries alone.  The checks are
``chipbench_helpers.check_spec``'s, which name no system: each system's
own rules are its ``validate``."""
import json

import pytest

from chipbench_helpers import (BENCH, REPO, SPEC,
                               check_cells_and_configurations, check_files,
                               check_names_units_and_bounds, check_spec,
                               small_root)

from benchmarks.chip import harness


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmarks/chip/run.py"]
    assert (REPO / SPEC["command"][1]).is_file()
    for p in SPEC["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    assert any(SPEC["command"][1].startswith(p + "/") for p in SPEC["paths"])
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_bounds():
    check_names_units_and_bounds(SPEC)


def test_cells_and_configurations_load_by_name():
    """Every cell loads, reports ``setup_s`` and ``solve_s``, and is
    accepted by its system's ``validate``."""
    check_cells_and_configurations(REPO, SPEC)


def test_every_metric_has_a_reader():
    """Each per-layer metric, traffic mix, configuration and system in
    BENCHMARK.json has its file, and the configuration of the four-chip
    cell, which waits for a later benchmark PR, forms a cell that its
    system accepts."""
    check_files(REPO, SPEC)


def test_peaks_are_keyed_by_device_kind():
    v5e = harness.load_peaks(REPO, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 1.97e14
    assert v5e["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.load_peaks(REPO, "TPU v9 imaginary")


def test_unknown_cell_is_an_error():
    with pytest.raises(harness.BenchError, match="no cell"):
        harness.load_cell(REPO, "no_such.cell")


def test_new_cell_and_metric_are_found_by_name(tmp_path):
    """A cell, a traffic mix and a metric reader added as new files in a
    copy of the benchmark are found by name; the copied files are
    byte-for-byte the committed ones."""
    root = small_root(tmp_path, traffic="burst")
    (root / "benchmarks/chip/traffic/burst.json").write_text(json.dumps(
        {"kappa": 0.15, "sources": "point", "spins": 4, "colours": 3,
         "clients": 1}))
    (root / "benchmarks/chip/metrics/solve.count.py").write_text(
        "def read(ctx):\n    return float(len(ctx.solves)) or None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "solve.count", "unit": "solves",
                              "better": "higher", "source": "program_counter",
                              "layer": "outer solver", "moves": "solve_s",
                              "workloads": ["small.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for p in BENCH.rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            assert (root / "benchmarks/chip" / p.relative_to(BENCH)
                    ).read_bytes() == p.read_bytes()
    check_spec(root)
    cell = harness.load_cell(root, "small.cell")
    assert cell.traffic["kappa"] == 0.15
    assert "solve.count" in [m["name"] for m in cell.per_layer]
    read = harness.load_reader(root, "solve.count")
    assert read(harness.Context(cell, [object()] * 3, [], None, None, 1)) == 3

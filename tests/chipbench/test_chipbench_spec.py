"""BENCHMARK.json and the files it names: every configuration, traffic mix
and metric reader loads under its name, the file keeps to the limits on
names, units and bounds, and a cell or metric is added by files and
entries alone."""
import json
import re

import pytest

from chipbench_helpers import BENCH, REPO, SPEC, small_root

from benchmarks.chip import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


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
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in SPEC[key]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert "\n" not in m["layer"] and m["layer"]


def test_cells_and_configurations_load_by_name():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    assert len({(c["source"], tuple(c["reduced"]))
                for c in SPEC["configs"]}) == len(SPEC["configs"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = harness.load_cell(REPO, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "solve_s"}
        if cell.config["system"] == "lqcd_wilson":
            assert cell.chips == cell.config["t_shards"] == w["chips"]
            assert cell.traffic["kappa"] > 0
    for c in SPEC["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert c["file"].startswith("benchmarks/chip/")
        assert cfg["reduced"] == c["reduced"]
        if cfg["system"] == "lqcd_wilson":
            assert cfg["reduced"] == []
            assert cfg["precision"] == {"outer": "float32",
                                        "inner": "bfloat16"}
        else:
            assert cfg["system"] == "hpl" and cfg["reduced"] == ["n"]
            assert cfg["precision"] == {"storage": "float32",
                                        "products": "float32"}
            assert cfg["n"] % cfg["nb"] == 0


def test_every_metric_has_a_reader():
    """Each per-layer metric, traffic mix, configuration and system in
    BENCHMARK.json has its file; the files of the four-chip cell, which
    waits for a later benchmark PR, load as well."""
    readers = {p.stem for p in (BENCH / "metrics").glob("*.py")}
    assert {m["name"] for m in SPEC["per_layer"]} <= readers
    for name in readers:
        assert callable(harness.load_reader(REPO, name))
    traffic = {p.stem for p in (BENCH / "traffic").glob("*.json")}
    assert {w["traffic"] for w in SPEC["workloads"]} <= traffic
    for name in traffic:
        t = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
        assert t["clients"] == 1
        assert t.get("sources") == "point" and t["kappa"] > 0 or (
            t["systems"] >= 2)
    systems = set()
    for path in (BENCH / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        assert cfg["name"] == path.stem
        systems.add(cfg["system"])
        if cfg["system"] == "lqcd_wilson":
            assert cfg["reduced"] == []
            assert cfg["lattice"][3] % cfg["t_shards"] == 0
    assert systems == {p.stem for p in (BENCH / "systems").glob("*.py")}
    for name in systems:
        system = harness.load_system(REPO, name)
        for attr in ("make_inputs", "Solver", "check", "control"):
            assert callable(getattr(system, attr)), (name, attr)


def test_harness_and_control_name_no_system():
    """What belongs to one system lives in its own files: the harness and
    the control name none of the Wilson solve's pieces."""
    for name in ("harness.py", "control.py"):
        text = (BENCH / name).read_text()
        for word in ("kappa", "solve_dirac", "relative_residual", "fields",
                     "lqcd", "hpl"):
            assert word not in text, (name, word)


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
    cell = harness.load_cell(root, "small.cell")
    assert cell.traffic["kappa"] == 0.15
    assert "solve.count" in [m["name"] for m in cell.per_layer]
    read = harness.load_reader(root, "solve.count")
    assert read(harness.Context(cell, [object()] * 3, [], None, None, 1)) == 3

"""Shared helpers of the chip benchmark's CPU tests: the checks of
``BENCHMARK.json`` that hold for every system, a checkout in a temporary
directory with a small cell added by files alone, and a harness run on it
with the look for a chip patched out."""
from __future__ import annotations

import json
import re
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
BENCH = REPO / "benchmarks" / "chip"
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what the harness takes from every system module (harness.py's docstring)
SYSTEM_CONTRACT = ("validate", "make_inputs", "Solver", "check", "control")


def check_spec(root: Path) -> None:
    """Every check of ``root``'s ``BENCHMARK.json`` and the files it names
    that holds whatever the system; each system's own rules are its
    ``validate``'s, which these checks call for every cell and every
    configuration file.  Raises ``AssertionError``, or ``BenchError`` from
    a system's ``validate``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    check_names_units_and_bounds(spec)
    check_cells_and_configurations(root, spec)
    check_files(root, spec)


def check_names_units_and_bounds(spec) -> None:
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in spec[key]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert "\n" not in m["layer"] and m["layer"]


def check_cells_and_configurations(root: Path, spec) -> None:
    """Each cell loads by name and its system accepts it."""
    from benchmarks.chip import harness
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    files = [c["file"] for c in spec["configs"]]
    assert len(files) == len(set(files))
    assert len({(c["source"], tuple(c["reduced"]))
                for c in spec["configs"]}) == len(spec["configs"])
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    for c in spec["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        assert json.loads((root / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = harness.load_cell(root, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "solve_s"}
        harness.load_system(root, cell.config["system"]).validate(
            cell.config, cell.traffic, cell.chips)


def check_files(root: Path, spec) -> None:
    """Each per-layer metric, traffic mix, configuration and system has
    its file, every system keeps the contract, and every configuration
    file and traffic mix under the benchmark, those that wait for a later
    cell too, forms some cell on 1 or 4 chips that its system accepts."""
    from benchmarks.chip import harness
    bench = root / "benchmarks" / "chip"
    readers = {p.stem for p in (bench / "metrics").glob("*.py")}
    assert {m["name"] for m in spec["per_layer"]} <= readers
    for name in readers:
        assert callable(harness.load_reader(root, name))
    traffic = {p.stem: json.loads(p.read_text())
               for p in (bench / "traffic").glob("*.json")}
    assert {w["traffic"] for w in spec["workloads"]} <= set(traffic)
    configs = {p.stem: json.loads(p.read_text())
               for p in (bench / "configs").glob("*.json")}
    systems = {}
    for stem, cfg in configs.items():
        assert cfg["name"] == stem
        systems.setdefault(cfg["system"],
                           harness.load_system(root, cfg["system"]))
    assert set(systems) == {p.stem for p in (bench / "systems").glob("*.py")}
    for name, system in systems.items():
        for attr in SYSTEM_CONTRACT:
            assert callable(getattr(system, attr, None)), (name, attr)
    accepted = set()
    for stem, cfg in configs.items():
        for mix, t in traffic.items():
            for chips in (1, 4):
                try:
                    systems[cfg["system"]].validate(cfg, t, chips)
                except harness.BenchError:
                    continue
                accepted.add((stem, mix))
    assert {c for c, _ in accepted} == set(configs)
    assert {t for _, t in accepted} == set(traffic)


def root_with_cell(tmp: Path, base: str, traffic: str, chips: int,
                   **changes) -> Path:
    """A copy of the benchmark under ``tmp`` with the configuration
    ``base`` changed by ``changes`` (``name`` among them) added as a new
    file, and a cell ``<name>.cell`` on ``traffic`` that joins the
    per-layer metrics of ``base``'s cell; no existing file is edited."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    cfg.update(changes)
    name = cfg["name"]
    cfg_file = f"benchmarks/chip/configs/{name}.json"
    (root / cfg_file).write_text(json.dumps(cfg))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": name, "source": "test", "file": cfg_file,
                            "reduced": cfg["reduced"], "why": "test"})
    spec["workloads"].append({"name": f"{name}.cell", "config": name,
                              "traffic": traffic, "chips": chips,
                              "why": "test"})
    like = next(w["name"] for w in SPEC["workloads"] if w["config"] == base)
    for m in spec["per_layer"]:
        if like in m["workloads"]:
            m["workloads"].append(f"{name}.cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def small_root(tmp: Path, lattice=(4, 4, 4, 4), t_shards: int = 1,
               traffic: str = "heavy") -> Path:
    """A copy of the benchmark with the thermal configuration at
    ``lattice`` as ``small`` and a cell ``small.cell``."""
    return root_with_cell(tmp, "thermal_32x8", traffic, t_shards,
                          name="small", lattice=list(lattice),
                          t_shards=t_shards, backend="jnp")


def small_hpl_root(tmp: Path) -> Path:
    """A copy of the benchmark with the HPL configuration at N = 256 in
    blocks of 32 as ``small_hpl`` and a cell ``small_hpl.cell``.  HPL's
    scaled residual falls slowly as N grows (a float32 LU reads about
    1e-2 at N = 256 and a few 1e-3 at 2048), so the small configuration
    states a limit of its own, 0.1; the bf16-product control reads about
    100 at N = 256."""
    return root_with_cell(tmp, "hpl_n8192", "factor", 1, name="small_hpl",
                          n=256, nb=32, scaled_residual_limit=0.1)


def patch_system(monkeypatch, patch):
    """Apply ``patch(monkeypatch, module)`` to every system module the
    harness loads from now on."""
    from benchmarks.chip import harness
    real = harness.load_system

    def load(root, name):
        module = real(root, name)
        patch(monkeypatch, module)
        return module
    monkeypatch.setattr(harness, "load_system", load)


def run_small(root: Path, workload: str, seed: int = 3, seconds: float = 0.0,
              trace: bool = False):
    """The harness's run; on the CPU only after ``on_cpu``."""
    from benchmarks.chip.harness import run
    return run(root, workload, seed, seconds, trace, time.perf_counter())


def on_cpu(monkeypatch):
    """Let the harness run on the CPU: its look for a chip hands back the
    CPU devices, the chip's peaks are unknown (the roofline readers then
    find nothing to read), and its persistent-cache settings stay out of
    other tests.  Returns what restores the cache setting."""
    import jax
    from benchmarks.chip import harness
    monkeypatch.setattr(harness, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "load_peaks", lambda root, kind: None)
    monkeypatch.setattr(
        "repro.runtime.compile_cache.enable_compile_cache",
        lambda: "off in tests")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    return lambda: jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", before)


def close_window_after(monkeypatch, n: int):
    """Make the harness's window close after exactly ``n`` solves, however
    long each takes: its clock jumps a day ahead once the ``n``-th
    ``Solve`` is made.  Run with ``seconds`` under a day.  Returns the
    window's ``Solve``s, in the order they are made."""
    from benchmarks.chip import harness
    made = []

    class Counted(harness.Solve):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            made.append(self)

    def clock():
        return time.perf_counter() + (86400.0 if len(made) >= n else 0.0)
    monkeypatch.setattr(harness, "Solve", Counted)
    monkeypatch.setattr(harness, "time", SimpleNamespace(perf_counter=clock))
    return made

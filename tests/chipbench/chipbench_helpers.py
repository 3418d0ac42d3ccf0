"""Shared helpers of the chip benchmark's CPU tests: a checkout in a
temporary directory with a small cell added by files alone, and a harness
run on it with the look for a chip patched out."""
from __future__ import annotations

import json
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


def _root_with_cell(tmp: Path, base: str, traffic: str, chips: int,
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
    return _root_with_cell(tmp, "thermal_32x8", traffic, t_shards,
                           name="small", lattice=list(lattice),
                           t_shards=t_shards, backend="jnp")


def small_hpl_root(tmp: Path) -> Path:
    """A copy of the benchmark with the HPL configuration at N = 256 in
    blocks of 32 as ``small_hpl`` and a cell ``small_hpl.cell``.  HPL's
    scaled residual falls slowly as N grows (a float32 LU reads about
    1e-2 at N = 256 and a few 1e-3 at 2048), so the small configuration
    states a limit of its own, 0.1; the bf16-product control reads about
    100 at N = 256."""
    return _root_with_cell(tmp, "hpl_n8192", "factor", 1, name="small_hpl",
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

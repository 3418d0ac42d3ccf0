"""One run of one cell: set-up, a closed loop of solves of the
configuration's system for a fixed number of seconds, the check of every
answer against the system's plain reference, and the result line.

Everything about a cell is data found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic mix in ``benchmarks/chip/traffic/<traffic>.json``, its system
under test in ``benchmarks/chip/systems/<system>.py`` (the
configuration's ``system``), each per-layer metric in
``benchmarks/chip/metrics/<metric>.py`` (a ``read(ctx)`` that returns a
number, or None where it finds nothing to read), and the chip's peaks in
``benchmarks/chip/peaks.json`` under its ``device_kind``.

A system module provides:

- ``validate(config, traffic, chips)``: raises ``BenchError`` naming each
  of the system's rules that the cell breaks (its configuration, its
  traffic mix, the chips it asks for), as ``require`` does; the harness
  calls it before any set-up, so that a cell the system refuses spends no
  chip time.  The spec checks hand it every traffic mix, those of other
  systems too, so it reads a traffic key with ``.get`` and refuses a mix
  that lacks one with ``BenchError``, never ``KeyError``;
- ``make_inputs(seed, config, traffic, devices)`` -> ``(shared,
  requests)``: the state every solve shares and the requests the window
  cycles through, made on the device from the seed;
- ``Solver(config, traffic, devices)``, whose ``warm_up(shared, request)``
  readies every program a solve runs and returns a line for the log, and
  whose call ``(shared, request)`` returns ``(answer, counters)`` once the
  answer is on the device: a JAX array, which the harness moves to the
  host as the window runs, and a dict of the numbers the per-layer
  readers take from ``Solve.counters``;
- ``check(solves, shared, requests, config, traffic, device)`` ->
  ``(failed, checks)``: every answer of the window judged by the system's
  plain reference, the number of answers over their limit, and each
  number compared, by name, as ``{"value": v, "limit": l}``;
- ``control(shared, request, config, traffic)``: the answer the control
  gives in the solver's place (``control.py``).
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

DATA = Path("benchmarks") / "chip"
TRACE_DIR = Path(".bench_out") / "trace"
TRACE_SOLVES = 2


class BenchError(RuntimeError):
    """The run cannot give a result (no chip, unknown chip, bad cell)."""


def require(subject: str, rules: Dict[str, bool]) -> None:
    """Raises ``BenchError`` naming every rule of ``rules`` (rule -> kept)
    that ``subject`` breaks."""
    broken = [rule for rule, kept in rules.items() if not kept]
    if broken:
        raise BenchError(f"{subject} breaks: {'; '.join(broken)}")


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _for_cell(metrics, name):
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(root: Path, name: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json; cells: "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / DATA / "traffic" / f"{cell['traffic']}.json").read_text())
    return Cell(name, int(cell["chips"]), config, traffic,
                _for_cell(spec["end_to_end"], name),
                _for_cell(spec["per_layer"], name))


def _load_module(root: Path, kind: str, name: str):
    path = root / DATA / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(root: Path, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    return _load_module(root, "metrics", metric).read


def load_system(root: Path, name: str):
    """The system module ``systems/<name>.py``."""
    if not (root / DATA / "systems" / f"{name}.py").is_file():
        raise BenchError(f"no system {name!r} under {DATA / 'systems'}")
    return _load_module(root, "systems", name)


def load_peaks(root: Path, kind: str) -> Dict[str, Any]:
    table = json.loads((root / DATA / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in peaks.json "
                         f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]


def require_chips(chips: int):
    """The first ``chips`` TPU devices; no other platform is measured."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices[:chips]


@dataclass
class Solve:
    """One completed solve of the window."""
    source: int                     # index of its request
    seconds: float
    counters: Dict[str, Any]        # as the system's solver reports them
    x: Any = field(repr=False)      # the answer


@dataclass
class Context:
    """What a per-layer metric's ``read`` gets."""
    cell: Cell
    solves: List[Solve]             # every solve of the window
    traced: List[Solve]             # the solves the trace covers
    trace: Any                      # trace.Trace, or None
    peaks: Optional[Dict[str, Any]]
    chips: int


class CompileCounter:
    """Counts programs that JAX compiles or loads from its cache."""

    def __init__(self):
        from jax import monitoring
        self.requests = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.requests, self.cache_hits


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _traced(root: Path, solve_next, devices):
    """The window's first ``TRACE_SOLVES`` solves under the profiler, and
    the reduced trace.  A short window: the trace of one solve holds some
    10^5 device events."""
    import jax
    from jax.profiler import ProfileData
    from benchmarks.chip import trace as trace_mod
    trace_dir = root / TRACE_DIR
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        for _ in range(TRACE_SOLVES):
            solve_next()
    jax.profiler.stop_trace()
    try:
        files = sorted(trace_dir.rglob("*.xplane.pb"))
        tr = trace_mod.reduce_profile(ProfileData.from_file(str(files[-1])),
                                      devices=[d.id for d in devices])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return tr


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    """One run; returns the result line as a dict (``checks`` last)."""
    import jax
    import numpy as np
    from repro.runtime.compile_cache import enable_compile_cache

    cell = load_cell(root, workload)
    system = load_system(root, cell.config["system"])
    system.validate(cell.config, cell.traffic, cell.chips)
    devices = require_chips(cell.chips)
    kind = devices[0].device_kind
    peaks = load_peaks(root, kind)
    cache = enable_compile_cache()
    # small programs too, so that a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    log(f"[device] {devices[0].platform} {kind} x{len(devices)}; "
        f"compile cache {cache}")

    config, traffic = cell.config, cell.traffic
    solver = system.Solver(config, traffic, devices)
    t = time.perf_counter()
    shared, requests = system.make_inputs(seed, config, traffic, devices)
    log(f"[setup] JAX up and the cache set in {t - t_start:.2f} s; inputs "
        f"made in {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    warm = solver.warm_up(shared, requests[0])
    log(f"[setup] warm-up: {warm}, {time.perf_counter() - t:.2f} s; "
        f"programs compiled or loaded so far: {compiles.snapshot()[0]} "
        f"({compiles.snapshot()[1]} from the cache)")

    solves: List[Solve] = []

    def solve_next():
        i = len(solves) % len(requests)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.solve"):
            x, counters = solver(shared, requests[i])
        took = time.perf_counter() - t
        # the window's answers leave the device as it runs: this one's
        # copy starts now, and the last one's, which landed while this
        # solve ran, takes its place, so at most two stay on the chip
        x.copy_to_host_async()
        if solves:
            solves[-1].x = np.asarray(solves[-1].x)
        solves.append(Solve(i, took, counters, x))

    before = compiles.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    tr = _traced(root, solve_next, devices) if trace else None
    while not solves or time.perf_counter() - t0 < seconds:
        solve_next()
    window_s = time.perf_counter() - t0
    after = compiles.snapshot()
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                       0)) for d in devices)
    times = sorted(s.seconds for s in solves)
    log(f"[window] {len(solves)} solves in {window_s:.3f} s (each "
        f"{times[0]:.3f} / {times[len(times) // 2]:.3f} / {times[-1]:.3f} s "
        f"min / median / max); programs compiled or loaded in the window: "
        f"{after[0] - before[0]} ({after[1] - before[1]} from the cache)")
    log("[window] seconds per solve: "
        + " ".join(f"{s.seconds:.3f}" for s in solves))

    failed, checks = system.check(solves, shared, requests, config, traffic,
                                  devices[0])
    correct = failed == 0

    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        values = {"solve_s": window_s / len(solves), "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = Context(cell, solves, solves[:TRACE_SOLVES], tr, peaks,
                      cell.chips)
        for m in cell.per_layer:
            value = load_reader(root, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out: Dict[str, Any] = {"correct": correct, "attempted": len(solves),
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": tr.top_ops(),
                            "idle_gaps": tr.idle_by_host()}
    out["checks"] = checks
    return out

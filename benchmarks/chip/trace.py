"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: for each device, the intervals of its operations and of its
programs, clipped to the window the benchmark annotated; and the spans of
the host thread that ran the window, to say what it was doing while a
device sat idle.

Read with ``jax.profiler.ProfileData`` and nothing else.  Times are in
nanoseconds on the trace's own clock, on which host and device events
share one time base.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the collectives XLA emits for ppermute and psum, sync or async halves
COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"send|recv)", re.IGNORECASE)
# control flow whose event spans the operations of its body
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: the device
    trace names an operation by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclass
class Device:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    """One traced window: per device the (name, start, end) of each
    operation and program, and the spans of the host thread that ran the
    window."""
    window: Interval
    devices: Dict[int, Device]
    host: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        return _mean(union_length((s, e) for _, s, e in d.ops)
                     for d in self.devices.values()) * 1e-9

    def module_s(self, pattern: re.Pattern, holding: Optional[re.Pattern]
                 = None) -> float:
        """Seconds in the programs whose name matches ``pattern`` (and, with
        ``holding``, that run an operation whose name matches it),
        averaged over devices."""
        total = []
        for d in self.devices.values():
            starts = sorted(s for n, s, _ in d.ops
                            if holding is not None and holding.match(n))
            t = 0.0
            for n, s, e in d.modules:
                if pattern.search(n) and (holding is None
                                          or _any_in(starts, s, e)):
                    t += e - s
            total.append(t)
        return _mean(total) * 1e-9

    def exposed_collective_s(self) -> float:
        """Seconds in which a collective ran and no other operation did,
        averaged over devices (control flow, which spans its body, does
        not count as another operation)."""
        total = []
        for d in self.devices.values():
            coll = [(s, e) for n, s, e in d.ops if COLLECTIVE.match(n)]
            comp = [(s, e) for n, s, e in d.ops
                    if not COLLECTIVE.match(n) and not CONTAINER.match(n)]
            total.append(union_length(coll) - overlap_length(coll, comp))
        return _mean(total) * 1e-9

    def top_ops(self, k: int = 10) -> List[List]:
        """The operations that took most device time, averaged over
        devices, as [name, seconds]; control flow is left out, its body's
        operations are listed."""
        acc: Dict[str, float] = {}
        for d in self.devices.values():
            for n, s, e in d.ops:
                if not CONTAINER.match(n):
                    acc[n] = acc.get(n, 0.0) + (e - s)
        n_dev = max(len(self.devices), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9 / n_dev] for n, t in top]

    def idle_by_host(self, k: int = 10) -> List[List]:
        """Idle device time on the first device, by the innermost host
        span open at the middle of each gap, as [host span, seconds]."""
        if not self.devices:
            return []
        dev = self.devices[min(self.devices)]
        acc: Dict[str, float] = {}
        for lo, hi in gaps([(s, e) for _, s, e in dev.ops], self.window):
            mid = 0.5 * (lo + hi)
            open_spans = [(e - s, n) for n, s, e in self.host if s <= mid < e]
            name = min(open_spans)[1] if open_spans else "(no host span)"
            acc[name] = acc.get(name, 0.0) + (hi - lo)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9] for n, t in top]


def _any_in(sorted_starts: List[float], lo: float, hi: float) -> bool:
    i = bisect.bisect_left(sorted_starts, lo)
    return i < len(sorted_starts) and sorted_starts[i] < hi


def _mean(values: Iterable[float]) -> float:
    vals = list(values)
    return sum(vals) / len(vals) if vals else 0.0


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def union_length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def overlap_length(a: Iterable[Interval], b: Iterable[Interval]) -> float:
    """Length of union(a) intersected with union(b)."""
    ma, mb = merge(a), merge(b)
    i = j = 0
    total = 0.0
    while i < len(ma) and j < len(mb):
        lo, hi = max(ma[i][0], mb[j][0]), min(ma[i][1], mb[j][1])
        total += max(0.0, hi - lo)
        if ma[i][1] < mb[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval covers."""
    out, t = [], window[0]
    for s, e in merge(intervals):
        if s > t:
            out.append((t, min(s, window[1])))
        t = max(t, e)
        if t >= window[1]:
            break
    if t < window[1]:
        out.append((t, window[1]))
    return [(s, e) for s, e in out if e > s]


def _clip(events, window: Interval, name=lambda n: n
          ) -> List[Tuple[str, float, float]]:
    lo, hi = window
    out = []
    for ev in events:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        if e > lo and s < hi:
            out.append((name(ev.name), max(s, lo), min(e, hi)))
    return out


def reduce_profile(profile, devices: Optional[Sequence[int]] = None,
                   window_span: str = WINDOW_SPAN) -> Trace:
    """Reduce a ``ProfileData`` to a :class:`Trace` over the host span named
    ``window_span``; the host spans kept are those of the thread that
    opened it.  ``devices`` limits the device planes (by TPU id)."""
    lines = [(line, [ev for ev in line.events if ev.name == window_span])
             for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    found = [(line, spans) for line, spans in lines if spans]
    if len(found) != 1 or len(found[0][1]) != 1:
        raise ValueError(f"expected one {window_span!r} span in the trace, "
                         f"found {sum(len(s) for _, s in found)}")
    line, (span,) = found[0]
    window = (span.start_ns, span.start_ns + span.duration_ns)
    devs: Dict[int, Device] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or (devices is not None and int(m.group(1)) not in devices):
            continue
        dev = devs.setdefault(int(m.group(1)), Device())
        for dline in plane.lines:
            if dline.name == OPS_LINE:
                dev.ops.extend(_clip(dline.events, window, op_name))
            elif dline.name == MODULES_LINE:
                dev.modules.extend(_clip(dline.events, window))
    return Trace(window, devs, _clip(line.events, window))

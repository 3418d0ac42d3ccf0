"""The readers of the solver's own spans, ``outer.syncs_per_solve`` and
``outer.host_idle_share``: on hand-made traces whose answers are known, on
traces without the spans, and on a trace recorded on a TPU v5e chip (one
solve of the thermal lattice with the program's spans)."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import chipbench_helpers  # noqa: F401  (puts the repository on the path)
from benchmarks.chip import harness
from benchmarks.chip import trace as T
from chipbench_helpers import REPO

RECORDED = (Path(__file__).parent / "data"
            / "thermal_one_solve_spans.xplane.pb")
READERS = ("outer.syncs_per_solve", "outer.host_idle_share",
           "device.idle_share")


def _line(name, events):
    """A trace line of (name, start_us, end_us) events, from t = 0 ns."""
    names = sorted({n for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    evs = "".join(
        f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1e6)} "
        f"duration_ps: {int((e - s) * 1e6)} }}\n" for n, s, e in events)
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())
    return f'lines {{ name: "{name}" timestamp_ns: 0\n{evs}}}\n', meta


def _xspace(host, devices):
    """A profile whose host thread holds ``host`` spans and whose TPU
    ``i`` runs the ops ``devices[i]`` (both as (name, start, end) in us)."""
    line, meta = _line("python3", host)
    text = f'planes {{ name: "/host:CPU"\n{line}{meta}}}\n'
    for i, ops in enumerate(devices):
        line, meta = _line("XLA Ops", ops)
        text += f'planes {{ name: "/device:TPU:{i}"\n{line}{meta}}}\n'
    return ProfileData.from_text_proto(text)


# a 100 us window with two solves and three readbacks:
#   solve A [0, 45) with a sync [10, 20); nothing at [45, 50);
#   solve B [50, 100) with syncs [70, 80) and [85, 90)
HOST = [("bench.window", 0, 100),
        ("lqcd.solve", 0, 45), ("lqcd.sync", 10, 20),
        ("lqcd.solve", 50, 100), ("lqcd.sync", 70, 80),
        ("lqcd.sync", 85, 90)]
# device 0 idles [5, 15): 5 us of host work, then 5 us inside a sync;
# [45, 55): 5 us between solves, then 5 us of solve B's host work;
# [75, 80): inside a sync.  Host-bound: 10 us.
DEV0 = [("fusion.1", 0, 5), ("fusion.2", 15, 45), ("fusion.3", 55, 75),
        ("fusion.4", 80, 100)]
# device 1 idles [30, 50): 15 us of solve A's host work, then 5 us
# between solves.  Host-bound: 15 us.
DEV1 = [("fusion.1", 0, 30), ("fusion.2", 50, 100)]


def _ctx(profile, devices=None):
    tr = T.reduce_profile(profile, devices=devices)
    cell = harness.load_cell(REPO, "thermal_32x8.light")
    solves = [harness.Solve(0, 1.0, {"iters": 20, "outer_iters": 3}, None)]
    return harness.Context(cell, solves, solves, tr, None, len(tr.devices))


def _read(ctx):
    return {m: harness.load_reader(REPO, m)(ctx) for m in READERS}


def test_hand_made_spans_on_two_devices():
    got = _read(_ctx(_xspace(HOST, [DEV0, DEV1])))
    assert got["outer.syncs_per_solve"] == 1.5
    # (10 + 15) us of 100, averaged over the two devices
    assert got["outer.host_idle_share"] == pytest.approx(12.5)
    # all idle: (25 + 20) us of 100, averaged
    assert got["device.idle_share"] == pytest.approx(22.5)


@pytest.mark.parametrize("device,host_bound,idle", [(0, 10.0, 25.0),
                                                    (1, 15.0, 20.0)])
def test_hand_made_spans_one_device(device, host_bound, idle):
    """Each device alone: idle inside a sync and between solves does not
    count, idle in the solver's host work does."""
    got = _read(_ctx(_xspace(HOST, [DEV0, DEV1]), devices=[device]))
    assert got["outer.host_idle_share"] == pytest.approx(host_bound)
    assert got["device.idle_share"] == pytest.approx(idle)
    assert got["outer.syncs_per_solve"] == 1.5


def test_idle_inside_syncs_only_reads_zero():
    """A device that idles only while the host waits on readbacks is not
    starved by the solver's host work."""
    ops = [("fusion.1", 0, 10), ("fusion.2", 20, 70), ("fusion.3", 80, 85),
           ("fusion.4", 90, 100)]
    got = _read(_ctx(_xspace(HOST, [ops])))
    assert got["outer.host_idle_share"] == 0.0
    assert got["device.idle_share"] == pytest.approx(25.0)


def test_nothing_to_read_without_the_spans():
    """No solver spans (a program without them, or a stub solver), no
    device in the trace, or no trace: no value, never a zero."""
    bare = [("bench.window", 0, 100), ("bench.solve", 0, 100)]
    got = _read(_ctx(_xspace(bare, [DEV0])))
    assert got["outer.syncs_per_solve"] is None
    assert got["outer.host_idle_share"] is None
    assert got["device.idle_share"] == pytest.approx(25.0)
    no_device = _read(_ctx(_xspace(HOST, [])))
    assert no_device["outer.syncs_per_solve"] is None
    assert no_device["outer.host_idle_share"] is None
    ctx = _ctx(_xspace(HOST, [DEV0]))
    ctx.trace = None
    assert all(v is None for v in _read(ctx).values())


def test_readers_on_the_trace_recorded_on_the_chip():
    """One solve of the thermal lattice at kappa = 0.01 (3 + 3 ops) on a TPU
    v5 lite with the program's spans, under the harness's window span.
    The committed file keeps the host thread of the window (stats only on
    the ``lqcd.*`` spans) and the device's op and program lines, each op
    named by its HLO name only."""
    tr = T.reduce_profile(ProfileData.from_file(str(RECORDED)), devices=[0])
    rounds = sum(n == "lqcd.round" for n, _, _ in tr.host)
    assert rounds == 3
    got = _read(_ctx(ProfileData.from_file(str(RECORDED)), devices=[0]))
    assert got["outer.syncs_per_solve"] == 2 * rounds + 3
    assert got["device.idle_share"] == pytest.approx(
        100 * (1 - 0.182309831 / 0.19463981), rel=1e-9)
    # the device waited almost only on readbacks: the solver's own host
    # work starved it for about 0.1 ms of the 195 ms window
    assert got["outer.host_idle_share"] == pytest.approx(0.0540516352,
                                                         rel=1e-6)
    assert got["outer.host_idle_share"] < got["device.idle_share"] / 50

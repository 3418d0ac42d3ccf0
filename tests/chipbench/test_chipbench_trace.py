"""The reduction from a profiler trace to the per-layer metrics: interval
arithmetic, a hand-made trace whose answers are known, and a trace
recorded on a TPU v5e chip (one solve of ``thermal_32x8.light``)."""
import re
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import chipbench_helpers  # noqa: F401  (puts the repository on the path)
from benchmarks.chip import trace as T
from benchmarks.chip import harness
from chipbench_helpers import REPO

RECORDED = Path(__file__).parent / "data" / "thermal_one_solve.xplane.pb"

# two devices, times in ns; the window is [1000, 101000)
HAND_MADE = '''
planes {
  name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 60000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 40000000 }
    events { metadata_id: 4 offset_ps: 200000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.solve" } }
  event_metadata { key: 3 value { id: 3 name: "host work" } }
  event_metadata { key: 4 value { id: 4 name: "after" } }
}
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 40000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 40000000 }
    events { metadata_id: 4 offset_ps: 90000000 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.3" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.1" } }
  event_metadata { key: 3 value { id: 3 name: "jit__eo_inner(12)" } }
  event_metadata { key: 4 value { id: 4 name: "jit__eo_update(7)" } }
  event_metadata { key: 5 value { id: 5 name: "%while.4 = (f32[2]) while()" } }
}
planes {
  name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 2 offset_ps: 50000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 60000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 60000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.3" } }
  event_metadata { key: 2 value { id: 2 name: "collective-permute-done" } }
  event_metadata { key: 3 value { id: 3 name: "jit__eo_inner(12)" } }
  event_metadata { key: 4 value { id: 4 name: "while.2" } }
}
'''


def test_interval_arithmetic():
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert T.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert T.overlap_length([(0, 4), (6, 8)], [(3, 7)]) == 2
    assert T.gaps([(2, 3), (5, 6)], (0, 8)) == [(0, 2), (3, 5), (6, 8)]
    assert T.gaps([(-1, 9)], (0, 8)) == []


def test_hand_made_trace():
    tr = T.reduce_profile(ProfileData.from_text_proto(HAND_MADE))
    assert tr.window == (1000, 101000) and tr.window_s == 1e-4
    assert sorted(tr.devices) == [0, 1]
    # device 0 runs [11k, 51k) and [91k, 101k) after clipping: 50 us;
    # device 1 runs [1k, 61k): 60 us
    assert tr.busy_s() == pytest.approx(55e-6)
    # collectives alone: device 0 [41k, 51k), device 1 [51k, 61k)
    assert tr.exposed_collective_s() == pytest.approx(10e-6)
    inner = re.compile(r"^jit__eo_inner\(")
    assert tr.module_s(inner) == pytest.approx(50e-6)
    assert tr.module_s(inner, holding=re.compile(r"^while\.")) == (
        pytest.approx(50e-6))
    assert tr.module_s(re.compile(r"^jit__eo_update\("),
                       holding=re.compile(r"^while\.")) == 0
    top = dict(tr.top_ops())
    assert "while.4" not in top and "while.2" not in top
    assert top["fusion.3"] == pytest.approx((30 + 10 + 50) * 1e-6 / 2)
    # device 0 idles [1k, 11k) in bench.solve and [51k, 91k) mostly in
    # host work, which is open at the gap's middle; the span after the
    # window is dropped
    idle = dict(tr.idle_by_host())
    assert idle == {"bench.solve": pytest.approx(10e-6),
                    "host work": pytest.approx(40e-6)}
    only_one = T.reduce_profile(ProfileData.from_text_proto(HAND_MADE),
                                devices=[1])
    assert list(only_one.devices) == [1]


def test_metric_readers_on_the_hand_made_trace():
    tr = T.reduce_profile(ProfileData.from_text_proto(HAND_MADE))
    cell = harness.load_cell(REPO, "thermal_32x8.light")
    peaks = harness.load_peaks(REPO, "TPU v5 lite")
    solves = [harness.Solve(0, 1.0, {"iters": 20, "outer_iters": 3}, None)]
    ctx = harness.Context(cell, solves, solves, tr, peaks, 2)
    read = {m: harness.load_reader(REPO, m) for m in (
        "device.idle_share", "collective.exposed_share", "inner_cg_roofline",
        "inner_cg.ops_per_solve", "outer.rounds_per_solve",
        "solve_roofline")}
    assert read["device.idle_share"](ctx) == pytest.approx(45.0)
    assert read["collective.exposed_share"](ctx) == pytest.approx(10.0)
    assert read["inner_cg.ops_per_solve"](ctx) == 20
    assert read["outer.rounds_per_solve"](ctx) == 3
    assert read["inner_cg_roofline"](ctx) > 0
    assert read["solve_roofline"](ctx) > 0
    # nothing to read: no value, never a zero share
    empty = harness.Context(cell, solves, solves, None, peaks, 2)
    for name in ("device.idle_share", "collective.exposed_share",
                 "inner_cg_roofline"):
        assert read[name](empty) is None


def test_window_span_must_be_there_once():
    with pytest.raises(ValueError, match="bench.window"):
        T.reduce_profile(ProfileData.from_text_proto(
            HAND_MADE.replace('name: "bench.window"', 'name: "other"')))


def test_trace_recorded_on_the_chip():
    """Two solves of the thermal lattice at kappa = 0.01 (3 + 3 ops each),
    traced by the harness on a TPU v5 lite.  The committed file keeps the
    device's op and program lines and the host thread of the window, with
    each op named by its HLO name only and the event stats dropped; the
    run on the chip read busy_s and window_s below from the whole file."""
    tr = T.reduce_profile(ProfileData.from_file(str(RECORDED)), devices=[0])
    assert list(tr.devices) == [0]
    assert tr.busy_s() == pytest.approx(0.365426675, rel=1e-12)
    assert tr.window_s == pytest.approx(0.389148898, rel=1e-12)
    inner = tr.module_s(re.compile(r"^jit__eo_inner\("),
                        holding=re.compile(r"^while\."))
    assert inner == pytest.approx(0.140296378, rel=1e-12)
    assert tr.top_ops(1) == [["custom-call.2", pytest.approx(0.006147765)]]
    idle = tr.idle_by_host()
    assert idle[0] == ["$array.py:631 _value", pytest.approx(0.02372176)]
    assert sum(t for _, t in tr.idle_by_host(k=10 ** 6)) == pytest.approx(
        tr.window_s - tr.busy_s(), rel=1e-9)
    assert tr.exposed_collective_s() == 0.0


def test_metric_readers_on_the_recorded_trace():
    tr = T.reduce_profile(ProfileData.from_file(str(RECORDED)), devices=[0])
    cell = harness.load_cell(REPO, "thermal_32x8.light")
    peaks = harness.load_peaks(REPO, "TPU v5 lite")
    solves = [harness.Solve(i, 0.19, {"iters": 3, "outer_iters": 3},
                            None) for i in range(2)]
    ctx = harness.Context(cell, solves, solves, tr, peaks, 1)
    idle = harness.load_reader(REPO, "device.idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - 0.365426675 / 0.389148898))
    share = harness.load_reader(REPO, "inner_cg_roofline")(ctx)
    # 6 inner iterations of the bf16 work model over the loop's device time
    from benchmarks.chip import work
    least = work.inner_cg((32, 32, 32, 8), 6, "bfloat16").bytes / 8.19e11
    assert share == pytest.approx(100 * least / 0.140296378)
    assert 0 < share < 100
    assert harness.load_reader(REPO, "collective.exposed_share")(ctx) is None

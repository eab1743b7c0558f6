"""The reduction from a trace to busy, idle and exposed-collective time: on
hand-made intervals, on a hand-made trace (overlapping operations, a gap, a
collective half hidden behind compute) and on a small trace recorded on a
TPU v5e (``fixtures/tiny.xplane.pb``: four runs of a three-matmul program)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as run_lib, xplane  # noqa: E402

FIXTURE = os.path.join(ROOT, "tests", "chipbench", "fixtures", "tiny.xplane.pb")


def _reader(name):
    # the collective readers wait with the rehearsal files for their cell
    return run_lib.load_module(
        [os.path.join(ROOT, "chipbench"),
         os.path.join(ROOT, "tests", "chipbench", "rehearsal")],
        "layer_metrics", name)


def test_union_gaps_subtract():
    merged = xplane.union([(5, 9), (0, 4), (3, 6), (20, 22), (22, 22)])
    assert merged == [(0, 9), (20, 22)]
    assert xplane.total(merged) == 11
    assert xplane.gaps(merged, 0, 30) == [(9, 20), (22, 30)]
    assert xplane.clip(merged, 8, 21) == [(8, 9), (20, 21)]
    # a collective over [10, 20], compute hides [14, 30] of it
    assert xplane.subtract([(10, 20)], [(14, 30)]) == [(10, 14)]
    assert xplane.subtract([(0, 10)], []) == [(0, 10)]


# Four runs of the step program on device 0; the trace cut the first and the
# last short, so two whole ones count: [60,160] and [180,280] us.
# Run 1: fusion.1 [60,100], custom-call.7 [90,120] overlaps it, all-gather.2
# [120,140] of which fusion.3 [130,160] hides the second half.
# Run 2: fusion.1 [180,220], idle [220,240], fusion.3 [240,280]; an
# asynchronous all-reduce is in flight over [185,230]: fusion.1 hides it up
# to 220, the last 10 us nothing does.
_TRACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 1 offset_ps: 60000000 duration_ps: 40000000 }
    events { metadata_id: 4 offset_ps: 90000000 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 120000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 130000000 duration_ps: 30000000 }
    events { metadata_id: 1 offset_ps: 180000000 duration_ps: 40000000 }
    events { metadata_id: 3 offset_ps: 240000000 duration_ps: 40000000 }
    events { metadata_id: 1 offset_ps: 290000000 duration_ps: 30000000 }
  }
  lines { name: "Async XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 7 offset_ps: 185000000 duration_ps: 45000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 5 offset_ps: 60000000 duration_ps: 100000000 }
    events { metadata_id: 5 offset_ps: 180000000 duration_ps: 100000000 }
    events { metadata_id: 5 offset_ps: 290000000 duration_ps: 30000000 }
    events { metadata_id: 6 offset_ps: 330000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.2 = bf16[8]{0} all-gather(%p)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3" } }
  event_metadata { key: 4 value { id: 4 name: "%custom-call.7 = bf16[8]{0} custom-call(%p)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_train_step(1)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_norms(2)" } }
  event_metadata { key: 7 value { id: 7 name: "%all-reduce-start.5 = f32[8]{0} all-reduce-start(%g)" } }
}
planes { name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 225000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.next" } }
  event_metadata { key: 2 value { id: 2 name: "somebody.else" } }
}
"""
_STEP_TEXT = '''
  %custom-call.7 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call"
  %custom-call.8 = bf16[8]{0} custom-call(%p), custom_call_target="Sharding"
'''


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return xplane.from_profile(ProfileData.from_text_proto(_TRACE))


def test_hand_made_trace(trace):
    dev = trace.devices[0]
    assert xplane.step_window(dev) == (1_060_000, 1_280_000, 2)
    assert dev.ops[1].name == "fusion.1"
    assert [h.name for h in trace.host] == ["chipbench.next"]
    ctx = {"step_text": _STEP_TEXT}
    # busy: [60,160], [180,220] and [240,280] = 180 of 220 us
    assert _reader("device_idle_pct").read(trace, None, ctx) == pytest.approx(
        100 * 40 / 220)
    assert _reader("device_step_ms").read(trace, None, ctx) == pytest.approx(
        0.090)
    assert _reader("collective_ms").read(trace, None, ctx) == pytest.approx(
        0.0325)
    assert _reader("collective_exposed_ms").read(
        trace, None, ctx) == pytest.approx(0.010)
    assert _reader("pallas_ms").read(trace, None, ctx) == pytest.approx(0.015)


def test_idle_gap_goes_to_what_the_host_was_in(trace):
    driver = run_lib.load_module([os.path.join(ROOT, "chipbench")],
                                 "drivers", "train_window")
    breakdown = driver._breakdown(trace)
    assert dict(breakdown["idle_gaps"]) == pytest.approx(
        {"next": 20e-6, "other": 20e-6})
    assert breakdown["device_ops"][0][0] == "fusion"


def test_readers_return_nothing_without_a_device(trace):
    empty = xplane.Trace([], [])
    for name in ("device_idle_pct", "device_step_ms", "collective_ms",
                 "collective_exposed_ms", "pallas_ms", "step_mfu_pct"):
        assert _reader(name).read(empty, None, {}) is None
        assert _reader(name).read(None, None, {}) is None


def test_recorded_trace():
    recorded = xplane.load(FIXTURE)
    assert len(recorded.devices) == 1
    dev = recorded.devices[0]
    lo, hi, runs = xplane.step_window(dev, whole_only=False)
    assert runs == 4 and xplane.step_window(dev)[2] == 2
    busy = xplane.busy(dev, lo, hi)
    assert 0 < busy < hi - lo
    # the host slept 2 ms after the second run: the device idled as long
    longest = max(b - a for a, b in xplane.gaps(
        xplane.union(xplane.spans(dev.ops)), lo, hi))
    assert longest > 1_500_000
    idle = 100.0 * (1 - busy / (hi - lo))
    assert 90 < idle < 100   # a tiny program: the device mostly waits
    assert sum(h.name == "chipbench.train_step" for h in recorded.host) == 4

"""The readers of the program's own instrumentation (``chipbench/
program_spans.py`` and the ten ``layer_metrics`` files that use it), on
hand-made spans, a hand-made ``xplane.Trace`` and a planted step text. No
number here comes from a device."""

import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import program_spans, run as run_lib, xplane  # noqa: E402

Span = collections.namedtuple(
    "Span", "kind name t0 t1 step id parent thread value", defaults=(None,))
E = xplane.Event
MS = 1_000_000


def _reader(name):
    return run_lib.load_module([os.path.join(ROOT, "chipbench")],
                               "layer_metrics", name)


# Two whole steps of 10 ms on device 0, [100, 110] and [110, 120] ms of the
# trace's clock, between a first and a last that the trace cut short. A step:
# embed 0.5, norm 0.25, attention (a projection 1.0, the forward kernel 1.0),
# mlp 2.0, head and loss forward 1.5 — then 0.5 idle — loss and head backward
# 1.0, the two backward kernels 0.75 + 1.0, an unnamed copy 0.25, optimizer
# 0.25.
_STEP = [("fusion.1", 0.0, 0.5), ("fusion.2", 0.5, 0.75), ("fusion.3", 0.75, 1.75),
         ("flash_fwd_online.4", 1.75, 2.75), ("fusion.5", 2.75, 4.75),
         ("fusion.6", 4.75, 6.25), ("multiply_reduce_fusion.7", 6.75, 7.75),
         ("flash_bwd_dq.8", 7.75, 8.5), ("flash_bwd_dkv.9", 8.5, 9.5),
         ("copy.10", 9.5, 9.75), ("fusion.11", 9.75, 10.0)]


def _trace(host=(), base_ms=0):
    ops, modules = [], []
    for base in (base_ms + 90, base_ms + 100, base_ms + 110, base_ms + 120):
        modules.append(E("jit_train_step(1)", base * MS, (base + 10) * MS))
        ops += [E(n, int((base + a) * MS), int((base + b) * MS))
                for n, a, b in _STEP]
    dev = xplane.Device("/device:TPU:0", ops, modules, [])
    return xplane.Trace([dev], sorted(host, key=lambda e: e.start))


_CALL = ('custom-call(%%p), custom_call_target="tpu_custom_call", '
         'metadata={op_name="jit(train_step)/%s/pallas_call"}')
STEP_TEXT = "\n".join([
    "ENTRY %main {",
    '  %fusion.1 = bf16[8]{0} fusion(%p), metadata={op_name="jit(train_step)/jvp(GPT2)/embed/wte/take"}',
    '  %fusion.2 = bf16[8]{0} fusion(%p), metadata={op_name="jit(train_step)/jvp(GPT2)/block_0/norm/ln_1/mul"}',
    '  %fusion.3 = bf16[8]{0} fusion(%p), metadata={op_name="jit(train_step)/jvp(GPT2)/block_0/attn/query/dot_general"}',
    "  %flash_fwd_online.4 = bf16[8]{0} " + _CALL % "jvp(GPT2)/block_0/attn/flash_fwd_online",
    '  %fusion.5 = bf16[8]{0} fusion(%p), metadata={op_name="jit(train_step)/jvp(GPT2)/block_0/mlp/mlp_up/dot_general"}',
    '  %fusion.6 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_step)/jvp(GPT2)/head_loss/dot_general"}',
    '  %multiply_reduce_fusion.7 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_step)/transpose(jvp(head_loss))/mul"}',
    "  %flash_bwd_dq.8 = bf16[8]{0} " + _CALL % "transpose(jvp(GPT2))/block_0/attn/flash_bwd_dq",
    "  %flash_bwd_dkv.9 = (bf16[8]{0}, bf16[8]{0}) " + _CALL % "transpose(jvp(GPT2))/block_0/attn/flash_bwd_dkv",
    "  %copy.10 = bf16[8]{0} copy(%p)",
    '  ROOT %fusion.11 = f32[8]{0} fusion(%p), metadata={op_name="jit(train_step)/optimizer/add"}',
    "}"])
CTX = {"step_text": STEP_TEXT}


def test_names_from_the_step_text():
    regions, kernels = program_spans.names(STEP_TEXT)
    assert kernels == {"flash_fwd_online.4": "flash_fwd_online",
                       "flash_bwd_dq.8": "flash_bwd_dq",
                       "flash_bwd_dkv.9": "flash_bwd_dkv"}
    assert regions["fusion.2"] == "norm" and regions["fusion.5"] == "mlp"
    assert regions["multiply_reduce_fusion.7"] == "head_loss"
    assert regions["flash_bwd_dq.8"] == "attn"     # a kernel has a region too
    assert "copy.10" not in regions
    assert program_spans.region_of("jit(f)/jvp(M)/block_1/attn/norm/x") == "norm"
    assert program_spans.region_of("jit(f)/jvp(M)/block_1/query") is None
    assert program_spans.kernel_of("jit(f)/attn/pallas_call") == "attn"


def test_flash_forward_and_backward_add_up_to_pallas_ms():
    trace = _trace()
    fwd = _reader("flash_fwd_ms").read(trace, {}, CTX)
    bwd = _reader("flash_bwd_ms").read(trace, {}, CTX)
    assert fwd == pytest.approx(1.0) and bwd == pytest.approx(1.75)
    assert fwd + bwd == pytest.approx(_reader("pallas_ms").read(trace, {}, CTX))


def test_region_times_and_coverage(capsys):
    trace = _trace()
    assert _reader("head_loss_ms").read(trace, {}, CTX) == pytest.approx(2.5)
    assert _reader("optimizer_ms").read(trace, {}, CTX) == pytest.approx(0.25)
    # of 9.5 ms busy a step, the copy's 0.25 has no name
    assert _reader("region_coverage_pct").read(trace, {}, CTX) == \
        pytest.approx(100 * 9.25 / 9.5)
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["row"] == "regions" and row["steps"] == 2
    assert row["busy_ms"] == pytest.approx(9.5)
    assert row["region_ms"] == pytest.approx(
        {"attn": 3.75, "head_loss": 2.5, "mlp": 2.0, "embed": 0.5,
         "norm": 0.25, "optimizer": 0.25})
    assert row["kernel_ms"]["flash_bwd_dkv"] == pytest.approx(1.0)
    assert row["unnamed_ms"] == pytest.approx({"copy": 0.25})


@pytest.mark.parametrize("metric", ["flash_fwd_ms", "flash_bwd_ms",
                                    "head_loss_ms", "optimizer_ms",
                                    "region_coverage_pct"])
def test_absent_names_give_none_and_a_line_never_zero(metric, capsys):
    """An executable served from the compilation cache carries the names of
    whoever compiled it first: a text without them must not read as 0 ms."""
    bare = {"step_text": "\n".join(
        line.split(", metadata=")[0] for line in STEP_TEXT.splitlines())}
    assert _reader(metric).read(_trace(), {}, bare) is None
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["row"] == "names" and said["missing"]
    assert _reader(metric).read(None, {}, CTX) is None          # off the chip


# -- the host side: hand-made spans ----------------------------------------------------

# perf_counter runs 7 s behind the trace's clock. Four iterations of 10 ms
# from 93.0 ms of perf_counter's time; the driver's rows bound the window.
OFFSET = 7_000_000_000
_IDS = iter(range(1, 10_000))


def _iteration(step, t):
    """An iteration at ``t`` ms: input_wait 1.0 (loader_wait 0.25, device_put
    0.5), dispatch 8.0, and 1.0 of its own."""
    it, wait = next(_IDS), next(_IDS)
    at = lambda a: int((t + a) * MS)
    return [Span("span", "iteration", at(0), at(10), step, it, None, "Main"),
            Span("span", "input_wait", at(0.25), at(1.25), step, wait, it, "Main"),
            Span("span", "loader_wait", at(0.25), at(0.5), step, next(_IDS), wait, "Main"),
            Span("span", "device_put", at(0.5), at(1.0), step, next(_IDS), wait, "Main"),
            Span("span", "dispatch", at(1.5), at(9.5), step, next(_IDS), it, "Main"),
            Span("span", "make_batch", at(2), at(6), step + 2, next(_IDS), None, "Worker"),
            Span("counter", "loader.ready_depth", at(0.25), at(0.25), step,
                 next(_IDS), None, "Main", 3 + step % 2)]


@pytest.fixture
def planted(monkeypatch):
    records = [Span("span", "init", 0, 50 * MS, None, next(_IDS), None, "Main")]
    for k in range(4):
        records += _iteration(k, 93.0 + 10 * k)
    monkeypatch.setattr(program_spans, "ring", lambda: records)
    rows = []
    for k in range(4):
        t = 0.093 + 0.010 * k
        rows += [("next", t + 0.00025, t + 0.00125),
                 ("train_step", t + 0.0015, t + 0.0095)]
    rows.append(("next", 0.093, 0.133))   # the wrapper's rows span the window
    return {"rows": rows, "window_s": 0.040}


def test_the_window_cuts_the_ring(planted):
    spans = program_spans.records(planted)
    assert "init" not in {s.name for s in spans}
    assert len([s for s in spans if s.name == "iteration"]) == 4
    assert len(program_spans.records(planted, kind="counter")) == 4


def test_shares_depth_and_self_time(planted, capsys):
    assert _reader("loader_wait_pct").read(None, planted, {}) == \
        pytest.approx(100 * 4 * 0.25 / 40)
    assert _reader("device_put_pct").read(None, planted, {}) == \
        pytest.approx(100 * 4 * 0.5 / 40)
    assert _reader("loader_ready_depth").read(None, planted, {}) == 3.5
    # 10 less input_wait 1.0 and dispatch 8.0; the worker's span is no child
    assert _reader("loop_self_ms").read(None, planted, {}) == pytest.approx(1.0)
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["row"] == "spans" and row["count"]["dispatch"] == 4
    assert row["median_ms"]["dispatch"] == pytest.approx(8.0)
    assert row["per_iteration"] == pytest.approx(6.0)
    # the wrapper's rows and the dispatch spans are the same 8 ms calls
    assert row["dispatch_minus_wrapper_ms"] == pytest.approx(0.0, abs=1e-6)


def test_a_program_without_a_recorder_reports_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    host = {"rows": [("next", 0.0, 1.0)], "window_s": 1.0}
    for metric in ("loader_wait_pct", "device_put_pct", "loader_ready_depth",
                   "loop_self_ms", "idle_attributed_pct"):
        assert _reader(metric).read(_trace(), host, CTX) is None


def _annotations(rows, jitter=()):
    """The benchmark's own annotation of each ``train_step`` row, on the
    trace's clock: it opens just after the first stamp and closes just before
    the second."""
    out = []
    for k, (_, t0, t1) in enumerate(r for r in rows if r[0] == "train_step"):
        late = jitter[k] if k < len(jitter) else 0
        out.append(E(program_spans.STEP_ANNOTATION,
                     int(t0 * 1e9) + OFFSET + 2_000 + late,
                     int(t1 * 1e9) + OFFSET - 2_000 + late))
    return out


def test_clock_mapping_finds_a_known_offset(planted):
    # the trace began late: it holds the last three calls only
    trace = _trace(_annotations(planted["rows"], jitter=(0, 0, 0, 40_000))[1:])
    clock = program_spans.clock(trace, planted)
    assert clock["pairs"] == 3
    assert clock["offset_ns"] == OFFSET
    assert clock["residual_ns"] == 0 and clock["residual_max_ns"] == 40_000
    assert program_spans.clock(_trace(), planted) is None


def test_idle_time_is_attributed_through_the_clock(planted, capsys):
    """The device's whole steps lie 7 s later than in ``_trace()``: on the
    trace's clock the iterations start at 7093 + 10 k ms, so each step's idle
    half millisecond (6.25 ms into the step) falls 3.25 ms into an iteration:
    inside its ``dispatch``."""
    trace = _trace(_annotations(planted["rows"]), base_ms=7000)
    value = _reader("idle_attributed_pct").read(trace, planted, CTX)
    assert value == pytest.approx(100.0)
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["row"] == "clock" and lines[0]["offset_ns"] == OFFSET
    assert lines[0]["residual_ms"] == 0.0
    assert lines[1]["row"] == "idle_by_span"
    assert lines[1]["ms"] == pytest.approx({"dispatch": 1.0})
    assert lines[1]["idle_ms"] == pytest.approx(1.0)


def test_idle_outside_every_span_is_not_attributed(planted):
    """Here the device's window lies seconds before the planted spans on the
    trace's clock: no gap has a name."""
    trace = _trace(_annotations(planted["rows"]))
    assert _reader("idle_attributed_pct").read(trace, planted, CTX) == 0.0


def test_innermost_span_of_a_thread():
    outer = Span("span", "iteration", 0, 100, 0, 1, None, "Main")
    inner = Span("span", "input_wait", 10, 40, 0, 2, 1, "Main")
    leaf = Span("span", "loader_wait", 12, 20, 0, 3, 2, "Main")
    later = Span("span", "iteration", 120, 200, 1, 4, None, "Main")
    spans = [outer, inner, leaf, later]
    assert program_spans.innermost(spans, 15).name == "loader_wait"
    assert program_spans.innermost(spans, 30).name == "input_wait"
    assert program_spans.innermost(spans, 50).name == "iteration"
    assert program_spans.innermost(spans, 110) is None
    assert program_spans.innermost(spans, 150) is later
    assert program_spans.self_ns(outer, [inner]) == 70

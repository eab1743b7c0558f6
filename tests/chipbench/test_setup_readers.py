"""The eight readers of the program's start-up (``chipbench/setup_spans.py``
and the ``layer_metrics/setup_*_s.py`` files), on hand-made records, and once
through the whole harness off the chip. No number here comes from a device."""

import collections
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import program_spans, run as run_lib, setup_spans  # noqa: E402

Span = collections.namedtuple(
    "Span", "kind name t0 t1 step id parent thread value", defaults=(None,))
REHEARSAL = os.path.join("tests", "chipbench", "rehearsal")
CHAIN = ["setup_preinit_s", "setup_init_s", "setup_between_s",
         "setup_first_step_s", "setup_warmup_s"]
KINDS = ["setup_trace_lower_s", "setup_cache_load_s", "setup_xla_compile_s"]
S = 1_000_000_000
#: odd nanoseconds, so that a float that lost one would show
PROCESS_T0, W = 5 * S + 1, 47 * S + 123_456_789


def _reader(name):
    return run_lib.load_module([os.path.join(ROOT, "chipbench")],
                               "layer_metrics", name)


def _span(name, t0, t1, id, parent=None, thread="MainThread", step=None):
    return Span("span", name, t0, t1, step, id, parent, thread)


def _compile(name, t0, t1, id, parent, fun, thread="MainThread"):
    return Span("compile", name, t0, t1, None, id, parent, thread, fun)


def _records():
    """A warm start of 42.1 s: the process began at 5 s, the recorder at 12,
    ``init`` runs 13..24, the benchmark's weights follow, the first iteration
    runs 27..38 with its ``compile`` span ending at 37.5, three warm-up
    steps, and the window opens at ``W``. In the order the recorder appends:
    a span when it closes."""
    return [
        _span("process", PROCESS_T0, 12 * S, 1),
        _span("build_mesh", 13 * S + 7, 13 * S + 9, 3, parent=2),
        _span("build_model", 13 * S + 9, 14 * S, 4, parent=2),
        _span("build_data", 14 * S, 15 * S, 5, parent=2),
        # the state's init: traced twice, lowered, loaded from the cache
        _compile("trace", 15 * S, 17 * S, 7, 6, "init_fn"),
        _compile("trace", 17 * S, 19 * S, 8, 6, "init_fn"),
        _compile("lower", 19 * S, 20 * S, 9, 6, "jit(init_fn)"),
        _compile("cache_retrieval", 20 * S + 5, 22 * S, 10, 6, "jit(init_fn)"),
        _compile("cache_load", 20 * S, 22 * S, 11, 6, "jit(init_fn)"),
        _span("init_state", 15 * S, 23 * S, 6, parent=2),
        _span("init", 13 * S + 3, 24 * S + 11, 2),
        # the benchmark's own weights, no span open
        _compile("trace", 24 * S + 20, 25 * S, 12, None, "<lambda>"),
        _compile("lower", 25 * S, 25 * S + S // 2, 13, None, "jit(<lambda>)"),
        _compile("cache_load", 25 * S + S // 2, 26 * S, 14, None,
                 "jit(<lambda>)"),
        # a loader thread's batch, while the loop waits for it
        _span("make_batch", 27 * S, 27 * S + S // 4, 30, thread="loader-0",
              step=0),
        _span("input_wait", 27 * S + 1, 27 * S + S // 2, 16, parent=15, step=0),
        _compile("trace", 28 * S, 32 * S, 18, 17, "train_step"),
        # a function the step's lowering traced and lowered: inside ``lower``
        _compile("lower", 32 * S, 34 * S, 19, 17, "jit(train_step)"),
        _compile("trace", 32 * S + 1, 33 * S, 20, 17, "add"),
        _compile("cache_load", 34 * S, 36 * S, 21, 17, "jit(train_step)"),
        # the benchmark's norms, under the program's ``compile`` span
        _compile("trace", 36 * S, 36 * S + S // 4, 22, 17, "norms"),
        _compile("compile", 36 * S + S // 4, 36 * S + S // 2, 23, 17,
                 "jit(norms)"),
        _compile("cache_miss", 36 * S + S // 2, 36 * S + S // 2, 24, 17, None),
        _span("first_step_wait", 37 * S, 37 * S + S // 2, 25, parent=17,
              step=0),
        _span("compile", 27 * S + S // 2, 37 * S + S // 2, 17, parent=15,
              step=0),
        _span("iteration", 27 * S + 1, 38 * S, 15, step=0),
        _span("dispatch", 38 * S + 2, 39 * S, 27, parent=26, step=1),
        _span("iteration", 38 * S + 1, 39 * S + 1, 26, step=1),
        _span("iteration", 39 * S + 2, 40 * S, 28, step=2),
        # the window: an iteration that a compile would spoil, were it counted
        _compile("trace", W + S, W + 2 * S, 41, 40, "eval_step"),
        _span("iteration", W + 5, W + 3 * S, 40, step=3),
    ]


@pytest.fixture
def planted(monkeypatch):
    records = _records()
    monkeypatch.setattr(program_spans, "ring", lambda: records)
    monkeypatch.setattr(setup_spans, "_said", False)
    # the driver's rows: the window opens with the first ``next`` it timed
    host = {"rows": [("next", W / 1e9, W / 1e9 + 0.5),
                     ("train_step", W / 1e9 + 0.5, W / 1e9 + 3.0)],
            "window_s": 20.0}
    return records, host


def test_the_window_starts_where_the_drivers_rows_start(planted):
    _, host = planted
    assert program_spans.bounds(host)[0] in (W - 1, W, W + 1)  # a float's ns


def test_the_five_parts_add_up_to_the_set_up_to_the_nanosecond(planted):
    _, host = planted
    w = program_spans.bounds(host)[0]
    parts = setup_spans.chain(host)
    assert list(parts) == list(setup_spans.CHAIN)
    assert all(isinstance(v, int) and v > 0 for v in parts.values())
    assert sum(parts.values()) == w - PROCESS_T0
    assert parts["preinit"] == 13 * S + 3 - PROCESS_T0
    assert parts["init"] == 11 * S + 8
    assert parts["between"] == 27 * S + 1 - (24 * S + 11)
    assert parts["first_step"] == 37 * S + S // 2 - (27 * S + 1)
    assert parts["warmup"] == w - (37 * S + S // 2)
    values = [_reader(name).read(None, host, {}) for name in CHAIN]
    assert values == [pytest.approx(v / 1e9, abs=1e-12)
                      for v in parts.values()]
    assert sum(values) == pytest.approx((w - PROCESS_T0) / 1e9, abs=1e-9)


def test_the_pipeline_counts_no_instant_twice(planted):
    _, host = planted
    trace_lower, cache_load, xla = [_reader(name).read(None, host, {})
                                    for name in KINDS]
    # init 2+2+1, weights 0.98+0.5 (its trace began 20 ns late), the step
    # 4+2 (the ``add`` that its lowering traced is inside ``lower``), the
    # norms 0.25; the window's trace of ``eval_step`` is not set-up
    assert trace_lower == pytest.approx(5 + (S - 20) / 1e9 + 0.5 + 6 + 0.25)
    # the retrieval is inside the load: 2 + 0.5 + 2
    assert cache_load == pytest.approx(4.5)
    assert xla == pytest.approx(0.25)
    parts = setup_spans.chain(host)
    assert trace_lower + cache_load + xla < sum(parts.values()) / 1e9


def test_two_threads_tracing_at_once_are_each_counted(planted):
    records, host = planted
    records.insert(5, _compile("trace", 15 * S, 16 * S, 50, None,
                               "device_put", thread="prefetch-0"))
    assert _reader("setup_trace_lower_s").read(None, host, {}) == (
        pytest.approx(1 + 5 + (S - 20) / 1e9 + 0.5 + 6 + 0.25))


def test_one_setup_row_whichever_reader_runs_first(planted, capsys):
    _, host = planted
    for name in reversed(CHAIN + KINDS):
        _reader(name).read(None, host, {})
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    (row,) = [r for r in rows if r.get("row") == "setup"]
    assert row["chain_sum_s"] == pytest.approx(sum(row["chain_s"].values()))
    assert list(row["init_s"]) == ["build_mesh", "build_model", "build_data",
                                   "init_state"]
    assert row["spans_s"]["process"] == pytest.approx(7 - 1e-9)
    assert set(row["spans_s"]) == {"process", "init"}      # not make_batch
    assert row["first_iteration_s"]["first_step_wait"] == pytest.approx(0.5)
    assert row["warmup_iterations"] == 2
    under = row["under"]
    assert set(under) == {"init_state", "-", "compile"}
    assert under["init_state"]["s"] == {
        "trace": 4.0, "lower": 1.0, "cache_retrieval": pytest.approx(2.0),
        "cache_load": 2.0}
    assert under["init_state"]["n"]["trace"] == 2
    assert under["compile"]["longest"][0] == [4.0, "trace", "train_step"]
    assert len(under["compile"]["longest"]) == setup_spans.LONGEST
    # the benchmark's own jits are told from the program's by function
    assert under["-"]["longest"][0][2] == "<lambda>"
    assert [0.25, "trace", "norms"] in under["compile"]["longest"]
    assert under["compile"]["s"]["compile"] == 0.25          # jit(norms)


@pytest.mark.parametrize("missing,silent", [
    # a commit before this recorder: spans, no ``process``, and its backend
    # records mix compiles with cache loads under one name
    ("process+kinds", CHAIN + KINDS),
    ("process", CHAIN),
    ("init", CHAIN),
    ("iteration", CHAIN),
    ("compile_span", CHAIN),
    ("trace", KINDS),
])
def test_a_reader_reports_nothing_where_its_records_are_absent(
        planted, missing, silent):
    records, host = planted
    drop = {
        "process": lambda r: r.name == "process",
        "init": lambda r: r.kind == "span" and r.name == "init",
        "iteration": lambda r: r.name == "iteration",
        "compile_span": lambda r: r.kind == "span" and r.name == "compile",
        "trace": lambda r: r.kind == "compile" and r.name != "compile",
    }
    drop["process+kinds"] = lambda r: drop["process"](r) or drop["trace"](r)
    records[:] = [r for r in records if not drop[missing](r)]
    got = {name: _reader(name).read(None, host, {}) for name in CHAIN + KINDS}
    assert {n for n, v in got.items() if v is None} == set(silent)
    assert all(v is None or v > 0 for v in got.values())


def test_a_warm_start_compiled_nothing_and_says_zero(planted):
    records, host = planted
    records[:] = [r for r in records
                  if not (r.kind == "compile" and r.name == "compile")]
    assert _reader("setup_xla_compile_s").read(None, host, {}) == 0.0


@pytest.mark.parametrize("ring,rows", [(None, [("next", 1.0, 2.0)]),
                                       ([], [])])
def test_no_recorder_or_no_window_reports_nothing(monkeypatch, ring, rows):
    monkeypatch.setattr(program_spans, "ring", lambda: ring)
    host = {"rows": rows, "window_s": 1.0}
    assert [_reader(n).read(None, host, {}) for n in CHAIN + KINDS] == [
        None] * 8


def test_the_manifest_gained_eight_entries_that_move_setup_s():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    new = manifest["per_layer"][-8:]
    assert [m["name"] for m in new] == CHAIN + KINDS
    for metric in new:
        assert metric == {"name": metric["name"], "unit": "s",
                          "better": "lower", "source": "program_span",
                          "layer": "start-up", "moves": "setup_s"}
    assert all(m["moves"] == "examples_per_s_chip"
               for m in manifest["per_layer"][:-8])
    for cell in manifest["workloads"]:     # no list: every cell reports them
        ctx = run_lib.context(cell["name"], 1, 1.0, 1)
        assert ctx["per_layer"][-8:] == CHAIN + KINDS


def test_all_eight_are_computed_in_a_rehearsal():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "CHIPBENCH_REHEARSAL": REHEARSAL}
    env.pop("BENCH_RUN", None)
    done = subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"), "--workload",
         "tiny_gpt2.b16.s64", "--seed", "2147484003", "--seconds", "4",
         "--trace", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(l) for l in done.stdout.splitlines()
            if l.startswith('{"row"')]
    assert set(CHAIN + KINDS) <= set(
        next(r for r in rows if r["row"] == "rehearsal")["computed"])
    (setup,) = [r for r in rows if r["row"] == "setup"]
    assert list(setup["chain_s"]) == list(setup_spans.CHAIN)
    assert all(v > 0 for v in setup["chain_s"].values())
    assert {"build_mesh", "build_model", "build_data",
            "init_state"} <= set(setup["init_s"])
    assert sum(setup["init_s"].values()) <= setup["chain_s"]["init"]
    # the program's own functions by name, under the spans that caused them
    longest = lambda where: {f for _, _, f in setup["under"][where]["longest"]}
    assert "init_fn" in longest("init_state")
    assert "train_step" in longest("compile")
    pipeline = setup["pipeline_s"]
    assert 0 < sum(pipeline.values()) < setup["chain_sum_s"]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is False and last["metrics"] == {}

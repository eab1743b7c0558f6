"""The program's own instrumentation (ISSUE 26): one process-wide span
recorder that is on whatever ``cfg.telemetry`` says, a name on every Pallas
kernel, and the region names of the compiled step."""

import ast
import os
import sys
import threading

import jax
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.core import train_loop
from pytorch_distributed_training_example_tpu.core.trainer import Trainer
from pytorch_distributed_training_example_tpu.data import loader as loader_lib
from pytorch_distributed_training_example_tpu.data import prefetch
from pytorch_distributed_training_example_tpu.utils import telemetry
from pytorch_distributed_training_example_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS = os.path.join(REPO, "pytorch_distributed_training_example_tpu", "ops")


@pytest.fixture
def ring():
    """The process's recorder with an empty ring."""
    rec = telemetry.recorder()
    rec.clear()
    rec.step = None
    return rec


def _tiny_lm(**kw):
    return Config(**{**dict(
        model="gpt2_tiny", dataset="lm", seq_len=32, epochs=1,
        global_batch_size=8, steps_per_epoch=4, log_every=2, workers=2,
        precision="fp32", warmup_epochs=0.0, telemetry=False,
        eval_every_epochs=100, checkpoint_every_epochs=100), **kw})


# -- the recorder -----------------------------------------------------------------


def test_ring_is_bounded_and_goodput_does_not_depend_on_it():
    rec = telemetry.SpanRecorder(capacity=8)
    for i in range(20):
        rec.step = i
        with rec.span("step"):
            pass
        rec.count("depth", i)
    records = rec.records()
    assert len(records) == 8
    assert records[-1].kind == "counter" and records[-1].value == 19
    assert records[-2].name == "step" and records[-2].step == 19
    assert rec.goodput()["counts"] == {"step": 20}   # running sums, no ring


def test_a_span_without_a_bucket_is_transparent_to_goodput():
    rec = telemetry.SpanRecorder()
    with rec.span("iteration", bucket=None) as iteration:
        with rec.span("input_wait") as wait:
            with rec.span("loader_wait", bucket=None):
                pass
        with rec.span("dispatch", bucket="step"):
            pass
        with rec.span("metrics_fetch", bucket="step"):
            with rec.span("init"):   # nested under a bucket: timeline only
                pass
    assert rec.goodput()["counts"] == {"input_wait": 1, "step": 2}
    assert iteration.seconds >= wait.seconds > 0.0
    by_name = {r.name: r for r in rec.records()}
    assert by_name["iteration"].parent is None
    assert by_name["input_wait"].parent == by_name["iteration"].id
    assert by_name["loader_wait"].parent == by_name["input_wait"].id
    assert by_name["init"].parent == by_name["metrics_fetch"].id


def test_four_loader_workers_append_to_one_ring(ring):
    """More workers than this test has cores to spare, a short switch
    interval: every batch leaves exactly one ``make_batch`` span, stamped
    with its batch index on its worker's thread, and one depth reading."""

    class Rows:
        def __len__(self):
            return 64 * 4

        def __getitem__(self, i):
            return {"x": np.full(8, i, np.int32)}

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ldr = loader_lib.DataLoader(Rows(), 4, num_workers=4)
        ldr.start_batch = 3
        batches = list(ldr)
    finally:
        sys.setswitchinterval(old)
    assert len(batches) == 61
    made = [r for r in ring.records() if r.name == "make_batch"]
    assert sorted(r.step for r in made) == list(range(3, 64))
    assert {r.thread for r in made}.isdisjoint({threading.current_thread().name})
    assert len({r.thread for r in made}) == 4
    assert all(r.parent is None and r.t1 >= r.t0 for r in made)
    depth = [r for r in ring.records() if r.name == "loader.ready_depth"]
    assert [r.step for r in depth] == list(range(3, 64))
    assert all(0 <= r.value <= 4 for r in depth)
    assert len({r.id for r in ring.records()}) == len(ring.records())


def test_inline_loader_and_prefetch_record_their_spans(ring, devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    class Rows:
        def __len__(self):
            return 24

        def __getitem__(self, i):
            return {"x": np.full(8, i, np.float32)}

    sharding = NamedSharding(Mesh(np.array(devices), ("data",)), P("data"))
    ring.step = 7
    # the process's totals outlive ``clear()``: whatever an earlier test of
    # this worker left in them, these spans add nothing
    counts = ring.goodput()["counts"]
    got = list(prefetch.device_prefetch(
        loader_lib.DataLoader(Rows(), 8, num_workers=0), sharding))
    assert len(got) == 3
    names = [r.name for r in ring.records()]
    assert names.count("make_batch") == 3       # inline, on this thread
    assert names.count("device_put") == 3
    assert names.count("loader_wait") == 4      # the last finds the end
    assert all(r.step == 7 for r in ring.records()
               if r.name in ("loader_wait", "device_put"))
    assert ring.goodput()["counts"] == counts   # detail, not goodput


def test_compile_events_name_the_function_and_the_step(ring):
    ring.step = 41

    @jax.jit
    def only_compiled_in_test_spans(x):
        return x * 3 + 1

    only_compiled_in_test_spans(np.arange(5.0))
    # the backend's record: compiled, or served by the persistent cache
    # where an earlier session left the program there
    events = [r for r in ring.records() if r.kind == "compile"
              and r.name in ("compile", "cache_load")]
    mine = [r for r in events if "only_compiled_in_test_spans" in (r.value or "")]
    assert len(mine) == 1 and mine[0].step == 41 and mine[0].t1 >= mine[0].t0
    assert ring.tail(4, kind="compile")[-1]["step"] == 41


def test_process_span_starts_before_the_origin_and_survives_adopt():
    """The process's recorder begins with the ``process`` span: from the
    operating system's start of this process to the recorder's creation."""
    t0 = telemetry.process_start_ns()
    assert t0 is not None and t0 < telemetry.recorder()._start_ns
    # the same start, read twice: the two clocks are read a few us apart
    assert abs(telemetry.recorder()._process.t0 - t0) < 1_000_000
    rec = telemetry.SpanRecorder(process_t0_ns=t0)
    created = rec._start_ns
    with rec.span("step"):
        pass
    rec.adopt("run")                   # what ``Telemetry`` does: a new origin
    (process,) = rec.records()         # the ring emptied, ``process`` kept
    assert (process.kind, process.name, process.parent) == (
        "span", "process", None)
    assert process.t0 == t0 < process.t1 <= created < rec._start_ns
    assert rec.goodput()["counts"] == {}             # no goodput bucket
    event = rec.trace_events()["traceEvents"][0]
    assert event["name"] == "process" and event["ts"] < 0   # as ``restart``
    rec.mark_first_step("cold")
    g = rec.goodput()
    assert g["process_to_first_step_s"] > g["time_to_first_step_s"] >= 0
    # a system that gives no start time: no record, and no second key
    bare = telemetry.SpanRecorder()
    bare.mark_first_step("cold")
    assert bare.records() == []
    assert "process_to_first_step_s" not in bare.goodput()
    assert "time_to_first_step_s" in bare.goodput()


def _compile_records(ring, fun):
    return [r for r in ring.records() if r.kind == "compile"
            and fun in (r.value or "")]


def test_one_trace_record_for_the_outermost_function_under_the_open_span(ring):
    """A jitted function that calls jitted functions: jax reports a trace for
    each, the inner ones inside the outer's interval. The ring has the
    outermost only, then its ``lower`` and its backend record, each with the
    open span as parent."""
    heard = []
    listener = lambda event, seconds, **kw: heard.append(
        (event, kw.get("fun_name")))
    jax.monitoring.register_event_duration_secs_listener(listener)

    @jax.jit
    def inner_in_test_spans(x):
        return x * 2 + 1

    @jax.jit
    def outer_in_test_spans(x):
        return inner_in_test_spans(x) + inner_in_test_spans(x + 1)

    try:
        with ring.span("init_state", bucket=None) as span:
            outer_in_test_spans(np.arange(7.0))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    traced = [name for event, name in heard if event.endswith("trace_duration")]
    assert "inner_in_test_spans" in traced and "outer_in_test_spans" in traced
    assert _compile_records(ring, "inner_in_test_spans") == []
    mine = _compile_records(ring, "outer_in_test_spans")
    assert [r.name for r in mine][:2] == ["trace", "lower"]
    assert mine[-1].name in ("compile", "cache_load")
    assert sum(r.name in ("compile", "cache_load") for r in mine) == 1
    assert all(r.parent == span.id for r in mine)
    assert mine[0].value == "outer_in_test_spans"
    # the stages follow each other: no instant is in two records
    stages = [r for r in mine if r.name != "cache_retrieval" and r.t1 > r.t0]
    assert all(a.t1 <= b.t0 + 1_000_000 for a, b in zip(stages, stages[1:]))
    traces = sorted((r for r in ring.records() if r.name == "trace"),
                    key=lambda r: r.t0)
    assert all(a.t1 <= b.t0 for a, b in zip(traces, traces[1:]))


def test_backend_record_says_compiled_or_served_by_the_cache(ring, tmp_path):
    """First call: XLA compiles (``compile``, and a ``cache_miss`` where the
    cache stored it). After the in-memory caches are dropped the persistent
    cache serves the same program: ``cache_load`` with the ``cache_retrieval``
    inside it, both with the function's name."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()

    def cached_in_test_spans(x):
        return (x * 5).sum() - 2

    try:
        jax.jit(cached_in_test_spans)(np.arange(9.0))
        first = [r.name for r in ring.records() if r.kind == "compile"
                 and (r.name == "cache_miss"
                      or "cached_in_test_spans" in (r.value or ""))]
        ring.clear()
        jax.clear_caches()
        jax.jit(cached_in_test_spans)(np.arange(9.0))
        second = _compile_records(ring, "cached_in_test_spans")
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert first == ["trace", "lower", "cache_miss", "compile"]
    assert [r.name for r in second] == ["trace", "lower", "cache_retrieval",
                                        "cache_load"]
    retrieval, load = second[2:]
    assert load.t0 <= retrieval.t0 and retrieval.t1 <= load.t1
    assert retrieval.value == load.value == "jit(cached_in_test_spans)"


def test_the_watchdog_dump_shows_backend_records_not_traces(ring):
    @jax.jit
    def dumped_in_test_spans(x):
        return x - 4

    dumped_in_test_spans(np.arange(3.0))
    names = [r.name for r in ring.records() if r.kind == "compile"]
    assert "trace" in names and "lower" in names
    shown = ring.tail(8, kind="compile", names=telemetry.BACKEND_RECORDS)
    assert shown and {s["name"] for s in shown} <= set(
        telemetry.BACKEND_RECORDS)
    assert "dumped_in_test_spans" in shown[-1]["value"]


def test_trace_events_keep_their_keys_with_counters_and_threads():
    rec = telemetry.SpanRecorder(run_id="r")
    with rec.span("step"):
        rec.count("loader.ready_depth", 3, step=5)
    events = rec.trace_events()["traceEvents"]
    assert [e["ph"] for e in events] == ["C", "X"]
    for e in events:
        assert {"name", "ph", "cat", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
    assert events[0]["args"] == {"step": 5, "id": events[0]["args"]["id"],
                                 "value": 3}


# -- the trainer, telemetry off ------------------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One epoch of the tiny LM with ``telemetry=False``, run from an empty
    directory: ``(trainer, records, files the run left there)``."""
    work = tmp_path_factory.mktemp("spans_cwd")
    rec = telemetry.recorder()
    rec.clear()
    old = os.getcwd()
    os.chdir(work)
    try:
        trainer = Trainer(_tiny_lm())
        trainer.train_epoch(0)
    finally:
        os.chdir(old)
    return trainer, rec.records(), sorted(os.listdir(work))


def test_telemetry_off_still_records_spans_and_writes_nothing(trained):
    trainer, records, files = trained
    assert trainer.telemetry is None
    assert trainer.recorder is telemetry.recorder()
    names = {r.name for r in records if r.kind == "span"}
    assert {"init", "iteration", "input_wait", "loader_wait", "device_put",
            "make_batch", "compile", "dispatch", "metrics_fetch"} <= names
    assert files == []


def test_init_is_split_at_its_layers_and_goodput_does_not_see_it(trained):
    _, records, _ = trained
    spans = {r.name: r for r in records if r.kind == "span"}
    init = spans["init"]
    children = [spans[n] for n in ("build_mesh", "build_model", "build_data",
                                   "init_state")]
    assert all(c.parent == init.id for c in children)
    assert all(a.t1 <= b.t0 for a, b in zip(children, children[1:]))
    assert init.t0 <= children[0].t0 and children[-1].t1 <= init.t1
    # the state's init is traced, lowered and compiled (or loaded) under
    # ``init_state``, by name
    under = [r for r in records if r.kind == "compile"
             and r.parent == spans["init_state"].id]
    assert {"trace", "lower"} <= {r.name for r in under
                                  if "init_fn" in (r.value or "")}
    # the first execution of the step is a child of ``compile``
    wait = spans["first_step_wait"]
    assert wait.parent == spans["compile"].id and wait.step == 0
    step = [r for r in records if r.kind == "compile"
            and r.parent == spans["compile"].id
            and "train_step" in (r.value or "")]
    assert [r.name for r in step if r.name != "cache_retrieval"][:2] == [
        "trace", "lower"]
    assert sum(r.name == "trace" for r in step) == 1
    assert all(r.t1 <= wait.t0 for r in step)
    assert {r.name for r in records if r.kind == "compile"} <= set(
        telemetry.COMPILE_RECORDS)
    # goodput counts the buckets it counted: one init, one compile
    counts = telemetry.recorder().goodput()["counts"]
    assert not {"build_mesh", "build_model", "build_data", "init_state",
                "first_step_wait", "process"} & set(counts)


def test_an_iteration_that_compiles_nothing_leaves_no_compile_record(trained):
    _, records, _ = trained
    iterations = [r for r in records if r.kind == "span"
                  and r.name == "iteration"]
    last = iterations[3]
    assert last.step == 3
    inside = [r for r in records if last.t0 <= r.t0 and r.t1 <= last.t1]
    assert inside and not [r for r in inside if r.kind == "compile"]


def test_spans_of_one_iteration_share_its_step_and_hang_together(trained):
    _, records, _ = trained
    spans = [r for r in records if r.kind == "span"]
    iterations = [r for r in spans if r.name == "iteration"]
    assert [r.step for r in iterations][:4] == [0, 1, 2, 3]
    loop_thread = iterations[0].thread
    for iteration in iterations[1:4]:        # 0 compiles, the rest dispatch
        inside = {r.name: r for r in spans if r.thread == loop_thread
                  and iteration.t0 <= r.t0 and r.t1 <= iteration.t1
                  and r is not iteration}
        assert iteration.parent is None
        assert {"input_wait", "loader_wait", "device_put",
                "dispatch"} <= set(inside)
        assert all(r.step == iteration.step for r in inside.values())
        assert inside["input_wait"].parent == iteration.id
        assert inside["dispatch"].parent == iteration.id
        assert inside["loader_wait"].parent == inside["input_wait"].id
        assert inside["device_put"].parent == inside["input_wait"].id
    fetches = [r for r in spans if r.name == "metrics_fetch"]
    assert [r.step for r in fetches] == [1, 3]   # log_every=2
    workers = {r.thread for r in spans if r.name == "make_batch"}
    assert workers and loop_thread not in workers


def test_telemetry_off_step_is_the_program_it_was(trained):
    """No health pack in the compiled step: the metrics are the three the LM
    step has always returned, and the state comes back in its own avals."""
    trainer, _, _ = trained
    batch = next(iter(trainer._make_step_iter(0, 0)))
    task = train_loop.get_task("lm")
    state, metrics = jax.eval_shape(
        train_loop.make_train_step(task, health=False), trainer.state, batch)
    assert set(metrics) == {"loss", "perplexity", "grad_norm"}
    assert all(v.shape == () and v.dtype == np.float32
               for v in metrics.values())
    avals = lambda tree: [(x.shape, x.dtype) for x in jax.tree.leaves(tree)]
    assert avals(state) == avals(trainer.state)
    with_pack = jax.eval_shape(
        train_loop.make_train_step(task, health=True), trainer.state, batch)[1]
    assert {"update_norm", "param_norm"} <= set(with_pack) - set(metrics)


def test_watchdog_context_has_the_last_spans_without_telemetry(trained):
    trainer, _, _ = trained
    context = trainer._context()
    assert "goodput" not in context              # the telemetry layer is off
    assert context["last_spans"] and len(context["last_spans"]) <= 16
    assert all({"name", "step", "ms", "thread"} <= set(s)
               for s in context["last_spans"])
    assert isinstance(context["last_compiles"], list)


def test_dispatch_and_fetch_are_goodputs_step_bucket(tmp_path):
    cfg = _tiny_lm(telemetry=True, checkpoint_dir=str(tmp_path), workers=0,
                   health_every=0)
    trainer = Trainer(cfg)
    trainer.train_epoch(0)
    assert trainer.telemetry.recorder is telemetry.recorder()
    g = trainer.telemetry.emit("test")
    # 1 compile, 3 dispatches, 2 fetches (log_every=2), 5 input waits (the
    # last finds the end of the epoch)
    assert g["counts"] == {"init": 1, "compile": 1, "step": 5,
                           "input_wait": 4}
    assert set(g["categories_s"]) == {"init", "compile", "step", "input_wait"}
    assert (tmp_path / "goodput.json").exists()
    assert (tmp_path / "trace_events.json").exists()


# -- names inside the compiled step -----------------------------------------------------


def _pallas_sites():
    sites = []
    for name in sorted(os.listdir(OPS)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(OPS, name)) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                sites.append(pytest.param(name, node, id=f"{name}:{node.lineno}"))
    return sites


def _site_names(call):
    """The names a ``pallas_call`` site gives its kernel: its literal, or, at
    the online flash kernels' three sites, the schedule's (``plan.name``: the
    kernel's own, or its window name under a schedule with a window), or, at
    the gate-and-norm stage's two, the order's (``stage + "_fwd"``)."""
    from pytorch_distributed_training_example_tpu.ops import (
        flash_attention, grouped_matmul)

    named = [k.value for k in call.keywords if k.arg == "name"]
    if len(named) != 1:
        return None
    if isinstance(named[0], ast.Constant):
        return [named[0].value]
    if isinstance(named[0], ast.Attribute) and named[0].attr == "name":
        return [*flash_attention.ONLINE_KERNELS, *flash_attention.WINDOW_KERNELS]
    if isinstance(named[0], ast.Name) and named[0].id == "name":
        return list(grouped_matmul.GATED_KERNELS)   # the gated FFN's launcher
    if (isinstance(named[0], ast.BinOp) and isinstance(named[0].op, ast.Add)
            and isinstance(named[0].left, ast.Name)
            and named[0].left.id == "stage"
            and isinstance(named[0].right, ast.Constant)):
        return [order + named[0].right.value
                for order in ("gate_norm", "norm_gate")]
    return None


@pytest.mark.parametrize("file,call", _pallas_sites())
def test_every_pallas_call_has_a_name(file, call):
    names = _site_names(call)
    assert names, f"{file}:{call.lineno} pl.pallas_call has no name="
    assert all(isinstance(n, str) and n.isidentifier() for n in names)
    assert len(names) == 1 or file in ("flash_attention.py",
                                       "grouped_matmul.py", "ssd.py")


def test_pallas_names_are_one_per_kernel():
    sites = [_site_names(p.values[1]) for p in _pallas_sites()]
    several = [s for s in sites if len(s) > 1]
    # the online forward, dq and dkv by their schedule; the gated FFN's six
    # kernels through one launcher; the gate and the group norm in two orders
    # through one launcher each way
    assert len(several) == 6 and several[0] == several[1] == several[2]
    names = [s[0] for s in sites if len(s) == 1] + sum(several[2:], [])
    assert len(names) == 30 and len(set(names)) == 30
    assert {n for n in names if n.startswith(("gated_ffn", "grouped_"))} == {
        "grouped_matmul", "grouped_matmul_dw", "gated_ffn_up",
        "gated_ffn_down", "gated_ffn_dh", "gated_ffn_dx", "gated_ffn_dw_up",
        "gated_ffn_dw_down"}
    assert {n for n in names if n.startswith("ssd_")} == {"ssd_fwd", "ssd_bwd"}
    assert {n for n in names if n.startswith("delta_rule")} == {
        "delta_rule_fwd", "delta_rule_bwd"}
    assert {n for n in names if n.startswith(
            ("conv_silu", "gate_norm", "norm_gate"))} == {
        "conv_silu_fwd", "conv_silu_bwd", "gate_norm_fwd", "gate_norm_bwd",
        "norm_gate_fwd", "norm_gate_bwd"}
    assert {n for n in names if n.startswith("flash_fwd")} == {
        "flash_fwd_online", "flash_fwd_oneshot", "flash_fwd_causal",
        "flash_fwd_window"}
    assert {n for n in names if n.startswith("flash_bwd")} == {
        "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_oneshot",
        "flash_bwd_stream", "flash_bwd_causal", "flash_bwd_window_dq",
        "flash_bwd_window_dkv"}


def test_region_vocabulary_is_in_the_compiled_tiny_gpt2_step(trained):
    """``lower().compile().as_text()`` keeps the scope path as ``op_name``.
    The persistent cache is off around the compile: programs that differ only
    in that metadata share a cache key, and an entry compiled before the
    names existed would be served without them."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    trainer, _, _ = trained
    batch = next(iter(trainer._make_step_iter(0, 0)))
    task = train_loop.get_task("lm")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        text = jax.jit(train_loop.make_train_step(task)).lower(
            trainer.state, batch).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    import re

    paths = re.findall(r'op_name="([^"]*)"', text)
    components = {part.rsplit("(", 1)[-1].split(")", 1)[0]
                  for p in paths for part in p.split("/")}
    assert {"embed", "attn", "mlp", "norm", "head_loss",
            "optimizer"} <= components
    assert any("block_0/norm/ln_1" in p for p in paths)
    assert any("block_3/mlp/mlp_up" in p for p in paths)

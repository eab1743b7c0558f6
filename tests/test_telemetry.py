"""Unified telemetry layer (utils/telemetry.py): on-device health pack,
span timeline / goodput accounting, anomaly guard — plus the logging and
watchdog satellites that ride with it."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.core import (
    mesh as mesh_lib, optim, train_loop)
from pytorch_distributed_training_example_tpu.data import prefetch
from pytorch_distributed_training_example_tpu.models import registry
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib
from pytorch_distributed_training_example_tpu.parallel import (
    sharding as sharding_lib)
from pytorch_distributed_training_example_tpu.utils import (
    logging as logging_lib, metrics as metrics_lib,
    telemetry as telemetry_lib, watchdog as watchdog_lib)
from pytorch_distributed_training_example_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lm_batch(n, seq, vocab=512, seed=0):
    r = np.random.RandomState(seed)
    toks = r.randint(0, vocab, (n, seq + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def _np_norm(tree) -> float:
    return float(np.sqrt(sum(
        float(np.sum(np.asarray(x, np.float64) ** 2))
        for x in jax.tree.leaves(tree))))


# ---------------------------------------------------------------------------
# Health pack (device side)
# ---------------------------------------------------------------------------


def test_health_pack_matches_reference_norms(devices):
    """grad/update/param norms from the compiled step equal host-side
    recomputation (optax.global_norm on jax.grad / numpy on fetched params)."""
    import optax

    mesh = mesh_lib.single_device_mesh()
    bundle = registry.create_model("llama_tiny", seq_len=16,
                                   dtype=jnp.float32, param_dtype=jnp.float32)
    tx, _ = optim.build_optimizer(Config(lr=0.01, warmup_epochs=0.0),
                                  steps_per_epoch=10)
    rules = sharding_lib.strategy_rules("dp", bundle.rules)
    state = train_loop.create_train_state(
        bundle.module, tx, bundle.input_template, mesh, rules, seed=0)
    step = jax.jit(train_loop.make_train_step(
        train_loop.get_task("lm"), health=True))  # no donation: state reused
    batch = _lm_batch(4, 16)

    old_params = jax.device_get(state.params)
    with mesh_lib.use_mesh(mesh):
        b = prefetch.shard_batch(batch, mesh_lib.batch_sharding(mesh))
        new_state, metrics = step(state, b)
    m = {k: float(v) for k, v in jax.device_get(metrics).items()}
    new_params = jax.device_get(new_state.params)

    # Reference gradient: same forward the step traces (llama_tiny has no
    # aux losses and dropout 0.0, so the loss is plain cross-entropy).
    step_rng = jax.random.fold_in(state.rng, state.step)

    def loss_fn(params):
        logits, _ = state.apply_fn(
            {"params": params}, jnp.asarray(batch["tokens"]), train=True,
            rngs={"dropout": step_rng}, mutable=["losses"])
        return metrics_lib.cross_entropy(logits, jnp.asarray(batch["targets"]))

    grads = jax.grad(loss_fn)(state.params)
    ref_grad_norm = float(optax.global_norm(grads))

    assert np.isclose(m["grad_norm"], ref_grad_norm, rtol=1e-4)
    update = jax.tree.map(lambda n, o: np.asarray(n) - np.asarray(o),
                          new_params, old_params)
    assert np.isclose(m["update_norm"], _np_norm(update), rtol=1e-4)
    assert np.isclose(m["param_norm"], _np_norm(new_params), rtol=1e-4)
    assert m["loss_finite"] == 1.0
    assert m["grads_finite_all"] == 1.0


EXPERT_SOWS = ("moe_held_rows", "moe_held_peak", "moe_whole",
               "moe_source_parts")


def _grad_accum_step(name, shapes_only=False):
    """``(module, state, step)``: the train step of model ``name`` over two
    microbatches with the health pack on, on one device; the state as shapes
    alone (nothing initialised) where ``shapes_only``."""
    bundle = registry.create_model(name, seq_len=16, dtype=jnp.float32,
                                   param_dtype=jnp.float32)
    tx, _ = optim.build_optimizer(Config(lr=0.01, warmup_epochs=0.0),
                                  steps_per_epoch=10)
    make = lambda: train_loop.create_train_state(
        bundle.module, tx, bundle.input_template,
        mesh_lib.single_device_mesh(),
        sharding_lib.strategy_rules("fsdp", bundle.rules), seed=0)
    state = jax.eval_shape(make) if shapes_only else make()
    return bundle.module, state, train_loop.make_train_step(
        train_loop.get_task("lm"), grad_accum=2, health=True)


@pytest.mark.parametrize("name", ["afmoe_tiny", "smallthinker_tiny",
                                  "glm_moe_lite_tiny", "nemotron_h_tiny",
                                  "lfm2_moe_tiny", "qwen3_next_tiny",
                                  "xing4_tiny"])
def test_train_step_carries_the_expert_sows_through_grad_accum(devices, name):
    """The expert layers' four sows survive the grad-accum scan carry and land
    in the metrics dict beside the health pack, one float32 scalar a sow and
    an expert layer (traced on shapes: the carry's structure is what a model
    can break)."""
    module, state, step = _grad_accum_step(name, shapes_only=True)
    tokens = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    with mesh_lib.use_mesh(mesh_lib.single_device_mesh()):
        _, metrics = jax.eval_shape(
            step, state, {"tokens": tokens, "targets": tokens})
    for key in ("update_norm", "param_norm", "loss_finite",
                "grads_finite_all"):
        assert key in metrics, (key, sorted(metrics))
    layers = {k.split(".", 1)[1] for k in metrics if k.startswith("moe_")}
    assert layers, sorted(metrics)
    for layer in layers:
        for sow in EXPERT_SOWS:
            leaf = metrics[f"{sow}.{layer}"]
            assert (leaf.shape, leaf.dtype) == ((), jnp.float32), (sow, leaf)


def test_grad_accum_means_the_expert_sows_over_the_microbatches(devices):
    """Each of the four in the step's metrics is the mean over the two
    microbatches of what the model sows for that microbatch alone (the
    buffers of ``batch_stats`` chained from the first to the second, as the
    step chains them)."""
    module, state, step = _grad_accum_step("nemotron_h_tiny")
    batch = _lm_batch(4, 16, vocab=module.vocab_size)

    @jax.jit
    def sown(stats, tokens):
        _, new = state.apply_fn(
            {"params": state.params, "batch_stats": stats}, tokens,
            train=True, mutable=["telemetry", "losses", "batch_stats"])
        return (telemetry_lib.collect_sowed(new["telemetry"]),
                new["batch_stats"])

    mesh = mesh_lib.single_device_mesh()
    with mesh_lib.use_mesh(mesh):
        first, stats = sown(state.batch_stats, batch["tokens"][:2])
        second, _ = sown(stats, batch["tokens"][2:])
        _, metrics = jax.jit(step)(state, prefetch.shard_batch(
            batch, mesh_lib.batch_sharding(mesh)))
    m = {k: float(v) for k, v in jax.device_get(metrics).items()}
    keys = [k for k in first if k.split(".")[0] in EXPERT_SOWS]
    assert len(keys) == 2 * len(EXPERT_SOWS), sorted(first)   # "ME*EM"
    for key in keys:
        want = (float(first[key]) + float(second[key])) / 2
        assert np.isclose(m[key], want, rtol=1e-6), (key, m[key], want)
    assert all(m[k] > 0 for k in keys if k.startswith("moe_held_rows")), m


def _expert_layer_sows(router, routing, E=16, k=2, held=(2, 6), T=64, d=32):
    """One expert layer under ``router`` with tokens and router weights
    planted so that the routing is ``level`` (no expert favoured),
    ``collapsed`` (every token on the held experts) or ``absent`` (no token on
    them): the sown scalars, and the router's choices ``[T, k]`` recomputed in
    numpy from the same float32 tokens and router weights."""
    rng = np.random.RandomState(3)
    x = rng.randn(1, T, d).astype(np.float32)
    x[..., 0] = 1.0                         # the feature a planted row reads
    kernel = (0.3 * rng.randn(d, E)).astype(np.float32)
    n, first = held
    if routing == "collapsed":
        kernel[0, first:first + n] += 30.0
    elif routing == "absent":
        kernel[0, first:first + n] -= 30.0
    scores = x[0].astype(np.float64) @ kernel.astype(np.float64)
    if router == "sigmoid_bias":
        layer = moe_lib.SharedExpertMoE(num_experts=E, ffn_dim=16, top_k=k,
                                        held_experts=held)
        variables = layer.init(jax.random.PRNGKey(0), jnp.asarray(x),
                               train=False)
        params = {**variables["params"], "router": jnp.asarray(kernel)}
        _, new = layer.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            jnp.asarray(x), train=False, mutable=["telemetry"])
        scores = 1.0 / (1.0 + np.exp(-scores))      # the bias is zero
    else:
        top = moe_lib.TopKSoftmaxRouter(num_experts=E, top_k=k)
        route = top.apply({"params": {"kernel": jnp.asarray(kernel)}},
                          jnp.asarray(x))
        layer = moe_lib.HeldExperts(ffn_dim=16, held_experts=held)
        variables = layer.init(jax.random.PRNGKey(0), jnp.asarray(x), route)
        _, new = layer.apply(variables, jnp.asarray(x), route,
                             mutable=["telemetry"])
    order = np.argsort(-scores, axis=-1, kind="stable")
    ranked = np.take_along_axis(scores, order, axis=-1)
    assert np.min(ranked[:, k - 1] - ranked[:, k]) > 1e-6    # no near tie
    sows = {name: float(np.asarray(value[0]))
            for name, value in new["telemetry"].items()}
    return sows, order[:, :k]


@pytest.mark.parametrize("routing", ["level", "collapsed", "absent"])
@pytest.mark.parametrize("router", ["sigmoid_bias", "softmax_chosen"])
def test_expert_sows_match_a_numpy_count_of_the_choices(router, routing):
    """``moe_held_rows`` / ``moe_held_peak`` / ``moe_whole`` from the sow
    collection equal a from-scratch numpy count of the router's choices:
    the pairs that fell on each held expert, the fullest over their mean, and
    whether their tiles fit the bounded layout (a ``chunks``-th part of the
    tokens, every choice held here), under both routers."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    E, k, (n, first), T = 16, 2, (2, 6), 64
    sows, chosen = _expert_layer_sows(router, routing, E, k, (n, first), T)
    rows = np.array([(chosen == first + e).sum() for e in range(n)], float)
    bt = min(moe_lib.EXPERT_TILE_ROWS, gmm_lib._block_rows(T * k, n))
    chunks = E // (2 * n)
    cap = -(-(T // chunks) * k // bt) + n
    tiles = np.maximum(np.ceil(rows / bt), 1).sum()
    want = {"moe_held_rows": rows.sum(),
            "moe_held_peak": rows.max() / max(rows.mean(), 1.0),
            "moe_whole": float(tiles <= cap)}
    for name, value in want.items():
        assert np.isclose(sows[name], value, rtol=1e-6), (name, sows, want)
    assert want["moe_whole"] == (routing != "collapsed")
    assert (rows.sum() == 0) == (routing == "absent")
    assert routing != "collapsed" or rows.sum() == T * k


# ---------------------------------------------------------------------------
# Span recorder + goodput (host side)
# ---------------------------------------------------------------------------


def test_span_recorder_perfetto_and_goodput(tmp_path):
    rec = telemetry_lib.SpanRecorder(run_id="r1")
    with rec.span("init"):
        with rec.span("checkpoint_restore"):  # nested: timeline only
            time.sleep(0.01)
        time.sleep(0.01)
    for _ in range(3):
        with rec.span("step"):
            time.sleep(0.01)
    rec.write(str(tmp_path))

    trace = json.load(open(tmp_path / "trace_events.json"))
    events = trace["traceEvents"]
    assert {e["name"] for e in events} == {"init", "checkpoint_restore",
                                          "step"}
    for e in events:  # Perfetto complete-event shape
        assert e["ph"] == "X"
        assert isinstance(e["ts"], int) and e["ts"] >= 0
        assert isinstance(e["dur"], int) and e["dur"] > 0
        assert "pid" in e and "tid" in e

    g = json.load(open(tmp_path / "goodput.json"))
    # Only OUTERMOST spans accrue: the nested restore is on the timeline
    # but never double-counts wall time.
    assert g["counts"] == {"init": 1, "step": 3}
    assert 0.0 < g["goodput_fraction"] <= 1.0
    assert sum(g["fractions"].values()) <= 1.0 + 1e-9
    # goodput/badput/coverage are each rounded to 4 decimals independently,
    # so the identity only holds to that rounding.
    assert np.isclose(g["coverage"],
                      g["goodput_fraction"] + g["badput_fraction"], atol=2e-4)
    assert g["run_id"] == "r1"


# ---------------------------------------------------------------------------
# Anomaly guard
# ---------------------------------------------------------------------------


def test_anomaly_guard_abort_dumps_bundle(tmp_path):
    guard = telemetry_lib.AnomalyGuard(str(tmp_path), action="abort",
                                       config=Config(), run_id="rid")
    assert guard.check(0, {"loss": 1.0, "grad_norm": 2.0}) is False
    with pytest.raises(telemetry_lib.AnomalyError):
        guard.check(1, {"loss": float("nan"), "grad_norm": 1.0})
    bundles = sorted(tmp_path.glob("anomaly_step*.json"))
    assert len(bundles) == 1
    b = json.load(open(bundles[0]))
    assert b["trigger_keys"] == ["loss"]
    assert b["step"] == 1
    assert len(b["history"]) == 2  # last-K rows, including the trigger
    assert b["config"]["model"] == "resnet18"
    assert b["run_id"] == "rid"


def test_anomaly_guard_continue_and_scaler_skip(tmp_path):
    guard = telemetry_lib.AnomalyGuard(str(tmp_path), action="continue",
                                       allow_scaler_skips=True)
    # fp16 overflow-skip row: inf grad norm with grads_finite==0 is the
    # scaler's HANDLED branch, not an anomaly.
    assert guard.check(0, {"loss": 2.0, "grad_norm": float("inf"),
                           "grads_finite": 0.0}) is False
    assert not guard.tripped
    # A real non-finite loss trips, dumps, and continues (no raise).
    assert guard.check(1, {"loss": float("inf"), "grads_finite": 1.0}) is True
    assert guard.tripped
    assert (tmp_path / "anomaly_step00000001.json").exists()
    with pytest.raises(ValueError):
        telemetry_lib.AnomalyGuard(str(tmp_path), action="explode")


def test_anomaly_guard_flight_dump_once_per_episode(tmp_path):
    guard = telemetry_lib.AnomalyGuard(str(tmp_path), action="continue")
    dumps = []
    guard.flight_dump_fn = lambda reason, **kw: dumps.append(
        (reason, kw["step"]))
    # A NaN that sticks in the params flags every subsequent check — the
    # bundle is per-step, but the flight ring dumps once per episode.
    for s in (4, 5, 6):
        assert guard.check(s, {"loss": float("nan")}) is True
    assert dumps == [("anomaly", 4)]
    assert (tmp_path / "anomaly_step00000006.json").exists()
    # A clean row closes the episode; the next trip dumps again.
    assert guard.check(7, {"loss": 1.0}) is False
    assert guard.check(8, {"loss": float("inf")}) is True
    assert dumps == [("anomaly", 4), ("anomaly", 8)]


def test_telemetry_facade_observe_snapshot_emit(tmp_path):
    tele = telemetry_lib.Telemetry(str(tmp_path), run_id="rid",
                                   anomaly_action="continue")
    with tele.span("step"):
        time.sleep(0.005)
    assert tele.observe(3, {"loss": 1.5}) is False
    snap = tele.snapshot()
    assert snap["last_step"] == 3
    assert snap["last_health"]["loss"] == 1.5
    assert "goodput" in snap
    g = tele.emit("test")
    assert g["run_id"] == "rid"
    assert (tmp_path / "trace_events.json").exists()
    assert (tmp_path / "goodput.json").exists()


# ---------------------------------------------------------------------------
# Trainer end-to-end: health rows, timeline artifacts, injected-NaN bundle
# ---------------------------------------------------------------------------


def test_trainer_telemetry_end_to_end_with_nan_injection(tmp_path, devices):
    """A NaN learning rate makes the very first applied update non-finite,
    so the first health fetch must trip the guard (action=continue), dump a
    diagnostic bundle, and the run must still produce the full telemetry
    surface: health rows in metrics.jsonl, trace_events.json, goodput.json."""
    from pytorch_distributed_training_example_tpu.core.trainer import Trainer

    ckdir = tmp_path / "ck"
    cfg = Config(model="llama_tiny", dataset="lm", seq_len=16, epochs=1,
                 global_batch_size=8, lr=float("nan"), warmup_epochs=0.0,
                 optimizer="sgd", precision="fp32", workers=0,
                 steps_per_epoch=3, log_every=1, telemetry=True,
                 health_every=1, anomaly_action="continue",
                 checkpoint_dir=str(ckdir), checkpoint_every_epochs=100,
                 eval_every_epochs=100)
    Trainer(cfg).train()

    rows = [json.loads(line) for line in open(ckdir / "metrics.jsonl")]
    train_rows = [r for r in rows if r.get("kind") == "train"]
    assert train_rows and all("update_norm" in r for r in train_rows)
    assert any(r.get("kind") == "goodput" for r in rows)
    assert all("run_id" in r for r in rows)

    bundles = sorted(ckdir.glob("anomaly_step*.json"))
    assert bundles, "injected NaN never produced a diagnostic bundle"
    b = json.load(open(bundles[0]))
    assert any(k in b["trigger_keys"] for k in ("update_norm", "param_norm",
                                                "loss", "grad_norm"))
    assert b["config"]["anomaly_action"] == "continue"

    trace = json.load(open(ckdir / "trace_events.json"))
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"init", "compile", "input_wait"} <= names
    good = json.load(open(ckdir / "goodput.json"))
    assert sum(good["fractions"].values()) <= 1.0 + 1e-9
    assert good["counts"].get("compile") == 1


# ---------------------------------------------------------------------------
# Satellites: watchdog context, logger run_id, AverageMeter fmt, health scan
# ---------------------------------------------------------------------------


def test_watchdog_calls_context_fn_on_timeout():
    calls = []

    def ctx():
        calls.append(1)
        return {"last_step": 7}

    wd = watchdog_lib.Watchdog(timeout_s=0.2, context_fn=ctx).start()
    try:
        deadline = time.monotonic() + 10.0
        while not calls and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        wd.stop()
    assert calls, "watchdog never fired its context hook"


def test_metric_logger_stamps_run_id(tmp_path):
    path = tmp_path / "m.jsonl"
    ml = logging_lib.MetricLogger(str(path))
    ml.write(kind="train", step=0, loss=1.0)
    ml.write(kind="health", step=1, loss=0.9)
    ml.close()
    rows = [json.loads(line) for line in open(path)]
    assert len(rows) == 2
    assert all(r["run_id"] == ml.run_id for r in rows)
    assert len(ml.run_id) == 12


def test_average_meter_fmt_with_and_without_colon():
    m1 = logging_lib.AverageMeter("loss", ":.2f")
    m2 = logging_lib.AverageMeter("loss", ".2f")
    m1.update(1.234)
    m2.update(1.234)
    assert str(m1) == str(m2) == "loss 1.23 (1.23)"


def test_check_regression_flags_nonfinite_health(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import check_regression as cr

    p = tmp_path / "metrics.jsonl"
    rows = [{"kind": "train", "step": 0, "loss": 1.0, "update_norm": 0.5}]
    p.write_text("\n".join(json.dumps(r, default=float) for r in rows) + "\n")
    failures, _ = cr.check_health(str(p))
    assert not failures

    rows.append({"kind": "health", "step": 1, "loss": 2.0,
                 "update_norm": float("nan")})
    p.write_text("\n".join(json.dumps(r, default=float) for r in rows) + "\n")
    failures, report = cr.check_health(str(p))
    assert failures and "update_norm" in failures[0]
    assert any(line.startswith("NON-FINITE") for line in report)


def test_span_recorder_ttfs_and_restart_breakdown(tmp_path):
    rec = telemetry_lib.SpanRecorder(run_id="r1")
    with rec.span("compile"):
        time.sleep(0.01)
    with rec.span("step"):
        time.sleep(0.005)
    rec.mark_first_step("cold")
    rec.mark_first_step("warm")  # later calls are no-ops: TTFS is ONE number
    g1 = rec.goodput()
    assert g1["ttfs_mode"] == "cold"
    assert g1["time_to_first_step_s"] >= 0.01
    assert g1["ttfs_history"] == [{"attempt": 1, "mode": "cold",
                                   "ttfs_s": g1["time_to_first_step_s"]}]
    assert "restart_breakdown" not in g1  # no restart gap yet

    # Attempt 2 carries attempt 1's goodput: history accumulates and the
    # restart gap is decomposed into the three costs r21 exists to shrink.
    time.sleep(0.02)  # a measurable supervisor gap past ended_at's rounding
    rec2 = telemetry_lib.SpanRecorder(run_id="r1", carry=g1)
    with rec2.span("checkpoint_restore"):
        time.sleep(0.005)
    with rec2.span("step"):
        pass
    rec2.mark_first_step("warm")
    g2 = rec2.goodput()
    assert g2["attempts"] == 2
    assert [h["mode"] for h in g2["ttfs_history"]] == ["cold", "warm"]
    assert g2["ttfs_history"][1]["attempt"] == 2
    bd = g2["restart_breakdown"]
    assert bd["gap_s"] > 0.0  # the supervisor gap between the attempts
    assert bd["restore_s"] >= 0.005
    assert set(bd) == {"gap_s", "compile_s", "restore_s"}

"""What PR 35 added to the benchmark, off the chip: the SmallThinker
configuration's plain reference through the whole harness at toy size (the
rehearsal twin ``tiny_smallthinker``), its control, a reference that moves the
rotary positions, the window or the held experts, a hand-checked case of the
reference's own routing and AdamW step, the six readers on a hand-made trace,
and the new entries of the manifest. No number here comes from a device."""

import copy
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, run as run_lib, weights, xplane  # noqa: E402

REHEARSAL = os.path.join(ROOT, "tests", "chipbench", "rehearsal")
BENCH = os.path.join(ROOT, "chipbench")
CELL = "tiny_smallthinker.b8.s48"
NEW_CELL = "smallthinker_21b.b1.s8192.v37984"
NEW_METRICS = ["expert_block_ms", "expert_route_ms", "expert_matmul_ms",
               "expert_matmul_roofline", "attn_kernels_ms",
               "attn_kernels_roofline"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = xplane.Event
MS = 1_000_000


def _reader(name):
    return run_lib.load_module([BENCH], "layer_metrics", name)


def _config():
    with open(os.path.join(BENCH, "configs", "smallthinker_21b.json")) as fh:
        return json.load(fh)


# -- the twin through the harness ------------------------------------------------


@pytest.mark.slow  # a second process on eight CPU devices beside the suite's
# own (Trinity's took 145 s there): the tier-1 run keeps the in-process twin
# below (same harness, same reference), and test_granite_cells.py the command
def test_twin_runs_through_the_command():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "CHIPBENCH_REHEARSAL": os.path.join("tests", "chipbench",
                                               "rehearsal")}
    env.pop("BENCH_RUN", None)
    done = subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"), "--workload",
         CELL, "--seed", "2147489999", "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [l for l in done.stdout.splitlines() if l.startswith("{")]
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["metrics"] == {}  # no chip
    assert last["attempted"] > 0 and last["failed"] == 0
    rows = [json.loads(l) for l in lines[:-1] if l.startswith('{"row"')]
    assert next(r for r in rows if r["row"] == "rehearsal")["compared_ok"]
    window = next(r for r in rows if r["row"] == "window")
    assert window["compiles_in_window"] == 0 and window["tokens_per_s"] > 0
    held = next(r for r in rows if r["row"] == "reference_held_rows")
    assert len(held["by_step_and_layer"]) == 3


@pytest.fixture(scope="module")
def sound():
    ctx = run_lib.context(CELL, 2147484123, 2.0, 0, REHEARSAL)
    driver = run_lib.load_module(ctx["search"], "drivers",
                                 ctx["traffic"]["driver"])
    result, extra = driver.measure(ctx, None)
    return ctx, result, extra


def _reference_again(sound, change=None, precision="highest"):
    """The numbers compared when the reference follows the same three steps
    with ``change`` applied to its model."""
    import jax

    ctx, _, extra = sound
    config = copy.deepcopy(ctx["config"])
    config["model"].update(change or {})
    reference = run_lib.load_module(ctx["search"], "references",
                                    config["reference"])
    params = jax.jit(lambda k: weights.make_flat(
        extra["shapes"], config["init"], k))(extra["key"])
    other = reference.run(config, params, extra["batches"],
                          precision=precision)
    return compare.judge(compare.readings(extra["program"], other),
                         ctx["config"]["limits"])


def test_twin_agrees_with_the_plain_reference(sound):
    _, result, _ = sound
    assert result["correct"], result["compared"]
    assert {r["number"] for r in result["compared"]} == set(compare.NUMBERS)
    ok, rows = _reference_again(sound)
    assert ok, rows


def test_twin_control_fails_the_limits(sound):
    ok, rows = _reference_again(
        sound, precision=sound[0]["config"]["control_precision"])
    assert not ok, rows
    assert sum(not r["ok"] for r in rows) >= 1


@pytest.mark.parametrize("left_out,change", [
    ("rotary positions", {"rope_layout": [0, 0, 0, 0]}),
    ("the window", {"sliding_window_size": 10 ** 6}),
    ("the position-free full layer", {"rope_layout": [1, 1, 1, 1],
                                      "sliding_window_layout": [1, 1, 1, 1]}),
    ("the held experts (another chip's)", {"held_experts_start": 2})])
def test_twin_fails_on_a_step_that_leaves_a_piece_out(sound, left_out, change):
    """The program against a reference without the piece is a program without
    it against the reference: the limits part them."""
    ok, rows = _reference_again(sound, change)
    assert not ok, (left_out, rows)


# -- the reference by hand ---------------------------------------------------------


def test_reference_routes_and_gates_a_hand_checked_case():
    """Two tokens, four experts of which the first two are held, two a token:
    token 0's logits (3, 1, 2, 0) choose experts 0 and 2 with weights
    softmax(3, 2) = (0.7311, 0.2689); token 1's (0, 1, 2, 3) choose 3 and 2,
    neither held: zero. Expert 0 on y = (1, -1): gate (1, -2) -> relu (1, 0),
    up (2, 3), product (2, 0), down -> (2, 4)."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.references import smallthinker_21b as reference

    r = jnp.array([[[1.0, 0.0], [0.0, 1.0]]])
    y = jnp.array([[[1.0, -1.0], [5.0, 7.0]]])
    w = {"moe_router/kernel": jnp.array([[3.0, 1.0, 2.0, 0.0],
                                         [0.0, 1.0, 2.0, 3.0]]),
         "moe/w_gate": jnp.array([[[1.0, -1.0], [0.0, 1.0]]] * 2),
         "moe/w_up": jnp.array([[[2.0, 1.0], [0.0, -2.0]]] * 2),
         "moe/w_down": jnp.array([[[1.0, 2.0], [5.0, 5.0]]] * 2)}
    z = {"k": 2, "routed": 4, "first": 0, "held": 2}
    m, counts = reference.experts(r, y, w, z, lambda a: a)
    share = np.exp(3.0) / (np.exp(3.0) + np.exp(2.0))
    np.testing.assert_allclose(m[0, 0], share * np.array([2.0, 4.0]),
                               rtol=1e-6)
    np.testing.assert_array_equal(m[0, 1], 0.0)
    np.testing.assert_array_equal(counts, [1, 0, 2, 1])
    chosen, weight = reference.route(r, w["moe_router/kernel"], 2)
    np.testing.assert_array_equal(chosen[0], [[0, 2], [3, 2]])
    np.testing.assert_allclose(weight[0, 0], [share, 1 - share], rtol=1e-6)
    # masks: the window's pairs and the causal half, counted by hand
    assert reference.window_pairs(8, 3) == 1 + 2 + 3 * 6
    assert reference.causal_pairs(8) == 36
    assert reference.window_pairs(8, 100) == reference.causal_pairs(8)


def test_reference_step_is_adamw_by_hand(sound):
    """One step of the reference's own AdamW from the twin's weights: after a
    first Adam step every entry with a gradient moves by the learning rate
    (the update is g / (|g| + eps): the gradient's sign), a matrix by the
    decay of 0.1 of itself besides, so a norm scale's change has the norm lr x
    sqrt(size) and a matrix's is within lr x 0.1 x |p| of that; and the first
    moment times ``first_moment_scale`` is the clipped gradient, whose norms
    over all leaves make at most the clip."""
    import jax
    import math

    ctx, _, extra = sound
    config = ctx["config"]
    reference = run_lib.load_module(ctx["search"], "references",
                                    config["reference"])
    params = jax.jit(lambda k: weights.make_flat(
        extra["shapes"], config["init"], k))(extra["key"])
    sizes = {k: v.size for k, v in params.items()}
    norms = {k: float(jax.numpy.linalg.norm(v.reshape(-1)))
             for k, v in params.items()}
    out = reference.run(config, params, extra["batches"][:1])
    lr, wd = config["optimizer"]["lr"], config["optimizer"]["weight_decay"]
    assert out["dparam_norms"]["final_norm/scale"] == pytest.approx(
        lr * math.sqrt(sizes["final_norm/scale"]), rel=2e-3)
    for path in ("block_2/attn_norm/scale", "block_0/ffn_norm/scale"):
        # gradients of 1e-7, where eps = 1e-8 takes a little off the step
        assert 0.9 < out["dparam_norms"][path] / (
            lr * math.sqrt(sizes[path])) <= 1 + 1e-5, path
    for path in ("lm_head/kernel", "block_3/moe/w_down"):
        # (attention's matrices lie behind an out projection of 0.002: their
        # gradients are of eps's size, and their steps shorter)
        sign_step = lr * math.sqrt(sizes[path])
        assert 0.9 * sign_step - lr * wd * norms[path] \
            <= out["dparam_norms"][path] \
            <= sign_step * (1 + 1e-5) + lr * wd * norms[path], path
    total = math.sqrt(sum(v * v for v in out["moment_norms"].values()))
    assert total <= config["optimizer"]["grad_clip"] * (1 + 1e-5)
    assert len(out["loss"]) == 1 and out["loss"][0] == pytest.approx(
        math.log(96), abs=0.6)    # near uniform over the 96 ids


# -- the configuration's file and the manifest's new entries -----------------------


def test_configuration_keeps_every_published_key():
    config = _config()
    model = config["model"]
    own = {"routed_experts", "held_experts_start", "held_layers"}
    assert {k: config[k] for k in model if k not in own} == {
        k: v for k, v in model.items() if k not in own}
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    assert [model[k] for k in config["reduced"]] == [4, 16, 37984]
    assert [config["published"][k] for k in config["reduced"]] == [
        52, 64, 151936]
    assert model["routed_experts"] == 64 and model["held_layers"] == [
        0, 1, 2, 3] and model["held_experts_start"] == 0
    for layout in ("sliding_window_layout", "rope_layout"):
        assert model[layout] == [0, 1, 1, 1] * 13
    assert "656,529,920" in config["deployment"]
    assert "experts 0..15" in config["deployment"]
    assert "rows 0..37,983" in config["deployment"]
    assert "layers 0..3" in config["deployment"]
    assert set(config["limits"]) == set(compare.NUMBERS)
    for key in ("layer", "attention", "expert_ffn", "optimizer", "init",
                "lr_schedule", "data", "provenance"):
        assert config["assumed"][key]
    assert config["control_precision"] == "fp8"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["source_url"] == config["source"])
    changed = {k for k, v in row["config"].items() if model.get(k) != v}
    assert changed == set(config["reduced"])
    # no width differs from the source
    for key in ("hidden_size", "head_dim", "moe_ffn_hidden_size",
                "moe_num_active_primary_experts", "sliding_window_size",
                "num_attention_heads", "num_key_value_heads", "rope_theta"):
        assert model[key] == row["config"][key], key


def test_held_parameters_are_the_modules_own_leaves():
    """656,529,920: the configuration's count, the family's ``num_params``
    and the leaves of the module that the preset builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_example_tpu.core import (
        trainer as trainer_lib)
    from pytorch_distributed_training_example_tpu.utils.config import (
        from_preset)

    config = _config()
    bundle = trainer_lib.build_model(from_preset(
        config["preset"], **config["overrides"]))
    shapes = jax.eval_shape(lambda: bundle.module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    held = sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(shapes["params"]))
    assert held == 656_529_920 == 4 * 115_512_320 + 194_478_080 + 2_560
    assert "batch_stats" not in shapes
    # the benchmark's own count of the step's FLOPs is the program's
    from chipbench.references import smallthinker_21b as reference
    assert bundle.fwd_flops_per_example == pytest.approx(
        reference.forward_flops(config["model"], {"seq_len": 8192}),
        rel=1e-12)


@pytest.mark.parametrize("path,kind,value", [
    ("block_0/attn_norm/scale", "const", 1.0),
    ("block_2/ffn_norm/scale", "const", 1.0),
    ("final_norm/scale", "const", 1.0),
    ("embed/embedding", "normal", 1.0),
    ("block_3/attn/out/kernel", "normal", 0.002),
    ("block_3/attn/query/kernel", "normal", 0.02),
    ("block_1/moe_router/kernel", "normal", 0.02),
    ("block_1/moe/w_gate", "normal", 0.02),
    ("block_1/moe/w_down", "normal", 0.02),
    ("lm_head/kernel", "normal", 0.02)])
def test_init_rules_reach_the_leaves_they_name(path, kind, value):
    rule = next(r for r in _config()["init"] if re.search(r[0], path))
    assert rule[1:] == [kind, value]


def _mid_size_shapes(model):
    """The reference's flat layout for ``model``, with no program behind it."""
    import jax
    import jax.numpy as jnp

    d, hd, f = (model["hidden_size"], model["head_dim"],
                model["moe_ffn_hidden_size"])
    H, kv = model["num_attention_heads"], model["num_key_value_heads"]
    out = {"embed/embedding": (model["vocab_size"], d),
           "final_norm/scale": (d,), "lm_head/kernel": (d, model["vocab_size"])}
    for i in range(len(model["held_layers"])):
        p = f"block_{i}/"
        out[p + "attn_norm/scale"] = out[p + "ffn_norm/scale"] = (d,)
        for n, heads in (("query", H), ("key", kv), ("value", kv)):
            out[p + f"attn/{n}/kernel"] = (d, heads, hd)
        out[p + "attn/out/kernel"] = (H, hd, d)
        out[p + "moe_router/kernel"] = (d, model["routed_experts"])
        for n, shape in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d))):
            out[p + f"moe/w_{n}"] = (model["moe_num_primary_experts"], *shape)
    return {k: jax.ShapeDtypeStruct(v, jnp.float32) for k, v in out.items()}


@pytest.mark.parametrize("seed", [5, 2147483999])
def test_init_gives_every_seed_the_same_routed_work(seed):
    """Why the embedding starts at 1 and the attention's out projection at a
    tenth of the other kernels: on uniform random tokens the router's 64
    loads at the start are level whatever the seed, so the held experts see
    the rows the expectation says; with every kernel and the embedding at
    0.02 the vector that attention hands all tokens alike grows from layer to
    layer (nothing norms a branch here), the later layers' loads spread, and
    the held rows follow the seed. The configuration's layers, routing and
    init at a width a CPU takes (hidden 512, 1,024 tokens, window 256),
    through the plain reference."""
    import jax
    import numpy as np

    from chipbench.references import smallthinker_21b as reference

    config = _config()
    model = dict(config["model"], hidden_size=512, head_dim=64,
                 num_attention_heads=8, num_key_value_heads=2,
                 moe_ffn_hidden_size=128, vocab_size=2048,
                 sliding_window_size=256)
    shapes, key = _mid_size_shapes(model), weights.seed_key(seed)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (1, 1024), 0,
                                model["vocab_size"])

    def loads(rules):
        params = jax.jit(lambda k: weights.make_flat(shapes, rules, k))(key)
        _, counts = jax.jit(lambda p, t: reference.hidden_fn(
            p, t, model))(params, tokens)
        return np.asarray(counts)

    level = loads(config["init"])
    plain = loads([["scale$", "const", 1.0], [".*", "normal", 0.02]])
    spread = lambda c: c.std(-1) / c.mean(-1)
    # 96 rows an expert: sampling alone spreads them by 0.10
    assert spread(level).max() < 0.2 < 0.3 < spread(plain)[-1], (
        spread(level), spread(plain))
    held = level[:, :model["moe_num_primary_experts"]].sum(-1)
    expected = 1024 * model["moe_num_active_primary_experts"] * model[
        "moe_num_primary_experts"] / model["routed_experts"]
    assert np.all(np.abs(held / expected - 1) < 0.08), held


def test_manifest_gained_one_configuration_one_cell_and_six_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    assert manifest["configs"][-1]["name"] == "smallthinker_21b"
    assert manifest["configs"][-1]["reduced"] == _config()["reduced"]
    assert manifest["configs"][-1]["source"] == _config()["source"]
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        NEW_CELL, "smallthinker_21b", "b1.s8192.v37984", 1)
    assert len(cell["why"]) <= 200
    new = {m["name"]: m for m in manifest["per_layer"][-6:]}
    assert list(new) == NEW_METRICS
    # what was there is where it was, before the new entries
    assert [m["name"] for m in manifest["per_layer"][-12:-6]] == [
        "moe_ms", "moe_route_ms", "moe_experts_ms", "gmm_roofline",
        "window_attn_ms", "window_attn_roofline"]
    assert [w["name"] for w in manifest["workloads"][:-1]] == [
        "gpt2_124m.b24.s1024", "granite4_h_micro.b1.s4096",
        "trinity_mini.b1.s8192"]
    for metric in new.values():
        assert metric["workloads"] == [NEW_CELL]
        assert metric["moves"] == "examples_per_s_chip"
        assert metric["source"] == "device_trace"
    assert {new[n]["unit"] for n in ("expert_matmul_roofline",
                                     "attn_kernels_roofline")} == {"%"}
    assert {new[n]["layer"] for n in NEW_METRICS[:2]} == {"model step"}
    assert {new[n]["layer"] for n in NEW_METRICS[2:]} == {"kernels"}
    with open(os.path.join(BENCH, "traffic", "b1.s8192.v37984.json")) as fh:
        traffic = json.load(fh)
    assert traffic["overrides"] == {"global_batch_size": 1, "seq_len": 8192}
    assert traffic["data"] == {"kind": "tokens", "seq_len": 8192,
                               "vocab_size": 37984}
    assert (traffic["driver"], traffic["warmup_steps"],
            traffic["trace_seconds"]) == ("train_window", 5, 3.0)
    # the cells that were there report what they reported
    ctx = run_lib.context("trinity_mini.b1.s8192", 1, 1.0, 1)
    assert not set(new) & set(ctx["per_layer"])
    ours = run_lib.context(NEW_CELL, 1, 1.0, 1)["per_layer"]
    assert set(new) <= set(ours)
    # and the metrics without a list report here by themselves
    assert {"step_mfu_pct", "optimizer_ms", "region_coverage_pct",
            "step_mem_gb", "device_idle_pct", "device_step_ms"} <= set(ours)
    assert not {"moe_ms", "gmm_roofline", "window_attn_ms"} & set(ours)


# -- the readers, on a hand-made trace ----------------------------------------------

# Two whole steps of 12 ms on device 0 between a first and a last that the
# trace cut short. A step: the router ahead of attention 0.5 (under
# ``moe_router`` and under neither ``mlp`` nor ``moe``), a window kernel 1.0,
# then under ``mlp/moe`` the sort and gathers 1.0, the grouped matmul kernels
# 2.0 + 1.5 (backward), the gate between them 0.5, the combine 0.5; an
# operation that lies under both ``moe`` and ``moe_router`` 0.25 (another
# family's router inside its layer); the window's backward 1.5, the full
# layer's kernels 1.0 + 0.75, an unnamed copy 0.5; a conditional that wraps
# the routed part spans its 5.5 ms and is no operation of its own.
_STEP = [("fusion.1", 0.0, 0.5), ("flash_fwd_window.2", 0.5, 1.5),
         ("cond.3", 1.5, 7.0), ("fusion.4", 1.5, 2.5),
         ("grouped_matmul.5", 2.5, 4.5), ("fusion.6", 4.5, 5.0),
         ("grouped_matmul_dw.7", 5.0, 6.5), ("fusion.8", 6.5, 7.0),
         ("fusion.9", 7.0, 7.25), ("flash_bwd_window_dq.10", 7.25, 8.75),
         ("flash_fwd_online.11", 8.75, 9.75), ("flash_bwd_dkv.12", 9.75, 10.5),
         ("copy.13", 10.5, 11.0)]
_PRE = "jit(train_step)/jvp(SmallThinker)/checkpoint/block_1/"
_BWD = "jit(train_step)/transpose(jvp(SmallThinker))/checkpoint/block_1/"
_KERNEL = 'custom_call_target="tpu_custom_call", '


def _line(name, scope, kernel=False):
    return (f'  %{name} = bf16[8]{{0}} {"custom-call" if kernel else "fusion"}'
            f'(%p), {_KERNEL if kernel else ""}'
            f'metadata={{op_name="{scope}"}}')


STEP_TEXT = "\n".join(["ENTRY %main {"] + [
    _line("fusion.1", _PRE + "moe_router/dot_general"),
    _line("flash_fwd_window.2", _PRE + "attn/flash_fwd_window/pallas_call",
          kernel=True),
    _line("cond.3", _PRE + "mlp/moe/cond"),
    _line("fusion.4", _PRE + "mlp/moe/cond/branch_0_fun/moe_dispatch/gather"),
    _line("grouped_matmul.5", _PRE + "mlp/moe/cond/branch_0_fun/moe_experts/"
          "grouped_matmul/pallas_call", kernel=True),
    _line("fusion.6", _PRE + "mlp/moe/cond/branch_0_fun/moe_experts/mul"),
    _line("grouped_matmul_dw.7", _BWD + "mlp/moe/cond/branch_0_fun/"
          "moe_experts/grouped_matmul_dw/pallas_call", kernel=True),
    _line("fusion.8", _BWD + "mlp/moe/cond/branch_0_fun/moe_combine/mul"),
    _line("fusion.9", _PRE + "mlp/moe/moe_router/top_k"),
    _line("flash_bwd_window_dq.10", _BWD + "attn/flash_bwd_window_dq/"
          "pallas_call", kernel=True),
    _line("flash_fwd_online.11", _PRE.replace("block_1", "block_0")
          + "attn/flash_fwd_online/pallas_call", kernel=True),
    _line("flash_bwd_dkv.12", _BWD.replace("block_1", "block_0")
          + "attn/flash_bwd_dkv/pallas_call", kernel=True),
    "  %copy.13 = bf16[8]{0} copy(%p)", "}"])


def _trace():
    ops, modules = [], []
    for base in (88, 100, 112, 124):
        modules.append(E("jit_train_step(1)", base * MS, (base + 12) * MS))
        ops += [E(n, int((base + a) * MS), int((base + b) * MS))
                for n, a, b in _STEP]
    return xplane.Trace([xplane.Device("/device:TPU:0", ops, modules, [])],
                        [])


def _ctx():
    return {"step_text": STEP_TEXT, "config": _config(), "peaks": PEAK,
            "traffic": {"seq_len": 8192}, "global_batch": 1, "chips": 1}


@pytest.mark.parametrize("metric,want", [
    ("expert_block_ms", 6.25), ("expert_route_ms", 2.25),
    ("expert_matmul_ms", 4.0), ("attn_kernels_ms", 4.25)])
def test_readers_sum_their_scopes_or_their_kernels(metric, want):
    """``expert_block_ms``: the router outside ``moe`` (0.5), everything under
    ``moe`` (5.5 without the ``cond``), and the operation under both scopes
    once (0.25). ``attn_kernels_ms``: window and full kernels together."""
    assert _reader(metric).read(_trace(), {}, _ctx()) == pytest.approx(want)


def test_the_expert_blocks_row_splits_it_by_inner_scope(capsys):
    _reader("expert_block_ms").read(_trace(), {}, _ctx())
    row = next(json.loads(l) for l in capsys.readouterr().out.splitlines()
               if l.startswith('{"row": "moe"'))
    assert row["by_scope_ms"] == pytest.approx(
        {"moe_experts": 4.0, "moe_dispatch": 1.0, "moe_router": 0.75,
         "moe_combine": 0.5})
    assert row["top_ops"][0]["op"] == "grouped_matmul.5"
    assert not any(op["op"].startswith("cond") for op in row["top_ops"])
    assert row["steps"] == 2


def test_readers_give_nothing_without_their_names(capsys):
    """The parent's step has no such scope (its expert layer's are found by
    the accepted readers) and a dense model's has none at all: no value and
    no exception, with a trace and without one."""
    ctx = {**_ctx(), "step_text": STEP_TEXT.replace("moe", "ffn")
           .replace("flash_", "splash_")}
    for metric in NEW_METRICS:
        assert _reader(metric).read(_trace(), {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, _ctx()) is None, metric
    assert '"missing"' in capsys.readouterr().out


def test_expert_matmul_roofline_counts_the_expected_rows():
    least = _reader("expert_matmul_roofline").least_seconds(
        _config()["model"], {"seq_len": 8192}, 1, PEAK)
    assert least["rows"] == 8192 * 6 * 16 / 64 == 12288
    # four expert layers, three matrices, three passes
    assert least["flops"] == 4 * 12288 * 9 * 2 * 2560 * 768
    assert least["bytes"] == 4 * 2 * (3 * 16 * 3 * 2560 * 768
                                      + 4 * 12288 * 2560)
    # 768 rows an expert: the weights' bytes take 4.0 ms, under half of the
    # matmuls' 8.8 ms at the peak
    assert least["bound"] == "flops"
    assert least["bytes"] / PEAK["hbm_bytes_per_s"] == pytest.approx(
        3.994e-3, rel=1e-3)
    assert least["seconds"] == pytest.approx(8.830e-3, rel=1e-3)
    share = _reader("expert_matmul_roofline").read(_trace(), {}, _ctx())
    assert share == pytest.approx(100 * 8.830 / 4.0, rel=1e-3)


def test_attention_roofline_counts_each_layer_under_its_own_mask():
    reader = _reader("attn_kernels_roofline")
    least = reader.least_seconds(_config()["model"], {"seq_len": 8192}, 1,
                                 PEAK)
    window = sum(min(i + 1, 4096) for i in range(8192))
    causal = sum(i + 1 for i in range(8192))
    assert window == 25_167_872 and causal == 33_558_528
    assert window / 8192 == pytest.approx(3072.2, abs=0.1)
    assert least["pairs"] == 3 * window + causal
    assert least["flops"] == 28 * 7 * 2.0 * (3 * window + causal) * 128
    assert least["flops"] == pytest.approx(3.788e12 + 1.684e12, rel=1e-3)
    assert least["bytes"] == 4 * 8192 * (2 * 128 * 4 * (28 + 4) + 4 * 28)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(27.78e-3, rel=1e-3)
    share = reader.read(_trace(), {}, _ctx())
    assert share == pytest.approx(100 * 27.78 / 4.25, rel=1e-3)
    # a model of full layers alone, and of window layers alone
    model = dict(_config()["model"], held_layers=[0, 4])
    assert reader.least_seconds(model, {"seq_len": 8192}, 1, PEAK)[
        "pairs"] == 2 * causal
    model = dict(_config()["model"], held_layers=[1, 2])
    assert reader.least_seconds(model, {"seq_len": 2048}, 2, PEAK)[
        "flops"] == 2 * 28 * 7 * 2.0 * 2 * (2048 * 2049 / 2) * 128

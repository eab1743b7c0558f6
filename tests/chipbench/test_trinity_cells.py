"""What PR 33 added to the benchmark, off the chip: the Trinity configuration's
plain reference through the whole harness at toy size (the rehearsal twin
``tiny_trinity``), its control, a reference that leaves out the shared
expert, the bias in the choice or the window, the six readers of the expert
and window layers on a hand-made trace, and the new entries of the manifest.
No number here comes from a device."""

import copy
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, run as run_lib, weights, xplane  # noqa: E402

REHEARSAL = os.path.join(ROOT, "tests", "chipbench", "rehearsal")
BENCH = os.path.join(ROOT, "chipbench")
CELL = "tiny_trinity.b8.s48"
NEW_CELL = "trinity_mini.b1.s8192"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = xplane.Event
MS = 1_000_000


def _reader(name):
    return run_lib.load_module([BENCH], "layer_metrics", name)


def _config():
    with open(os.path.join(BENCH, "configs", "trinity_mini.json")) as fh:
        return json.load(fh)


# -- the twin through the harness ------------------------------------------------


@pytest.mark.slow  # 145 s of a second process on eight CPU devices beside the
# suite's own: the tier-1 run keeps the in-process twin below (same harness,
# same reference), and test_granite_cells.py the command line
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


@pytest.mark.parametrize("left_out,change", [
    ("the shared expert", {"num_shared_experts": 0}),
    ("the bias in the choice", {"load_balance_coeff": 0.0}),
    ("the window", {"sliding_window": 10 ** 6})])
def test_twin_fails_on_a_step_that_leaves_a_piece_out(sound, left_out, change):
    """The program against a reference without the piece is a program without
    it against the reference: the limits part them."""
    ok, rows = _reference_again(sound, change)
    assert not ok, (left_out, rows)


# -- the configuration's file and the manifest's new entries -----------------------


def test_configuration_keeps_every_published_key():
    config = _config()
    model = config["model"]
    own = {"routed_experts", "held_experts_start", "held_layers"}
    assert {k: config[k] for k in model if k not in own} == {
        k: v for k, v in model.items() if k not in own}
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert [model[k] for k in config["reduced"]] == [5, 1, 16, 25024]
    assert [config["published"][k] for k in config["reduced"]] == [
        32, 2, 128, 200192]
    assert model["routed_experts"] == 128 and model["held_layers"] == [
        0, 4, 5, 6, 7]
    assert [model["layer_types"][j] for j in model["held_layers"]] == [
        "sliding_attention"] * 4 + ["full_attention"]
    assert "705,473,792" in config["deployment"]
    assert set(config["limits"]) == set(compare.NUMBERS)
    for key in ("attention", "expert_ffn", "bias", "optimizer", "init",
                "lr_schedule", "data", "provenance"):
        assert config["assumed"][key]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["source_url"] == config["source"])
    changed = {k for k, v in row["config"].items() if model.get(k) != v}
    assert changed == set(config["reduced"])
    # no width differs from the source
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "sliding_window", "num_attention_heads",
                "num_key_value_heads"):
        assert model[key] == row["config"][key], key


@pytest.mark.parametrize("path,kind,value", [
    ("block_0/attn_norm/scale", "const", 1.0),
    ("block_3/attn/q_norm/scale", "const", 1.0),
    ("block_2/ffn_norm/scale", "const", 1.0),
    ("final_norm/scale", "const", 1.0),
    ("block_0/post_attn_norm/scale", "const", 0.05),
    ("block_4/post_ffn_norm/scale", "const", 0.05),
    ("block_1/moe/router", "normal", 0.02),
    ("block_1/moe/w_gate", "normal", 0.02),
    ("block_1/moe/shared/down/kernel", "normal", 0.02),
    ("lm_head/kernel", "normal", 0.02)])
def test_init_rules_reach_the_leaves_they_name(path, kind, value):
    import re

    rule = next(r for r in _config()["init"] if re.search(r[0], path))
    assert rule[1:] == [kind, value]


def _mid_size_shapes(model):
    """The reference's flat layout for ``model``, with no program behind it."""
    import jax
    import jax.numpy as jnp

    d, hd = model["hidden_size"], model["head_dim"]
    H, kv = model["num_attention_heads"], model["num_key_value_heads"]
    f, fe = model["intermediate_size"], model["moe_intermediate_size"]
    out = {"embed/embedding": (model["vocab_size"], d),
           "final_norm/scale": (d,), "lm_head/kernel": (d, model["vocab_size"])}
    for i in range(model["num_hidden_layers"]):
        p = f"block_{i}/"
        for n in ("attn_norm", "post_attn_norm", "ffn_norm", "post_ffn_norm"):
            out[p + n + "/scale"] = (d,)
        for n, heads in (("query", H), ("gate", H), ("key", kv), ("value", kv)):
            out[p + f"attn/{n}/kernel"] = (d, heads, hd)
        out[p + "attn/out/kernel"] = (H, hd, d)
        out[p + "attn/q_norm/scale"] = out[p + "attn/k_norm/scale"] = (hd,)
        ffn = (("gate", (d, fe)), ("up", (d, fe)), ("down", (fe, d)))
        if i < model["num_dense_layers"]:
            for n, shape in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d))):
                out[p + n + "/kernel"] = shape
            continue
        out[p + "moe/router"] = (d, model["routed_experts"])
        for n, shape in ffn:
            out[p + f"moe/shared/{n}/kernel"] = shape
            out[p + f"moe/w_{n}"] = (model["num_experts"], *shape)
    return {k: jax.ShapeDtypeStruct(v, jnp.float32) for k, v in out.items()}


@pytest.mark.parametrize("seed", [5, 2147483999])
def test_init_gives_every_seed_the_same_routed_work(seed):
    """Why the branch-closing norms start at 0.05: on uniform random tokens
    the router's 128 loads at the start are level whatever the seed, so the
    held experts see the rows the expectation says; with every norm scale at
    1 the loads spread by their own mean and the held rows follow the seed.
    The configuration's layers, routing and init at a width a CPU takes
    (hidden 256, 2,048 tokens, window 512), through the plain reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.references import trinity_mini as reference

    config = _config()
    model = dict(config["model"], hidden_size=256, head_dim=64,
                 num_attention_heads=4, num_key_value_heads=2,
                 intermediate_size=512, moe_intermediate_size=128,
                 vocab_size=2048, sliding_window=512)
    shapes, key = _mid_size_shapes(model), weights.seed_key(seed)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (1, 2048), 0,
                                model["vocab_size"])
    biases = jnp.zeros((model["num_hidden_layers"], model["routed_experts"]))

    def loads(rules):
        params = jax.jit(lambda k: weights.make_flat(shapes, rules, k))(key)
        _, counts = jax.jit(lambda p, t: reference.hidden_fn(
            p, biases, t, model))(params, tokens)
        return np.asarray(counts)[model["num_dense_layers"]:]

    level = loads(config["init"])
    ones = loads([["scale$", "const", 1.0], [".*", "normal", 0.02]])
    spread = lambda c: c.std(-1) / c.mean(-1)
    # 128 tokens an expert: sampling alone spreads them by 0.09
    assert spread(level).max() < 0.5 < 0.8 < spread(ones).min(), (
        spread(level), spread(ones))
    held = level[:, :model["num_experts"]].sum(-1)
    expected = 2048 * model["num_experts_per_tok"] * model[
        "num_experts"] / model["routed_experts"]
    assert np.all(np.abs(held / expected - 1) < 0.15), held


def test_manifest_gained_one_configuration_one_cell_and_six_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    assert manifest["configs"][-1]["name"] == "trinity_mini"
    assert manifest["configs"][-1]["reduced"] == _config()["reduced"]
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        NEW_CELL, "trinity_mini", "b1.s8192", 1)
    assert len(cell["why"]) <= 200
    new = {m["name"]: m for m in manifest["per_layer"][-6:]}
    assert list(new) == ["moe_ms", "moe_route_ms", "moe_experts_ms",
                         "gmm_roofline", "window_attn_ms",
                         "window_attn_roofline"]
    # what was there is where it was, before the new entries
    assert [m["name"] for m in manifest["per_layer"][-9:-6]] == [
        "mamba_mixer_ms", "ssd_ms", "ssd_roofline"]
    assert [w["name"] for w in manifest["workloads"][:-1]] == [
        "gpt2_124m.b24.s1024", "granite4_h_micro.b1.s4096"]
    for metric in new.values():
        assert metric["workloads"] == [NEW_CELL]
        assert metric["moves"] == "examples_per_s_chip"
        assert metric["source"] == "device_trace"
    assert {new[n]["unit"] for n in ("gmm_roofline",
                                     "window_attn_roofline")} == {"%"}
    with open(os.path.join(BENCH, "traffic", "b1.s8192.json")) as fh:
        traffic = json.load(fh)
    assert traffic["overrides"] == {"global_batch_size": 1, "seq_len": 8192}
    assert traffic["data"] == {"kind": "tokens", "seq_len": 8192,
                               "vocab_size": 25024}
    assert (traffic["driver"], traffic["warmup_steps"]) == ("train_window", 5)
    # the cells that were there report what they reported
    ctx = run_lib.context("granite4_h_micro.b1.s4096", 1, 1.0, 1)
    assert not set(new) & set(ctx["per_layer"])
    assert set(new) <= set(run_lib.context(NEW_CELL, 1, 1.0, 1)["per_layer"])


# -- the readers, on a hand-made trace ----------------------------------------------

# Two whole steps of 12 ms on device 0 between a first and a last that the
# trace cut short. A step: router 0.5, sort and gathers 1.0, the grouped
# matmul kernels 2.0 + 1.5 (backward), the gate between them 0.5, combine
# 0.5, shared expert 1.0 (all under mlp/moe: 7.0), the window kernels 1.0 +
# 1.5, the full layer's online kernel 1.0, a dense mlp 0.5, an unnamed copy
# 0.5; a conditional that wraps the routed part spans its 5.5 ms and is no
# operation of its own.
_STEP = [("fusion.1", 0.0, 0.5), ("cond.2", 0.5, 6.0),
         ("fusion.3", 0.5, 1.5), ("grouped_matmul.4", 1.5, 3.5),
         ("fusion.5", 3.5, 4.0), ("grouped_matmul_dw.6", 4.0, 5.5),
         ("fusion.7", 5.5, 6.0), ("fusion.8", 6.0, 7.0),
         ("flash_fwd_window.9", 7.0, 8.0), ("flash_bwd_window_dq.10", 8.0, 9.5),
         ("flash_fwd_online.11", 9.5, 10.5), ("fusion.12", 10.5, 11.0),
         ("copy.13", 11.0, 11.5)]
_PRE = "jit(train_step)/jvp(Afmoe)/checkpoint/block_1/"
_BWD = "jit(train_step)/transpose(jvp(Afmoe))/checkpoint/block_1/"
_KERNEL = 'custom_call_target="tpu_custom_call", '


def _line(name, scope, kernel=False):
    return (f'  %{name} = bf16[8]{{0}} {"custom-call" if kernel else "fusion"}'
            f'(%p), {_KERNEL if kernel else ""}'
            f'metadata={{op_name="{scope}"}}')


STEP_TEXT = "\n".join(["ENTRY %main {"] + [
    _line("fusion.1", _PRE + "mlp/moe/moe_router/dot_general"),
    _line("cond.2", _PRE + "mlp/moe/cond"),
    _line("fusion.3", _PRE + "mlp/moe/cond/branch_0_fun/moe_dispatch/gather"),
    _line("grouped_matmul.4", _PRE + "mlp/moe/cond/branch_0_fun/moe_experts/"
          "grouped_matmul/pallas_call", kernel=True),
    _line("fusion.5", _PRE + "mlp/moe/cond/branch_0_fun/moe_experts/mul"),
    _line("grouped_matmul_dw.6", _BWD + "mlp/moe/cond/branch_0_fun/"
          "moe_experts/grouped_matmul_dw/pallas_call", kernel=True),
    _line("fusion.7", _BWD + "mlp/moe/cond/branch_0_fun/moe_combine/mul"),
    _line("fusion.8", _PRE + "mlp/moe/moe_shared/shared/gate/dot_general"),
    _line("flash_fwd_window.9", _PRE + "attn/flash_fwd_window/pallas_call",
          kernel=True),
    _line("flash_bwd_window_dq.10", _BWD + "attn/flash_bwd_window_dq/"
          "pallas_call", kernel=True),
    _line("flash_fwd_online.11", _PRE.replace("block_1", "block_4")
          + "attn/flash_fwd_online/pallas_call", kernel=True),
    _line("fusion.12", _PRE.replace("block_1", "block_0")
          + "mlp/gate/dot_general"),
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
    ("moe_ms", 7.0), ("moe_route_ms", 2.0), ("moe_experts_ms", 4.0),
    ("window_attn_ms", 2.5)])
def test_readers_sum_their_scope_or_their_kernels(metric, want):
    assert _reader(metric).read(_trace(), {}, _ctx()) == pytest.approx(want)


def test_the_expert_layers_row_splits_it_by_inner_scope(capsys):
    _reader("moe_ms").read(_trace(), {}, _ctx())
    row = next(json.loads(l) for l in capsys.readouterr().out.splitlines()
               if l.startswith('{"row": "moe"'))
    assert row["by_scope_ms"] == pytest.approx(
        {"moe_experts": 4.0, "moe_dispatch": 1.0, "moe_shared": 1.0,
         "moe_router": 0.5, "moe_combine": 0.5})
    assert row["top_ops"][0]["op"] == "grouped_matmul.4"
    assert not any(op["op"].startswith("cond")
                   for op in row["top_ops"])


def test_readers_give_nothing_without_their_names(capsys):
    """The parent's step has no such scope and no such kernel: no value and
    no exception, with a trace and without one."""
    ctx = {**_ctx(), "step_text": STEP_TEXT.replace("moe", "ffn")
           .replace("_window", "_causal")}
    for metric in ("moe_ms", "moe_route_ms", "moe_experts_ms", "gmm_roofline",
                   "window_attn_ms", "window_attn_roofline"):
        assert _reader(metric).read(_trace(), {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, _ctx()) is None, metric
    assert '"missing"' in capsys.readouterr().out


def test_gmm_roofline_counts_the_expected_rows():
    least = _reader("gmm_roofline").least_seconds(
        _config()["model"], {"seq_len": 8192}, 1, PEAK)
    assert least["rows"] == 8192 * 8 * 16 / 128 == 8192
    # four expert layers, three matrices, three passes
    assert least["flops"] == 4 * 8192 * 9 * 2 * 2048 * 1024
    assert least["bytes"] == 4 * 2 * (3 * 16 * 3 * 2048 * 1024
                                      + 4 * 8192 * 2048)
    # 512 rows an expert: the weights' bytes take 3.6 ms, over half of the
    # matmuls' 6.3 ms at the peak
    assert least["bound"] == "flops"
    assert least["bytes"] / PEAK["hbm_bytes_per_s"] == pytest.approx(
        3.606e-3, rel=1e-3)
    assert least["seconds"] == pytest.approx(6.279e-3, rel=1e-3)
    share = _reader("gmm_roofline").read(_trace(), {}, _ctx())
    assert share == pytest.approx(100 * 6.279 / 4.0, rel=1e-3)


def test_window_roofline_counts_the_keys_inside_the_window():
    least = _reader("window_attn_roofline").least_seconds(
        _config()["model"], {"seq_len": 8192}, 1, PEAK)
    pairs = sum(min(i + 1, 2048) for i in range(8192))
    assert pairs / 8192 == pytest.approx(1792.1, abs=0.1)
    assert least["flops"] == 4 * 32 * 7 * 2.0 * pairs * 128
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(17.09e-3, rel=1e-3)
    share = _reader("window_attn_roofline").read(_trace(), {}, _ctx())
    assert share == pytest.approx(100 * 17.09 / 2.5, rel=1e-3)

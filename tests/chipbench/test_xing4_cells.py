"""What PR 54 added to the benchmark, off the chip: the Xing4.0-29B-A4B
configuration's plain reference through the whole harness at toy size (the
rehearsal twin ``tiny_xing4``), its control, a reference that leaves out the
shared expert, the bias, YaRN or most of the Sinkhorn iterations, a
hand-checked token of the reference's hyper-connection, the six
readers on a hand-made trace, and the new entries of the manifest. No number
here comes from a device."""

import copy
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, run as run_lib, weights, xplane  # noqa: E402

REHEARSAL = os.path.join(ROOT, "tests", "chipbench", "rehearsal")
BENCH = os.path.join(ROOT, "chipbench")
CELL = "tiny_xing4.b8.s48"
NEW_CELL = "xing4_29b.b1.s2048.v16384"
NEW_METRICS = ["xing4_hc_ms", "xing4_hc_roofline", "xing4_mla_ms",
               "xing4_mla_kernels_ms", "xing4_mla_kernels_roofline",
               "xing4_moe_ms"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = xplane.Event
MS = 1_000_000


def _reader(name):
    return run_lib.load_module([BENCH], "layer_metrics", name)


def _config():
    with open(os.path.join(BENCH, "configs", "xing4_29b.json")) as fh:
        return json.load(fh)


def _reference():
    from chipbench.references import xing4_29b as reference
    return reference


# -- the twin through the harness ------------------------------------------------


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
    """Three steps through ``Trainer.train_epoch``: the loss, the first
    gradient and the parameters' change, the hyper-connections' nine leaves a
    sub-layer among them."""
    _, result, extra = sound
    assert result["correct"], result["compared"]
    assert {r["number"] for r in result["compared"]} == set(compare.NUMBERS)
    ok, rows = _reference_again(sound)
    assert ok, rows
    ref = extra["reference"]
    for leaf in ("phi_pre", "phi_post", "phi_res", "alpha_pre", "alpha_post",
                 "alpha_res", "b_pre", "b_post", "b_res"):
        assert f"block_2/hc_ffn/{leaf}" in ref["moment_norms"], leaf
    assert ref["biases"].shape == (3, 8) and not ref["biases"][0].any()


def test_twin_control_fails_the_limits(sound):
    ok, rows = _reference_again(
        sound, precision=sound[0]["config"]["control_precision"])
    assert not ok, rows


@pytest.mark.parametrize("left_out,change", [
    ("the shared expert", {"n_shared_experts": 0}),
    ("the bias in the choice", {"load_balance_coeff": 0.0}),
    ("YaRN", {"rope_scaling": None}),
    ("most of the iterations", {"hc_sinkhorn_iters": 3})])
def test_twin_fails_on_a_step_that_leaves_a_piece_out(sound, left_out, change):
    """The program against a reference without the piece is a program without
    it against the reference: the limits part them."""
    ok, rows = _reference_again(sound, change)
    assert not ok, (left_out, rows)


# -- the reference by hand ---------------------------------------------------------


def test_reference_hyper_connection_is_the_written_out_token():
    """One token, two streams of three numbers, every step written out with
    numpy: the norm over all six entries, the three maps, two Sinkhorn
    iterations an entry at a time, the read, the mix and the write."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = _reference()
    n, d = 2, 3
    model = {"rms_norm_eps": 1e-6, "hc_sinkhorn_iters": 2, "hc_eps": 1e-6,
             "mhc_h_res_clamp_min": -1.0, "mhc_h_res_clamp_max": 30}
    keys = jax.random.split(jax.random.key(0), 8)
    X = np.asarray(jax.random.normal(keys[0], (n, d)), np.float64)
    w = {"phi_pre": jax.random.normal(keys[1], (n * d, n)),
         "phi_post": jax.random.normal(keys[2], (n * d, n)),
         "phi_res": jax.random.normal(keys[3], (n * d, n * n)),
         "alpha_pre": jnp.float32(0.5), "alpha_post": jnp.float32(-0.3),
         "alpha_res": jnp.float32(2.0),
         "b_pre": jnp.array([0.1, -0.2]), "b_post": jnp.array([0.3, 0.0]),
         "b_res": jnp.array([1.0, -4.0, 0.5, 2.0])}
    branch = lambda u: 2.0 * u + 1.0
    with jax.default_matmul_precision("highest"):
        got = reference.sub_layer(
            jnp.asarray(X, jnp.float32)[None, None],
            {"hc/" + k: v for k, v in w.items()}, "hc/", branch, model)
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    flat = X.reshape(-1)
    normed = flat / np.sqrt((flat * flat).mean() + 1e-6)
    sig = lambda v: 1 / (1 + np.exp(-v))
    pre = sig(0.5 * (normed @ w["phi_pre"]) + w["b_pre"])
    post = 2 * sig(-0.3 * (normed @ w["phi_post"]) + w["b_post"])
    logits = (2.0 * (normed @ w["phi_res"]) + w["b_res"]).reshape(n, n)
    assert logits.min() < -1.0          # the clamp is reached
    m = np.exp(np.clip(logits, -1.0, 30))
    for _ in range(2):
        for j in range(n):
            m[:, j] = m[:, j] / (m[:, j].sum() + 1e-6)
        for i in range(n):
            m[i, :] = m[i, :] / (m[i, :].sum() + 1e-6)
    u = pre[0] * X[0] + pre[1] * X[1]
    y = branch(u)
    want = np.stack([m[i, 0] * X[0] + m[i, 1] * X[1] + post[i] * y
                     for i in range(n)])
    np.testing.assert_allclose(got[0, 0], want, rtol=2e-5, atol=2e-6)


# -- the configuration's file and the manifest's new entries -----------------------


def test_configuration_keeps_every_published_key():
    config = _config()
    model = config["model"]
    own = {"routed_experts", "held_experts_start", "held_layers",
           "load_balance_coeff"}
    assert own <= set(model)
    assert {k: config[k] for k in model if k not in own} == {
        k: v for k, v in model.items() if k not in own}
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert [model[k] for k in config["reduced"]] == [5, 1, 8, 16384, 0]
    assert [config["published"][k] for k in config["reduced"]] == [
        40, 2, 64, 131072, 1]
    assert (model["routed_experts"], model["held_layers"],
            model["held_experts_start"]) == (64, [1, 2, 3, 4, 5], 0)
    for text in ("759,346,190", "experts 0..7", "rows 0..16,383",
                 "Eight chips", "128 rows"):
        assert text in config["deployment"], text
    assert set(config["limits"]) == set(compare.NUMBERS)
    for key in ("layer", "hyper_connection", "streams", "attention", "yarn",
                "expert_ffn", "bias", "mtp", "optimizer", "init",
                "lr_schedule", "data", "provenance"):
        assert config["assumed"][key], key
    assert config["control_precision"] == "fp8"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["source_url"] == config["source"])
    assert set(row["config"]) <= set(model)
    changed = {k for k, v in row["config"].items() if model.get(k) != v}
    assert changed == set(config["reduced"])
    # no width differs from the source, nor the residual path's keys
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "routed_scaling_factor", "hc_mult",
                "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                "mhc_h_res_clamp_max", "rope_theta", "rope_scaling",
                "rms_norm_eps"):
        assert model[key] == row["config"][key], key


def test_held_parameters_are_the_modules_own_leaves():
    """759,346,190: the configuration's count, the family's ``num_params``
    and the leaves of the module that the preset builds; the reference's
    leaves are the program's, name for name, and every one has a rule."""
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
    assert held == 759_346_190 == (128_196_918 + 4 * 128_426_294
                                   + 117_440_512 + 3_584)
    assert len(jax.tree.leaves(shapes["batch_stats"])) == 4
    reference = _reference()
    assert bundle.fwd_flops_per_example == pytest.approx(
        reference.forward_flops(config["model"], {"seq_len": 2048}),
        rel=1e-12)
    flat = weights.flatten(shapes["params"])
    assert flat["block_4/attn/kv_b/kernel"].shape == (512, 32, 256)
    assert flat["block_4/hc_ffn/phi_res"].shape == (14336, 16)
    assert all(any(re.search(rule[0], path) for rule in config["init"])
               for path in flat)
    model = bundle.module
    assert (model.hc_mult, model.hc_sinkhorn_iters, model.hc_eps,
            model.hc_res_clamp, model.yarn_factor, model.epsilon) == (
                4, 20, 1e-6, (-30.0, 30.0), 64.0, 1e-6)


@pytest.mark.parametrize("path,kind,value", [
    ("block_0/attn_norm/scale", "const", 1.0),
    ("block_2/attn/kv_norm/scale", "const", 1.0),
    ("final_norm/scale", "const", 1.0),
    ("block_1/hc_attn/alpha_res", "const", 2.5),
    ("block_1/hc_ffn/alpha_pre", "const", 2.5),
    ("block_3/hc_ffn/b_res", "normal", 2.0),
    ("block_3/hc_attn/b_post", "normal", 0.5),
    ("block_0/hc_attn/phi_res", "normal", 0.002),
    ("embed/embedding", "normal", 1.0),
    ("block_3/attn/out/kernel", "normal", 0.02),
    ("block_3/attn/q_b/kernel", "normal", 0.02),
    ("block_1/moe/router", "normal", 0.02),
    ("block_1/moe/w_gate", "normal", 0.02),
    ("block_0/down/kernel", "normal", 0.02),
    ("lm_head/kernel", "normal", 0.02)])
def test_init_rules_reach_the_leaves_they_name(path, kind, value):
    rule = next(r for r in _config()["init"] if re.search(r[0], path))
    assert rule[1:] == [kind, value]


def test_manifest_gained_one_configuration_one_cell_and_six_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    at = [c["name"] for c in manifest["configs"]].index("xing4_29b")
    entry = manifest["configs"][at]
    assert at == 8 and entry["reduced"] == _config()["reduced"]
    assert entry["source"] == _config()["source"]
    assert entry["file"] == "chipbench/configs/xing4_29b.json"
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cell = manifest["workloads"][8]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        NEW_CELL, "xing4_29b", "b1.s2048.v16384", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 6] == NEW_METRICS
    new = {m["name"]: m for m in manifest["per_layer"][first:first + 6]}
    # what was there is where it was, before the new entries
    assert names[first - 5:first] == [
        "step_fwd_ms", "step_bwd_ms", "step_recompute_ms", "scope_mixed_pct",
        "scope_coverage_pct"]
    assert [w["name"] for w in manifest["workloads"][:8]] == [
        "gpt2_124m.b24.s1024", "granite4_h_micro.b1.s4096",
        "trinity_mini.b1.s8192", "smallthinker_21b.b1.s8192.v37984",
        "glm47_flash.b1.s8192.v19360", "nemotron3_nano.b1.s8192.v16384",
        "lfm2_8b_a1b.b1.s8192.v16384", "qwen3_next_80b.b1.s8192.v18992"]
    for metric in new.values():
        assert metric["workloads"] == [NEW_CELL]
        assert metric["moves"] == "examples_per_s_chip"
        assert metric["source"] == "device_trace"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert {new[n]["unit"] for n in ("xing4_hc_roofline",
                                     "xing4_mla_kernels_roofline")} == {"%"}
    assert {new[n]["layer"] for n in (
        "xing4_hc_ms", "xing4_hc_roofline", "xing4_mla_ms",
        "xing4_moe_ms")} == {"model step"}
    assert {new[n]["layer"] for n in (
        "xing4_mla_kernels_ms", "xing4_mla_kernels_roofline")} == {"kernels"}
    with open(os.path.join(BENCH, "traffic", "b1.s2048.v16384.json")) as fh:
        traffic = json.load(fh)
    assert traffic["overrides"] == {"global_batch_size": 1, "seq_len": 2048}
    assert traffic["data"] == {"kind": "tokens", "seq_len": 2048,
                               "vocab_size": 16384}
    assert (traffic["driver"], traffic["warmup_steps"],
            traffic["trace_seconds"]) == ("train_window", 5, 3.0)
    # the cells that were there report what they reported
    for old in ("glm47_flash.b1.s8192.v19360", "gpt2_124m.b24.s1024"):
        assert not set(new) & set(run_lib.context(old, 1, 1.0, 1)["per_layer"])
    ours = run_lib.context(NEW_CELL, 1, 1.0, 1)["per_layer"]
    assert set(new) <= set(ours)
    # the metrics without a list report here by themselves; PR 52's five keep
    # their lists (PERF.md section 7 asks a benchmark issue to append the cell)
    assert {"step_mfu_pct", "optimizer_ms", "region_coverage_pct",
            "step_mem_gb", "device_idle_pct", "device_step_ms",
            "setup_init_s", "setup_xla_compile_s"} <= set(ours)
    assert not {"mla_ms", "glm_moe_ms", "step_fwd_ms",
                "scope_coverage_pct"} & set(ours)


# -- the readers, on a hand-made trace ----------------------------------------------

# Two whole steps of 14 ms on device 0 between a first and a last that the
# trace cut short. A step, in a block: the hyper-connection ahead of
# attention: the norm and the ``phi`` product 0.5, the Sinkhorn iterations
# 0.25, the read 0.5; attention: the q path 0.5, the forward kernel 1.0; the
# write 1.0; the expert layer under ``mlp/moe``: a conditional that spans 1.5
# and is no operation of its own, the gathers 0.5, the gated matmul 1.0; in the
# backward: the write's 1.5, the Sinkhorn chain's 0.5 (under a ``while`` of
# the compiler's that is no operation of its own), the dkv kernel 1.5 and the
# dq kernel 1.0, the maps' 0.75; the recomputed read 0.5; the head 0.5, an
# unnamed copy 0.5.
_STEP = [("fusion.1", 0.0, 0.5), ("fusion.2", 0.5, 0.75),
         ("fusion.3", 0.75, 1.25), ("fusion.4", 1.25, 1.75),
         ("flash_fwd_online.5", 1.75, 2.75), ("fusion.6", 2.75, 3.75),
         ("cond.7", 3.75, 5.25), ("fusion.8", 3.75, 4.25),
         ("gated_ffn_up.9", 4.25, 5.25), ("fusion.10", 5.25, 6.75),
         ("while.11", 6.75, 7.25), ("fusion.12", 6.75, 7.25),
         ("flash_bwd_dkv.13", 7.25, 8.75), ("flash_bwd_dq.14", 8.75, 9.75),
         ("fusion.15", 9.75, 10.5), ("fusion.16", 10.5, 11.0),
         ("fusion.17", 11.0, 11.5), ("copy.18", 11.5, 12.0)]
_FWD = "jit(train_step)/jvp(Xing4)/checkpoint/block_1/"
_BWD = "jit(train_step)/transpose(jvp(Xing4))/checkpoint/block_1/"
_REMAT = ("jit(train_step)/transpose(jvp(Xing4))/jvp(Xing4)/checkpoint/"
          "rematted_computation/block_1/")
_KERNEL = 'custom_call_target="tpu_custom_call", '


def _line(name, scope, kernel=False):
    op = ("custom-call" if kernel else
          name.split(".")[0] if name.startswith(("while", "cond")) else
          "fusion")
    return (f'  %{name} = f32[8]{{0}} {op}'
            f'(%p), {_KERNEL if kernel else ""}'
            f'metadata={{op_name="{scope}"}}')


STEP_TEXT = "\n".join(["ENTRY %main (p: f32[8]) -> f32[8] {"] + [
    _line("fusion.1", _FWD + "hc_attn/hc/hc_maps/dot_general"),
    _line("fusion.2", _FWD + "hc_attn/hc/hc_sinkhorn/div"),
    _line("fusion.3", _FWD + "hc_attn/hc/hc_read/reduce_sum"),
    _line("fusion.4", _FWD + "attn/mla/mla_q/q_b/dot_general"),
    _line("flash_fwd_online.5", _FWD + "attn/mla/flash_fwd_online/pallas_call",
          kernel=True),
    _line("fusion.6", _FWD + "hc/hc_write/add"),
    _line("cond.7", _FWD + "mlp/moe/cond"),
    _line("fusion.8", _FWD + "mlp/moe/cond/branch_0_fun/moe_dispatch/gather"),
    _line("gated_ffn_up.9", _FWD + "mlp/moe/cond/branch_0_fun/moe_experts/"
          "gated_ffn_up/pallas_call", kernel=True),
    _line("fusion.10", _BWD + "hc/hc_write/mul"),
    _line("while.11", _BWD + "hc_ffn/hc/hc_sinkhorn/while"),
    _line("fusion.12", _BWD + "hc_ffn/hc/hc_sinkhorn/while/body/div"),
    _line("flash_bwd_dkv.13", _BWD + "attn/mla/flash_bwd_dkv/pallas_call",
          kernel=True),
    _line("flash_bwd_dq.14", _BWD + "attn/mla/flash_bwd_dq/pallas_call",
          kernel=True),
    _line("fusion.15", _BWD + "hc_attn/hc/hc_maps/transpose"),
    _line("fusion.16", _REMAT + "hc_ffn/hc/hc_read/reduce_sum"),
    _line("fusion.17", "jit(train_step)/jvp(Xing4)/head_loss/lm_head/"
          "dot_general"),
    "  %copy.18 = f32[8]{0} copy(%p)", "}"])


def _trace():
    ops, modules = [], []
    for base in (86, 100, 114, 128):
        modules.append(E("jit_train_step(1)", base * MS, (base + 14) * MS))
        ops += [E(n, int((base + a) * MS), int((base + b) * MS))
                for n, a, b in _STEP]
    return xplane.Trace([xplane.Device("/device:TPU:0", ops, modules, [])],
                        [])


def _ctx():
    return {"step_text": STEP_TEXT, "config": _config(), "peaks": PEAK,
            "traffic": {"seq_len": 2048}, "global_batch": 1, "chips": 1}


@pytest.mark.parametrize("metric,want", [
    ("xing4_hc_ms", 5.5), ("xing4_mla_ms", 4.0),
    ("xing4_mla_kernels_ms", 3.5), ("xing4_moe_ms", 1.5)])
def test_readers_sum_their_scopes_or_their_kernels(metric, want):
    """``xing4_hc_ms``: the maps (0.5 + 0.75), the Sinkhorn chains (0.25 + 0.5,
    the ``while`` that spans the second left out), the reads (0.5 + 0.5
    recomputed), the writes (1.0 + 1.5). ``xing4_mla_ms``: the q path and the
    three kernels. ``xing4_moe_ms``: the block's 1.5 without its ``cond``."""
    assert _reader(metric).read(_trace(), {}, _ctx()) == pytest.approx(want)


def test_the_hc_row_splits_it_by_inner_scope_and_pass(capsys):
    _reader("xing4_hc_ms").read(_trace(), {}, _ctx())
    row = next(json.loads(l) for l in capsys.readouterr().out.splitlines()
               if l.startswith('{"row": "hc"'))
    assert list(row["by_scope_ms"]) == ["hc_write", "hc_maps", "hc_read",
                                        "hc_sinkhorn"]
    assert row["by_scope_ms"]["hc_write"] == pytest.approx(
        {"forward": 1.0, "backward": 1.5})
    assert row["by_scope_ms"]["hc_maps"] == pytest.approx(
        {"forward": 0.5, "backward": 0.75})
    # the recomputed read has no forward twin at its site: hc_ffn's read ran
    # once, under the recomputation's path
    assert sum(row["by_scope_ms"]["hc_read"].values()) == pytest.approx(1.0)
    assert row["by_scope_ms"]["hc_sinkhorn"] == pytest.approx(
        {"forward": 0.25, "backward": 0.5})
    assert row["top_ops"][0]["op"] == "fusion.10" and row["steps"] == 2
    assert len(row["top_ops"]) == 8


def test_readers_give_nothing_without_their_names(capsys):
    """The parent's step has no such scope and a dense model's has none at
    all: no value and no exception, with a trace and without one."""
    ctx = {**_ctx(), "step_text": STEP_TEXT.replace("hc", "res")
           .replace("mla", "gqa").replace("moe", "ffn")
           .replace("flash_", "splash_")}
    for metric in NEW_METRICS:
        assert _reader(metric).read(_trace(), {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, _ctx()) is None, metric
    assert '"missing"' in capsys.readouterr().out


def test_hc_roofline_on_a_hand_counted_case():
    reader = _reader("xing4_hc_roofline")
    # 3 layers, 2 streams of 8, 2 sequences of 4 tokens: 6 sub-layers x 8
    # tokens x 5 passes x 2 x 8 x 4 bytes
    least = reader.least_seconds(
        {"hc_mult": 2, "hidden_size": 8, "num_hidden_layers": 3},
        {"seq_len": 4}, 2, PEAK)
    assert least["bytes"] == 6 * 8 * 5 * 64 == 15360
    assert least["flops"] == 6 * 8 * 3 * 2.0 * 16 * (4 + 4)
    assert (least["sublayers"], least["bound"]) == (6, "bytes")
    assert least["seconds"] == pytest.approx(15360 / 819e9)
    # the cell: ten sub-layers over 2,048 tokens of 4 x 3584 float32
    least = reader.least_seconds(_config()["model"], {"seq_len": 2048}, 1,
                                 PEAK)
    assert least["bytes"] == 10 * 2048 * 5 * 57344 == 5_872_025_600
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(7.170e-3, rel=1e-3)
    share = reader.read(_trace(), {}, _ctx())
    assert share == pytest.approx(100 * 7.170 / 5.5, rel=1e-3)


def test_mla_roofline_counts_the_two_widths_in_every_block():
    reader = _reader("xing4_mla_kernels_roofline")
    shared = _reader("mla_kernels_roofline")
    least = shared.least_seconds(_config()["model"], {"seq_len": 2048}, 1,
                                 PEAK)
    causal = 2048 * 2049 // 2
    assert causal == least["pairs"] and least["layers"] == 5
    # four products at the query/key width 192, three at the value width 128
    assert least["flops"] == 5 * 32 * 2.0 * causal * (4 * 192 + 3 * 128)
    assert least["bytes"] == 5 * 2048 * 32 * (2 * 4 * 320 + 4)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(3.925e-3, rel=1e-3)
    share = reader.read(_trace(), {}, _ctx())
    assert share == pytest.approx(100 * 3.925 / 3.5, rel=1e-3)

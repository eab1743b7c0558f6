"""What PR 47 added to the benchmark, off the chip: the LFM2-8B-A1B
configuration's plain reference through the whole harness at toy size (the
rehearsal twin ``tiny_lfm2``), its control, a reference that leaves a piece
out, a hand-checked case of the reference's gated conv, the configuration
against the catalog row, the eight readers on a hand-made trace, and the new
entries of the manifest. No number here comes from a device."""

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
CELL = "tiny_lfm2.b8.s48"
NEW_CELL = "lfm2_8b_a1b.b1.s8192.v16384"
NEW_METRICS = ["lfm2_conv_ms", "lfm2_conv_gate_ms", "lfm2_conv_gate_roofline",
               "lfm2_moe_ms", "lfm2_experts_ms", "lfm2_experts_roofline",
               "lfm2_attn_kernels_ms", "lfm2_attn_kernels_roofline"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = xplane.Event
MS = 1_000_000


def _reader(name):
    return run_lib.load_module([BENCH], "layer_metrics", name)


def _config():
    with open(os.path.join(BENCH, "configs", "lfm2_8b_a1b.json")) as fh:
        return json.load(fh)


# -- the twin through the harness ------------------------------------------------


@pytest.mark.slow  # a second process on eight CPU devices beside the suite's
# own: the tier-1 run keeps the in-process twin below (same harness, same
# reference), and test_granite_cells.py the command line
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


@pytest.fixture(scope="module")
def sound():
    ctx = run_lib.context(CELL, 2147484123, 2.0, 0, REHEARSAL)
    driver = run_lib.load_module(ctx["search"], "drivers",
                                 ctx["traffic"]["driver"])
    result, extra = driver.measure(ctx, None)
    return ctx, result, extra


def _reference_again(sound, change=None, precision="highest", patch=None):
    """The numbers compared when the reference follows the same three steps
    with ``change`` applied to its model, or with ``patch(module)`` applied to
    the reference itself."""
    import jax

    ctx, _, extra = sound
    config = copy.deepcopy(ctx["config"])
    config["model"].update(change or {})
    reference = run_lib.load_module(ctx["search"], "references",
                                    config["reference"])
    if patch is not None:
        patch(reference)
    params = jax.jit(lambda k: weights.make_flat(
        extra["shapes"], config["init"], k))(extra["key"])
    other = reference.run(config, params, extra["batches"],
                          precision=precision)
    return compare.judge(compare.readings(extra["program"], other),
                         ctx["config"]["limits"])


def test_twin_agrees_with_the_plain_reference(sound):
    """Three steps through ``Trainer.train_epoch``: the loss, the first
    gradient and the parameters' change, both kinds of operator and both
    kinds of FFN in them."""
    _, result, extra = sound
    assert result["correct"], result["compared"]
    assert {r["number"] for r in result["compared"]} == set(compare.NUMBERS)
    ok, rows = _reference_again(sound)
    assert ok, rows
    paths = set(extra["reference"]["moment_norms"])
    for leaf in ("block_0/short_conv/conv_kernel", "block_0/down/kernel",
                 "block_1/attn/q_norm/scale", "block_1/moe/w_gate",
                 "block_2/short_conv/in_proj/kernel", "block_4/moe/router",
                 "embed/embedding", "final_norm/scale"):
        assert leaf in paths, leaf
    assert not any("lm_head" in p or "shared" in p or "attn/gate" in p
                   for p in paths)
    counts = extra["reference"]["counts"][0]         # [blocks, routed]
    assert counts.shape == (5, 8) and counts[0].sum() == 0
    assert all(counts[i].sum() == 8 * 48 * 2 for i in range(1, 5))


def test_twin_control_fails_the_limits(sound):
    ok, rows = _reference_again(
        sound, precision=sound[0]["config"]["control_precision"])
    assert not ok, rows


def _no_gate(which):
    """A reference whose gated conv lacks the gate before (0) or after (1)."""
    def patch(reference):
        import jax.numpy as jnp

        conv = reference._gated_conv

        def ungated(bcx, kernel, q):
            chunks = list(jnp.split(bcx, 3, axis=-1))
            chunks[which] = jnp.ones_like(chunks[which])
            return conv(jnp.concatenate(chunks, -1), kernel, q)

        reference._gated_conv = ungated
    return patch


def _no_oldest_tap(reference):
    conv = reference._gated_conv
    reference._gated_conv = lambda bcx, kernel, q: conv(
        bcx, kernel.at[0].set(0.0), q)


def _no_qk_norm(reference):
    rope = reference._rope
    reference._qk_normed = lambda qh, kh, w, z, model: (
        rope(qh, model["rope_theta"]), rope(kh, model["rope_theta"]))


@pytest.mark.parametrize("left_out,change,patch", [
    ("the gate before the conv", None, _no_gate(0)),
    ("the gate after the conv", None, _no_gate(1)),
    ("the oldest tap", None, _no_oldest_tap),
    ("a tap by the configuration", {"conv_L_cache": 2}, None),
    ("the q/k norm", None, _no_qk_norm),
    ("the rotary term", {"rope_theta": 1.0 + 1e-9}, None),
    ("the bias in the choice", {"use_expert_bias": False}, None),
    ("the normalised weights", {"norm_topk_prob": False}, None),
    ("the held experts' place", {"held_experts_start": 4}, None)],
    ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_twin_fails_on_a_step_that_leaves_a_piece_out(sound, left_out, change,
                                                      patch):
    """The program against a reference without the piece is a program without
    it against the reference: the limits part them (a configuration of two
    taps does not fit the three that the weights' maker filled: that too is a
    failure)."""
    try:
        ok, rows = _reference_again(sound, change, patch=patch)
    except (TypeError, ValueError) as e:
        ok, rows = False, str(e)
    assert not ok, (left_out, rows)


# -- the reference by hand -----------------------------------------------------------


def test_reference_conv_is_the_loop_token_by_token():
    """The reference's sum of three shifted products against a loop that
    carries two rows of history, the chunks in the published order ``[B; C;
    x]`` and the oldest tap first; and the operator around it on identity
    projections."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.references import lfm2_8b_a1b as reference

    S, d = 7, 5
    keys = jax.random.split(jax.random.key(0), 2)
    bcx = np.asarray(jax.random.normal(keys[0], (1, S, 3 * d)))
    kernel = np.asarray(jax.random.normal(keys[1], (3, d)))
    got = reference._gated_conv(jnp.asarray(bcx), jnp.asarray(kernel),
                                lambda a: a)[0]
    B, C, x = bcx[0, :, :d], bcx[0, :, d:2 * d], bcx[0, :, 2 * d:]
    history, want = np.zeros((2, d)), np.zeros((S, d))
    for t in range(S):
        rows = np.concatenate([history, (B[t] * x[t])[None]])
        want[t] = C[t] * np.sum(rows * kernel, axis=0)
        history = rows[1:]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    w = {"short_conv/conv_kernel": jnp.asarray(kernel),
         "short_conv/in_proj/kernel": jnp.concatenate(
             [jnp.eye(d), 2 * jnp.eye(d), 3 * jnp.eye(d)], axis=1),
         "short_conv/out_proj/kernel": jnp.eye(d)}
    u = jnp.asarray(x[None])
    with jax.default_matmul_precision("highest"):
        out = reference._short_conv(u, w, {"K": 3}, lambda a: a)
        same = reference._gated_conv(jnp.concatenate([u, 2 * u, 3 * u], -1),
                                     jnp.asarray(kernel), lambda a: a)
    np.testing.assert_allclose(out, same, rtol=1e-6)
    with pytest.raises(ValueError, match="taps"):
        reference._short_conv(u, w, {"K": 4}, lambda a: a)


def test_reference_imports_nothing_of_the_program():
    import ast

    with open(os.path.join(BENCH, "references", "lfm2_8b_a1b.py")) as fh:
        tree = ast.parse(fh.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "functools", "math", "jax", "jax.numpy",
                        "numpy", "chipbench.references", "concurrent.futures"}


# -- the configuration's file and the manifest's new entries -----------------------


def test_configuration_keeps_every_published_key():
    config = _config()
    model = config["model"]
    own = {"routed_experts", "held_experts_start", "held_layers",
           "load_balance_coeff"}
    assert own <= set(model)
    assert {k: config[k] for k in model if k not in own} == {
        k: v for k, v in model.items() if k not in own}
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert [model[k] for k in config["reduced"]] == [7, 1, 8, 16384]
    assert [config["published"][k] for k in config["reduced"]] == [
        24, 2, 32, 65536]
    assert config["published"]["parameters"] == 8_339_929_856
    assert (model["routed_experts"], model["held_layers"],
            model["held_experts_start"]) == (32, list(range(1, 8)), 0)
    assert [model["layer_types"][j] for j in model["held_layers"]] == [
        "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv"]
    # the published widths
    assert (model["hidden_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["intermediate_size"],
            model["moe_intermediate_size"], model["num_experts_per_tok"],
            model["conv_L_cache"], model["rope_theta"]) == (
                2048, 32, 8, 7168, 1792, 4, 3, 1_000_000)
    for text in ("711,389,440", "experts 0..7", "rows 0..16,383",
                 "layers 1..7", "Four chips", "1,024 rows", "11.38 GB",
                 "677,832,960"):
        assert text in config["deployment"], text
    assert set(config["limits"]) == set(compare.NUMBERS)
    for key in ("stream", "conv", "attention", "expert_ffn", "bias",
                "tie_word_embeddings", "head_dim", "optimizer", "init",
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
    # no width differs from the source
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "conv_L_cache", "layer_types",
                "routed_scaling_factor", "norm_eps", "rope_theta"):
        assert model[key] == row["config"][key], key


def test_held_parameters_are_the_modules_own_leaves():
    """711,389,440: the configuration's count, the family's ``num_params``
    and the leaves of the module that the preset builds, none padded; the
    init's rules reach the leaves they name."""
    import jax
    import numpy as np

    from pytorch_distributed_training_example_tpu.core import (
        trainer as trainer_lib)
    from pytorch_distributed_training_example_tpu.models import lfm2_moe
    from pytorch_distributed_training_example_tpu.utils.config import (
        from_preset)

    config = _config()
    bundle = trainer_lib.build_model(from_preset(
        config["preset"], **config["overrides"]))
    module = bundle.module
    assert lfm2_moe.num_params(module) == 711_389_440
    shapes = weights.flatten(jax.eval_shape(lambda: module.init(
        jax.random.key(0), *bundle.input_template, train=False))["params"])
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == 711_389_440
    assert shapes["block_1/moe/w_up"].shape == (8, 2048, 1792)
    assert shapes["block_1/moe/w_down"].shape == (8, 1792, 2048)
    assert shapes["block_1/moe/router"].shape == (2048, 32)
    assert shapes["block_0/short_conv/in_proj/kernel"].shape == (2048, 6144)
    assert shapes["block_0/short_conv/conv_kernel"].shape == (3, 2048)
    assert shapes["block_0/down/kernel"].shape == (7168, 2048)
    assert shapes["block_1/attn/key/kernel"].shape == (2048, 8, 64)
    assert shapes["embed/embedding"].shape == (16384, 2048)
    assert not any(p.startswith("lm_head") for p in shapes)
    assert sorted(p for p in shapes if "short_conv/conv_kernel" in p) == [
        f"block_{i}/short_conv/conv_kernel" for i in (0, 2, 3, 4, 6)]
    assert bundle.fwd_flops_per_example == pytest.approx(
        run_lib.load_module([BENCH], "references", "lfm2_8b_a1b")
        .forward_flops(config["model"], {"seq_len": 8192}), rel=1e-12)
    small = {p: jax.ShapeDtypeStruct((2, 2), s.dtype)
             for p, s in shapes.items()}
    made = weights.make_flat(small, config["init"], weights.seed_key(1))
    kinds = {p: (float(np.abs(v).max()), float(np.asarray(v).std()))
             for p, v in made.items()}
    assert kinds["block_0/operator_norm/scale"] == (1.0, 0.0)
    assert kinds["block_1/attn/q_norm/scale"] == (1.0, 0.0)
    assert kinds["block_0/short_conv/conv_kernel"][0] > 0.1
    for leaf in ("block_1/attn/out/kernel", "block_5/attn/out/kernel",
                 "block_1/moe/w_down", "block_6/moe/w_down"):
        assert 0 < kinds[leaf][0] < 1e-2, leaf        # a tenth of the others
    for leaf in ("block_1/moe/router", "block_1/moe/w_up",
                 "block_0/short_conv/out_proj/kernel", "block_0/down/kernel",
                 "embed/embedding"):
        assert 5e-3 < kinds[leaf][0] < 0.1, leaf


def test_manifest_gained_one_configuration_one_cell_and_eight_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    assert manifest["configs"][-1]["name"] == "lfm2_8b_a1b"
    assert manifest["configs"][-1]["reduced"] == _config()["reduced"]
    assert manifest["configs"][-1]["source"] == _config()["source"]
    assert manifest["configs"][-1]["file"] == \
        "chipbench/configs/lfm2_8b_a1b.json"
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        NEW_CELL, "lfm2_8b_a1b", "b1.s8192.v16384", 1)
    assert len(cell["why"]) <= 200 and len(manifest["configs"][-1]["why"]) <= 200
    assert len(manifest["configs"]) == len(manifest["workloads"]) == 7
    new = {m["name"]: m for m in manifest["per_layer"][-8:]}
    assert list(new) == NEW_METRICS
    # what was there is where it was, before the new entries
    assert [m["name"] for m in manifest["per_layer"][-16:-8]] == [
        "nemo_mamba_ms", "nemo_ssd_ms", "nemo_ssd_roofline", "nemo_moe_ms",
        "nemo_route_ms", "nemo_experts_ms", "nemo_experts_roofline",
        "nemo_attn_kernels_ms"]
    assert [w["name"] for w in manifest["workloads"][:-1]] == [
        "gpt2_124m.b24.s1024", "granite4_h_micro.b1.s4096",
        "trinity_mini.b1.s8192", "smallthinker_21b.b1.s8192.v37984",
        "glm47_flash.b1.s8192.v19360", "nemotron3_nano.b1.s8192.v16384"]
    for metric in new.values():
        assert metric["workloads"] == [NEW_CELL]
        assert metric["moves"] == "examples_per_s_chip"
        assert metric["source"] == "device_trace"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert {n for n in new if new[n]["unit"] == "%"} == {
        "lfm2_conv_gate_roofline", "lfm2_experts_roofline",
        "lfm2_attn_kernels_roofline"}
    assert {new[n]["layer"] for n in ("lfm2_conv_ms", "lfm2_moe_ms")} == {
        "model step"}
    assert {new[n]["layer"] for n in NEW_METRICS if n not in (
        "lfm2_conv_ms", "lfm2_moe_ms")} == {"kernels"}
    # the traffic is the file the benchmark had
    with open(os.path.join(BENCH, "traffic", "b1.s8192.v16384.json")) as fh:
        traffic = json.load(fh)
    assert traffic["overrides"] == {"global_batch_size": 1, "seq_len": 8192}
    assert traffic["data"]["vocab_size"] == _config()["model"]["vocab_size"]
    # the cells that were there report what they reported
    for old in ("granite4_h_micro.b1.s4096", "trinity_mini.b1.s8192",
                "nemotron3_nano.b1.s8192.v16384"):
        assert not set(new) & set(run_lib.context(old, 1, 1.0, 1)["per_layer"])
    ours = run_lib.context(NEW_CELL, 1, 1.0, 1)["per_layer"]
    assert set(new) <= set(ours)
    # and the metrics without a list report here by themselves
    assert {"step_mfu_pct", "optimizer_ms", "region_coverage_pct",
            "step_mem_gb", "device_idle_pct", "device_step_ms",
            "setup_init_s", "setup_xla_compile_s"} <= set(ours)
    assert not {"moe_ms", "ssd_ms", "mamba_mixer_ms", "attn_kernels_ms",
                "nemo_moe_ms", "nemo_mamba_ms"} & set(ours)


# -- the readers, on a hand-made trace ----------------------------------------------

# Two whole steps of 12 ms on device 0 between a first and a last that the
# trace cut short. A step: in a conv block the in projection 1.0, the gated
# conv 0.5 forward and, in the backward, 0.75 in two fusions (0.5 + 0.25),
# the out projection 0.5; in the attention block the forward kernel 1.0 and
# the backward's two 1.5; in an expert block under ``mlp/moe``: the router
# 0.5, a conditional that spans 2.0 and is no operation of its own, the
# gathers 0.5, the grouped matmul 1.5; the head 0.5, an unnamed copy 0.5.
_STEP = [("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 1.5),
         ("fusion.3", 1.5, 2.0), ("flash_fwd_online.4", 2.0, 3.0),
         ("fusion.5", 3.0, 3.5), ("cond.6", 3.5, 5.5),
         ("fusion.7", 3.5, 4.0), ("grouped_matmul.8", 4.0, 5.5),
         ("flash_bwd_dq.9", 5.5, 6.5), ("flash_bwd_dkv.10", 6.5, 7.0),
         ("fusion.11", 7.0, 7.5), ("fusion.12", 7.5, 7.75),
         ("fusion.13", 9.0, 9.5), ("copy.14", 9.5, 10.0)]
_PRE = "jit(train_step)/jvp(Lfm2Moe)/checkpoint/"
_BWD = "jit(train_step)/transpose(jvp(Lfm2Moe))/checkpoint/"
_KERNEL = 'custom_call_target="tpu_custom_call", '


def _line(name, scope, kernel=False):
    return (f'  %{name} = bf16[8]{{0}} {"custom-call" if kernel else "fusion"}'
            f'(%p), {_KERNEL if kernel else ""}'
            f'metadata={{op_name="{scope}"}}')


STEP_TEXT = "\n".join(["ENTRY %main {"] + [
    _line("fusion.1", _PRE + "block_0/short_conv/in_proj/dot_general"),
    _line("fusion.2", _PRE + "block_0/short_conv/conv_gate/mul"),
    _line("fusion.3", _PRE + "block_0/short_conv/out_proj/dot_general"),
    _line("flash_fwd_online.4", _PRE + "block_1/attn/flash_fwd_online/"
          "pallas_call", kernel=True),
    _line("fusion.5", _PRE + "block_1/mlp/moe/moe_router/top_k"),
    _line("cond.6", _PRE + "block_1/mlp/moe/cond"),
    _line("fusion.7", _PRE + "block_1/mlp/moe/cond/branch_0_fun/moe_dispatch/"
          "gather"),
    _line("grouped_matmul.8", _PRE + "block_1/mlp/moe/cond/branch_0_fun/"
          "moe_experts/grouped_matmul/pallas_call", kernel=True),
    _line("flash_bwd_dq.9", _BWD + "block_1/attn/flash_bwd_dq/pallas_call",
          kernel=True),
    _line("flash_bwd_dkv.10", _BWD + "block_1/attn/flash_bwd_dkv/pallas_call",
          kernel=True),
    _line("fusion.11", _BWD + "block_0/short_conv/conv_gate/mul"),
    _line("fusion.12", _BWD + "block_0/short_conv/conv_gate/reduce_sum"),
    _line("fusion.13", "jit(train_step)/jvp(Lfm2Moe)/head_loss/dot_general"),
    "  %copy.14 = bf16[8]{0} copy(%p)", "}"])


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
    ("lfm2_conv_ms", 2.75), ("lfm2_conv_gate_ms", 1.25),
    ("lfm2_moe_ms", 2.5), ("lfm2_experts_ms", 1.5),
    ("lfm2_attn_kernels_ms", 2.5)])
def test_readers_sum_their_scopes_or_their_kernels(metric, want, capsys):
    """``lfm2_conv_ms``: both projections and the gated conv both ways;
    ``lfm2_conv_gate_ms``: the gated conv alone, forward and both fusions of
    its backward; ``lfm2_moe_ms``: the expert block without its ``cond``;
    ``lfm2_attn_kernels_ms``: the three flash kernels."""
    assert _reader(metric).read(_trace(), {}, _ctx()) == pytest.approx(want)
    if metric == "lfm2_conv_ms":
        row = next(json.loads(l) for l in capsys.readouterr().out.splitlines()
                   if '"row": "short_conv"' in l)
        assert row["by_scope_ms"] == pytest.approx(
            {"conv_gate": 1.25, "in_proj": 1.0, "out_proj": 0.5})
        assert row["steps"] == 2 and row["top_ops"][0]["op"] == "fusion.1"


def test_readers_give_nothing_without_their_names(capsys):
    """The parent's step has no such scope and a dense model's has none at
    all: no value and no exception, with a trace and without one."""
    ctx = {**_ctx(), "step_text": STEP_TEXT.replace("short_conv", "mixer")
           .replace("conv_gate", "stage").replace("moe", "ffn")
           .replace("flash_", "splash_")}
    for metric in NEW_METRICS:
        assert _reader(metric).read(_trace(), {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, _ctx()) is None, metric
    assert '"missing"' in capsys.readouterr().out


def test_rooflines_count_bytes_once_rows_expected_and_the_causal_half():
    """The gated conv's least time is 11 d bf16 elements a token over the five
    held conv layers (1.85 GB: 2.25 ms at the chip's 819 GB/s, the bytes
    deciding); the experts' is three matrices over the expected 8,192 rows of
    six layers (3.25 TFLOP, the FLOPs deciding); the attention's 7 products
    over the causal half of 32 heads of 64 in two layers."""
    model = _config()["model"]
    gate = _reader("lfm2_conv_gate_roofline")
    least = gate.least_seconds(model, {"seq_len": 8192}, 1, PEAK)
    assert least["layers"] == 5 and least["bound"] == "bytes"
    assert least["bytes"] == 5 * 8192 * 11 * 2048 * 2 == 1_845_493_760
    assert least["seconds"] == pytest.approx(2.2533e-3, rel=1e-3)
    assert least["flops"] / PEAK["bf16_flops_per_s"] < least["seconds"] / 100
    assert gate.read(_trace(), {}, _ctx()) == pytest.approx(
        100 * least["seconds"] / 1.25e-3)
    experts = _reader("gmm_roofline").least_seconds(
        model, {"seq_len": 8192}, 1, PEAK)
    assert experts["rows"] == 8192              # 1,024 an expert
    assert experts["flops"] == 6 * 8192 * 3 * 3 * 2.0 * 2048 * 1792
    assert experts["bytes"] == 6 * 2 * (3 * 8 * 3 * 2048 * 1792
                                        + 4 * 8192 * 2048)
    assert experts["bound"] == "flops"
    assert experts["seconds"] == pytest.approx(16.48e-3, rel=1e-3)
    assert _reader("lfm2_experts_roofline").read(
        _trace(), {}, _ctx()) == pytest.approx(
            100 * experts["seconds"] / 1.5e-3)
    attn = _reader("lfm2_attn_kernels_roofline")
    least = attn.least_seconds(model, {"seq_len": 8192}, 1, PEAK)
    assert least["layers"] == 2 and least["pairs"] == 8192 * 8193 / 2
    assert least["flops"] == 2 * 32 * 7 * 2.0 * (8192 * 8193 / 2) * 64
    assert least["bytes"] == 2 * 8192 * (2 * 64 * 4 * (32 + 8) + 4 * 32)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(9.77e-3, rel=1e-2)
    assert attn.read(_trace(), {}, _ctx()) == pytest.approx(
        100 * least["seconds"] / 2.5e-3)

"""What PR 50 added to the benchmark, off the chip: the Qwen3-Next-80B-A3B
configuration's plain reference through the whole harness at toy size (the
rehearsal twin ``tiny_qwen3_next``), its control, a reference that leaves a
piece out, a hand-checked case of the reference's recurrence, the
configuration against the catalog row, the nine readers on a hand-made trace,
and the new entries of the manifest. No number here comes from a device."""

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
CELL = "tiny_qwen3_next.b8.s48"
NEW_CELL = "qwen3_next_80b.b1.s8192.v18992"
NEW_METRICS = ["qwen3n_gdn_ms", "qwen3n_delta_rule_ms",
               "qwen3n_delta_rule_roofline", "qwen3n_moe_ms",
               "qwen3n_route_ms", "qwen3n_experts_ms",
               "qwen3n_experts_roofline", "qwen3n_attn_kernels_ms",
               "qwen3n_attn_kernels_roofline"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = xplane.Event
MS = 1_000_000


def _reader(name):
    return run_lib.load_module([BENCH], "layer_metrics", name)


def _config():
    with open(os.path.join(BENCH, "configs", "qwen3_next_80b.json")) as fh:
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


def test_twin_agrees_with_the_plain_reference(sound, capsys):
    """Three steps through ``Trainer.train_epoch``: the loss, the first
    gradient and the parameters' change, both kinds of mixer in them, the
    chunked rule (three chunks of 16) against the recurrence token by token."""
    _, result, extra = sound
    assert result["correct"], result["compared"]
    assert {r["number"] for r in result["compared"]} == set(compare.NUMBERS)
    ok, rows = _reference_again(sound)
    assert ok, rows
    paths = set(extra["reference"]["moment_norms"])
    for leaf in ("block_0/gated_delta_net/conv_kernel",
                 "block_0/gated_delta_net/A_log",
                 "block_1/gated_delta_net/dt_bias",
                 "block_2/gated_delta_net/norm_scale",
                 "block_2/gated_delta_net/in_proj_ba/kernel",
                 "block_3/attn/q_norm/scale", "block_3/attn/gate/kernel",
                 "block_0/moe/w_gate", "block_3/moe_router/kernel",
                 "block_1/shared_expert/down/kernel",
                 "block_1/shared_expert_gate/kernel", "embed/embedding",
                 "lm_head/kernel", "final_norm/scale"):
        assert leaf in paths, leaf
    assert not any("expert_bias" in p or "short_conv" in p for p in paths)
    held = next(json.loads(l) for l in capsys.readouterr().out.splitlines()
                if '"reference_held_rows"' in l)
    assert held["expected"] == 8 * 48 * 3 * 2 / 8
    assert len(held["by_step_and_layer"]) == 3
    assert all(len(step) == 4 for step in held["by_step_and_layer"])


def test_twin_control_fails_the_limits(sound):
    ok, rows = _reference_again(
        sound, precision=sound[0]["config"]["control_precision"])
    assert not ok, rows


def _recurrence_without(**pieces):
    def patch(reference):
        import functools

        reference._recurrence = functools.partial(reference._recurrence,
                                                  **pieces)
    return patch


def _no_shared_gate(reference):
    import functools

    reference._experts = functools.partial(reference._experts,
                                           shared_gate=False)


def _whole_head_rotary(reference):
    sizes = reference._sizes
    reference._sizes = lambda model: {**sizes(model),
                                      "rot": model["head_dim"]}


def _gate_before_the_norm(reference):
    """Mamba-2's order in the reference: ``norm(o * silu(z))``."""
    import functools

    reference._norm_gate = functools.partial(reference._norm_gate,
                                             norm_first=False)


@pytest.mark.parametrize("left_out,change,patch", [
    ("the readout", None, _recurrence_without(readout=False)),
    ("the decay", None, _recurrence_without(decay=False)),
    ("the shared expert's gate", None, _no_shared_gate),
    ("the rotary slice", None, _whole_head_rotary),
    ("the rotary term", {"rope_theta": 1.0 + 1e-9}, None),
    ("the norm before the gate", None, _gate_before_the_norm),
    ("a tap by the configuration", {"linear_conv_kernel_dim": 3}, None),
    ("the layer kinds' order", {"full_attention_interval": 2}, None),
    ("the held experts' place", {"held_experts_start": 4}, None)],
    ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_twin_fails_on_a_step_that_leaves_a_piece_out(sound, left_out, change,
                                                      patch):
    """The program against a reference without the piece is a program without
    it against the reference: the limits part them (a configuration of three
    taps, or of another order of layers, does not fit the leaves that the
    weights' maker filled: that too is a failure)."""
    try:
        ok, rows = _reference_again(sound, change, patch=patch)
    except (TypeError, ValueError, KeyError) as e:
        ok, rows = False, str(e)
    assert not ok, (left_out, rows)


# -- the reference by hand -----------------------------------------------------------


def test_reference_recurrence_is_the_four_lines_token_by_token():
    """The reference's nested scan against a loop in float64 on the host: the
    decay, the key's readout, the write of the difference, the query's
    readout; the segments are a rematerialisation and change no digit that a
    single scan gives."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.references import qwen3_next_80b as reference

    b, S, H, Dk, Dv = 1, 80, 2, 4, 3            # 80 = 5 segments of 16
    k = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(k[0], (b, S, H, Dk))
    key = jax.random.normal(k[1], (b, S, H, Dk))
    key = key / jnp.linalg.norm(key, axis=-1, keepdims=True)
    v = jax.random.normal(k[2], (b, S, H, Dv))
    g = -0.2 * jax.nn.softplus(jax.random.normal(k[3], (b, S, H)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (b, S, H)))
    with jax.default_matmul_precision("highest"):
        got = reference._recurrence(q, key, v, g, beta, lambda a: a)
    assert reference.SEGMENT == 64 and np.gcd(S, 64) == 16
    want = np.zeros((S, H, Dv))
    arrays = [np.asarray(a[0], np.float64) for a in (q, key, v, g, beta)]
    for h in range(H):
        state = np.zeros((Dk, Dv))
        for t in range(S):
            q_t, k_t, v_t, g_t, b_t = (a[t, h] for a in arrays)
            state = np.exp(g_t) * state
            state = state + np.outer(k_t, b_t * (v_t - state.T @ k_t))
            want[t, h] = state.T @ q_t
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=2e-6)
    # the same key twice, beta 1, no decay: the second value replaces the
    # first (an outer-product state would hold their sum)
    one = jnp.zeros((1, 2, 1, 4)).at[..., 1].set(1.0)
    twice = jnp.stack([jnp.full((1, 1, 3), 3.0), jnp.full((1, 1, 3), 7.0)], 1)
    o = reference._recurrence(one, one, twice, jnp.zeros((1, 2, 1)),
                              jnp.ones((1, 2, 1)), lambda a: a)
    np.testing.assert_allclose(o[0, :, 0], [[3.0] * 3, [7.0] * 3], atol=1e-6)
    no_read = reference._recurrence(one, one, twice, jnp.zeros((1, 2, 1)),
                                    jnp.ones((1, 2, 1)), lambda a: a,
                                    readout=False)
    np.testing.assert_allclose(no_read[0, 1, 0], [10.0] * 3, atol=1e-6)


def test_reference_rotates_a_quarter_of_the_head_and_routes_in_order():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.references import qwen3_next_80b as reference

    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 16))
    turned = reference._rope(x, 1e4, 4)
    np.testing.assert_array_equal(turned[..., 4:], x[..., 4:])
    np.testing.assert_array_equal(turned[:, 0], x[:, 0])       # position 0
    angle = 3 * 1e4 ** (-np.arange(2) / 2)
    np.testing.assert_allclose(
        turned[0, 3, 1, :2],
        x[0, 3, 1, :2] * np.cos(angle) - x[0, 3, 1, 2:4] * np.sin(angle),
        rtol=1e-5, atol=1e-6)
    u = jax.random.normal(jax.random.key(1), (5, 8))
    kernel = jax.random.normal(jax.random.key(2), (8, 6))
    chosen, weight = reference.route(u, kernel, 2)
    p = np.asarray(jax.nn.softmax(u @ kernel, -1))
    order = np.argsort(-p, -1)[:, :2]
    np.testing.assert_array_equal(chosen, order)
    top = np.take_along_axis(p, order, -1)
    np.testing.assert_allclose(weight, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    assert jnp.all(jnp.abs(jnp.sum(weight, -1) - 1) < 1e-6)


def test_reference_imports_nothing_of_the_program():
    import ast

    with open(os.path.join(BENCH, "references", "qwen3_next_80b.py")) as fh:
        tree = ast.parse(fh.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "functools", "json", "math", "jax",
                        "jax.numpy", "numpy", "chipbench.references",
                        "concurrent.futures"}


# -- the configuration's file and the manifest's new entries -----------------------


def test_configuration_keeps_every_published_key():
    config = _config()
    model = config["model"]
    own = {"routed_experts", "held_experts_start", "held_layers"}
    assert own <= set(model)
    assert {k: config[k] for k in model if k not in own} == {
        k: v for k, v in model.items() if k not in own}
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert [model[k] for k in config["reduced"]] == [4, 32, 18992]
    assert [config["published"][k] for k in config["reduced"]] == [
        48, 512, 151936]
    assert config["published"]["parameters"] == 79_674_391_296
    assert (model["routed_experts"], model["held_layers"],
            model["held_experts_start"]) == (512, [0, 1, 2, 3], 0)
    # the published widths
    assert (model["hidden_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"],
            model["partial_rotary_factor"], model["rope_theta"]) == (
                2048, 16, 2, 256, 0.25, 10_000_000)
    assert (model["linear_num_key_heads"], model["linear_num_value_heads"],
            model["linear_key_head_dim"], model["linear_value_head_dim"],
            model["linear_conv_kernel_dim"]) == (16, 32, 128, 128, 4)
    assert (model["num_experts_per_tok"], model["moe_intermediate_size"],
            model["shared_expert_intermediate_size"],
            model["full_attention_interval"]) == (10, 512, 512, 4)
    for text in ("625,667,136", "experts 0..31", "rows 0..18,991",
                 "layers 0..3", "Sixteen chips", "160 rows", "10.01 GB",
                 "547,873,856", "33,718,464", "27,263,488", "104,859,648"):
        assert text in config["deployment"], text
    assert set(config["limits"]) == set(compare.NUMBERS)
    for key in ("stream", "norms", "linear_attention", "full_attention",
                "expert_ffn", "mtp", "optimizer", "init", "lr_schedule",
                "data", "provenance", "remat"):
        assert config["assumed"][key], key
    assert "TO BE FILLED" not in json.dumps(config)
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


def test_held_parameters_are_the_modules_own_leaves():
    """625,667,136: the configuration's count, the family's ``num_params``
    and the leaves of the module that the preset builds, none padded; the
    init's rules reach the leaves they name."""
    import jax
    import numpy as np

    from pytorch_distributed_training_example_tpu.core import (
        trainer as trainer_lib)
    from pytorch_distributed_training_example_tpu.models import qwen3_next
    from pytorch_distributed_training_example_tpu.utils.config import (
        from_preset)

    config = _config()
    bundle = trainer_lib.build_model(from_preset(
        config["preset"], **config["overrides"]))
    module = bundle.module
    assert qwen3_next.num_params(module) == 625_667_136
    shapes = weights.flatten(jax.eval_shape(lambda: module.init(
        jax.random.key(0), *bundle.input_template, train=False))["params"])
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == 625_667_136
    assert shapes["block_1/moe/w_up"].shape == (32, 2048, 512)
    assert shapes["block_1/moe/w_down"].shape == (32, 512, 2048)
    assert shapes["block_1/moe_router/kernel"].shape == (2048, 512)
    assert shapes["block_2/shared_expert_gate/kernel"].shape == (2048, 1)
    assert shapes["block_0/gated_delta_net/in_proj_qkvz/kernel"].shape == (
        2048, 12288)
    assert shapes["block_0/gated_delta_net/in_proj_ba/kernel"].shape == (
        2048, 64)
    assert shapes["block_0/gated_delta_net/conv_kernel"].shape == (4, 8192)
    assert shapes["block_0/gated_delta_net/norm_scale"].shape == (128,)
    assert shapes["block_3/attn/key/kernel"].shape == (2048, 2, 256)
    assert shapes["block_3/attn/gate/kernel"].shape == (2048, 16, 256)
    assert shapes["embed/embedding"].shape == (18992, 2048)
    assert shapes["lm_head/kernel"].shape == (2048, 18992)
    assert sorted(p for p in shapes if p.endswith("A_log")) == [
        f"block_{i}/gated_delta_net/A_log" for i in (0, 1, 2)]
    assert bundle.fwd_flops_per_example == pytest.approx(
        run_lib.load_module([BENCH], "references", "qwen3_next_80b")
        .forward_flops(config["model"], {"seq_len": 8192}), rel=1e-12)
    small = {p: jax.ShapeDtypeStruct((2, 2), s.dtype)
             for p, s in shapes.items()}
    made = weights.make_flat(small, config["init"], weights.seed_key(1))
    kinds = {p: (float(np.abs(v).max()), float(np.asarray(v).std()))
             for p, v in made.items()}
    assert kinds["block_0/mixer_norm/scale"] == (1.0, 0.0)
    assert kinds["block_0/gated_delta_net/norm_scale"] == (1.0, 0.0)
    assert kinds["block_3/attn/q_norm/scale"] == (1.0, 0.0)
    assert kinds["block_0/gated_delta_net/A_log"] == (0.0, 0.0)
    assert kinds["block_0/gated_delta_net/dt_bias"] == (3.0, 0.0)
    assert kinds["block_0/gated_delta_net/conv_kernel"][0] > 0.1
    assert kinds["embed/embedding"][0] > 0.3
    for leaf in ("block_3/attn/out/kernel",
                 "block_0/gated_delta_net/out_proj/kernel",
                 "block_1/moe/w_down"):
        assert 0 < kinds[leaf][0] < 1e-2, leaf        # a tenth of the others
    for leaf in ("block_1/moe_router/kernel", "block_1/moe/w_up",
                 "block_0/gated_delta_net/in_proj_qkvz/kernel",
                 "block_0/shared_expert/down/kernel", "lm_head/kernel"):
        assert 5e-3 < kinds[leaf][0] < 0.1, leaf


def test_manifest_gained_one_configuration_one_cell_and_nine_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    assert manifest["configs"][-1]["name"] == "qwen3_next_80b"
    assert manifest["configs"][-1]["reduced"] == _config()["reduced"]
    assert manifest["configs"][-1]["source"] == _config()["source"]
    assert manifest["configs"][-1]["file"] == \
        "chipbench/configs/qwen3_next_80b.json"
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        NEW_CELL, "qwen3_next_80b", "b1.s8192.v18992", 1)
    assert len(cell["why"]) <= 200 and len(manifest["configs"][-1]["why"]) <= 200
    assert len(manifest["configs"]) == len(manifest["workloads"]) == 8
    assert not [w for w in manifest["workloads"] if w["chips"] != 1]
    new = {m["name"]: m for m in manifest["per_layer"][-9:]}
    assert list(new) == NEW_METRICS
    # what was there is where it was, before the new entries
    assert [m["name"] for m in manifest["per_layer"][-17:-9]] == [
        "lfm2_conv_ms", "lfm2_conv_gate_ms", "lfm2_conv_gate_roofline",
        "lfm2_moe_ms", "lfm2_experts_ms", "lfm2_experts_roofline",
        "lfm2_attn_kernels_ms", "lfm2_attn_kernels_roofline"]
    assert [w["name"] for w in manifest["workloads"][:-1]] == [
        "gpt2_124m.b24.s1024", "granite4_h_micro.b1.s4096",
        "trinity_mini.b1.s8192", "smallthinker_21b.b1.s8192.v37984",
        "glm47_flash.b1.s8192.v19360", "nemotron3_nano.b1.s8192.v16384",
        "lfm2_8b_a1b.b1.s8192.v16384"]
    for metric in new.values():
        assert metric["workloads"] == [NEW_CELL]
        assert metric["moves"] == "examples_per_s_chip"
        assert metric["source"] == "device_trace"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert {n for n in new if new[n]["unit"] == "%"} == {
        "qwen3n_delta_rule_roofline", "qwen3n_experts_roofline",
        "qwen3n_attn_kernels_roofline"}
    steps = ("qwen3n_gdn_ms", "qwen3n_moe_ms", "qwen3n_route_ms")
    assert {new[n]["layer"] for n in steps} == {"model step"}
    assert {new[n]["layer"] for n in NEW_METRICS if n not in steps} == {
        "kernels"}
    # the traffic is the accepted file with the slice's ids
    with open(os.path.join(BENCH, "traffic", "b1.s8192.v18992.json")) as fh:
        traffic = json.load(fh)
    with open(os.path.join(BENCH, "traffic", "b1.s8192.v16384.json")) as fh:
        accepted = json.load(fh)
    assert traffic["data"]["vocab_size"] == _config()["model"]["vocab_size"]
    accepted["data"]["vocab_size"] = 18992
    assert traffic == accepted
    # the cells that were there report what they reported
    for old in ("granite4_h_micro.b1.s4096", "trinity_mini.b1.s8192",
                "lfm2_8b_a1b.b1.s8192.v16384"):
        assert not set(new) & set(run_lib.context(old, 1, 1.0, 1)["per_layer"])
    ours = run_lib.context(NEW_CELL, 1, 1.0, 1)["per_layer"]
    assert set(new) <= set(ours)
    # and the metrics without a list report here by themselves
    assert {"step_mfu_pct", "optimizer_ms", "region_coverage_pct",
            "step_mem_gb", "device_idle_pct", "device_step_ms",
            "setup_init_s", "setup_xla_compile_s"} <= set(ours)
    assert not {"moe_ms", "ssd_ms", "mamba_mixer_ms", "attn_kernels_ms",
                "lfm2_moe_ms", "nemo_mamba_ms"} & set(ours)


# -- the readers, on a hand-made trace ----------------------------------------------

# Two whole steps of 14 ms on device 0 between a first and a last that the
# trace cut short. A step: in a delta-rule block the in projection 1.0, the
# conv kernel 0.5, under ``delta_rule`` a fusion of 0.5 (the chunks' solves), a
# ``while`` that spans 2.0 and is no operation of its own with two fusions of
# its body inside it (1.25 and 0.75), the head norm 0.25, the out projection
# 0.5; in the attention block the forward kernel 1.0 and the backward's two
# 1.5; in an expert FFN under ``mlp/moe``: the router 0.5, a conditional that
# spans 2.0 and is no operation of its own, the gathers 0.5, the gated-FFN
# kernel 1.5, the shared expert 0.25; in the backward the rule's transpose
# 1.0; the head 0.5, an unnamed copy 0.5.
_STEP = [("fusion.1", 0.0, 1.0), ("conv_silu_fwd.2", 1.0, 1.5),
         ("fusion.3", 1.5, 2.0), ("while.4", 2.0, 4.0),
         ("fusion.5", 2.0, 3.25), ("fusion.6", 3.25, 4.0),
         ("fusion.7", 4.0, 4.25), ("fusion.8", 4.25, 4.75),
         ("flash_fwd_online.9", 4.75, 5.75), ("fusion.10", 5.75, 6.25),
         ("cond.11", 6.25, 8.25), ("fusion.12", 6.25, 6.75),
         ("gated_ffn_up.13", 6.75, 8.25), ("fusion.14", 8.25, 8.5),
         ("flash_bwd_dq.15", 8.5, 9.5), ("flash_bwd_dkv.16", 9.5, 10.0),
         ("fusion.17", 10.0, 11.0), ("fusion.18", 11.0, 11.5),
         ("copy.19", 11.5, 12.0)]
_PRE = "jit(train_step)/jvp(Qwen3Next)/checkpoint/"
_BWD = "jit(train_step)/transpose(jvp(Qwen3Next))/checkpoint/"
_KERNEL = 'custom_call_target="tpu_custom_call", '
_GDN = "block_0/gated_delta_net/"
_MOE = "block_0/mlp/moe/"


def _line(name, scope, kernel=False):
    return (f'  %{name} = bf16[8]{{0}} {"custom-call" if kernel else "fusion"}'
            f'(%p), {_KERNEL if kernel else ""}'
            f'metadata={{op_name="{scope}"}}')


STEP_TEXT = "\n".join(["ENTRY %main {"] + [
    _line("fusion.1", _PRE + _GDN + "in_proj/in_proj_qkvz/dot_general"),
    _line("conv_silu_fwd.2", _PRE + _GDN + "conv_silu/conv_silu_fwd/"
          "pallas_call", kernel=True),
    _line("fusion.3", _PRE + _GDN + "delta_rule/checkpoint/dot_general"),
    _line("while.4", _PRE + _GDN + "delta_rule/checkpoint/while"),
    _line("fusion.5", _PRE + _GDN + "delta_rule/checkpoint/while/body/"
          "dot_general"),
    _line("fusion.6", _PRE + _GDN + "delta_rule/checkpoint/while/body/add"),
    _line("fusion.7", _PRE + _GDN + "gate_norm/mul"),
    _line("fusion.8", _PRE + _GDN + "out_proj/dot_general"),
    _line("flash_fwd_online.9", _PRE + "block_3/attn/flash_fwd_online/"
          "pallas_call", kernel=True),
    _line("fusion.10", _PRE + _MOE + "moe_router/top_k"),
    _line("cond.11", _PRE + _MOE + "moe/cond"),
    _line("fusion.12", _PRE + _MOE + "moe/cond/branch_0_fun/moe_dispatch/"
          "gather"),
    _line("gated_ffn_up.13", _PRE + _MOE + "moe/cond/branch_0_fun/"
          "moe_experts/gated_ffn_up/pallas_call", kernel=True),
    _line("fusion.14", _PRE + _MOE + "moe_shared/shared_expert/dot_general"),
    _line("flash_bwd_dq.15", _BWD + "block_3/attn/flash_bwd_dq/pallas_call",
          kernel=True),
    _line("flash_bwd_dkv.16", _BWD + "block_3/attn/flash_bwd_dkv/pallas_call",
          kernel=True),
    _line("fusion.17", _BWD + _GDN + "delta_rule/checkpoint/while/body/"
          "dot_general"),
    _line("fusion.18", "jit(train_step)/jvp(Qwen3Next)/head_loss/dot_general"),
    "  %copy.19 = bf16[8]{0} copy(%p)", "}"])


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
            "traffic": {"seq_len": 8192}, "global_batch": 1, "chips": 1}


@pytest.mark.parametrize("metric,want", [
    ("qwen3n_gdn_ms", 5.75), ("qwen3n_delta_rule_ms", 3.5),
    ("qwen3n_moe_ms", 2.75), ("qwen3n_route_ms", 1.0),
    ("qwen3n_experts_ms", 1.5), ("qwen3n_attn_kernels_ms", 2.5)])
def test_readers_sum_their_scopes_or_their_kernels(metric, want, capsys):
    """``qwen3n_gdn_ms``: the mixer's five stages both ways without the
    ``while`` that wraps the chunks; ``qwen3n_delta_rule_ms``: the rule alone,
    its solves, the scan's body and its transpose; ``qwen3n_moe_ms``: the
    router, the held experts without their ``cond`` and the shared expert;
    ``qwen3n_route_ms``: the router and the gathers; ``qwen3n_experts_ms``:
    the kernel; ``qwen3n_attn_kernels_ms``: the three flash kernels."""
    assert _reader(metric).read(_trace(), {}, _ctx()) == pytest.approx(want)
    if metric == "qwen3n_gdn_ms":
        row = next(json.loads(l) for l in capsys.readouterr().out.splitlines()
                   if '"row": "gated_delta_net"' in l)
        assert row["by_scope_ms"] == pytest.approx(
            {"delta_rule": 3.5, "in_proj": 1.0, "conv_silu": 0.5,
             "out_proj": 0.5, "gate_norm": 0.25})
        assert row["steps"] == 2 and row["top_ops"][0]["op"] == "fusion.5"
        assert not [op for op in row["top_ops"] if op["op"] == "while.4"]


def test_readers_give_nothing_without_their_names(capsys):
    """The parent's step has no such scope and a dense model's has none at
    all: no value and no exception, with a trace and without one."""
    ctx = {**_ctx(), "step_text": STEP_TEXT.replace("gated_delta_net", "mixer")
           .replace("delta_rule", "rule").replace("moe", "ffn")
           .replace("flash_", "splash_")}
    for metric in NEW_METRICS:
        assert _reader(metric).read(_trace(), {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, _ctx()) is None, metric
    assert '"missing"' in capsys.readouterr().out


def test_rooflines_count_the_chunked_form_the_expected_rows_and_the_causal_half():
    """The rule's least time at one small shape by hand, and at the cell's:
    0.31 TFLOP over three layers (1.58 ms at the chip's 197 TFLOP/s, the FLOPs
    deciding by a hair over 1.22 GB of operands); the experts' is the weights'
    bytes (32 experts of three 2048 x 512 matrices read twice and written
    once, four layers); the attention's 7 products over the causal half of 16
    heads of 256 in one layer."""
    rule = _reader("qwen3n_delta_rule_roofline")
    small = {"linear_num_key_heads": 1, "linear_num_value_heads": 2,
             "linear_key_head_dim": 8, "linear_value_head_dim": 4,
             "full_attention_interval": 4, "held_layers": [4, 5, 6, 7]}
    least = rule.least_seconds(small, {"seq_len": 6}, 2, PEAK)
    # one chunk of 6 a sequence: triangles of 21 pairs
    macs = 1 * 2 * 21 * 8 + 2 * (2 * 21 * 4 + 21 * 8 + 3 * 6 * 8 * 4)
    assert least["layers"] == 3 and least["macs_per_chunk"] == macs == 2160
    assert least["flops"] == 2 * 3 * 1 * 3 * 2.0 * macs
    assert least["bytes"] == 2 * 3 * 6 * 2 * (2 * 8 * 2 + 2 * 2 * 4 * 2
                                              + 2 * 2 * 4)
    model = _config()["model"]
    least = rule.least_seconds(model, {"seq_len": 8192}, 1, PEAK)
    assert least["layers"] == 3 and least["bound"] == "flops"
    per_chunk = 16 * 2 * 2080 * 128 + 32 * (3 * 2080 * 128
                                            + 3 * 64 * 128 * 128)
    assert least["macs_per_chunk"] == per_chunk
    assert least["flops"] == 3 * 128 * 3 * 2.0 * per_chunk
    assert least["flops"] == pytest.approx(0.3105e12, rel=1e-3)
    assert least["bytes"] == 3 * 8192 * 2 * (2 * 2048 * 2 + 2 * 4096 * 2
                                             + 2 * 32 * 4) == 1_220_542_464
    assert least["seconds"] == pytest.approx(1.576e-3, rel=1e-3)
    assert rule.read(_trace(), {}, _ctx()) == pytest.approx(
        100 * least["seconds"] / 3.5e-3)
    experts = _reader("qwen3n_experts_roofline")
    least = experts.least_seconds(model, {"seq_len": 8192}, 1, PEAK)
    assert least["rows"] == 5120                # 160 an expert
    assert least["flops"] == 4 * 5120 * 3 * 3 * 2.0 * 2048 * 512
    assert least["bytes"] == 4 * 2 * (3 * 32 * 3 * 2048 * 512
                                      + 4 * 5120 * 2048)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(3.36e-3, rel=1e-2)
    assert experts.read(_trace(), {}, _ctx()) == pytest.approx(
        100 * least["seconds"] / 1.5e-3)
    attn = _reader("qwen3n_attn_kernels_roofline")
    least = attn.least_seconds(model, {"seq_len": 8192}, 1, PEAK)
    assert least["pairs"] == 8192 * 8193 / 2        # one layer, the half
    assert least["flops"] == 16 * 7 * 2.0 * (8192 * 8193 / 2) * 256
    assert least["bytes"] == 8192 * (2 * 256 * 4 * (16 + 2) + 4 * 16)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(9.77e-3, rel=1e-2)
    assert attn.read(_trace(), {}, _ctx()) == pytest.approx(
        100 * least["seconds"] / 2.5e-3)

"""What PR 41 added to the benchmark, off the chip: the GLM-4.7-Flash
configuration's plain reference through the whole harness at toy size (the
rehearsal twin ``tiny_glm47``), its control, a reference that leaves out the
shared expert, the bias, the second depth's loss or the rotary term, a
hand-checked case of the reference's latent attention and of its routing, the
six readers on a hand-made trace, and the new entries of the manifest. No
number here comes from a device."""

import copy
import json
import math
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
CELL = "tiny_glm47.b8.s48"
NEW_CELL = "glm47_flash.b1.s8192.v19360"
NEW_METRICS = ["mla_ms", "mla_proj_ms", "mla_kernels_ms",
               "mla_kernels_roofline", "mtp_ms", "glm_moe_ms"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = xplane.Event
MS = 1_000_000


def _reader(name):
    return run_lib.load_module([BENCH], "layer_metrics", name)


def _config():
    with open(os.path.join(BENCH, "configs", "glm47_flash.json")) as fh:
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
    """Three steps through ``Trainer.train_epoch``: the loss with the second
    depth's term in it, the first gradient and the parameters' change."""
    _, result, extra = sound
    assert result["correct"], result["compared"]
    assert {r["number"] for r in result["compared"]} == set(compare.NUMBERS)
    ok, rows = _reference_again(sound)
    assert ok, rows
    ref = extra["reference"]
    assert ref["loss"][0] == pytest.approx(
        ref["loss_main"][0] + 0.3 * ref["loss_mtp"][0], rel=1e-6)
    assert ref["loss_mtp"][0] == pytest.approx(math.log(96), abs=0.6)
    assert any(path.startswith("mtp/") for path in ref["moment_norms"])


def test_twin_control_fails_the_limits(sound):
    ok, rows = _reference_again(
        sound, precision=sound[0]["config"]["control_precision"])
    assert not ok, rows


@pytest.mark.parametrize("left_out,change", [
    ("the shared expert", {"n_shared_experts": 0}),
    ("the bias in the choice", {"load_balance_coeff": 0.0}),
    ("the second depth's loss", {"mtp_loss_coeff": 0.0}),
    ("the second depth's weight", {"mtp_loss_coeff": 0.1}),
    ("the scaling factor", {"routed_scaling_factor": 1.0}),
    ("the rotary term", {"rope_theta": 1.0 + 1e-9})])
def test_twin_fails_on_a_step_that_leaves_a_piece_out(sound, left_out, change):
    """The program against a reference without the piece is a program without
    it against the reference: the limits part them."""
    ok, rows = _reference_again(sound, change)
    assert not ok, (left_out, rows)


# -- the reference by hand ---------------------------------------------------------


def _reference():
    from chipbench.references import glm47_flash as reference
    return reference


def test_reference_latent_attention_is_the_written_out_sum():
    """One head pair at toy widths, every step written out with numpy: the
    two low-rank paths with their norms, one rope key for both heads, rotary
    positions on the rope columns alone, scores over the joined width."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = _reference()
    z = {"heads": 2, "nope": 4, "rope": 2, "v": 6, "q_rank": 5, "kv_rank": 3}
    model = {"rms_norm_eps": 1e-5, "rope_theta": 100.0}
    keys = jax.random.split(jax.random.key(0), 8)
    d, S = 8, 5
    h = np.asarray(jax.random.normal(keys[0], (1, S, d)))
    w = {"attn/q_a/kernel": jax.random.normal(keys[1], (d, 5)),
         "attn/q_norm/scale": 1 + 0.1 * jax.random.normal(keys[2], (5,)),
         "attn/q_b/kernel": jax.random.normal(keys[3], (5, 2, 6)),
         "attn/kv_a/kernel": jax.random.normal(keys[4], (d, 3 + 2)),
         "attn/kv_norm/scale": 1 + 0.1 * jax.random.normal(keys[5], (3,)),
         "attn/kv_b/kernel": jax.random.normal(keys[6], (3, 2, 4 + 6)),
         "attn/out/kernel": jax.random.normal(keys[7], (2, 6, d))}
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        got = reference._attention(jnp.asarray(h), {
            k: jnp.asarray(v, jnp.float32) for k, v in w.items()}, z, model,
            lambda a: a)

    rms = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g

    def rope(x, pos):          # one vector of two columns: one pair
        angle = pos * 100.0 ** (-0.0)
        return np.array([x[0] * np.cos(angle) - x[1] * np.sin(angle),
                         x[1] * np.cos(angle) + x[0] * np.sin(angle)])

    x = h[0].astype(np.float64)
    c_q = rms(x @ w["attn/q_a/kernel"], w["attn/q_norm/scale"])
    down = x @ w["attn/kv_a/kernel"]
    c_kv = rms(down[:, :3], w["attn/kv_norm/scale"])
    k_r = np.stack([rope(down[i, 3:], i) for i in range(S)])
    out = np.zeros((S, d))
    for head in range(2):
        q = c_q @ w["attn/q_b/kernel"][:, head]               # [S, 6]
        kv = c_kv @ w["attn/kv_b/kernel"][:, head]            # [S, 10]
        q = np.concatenate([q[:, :4], np.stack(
            [rope(q[i, 4:], i) for i in range(S)])], -1)
        k = np.concatenate([kv[:, :4], k_r], -1)
        scores = q @ k.T / math.sqrt(6)
        scores[np.triu_indices(S, 1)] = -np.inf
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out += (p @ kv[:, 4:]) @ w["attn/out/kernel"][head]
    np.testing.assert_allclose(got[0], out, rtol=2e-4, atol=2e-4)


def test_reference_routes_a_hand_checked_case():
    """Two tokens, four experts of which the first two are held, two a token,
    scale 1.8, a bias that only chooses: token 0's scores sigmoid(2, 0, 1,
    -1) with bias (0, 0, 0, 3) choose experts 3 and 0 (the bias lifts the
    fourth over the third) with weights from the scores alone; token 1
    chooses 2 and 3, neither held: the shared expert alone."""
    import jax.numpy as jnp
    import numpy as np

    reference = _reference()
    h = jnp.array([[[1.0, 0.0], [0.0, 1.0]]])
    w = {"moe/router": jnp.array([[2.0, 0.0, 1.0, -1.0],
                                  [-3.0, -2.0, 2.0, 1.0]]),
         "moe/w_gate": jnp.array([[[1.0, -1.0], [0.0, 1.0]]] * 2),
         "moe/w_up": jnp.array([[[2.0, 1.0], [0.0, -2.0]]] * 2),
         "moe/w_down": jnp.array([[[1.0, 2.0], [5.0, 5.0]]] * 2),
         "moe/shared/gate/kernel": jnp.eye(2),
         "moe/shared/up/kernel": jnp.eye(2),
         "moe/shared/down/kernel": 2 * jnp.eye(2)}
    z = {"k": 2, "routed": 4, "first": 0, "held": 2}
    model = {"routed_scaling_factor": 1.8, "n_shared_experts": 1}
    bias = jnp.array([0.0, 0.0, 0.0, 3.0])
    y, counts = reference._experts(h, w, bias, z, model, lambda a: a)
    sig = lambda v: 1 / (1 + math.exp(-v))
    silu = lambda v: v * sig(v)
    weight = 1.8 * sig(2.0) / (sig(2.0) + sig(-1.0))
    # expert 0 on (1, 0): gate (1, -1) -> silu, up (2, 1), down
    hidden = np.array([silu(1.0) * 2.0, silu(-1.0) * 1.0])
    routed = weight * (hidden @ np.array([[1.0, 2.0], [5.0, 5.0]]))
    shared0 = np.array([silu(1.0) * 1.0 * 2, 0.0])
    np.testing.assert_allclose(y[0, 0], routed + shared0, rtol=1e-5)
    np.testing.assert_allclose(y[0, 1], [0.0, silu(1.0) * 2], rtol=1e-5)
    np.testing.assert_array_equal(counts, [1, 0, 1, 2])
    # the bias after a step: expert 3, over the mean of 1, goes down, expert
    # 1, under it, goes up, the two at the mean stay (the deltas' mean is 0)
    moved = reference.next_biases(bias[None], counts[None],
                                  {"load_balance_coeff": 0.1})
    np.testing.assert_allclose(moved[0], [0.0, 0.1, 0.0, 2.9], atol=1e-6)
    moved = reference.next_biases(bias[None], jnp.array([[0.0, 0, 0, 4]]),
                                  {"load_balance_coeff": 0.1})
    np.testing.assert_allclose(moved[0], [0.05, 0.05, 0.05, 2.85], atol=1e-6)


def test_reference_step_is_adamw_by_hand(sound):
    """One step of the reference's own AdamW from the twin's weights: after a
    first Adam step an entry with a gradient moves by the learning rate (the
    gradient's sign), a matrix by the decay of 0.1 of itself besides; and the
    first moment times ``first_moment_scale`` is the clipped gradient."""
    import jax

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
    for path in ("lm_head/kernel", "mtp/eh_proj/kernel",
                 "mtp/mtp_block/moe/w_down", "block_2/moe/w_down"):
        sign_step = lr * math.sqrt(sizes[path])
        assert 0.9 * sign_step - lr * wd * norms[path] \
            <= out["dparam_norms"][path] \
            <= sign_step * (1 + 1e-5) + lr * wd * norms[path], path
    total = math.sqrt(sum(v * v for v in out["moment_norms"].values()))
    assert total <= config["optimizer"]["grad_clip"] * (1 + 1e-5)
    assert out["biases"].shape == (4, 8) and not out["biases"][0].any()
    assert abs(out["biases"][1:]).max() == pytest.approx(0.05, rel=0.51)


# -- the configuration's file and the manifest's new entries -----------------------


def test_configuration_keeps_every_published_key():
    config = _config()
    model = config["model"]
    own = {"routed_experts", "held_experts_start", "held_layers", "mtp_layer",
           "mtp_loss_coeff", "load_balance_coeff"}
    assert own <= set(model)
    assert {k: config[k] for k in model if k not in own} == {
        k: v for k, v in model.items() if k not in own}
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert [model[k] for k in config["reduced"]] == [5, 8, 19360]
    assert [config["published"][k] for k in config["reduced"]] == [
        47, 64, 154880]
    assert (model["routed_experts"], model["held_layers"], model["mtp_layer"],
            model["held_experts_start"], model["mtp_loss_coeff"]) == (
                64, [0, 1, 2, 3, 4], 47, 0, 0.3)
    for text in ("706,518,528", "experts 0..7", "rows 0..19,359",
                 "layers 0..4", "layer 47", "Eight chips"):
        assert text in config["deployment"], text
    assert set(config["limits"]) == set(compare.NUMBERS)
    for key in ("layer", "attention", "expert_ffn", "bias", "mtp",
                "optimizer", "init", "lr_schedule", "data", "provenance"):
        assert config["assumed"][key]
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
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "routed_scaling_factor",
                "first_k_dense_replace", "num_nextn_predict_layers",
                "rope_theta"):
        assert model[key] == row["config"][key], key


def test_held_parameters_are_the_modules_own_leaves():
    """706,518,528: the configuration's count, the family's ``num_params``
    and the leaves of the module that the preset builds; the reference's
    leaves are the program's, name for name."""
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
    assert held == 706_518_528 == (84_677_888 + 4 * 106_829_056
                                   + 79_298_560 + 2_048 + 115_223_808)
    assert len(jax.tree.leaves(shapes["batch_stats"])) == 5
    reference = _reference()
    assert bundle.fwd_flops_per_example == pytest.approx(
        reference.forward_flops(config["model"], {"seq_len": 8192}),
        rel=1e-12)
    flat = weights.flatten(shapes["params"])
    assert flat["mtp/mtp_block/attn/kv_b/kernel"].shape == (512, 20, 448)
    assert all(any(re.search(rule[0], path) for rule in config["init"])
               for path in flat)


@pytest.mark.parametrize("path,kind,value", [
    ("block_0/attn_norm/scale", "const", 1.0),
    ("block_2/attn/q_norm/scale", "const", 1.0),
    ("block_2/attn/kv_norm/scale", "const", 1.0),
    ("mtp/enorm/scale", "const", 1.0),
    ("mtp/head_norm/scale", "const", 1.0),
    ("final_norm/scale", "const", 1.0),
    ("embed/embedding", "normal", 1.0),
    ("block_3/attn/out/kernel", "normal", 0.002),
    ("mtp/mtp_block/attn/out/kernel", "normal", 0.002),
    ("block_3/attn/q_b/kernel", "normal", 0.02),
    ("block_3/attn/kv_a/kernel", "normal", 0.02),
    ("block_1/moe/router", "normal", 0.02),
    ("block_1/moe/w_gate", "normal", 0.02),
    ("block_1/moe/shared/down/kernel", "normal", 0.02),
    ("mtp/eh_proj/kernel", "normal", 0.02),
    ("lm_head/kernel", "normal", 0.02)])
def test_init_rules_reach_the_leaves_they_name(path, kind, value):
    rule = next(r for r in _config()["init"] if re.search(r[0], path))
    assert rule[1:] == [kind, value]


def _mid_size_shapes(model):
    """The reference's flat layout for ``model``, with no program behind it."""
    import jax
    import jax.numpy as jnp

    d, f, ff = (model["hidden_size"], model["moe_intermediate_size"],
                model["intermediate_size"])
    H, V = model["num_attention_heads"], model["vocab_size"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    out = {"embed/embedding": (V, d), "final_norm/scale": (d,),
           "lm_head/kernel": (d, V), "mtp/enorm/scale": (d,),
           "mtp/hnorm/scale": (d,), "mtp/head_norm/scale": (d,),
           "mtp/eh_proj/kernel": (2 * d, d)}
    blocks = [(f"block_{i}/", i < model["first_k_dense_replace"])
              for i in range(model["num_hidden_layers"])]
    for p, dense in blocks + [("mtp/mtp_block/", False)]:
        out[p + "attn_norm/scale"] = out[p + "ffn_norm/scale"] = (d,)
        out[p + "attn/q_a/kernel"] = (d, model["q_lora_rank"])
        out[p + "attn/q_norm/scale"] = (model["q_lora_rank"],)
        out[p + "attn/q_b/kernel"] = (model["q_lora_rank"], H, qk)
        out[p + "attn/kv_a/kernel"] = (
            d, model["kv_lora_rank"] + model["qk_rope_head_dim"])
        out[p + "attn/kv_norm/scale"] = (model["kv_lora_rank"],)
        out[p + "attn/kv_b/kernel"] = (
            model["kv_lora_rank"], H,
            model["qk_nope_head_dim"] + model["v_head_dim"])
        out[p + "attn/out/kernel"] = (H, model["v_head_dim"], d)
        if dense:
            for n, shape in (("gate", (d, ff)), ("up", (d, ff)),
                             ("down", (ff, d))):
                out[p + f"{n}/kernel"] = shape
            continue
        out[p + "moe/router"] = (d, model["routed_experts"])
        for n, shape in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d))):
            out[p + f"moe/w_{n}"] = (model["n_routed_experts"], *shape)
            out[p + f"moe/shared/{n}/kernel"] = shape
    return {k: jax.ShapeDtypeStruct(v, jnp.float32) for k, v in out.items()}


@pytest.mark.parametrize("seed", [5, 2147483999])
def test_init_gives_every_seed_the_same_routed_work(seed):
    """Why the embedding starts at 1 and the attention's out projection at a
    tenth of the other kernels (PR 35's choice, for PR 35's reason: nothing
    norms a branch here either): on uniform random tokens the router's 64
    loads at the start are level whatever the seed, in every expert layer and
    in the prediction module's, so the held experts see the rows the
    expectation says; with everything at 0.02 the loads spread from the first
    expert layer on and the held rows follow the seed. The configuration's
    layers, routing and init at a width a CPU takes (hidden 512, 1,024
    tokens), through the plain reference; at the published width the readings
    are in the configuration's ``assumed``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = _reference()
    config = _config()
    model = dict(config["model"], hidden_size=512, num_attention_heads=8,
                 q_lora_rank=192, kv_lora_rank=128, qk_nope_head_dim=48,
                 qk_rope_head_dim=16, v_head_dim=64, intermediate_size=1024,
                 moe_intermediate_size=128, vocab_size=2048)
    shapes, key = _mid_size_shapes(model), weights.seed_key(seed)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (1, 1024), 0,
                                model["vocab_size"])
    rows = reference.bias_rows(model)

    def loads(rules):
        params = jax.jit(lambda k: weights.make_flat(shapes, rules, k))(key)

        def run(p, t):
            biases = jnp.zeros((rows, model["routed_experts"]))
            normed, counts = reference.hidden_fn(p, biases, t, model)
            _, c = reference.mtp_hidden_fn(p, biases[-1], normed, t, model)
            return jnp.concatenate([counts[1:], c[None]])
        return np.asarray(jax.jit(run)(params, tokens))

    level = loads(config["init"])
    plain = loads([["scale$", "const", 1.0], [".*", "normal", 0.02]])
    assert level.shape == (5, 64) and (level.sum(-1) == 4096).all()
    spread = lambda c: c.std(-1) / c.mean(-1)
    # 64 rows an expert: sampling alone spreads them by 0.125
    assert spread(level).max() < 0.25 < 0.5 < spread(plain).max(), (
        spread(level), spread(plain))
    held = level[:, :model["n_routed_experts"]].sum(-1)
    expected = 1024 * model["num_experts_per_tok"] * model[
        "n_routed_experts"] / model["routed_experts"]
    # 512 rows: sampling alone is 4% (one sigma)
    assert np.all(np.abs(held / expected - 1) < 0.18), held


def test_manifest_gained_one_configuration_one_cell_and_six_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    assert manifest["configs"][-1]["name"] == "glm47_flash"
    assert manifest["configs"][-1]["reduced"] == _config()["reduced"]
    assert manifest["configs"][-1]["source"] == _config()["source"]
    assert manifest["configs"][-1]["file"] == "chipbench/configs/glm47_flash.json"
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        NEW_CELL, "glm47_flash", "b1.s8192.v19360", 1)
    assert len(cell["why"]) <= 200
    new = {m["name"]: m for m in manifest["per_layer"][-6:]}
    assert list(new) == NEW_METRICS
    # what was there is where it was, before the new entries
    assert [m["name"] for m in manifest["per_layer"][-12:-6]] == [
        "expert_block_ms", "expert_route_ms", "expert_matmul_ms",
        "expert_matmul_roofline", "attn_kernels_ms", "attn_kernels_roofline"]
    assert [w["name"] for w in manifest["workloads"][:-1]] == [
        "gpt2_124m.b24.s1024", "granite4_h_micro.b1.s4096",
        "trinity_mini.b1.s8192", "smallthinker_21b.b1.s8192.v37984"]
    for metric in new.values():
        assert metric["workloads"] == [NEW_CELL]
        assert metric["moves"] == "examples_per_s_chip"
        assert metric["source"] == "device_trace"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert new["mla_kernels_roofline"]["unit"] == "%"
    assert {new[n]["layer"] for n in ("mla_ms", "mla_proj_ms", "mtp_ms",
                                      "glm_moe_ms")} == {"model step"}
    assert {new[n]["layer"] for n in ("mla_kernels_ms",
                                      "mla_kernels_roofline")} == {"kernels"}
    with open(os.path.join(BENCH, "traffic", "b1.s8192.v19360.json")) as fh:
        traffic = json.load(fh)
    assert traffic["overrides"] == {"global_batch_size": 1, "seq_len": 8192}
    assert traffic["data"] == {"kind": "tokens", "seq_len": 8192,
                               "vocab_size": 19360}
    assert (traffic["driver"], traffic["warmup_steps"],
            traffic["trace_seconds"]) == ("train_window", 5, 3.0)
    # the cells that were there report what they reported
    for old in ("trinity_mini.b1.s8192", "smallthinker_21b.b1.s8192.v37984"):
        assert not set(new) & set(run_lib.context(old, 1, 1.0, 1)["per_layer"])
    ours = run_lib.context(NEW_CELL, 1, 1.0, 1)["per_layer"]
    assert set(new) <= set(ours)
    # and the metrics without a list report here by themselves
    assert {"step_mfu_pct", "optimizer_ms", "region_coverage_pct",
            "step_mem_gb", "device_idle_pct", "device_step_ms",
            "setup_init_s", "setup_xla_compile_s"} <= set(ours)
    assert not {"moe_ms", "gmm_roofline", "attn_kernels_ms",
                "expert_block_ms"} & set(ours)


# -- the readers, on a hand-made trace ----------------------------------------------

# Two whole steps of 14 ms on device 0 between a first and a last that the
# trace cut short. A step, in a main block: the q path 0.5, the kv path 0.5,
# the rotary terms and joins 0.25, a transpose ahead of the kernel (under
# ``mla``, in no inner scope) 0.25, the forward kernel 1.0, the out
# projection 0.5; the expert layer under ``mlp/moe``: a conditional that
# spans 2.0 and is no operation of its own, the gathers 0.5, the grouped
# matmul 1.5; in the prediction module: the merge 0.25, its own q path 0.5,
# its backward dkv kernel 1.5 and dq kernel 1.0, its router 0.25, its head and
# loss 0.75; the main head 0.5, an unnamed copy 0.5.
_STEP = [("fusion.1", 0.0, 0.5), ("fusion.2", 0.5, 1.0),
         ("fusion.3", 1.0, 1.25), ("copy.4", 1.25, 1.5),
         ("flash_fwd_online.5", 1.5, 2.5), ("fusion.6", 2.5, 3.0),
         ("cond.7", 3.0, 5.0), ("fusion.8", 3.0, 3.5),
         ("grouped_matmul.9", 3.5, 5.0), ("fusion.10", 5.0, 5.25),
         ("fusion.11", 5.25, 5.75), ("flash_bwd_dkv.12", 5.75, 7.25),
         ("flash_bwd_dq.13", 7.25, 8.25), ("fusion.14", 8.25, 8.5),
         ("fusion.15", 8.5, 9.25), ("fusion.16", 9.25, 9.75),
         ("copy.17", 9.75, 10.25)]
_PRE = "jit(train_step)/jvp(GlmMoeLite)/checkpoint/block_1/"
_MTP = "jit(train_step)/jvp(GlmMoeLite)/mtp/mtp/"
_MTP_BWD = "jit(train_step)/transpose(jvp(GlmMoeLite))/mtp/mtp/checkpoint/mtp_block/"
_KERNEL = 'custom_call_target="tpu_custom_call", '


def _line(name, scope, kernel=False):
    return (f'  %{name} = bf16[8]{{0}} {"custom-call" if kernel else "fusion"}'
            f'(%p), {_KERNEL if kernel else ""}'
            f'metadata={{op_name="{scope}"}}')


STEP_TEXT = "\n".join(["ENTRY %main {"] + [
    _line("fusion.1", _PRE + "attn/mla/mla_q/q_b/dot_general"),
    _line("fusion.2", _PRE + "attn/mla/mla_kv/kv_norm/mul"),
    _line("fusion.3", _PRE + "attn/mla/mla_rope/concatenate"),
    _line("copy.4", _PRE + "attn/mla/transpose"),
    _line("flash_fwd_online.5", _PRE + "attn/mla/flash_fwd_online/pallas_call",
          kernel=True),
    _line("fusion.6", _PRE + "attn/mla/mla_out/out/dot_general"),
    _line("cond.7", _PRE + "mlp/moe/cond"),
    _line("fusion.8", _PRE + "mlp/moe/cond/branch_0_fun/moe_dispatch/gather"),
    _line("grouped_matmul.9", _PRE + "mlp/moe/cond/branch_0_fun/moe_experts/"
          "grouped_matmul/pallas_call", kernel=True),
    _line("fusion.10", _MTP + "mtp_merge/embed/eh_proj/dot_general"),
    _line("fusion.11", _MTP_BWD + "attn/mla/mla_q/q_a/dot_general"),
    _line("flash_bwd_dkv.12", _MTP_BWD + "attn/mla/flash_bwd_dkv/pallas_call",
          kernel=True),
    _line("flash_bwd_dq.13", _MTP_BWD + "attn/mla/flash_bwd_dq/pallas_call",
          kernel=True),
    _line("fusion.14", _MTP_BWD + "mlp/moe/moe_router/top_k"),
    _line("fusion.15", "jit(train_step)/jvp(GlmMoeLite)/mtp/head_loss/"
          "checkpoint/dot_general"),
    _line("fusion.16", "jit(train_step)/jvp(GlmMoeLite)/head_loss/lm_head/"
          "dot_general"),
    "  %copy.17 = bf16[8]{0} copy(%p)", "}"])


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
    ("mla_ms", 6.0), ("mla_proj_ms", 2.25), ("mla_kernels_ms", 3.5),
    ("mtp_ms", 4.25), ("glm_moe_ms", 2.25)])
def test_readers_sum_their_scopes_or_their_kernels(metric, want):
    """``mla_ms``: the four inner scopes (1.75 + 0.5 in the module's block),
    the transpose between them (0.25) and the three kernels (3.5), in the
    main block and in the module's. ``mtp_ms``: merge, block, head and loss.
    ``glm_moe_ms``: the main block's 2.0 without its ``cond`` and the
    module's router."""
    assert _reader(metric).read(_trace(), {}, _ctx()) == pytest.approx(want)


def test_the_mla_row_splits_it_by_inner_scope_and_kernel(capsys):
    _reader("mla_ms").read(_trace(), {}, _ctx())
    row = next(json.loads(l) for l in capsys.readouterr().out.splitlines()
               if l.startswith('{"row": "mla"'))
    assert row["by_scope_ms"] == pytest.approx(
        {"flash_bwd_dkv": 1.5, "mla_q": 1.0, "flash_fwd_online": 1.0,
         "flash_bwd_dq": 1.0, "mla_kv": 0.5, "mla_out": 0.5, "mla_rope": 0.25,
         "other": 0.25})
    assert row["top_ops"][0]["op"] == "flash_bwd_dkv.12"
    assert row["steps"] == 2


def test_readers_give_nothing_without_their_names(capsys):
    """The parent's step has no such scope and a dense model's has none at
    all: no value and no exception, with a trace and without one."""
    ctx = {**_ctx(), "step_text": STEP_TEXT.replace("mla", "gqa")
           .replace("mtp", "aux").replace("moe", "ffn")
           .replace("flash_", "splash_")}
    for metric in NEW_METRICS:
        assert _reader(metric).read(_trace(), {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, _ctx()) is None, metric
    assert '"missing"' in capsys.readouterr().out


def test_mla_roofline_counts_both_widths_in_every_block():
    reader = _reader("mla_kernels_roofline")
    least = reader.least_seconds(_config()["model"], {"seq_len": 8192}, 1,
                                 PEAK)
    causal = sum(i + 1 for i in range(8192))
    assert causal == 33_558_528 == least["pairs"] and least["layers"] == 6
    # four products at the query/key width, three at the value width: both 256
    assert least["flops"] == 6 * 20 * 2.0 * causal * (4 * 256 + 3 * 256)
    assert least["flops"] == pytest.approx(14.43e12, rel=1e-3)
    assert least["bytes"] == 6 * 8192 * 20 * (2 * 4 * 512 + 4)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(73.26e-3, rel=1e-3)
    share = reader.read(_trace(), {}, _ctx())
    assert share == pytest.approx(100 * 73.26 / 3.5, rel=1e-3)
    # unequal widths, fewer layers, two sequences
    model = dict(_config()["model"], v_head_dim=128, num_hidden_layers=2,
                 num_nextn_predict_layers=0)
    assert reader.least_seconds(model, {"seq_len": 2048}, 2, PEAK)[
        "flops"] == 2 * 2 * 20 * 2.0 * (2048 * 2049 / 2) * (4 * 256 + 3 * 128)

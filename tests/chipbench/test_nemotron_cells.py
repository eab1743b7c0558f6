"""What PR 43 added to the benchmark, off the chip: the Nemotron-3-Nano
configuration's plain reference through the whole harness at toy size (the
rehearsal twin ``tiny_nemotron``), its control, a reference that leaves a piece
out, a hand-checked case of the reference's grouped scan, the configuration
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
CELL = "tiny_nemotron.b8.s48"
NEW_CELL = "nemotron3_nano.b1.s8192.v16384"
NEW_METRICS = ["nemo_mamba_ms", "nemo_ssd_ms", "nemo_ssd_roofline",
               "nemo_moe_ms", "nemo_route_ms", "nemo_experts_ms",
               "nemo_experts_roofline", "nemo_attn_kernels_ms"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = xplane.Event
MS = 1_000_000


def _reader(name):
    return run_lib.load_module([BENCH], "layer_metrics", name)


def _config():
    with open(os.path.join(BENCH, "configs", "nemotron3_nano.json")) as fh:
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
    gradient and the parameters' change, every kind of layer in them."""
    _, result, extra = sound
    assert result["correct"], result["compared"]
    assert {r["number"] for r in result["compared"]} == set(compare.NUMBERS)
    ok, rows = _reference_again(sound)
    assert ok, rows
    paths = set(extra["reference"]["moment_norms"])
    for leaf in ("block_0/mamba/A_log", "block_1/moe/w_up",
                 "block_1/moe/shared/down/kernel", "block_2/attn/key/kernel",
                 "block_4/mamba/norm/scale", "lm_head/kernel"):
        assert leaf in paths, leaf
    assert not any("w_gate" in p for p in paths)
    counts = extra["reference"]["counts"][0]         # [blocks, routed]
    assert counts.shape == (5, 8) and counts[1].sum() == 8 * 48 * 2
    assert counts[0].sum() == counts[2].sum() == 0


def test_twin_control_fails_the_limits(sound):
    ok, rows = _reference_again(
        sound, precision=sound[0]["config"]["control_precision"])
    assert not ok, rows


@pytest.mark.parametrize("left_out,change", [
    ("the shared expert", {"n_shared_experts": 0}),
    ("the bias in the choice", {"load_balance_coeff": 0.0}),
    ("the scaling factor", {"routed_scaling_factor": 1.0}),
    ("the B/C groups", {"n_groups": 1}),
    ("the held experts' place", {"held_experts_start": 4})])
def test_twin_fails_on_a_step_that_leaves_a_piece_out(sound, left_out, change):
    """The program against a reference without the piece is a program without
    it against the reference: the limits part them (one group of B and C
    reads other columns of the same ``xBC``: another model of the same
    leaves but the conv's and ``in_proj``'s, which the weights' maker then
    cannot fill: that too is a failure)."""
    try:
        ok, rows = _reference_again(sound, change)
    except (TypeError, ValueError) as e:
        ok, rows = False, str(e)
    assert not ok, (left_out, rows)


# -- the reference by hand -----------------------------------------------------------


def test_reference_scan_is_the_recurrence_group_by_group():
    """The reference's closed form on a mixer whose projections are the
    identity-like pieces of a hand-made ``in_proj``: against a token-by-token
    recurrence in which head ``h`` reads group ``h // 2`` of four heads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.references import nemotron3_nano as reference

    H, P, N, G, S, d = 4, 2, 3, 2, 6, 5
    model = {"mamba_num_heads": H, "mamba_head_dim": P, "ssm_state_size": N,
             "n_groups": G, "conv_kernel": 1, "hidden_size": d,
             "head_dim": 2, "num_attention_heads": 2,
             "num_key_value_heads": 1, "n_routed_experts": 1,
             "held_experts_start": 0, "routed_experts": 2,
             "num_experts_per_tok": 1, "layer_norm_epsilon": 1e-5,
             "hybrid_override_pattern": "M", "held_layers": [0],
             "num_hidden_layers": 1}
    z = reference._sizes(model)
    inner, conv = H * P, H * P + 2 * G * N
    keys = jax.random.split(jax.random.key(0), 4)
    w = {"mamba/in_proj/kernel": jax.random.normal(keys[0],
                                                   (d, inner + conv + H)),
         "mamba/conv_kernel": jnp.ones((1, conv)),
         "mamba/conv_bias": jnp.zeros((conv,)),
         "mamba/dt_bias": jnp.zeros((H,)),
         "mamba/A_log": 0.3 * jax.random.normal(keys[1], (H,)),
         "mamba/D": jnp.arange(1.0, H + 1),
         "mamba/norm/scale": jnp.ones((inner,)),
         "mamba/out_proj/kernel": jnp.eye(inner)}
    h = jax.random.normal(keys[2], (1, S, d))
    with jax.default_matmul_precision("highest"):
        got = reference._mamba(h, w, z, lambda a: a)[0]
        gate, xBC, dt = jnp.split(h[0] @ w["mamba/in_proj/kernel"],
                                  [inner, inner + conv], -1)
    xBC = jax.nn.silu(xBC)
    x, B, C = (np.asarray(a) for a in jnp.split(xBC, [inner, inner + G * N],
                                                -1))
    dt, A = np.asarray(jax.nn.softplus(dt)), -np.exp(np.asarray(
        w["mamba/A_log"]))
    y = np.zeros((S, H, P))
    for head in range(H):
        grp = head // (H // G)
        state = np.zeros((P, N))
        for t in range(S):
            xt = x[t, head * P:(head + 1) * P]
            state = np.exp(dt[t, head] * A[head]) * state + dt[t, head] \
                * np.outer(xt, B[t, grp * N:(grp + 1) * N])
            y[t, head] = state @ C[t, grp * N:(grp + 1) * N] \
                + (head + 1) * xt
    y = y.reshape(S, inner) * np.asarray(jax.nn.silu(gate))
    y = y.reshape(S, G, -1)
    y = (y / np.sqrt((y ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(S, inner)
    np.testing.assert_allclose(got, y, rtol=2e-5, atol=2e-6)


# -- the configuration's file and the manifest's new entries -----------------------


def test_configuration_keeps_every_published_key():
    config = _config()
    model = config["model"]
    own = {"routed_experts", "held_experts_start", "held_layers",
           "load_balance_coeff"}
    assert own <= set(model)
    assert {k: config[k] for k in model if k not in own} == {
        k: v for k, v in model.items() if k not in own}
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert [model[k] for k in config["reduced"]] == [9, 8, 16384]
    assert [config["published"][k] for k in config["reduced"]] == [
        52, 128, 131072]
    assert config["published"]["parameters"] == 31_577_937_344
    assert (model["routed_experts"], model["held_layers"],
            model["held_experts_start"]) == (128, list(range(9)), 0)
    assert "".join(model["hybrid_override_pattern"][j]
                   for j in model["held_layers"]) == "MEMEM*EME"
    for text in ("666,962,944", "experts 0..7", "rows 0..16,383",
                 "layers 0..8", "Sixteen chips", "384 rows"):
        assert text in config["deployment"], text
    assert set(config["limits"]) == set(compare.NUMBERS)
    for key in ("stream", "mamba", "attention", "expert_ffn", "bias",
                "optimizer", "init", "lr_schedule", "data", "provenance"):
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
                "head_dim", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
                "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "expand"):
        assert model[key] == row["config"][key], key


def test_held_parameters_are_the_modules_own_leaves():
    """666,962,944: the configuration's count, the family's ``num_params``
    and the leaves of the module that the preset builds, none padded; the
    init's rules reach the leaves they name."""
    import jax
    import numpy as np

    from pytorch_distributed_training_example_tpu.core import (
        trainer as trainer_lib)
    from pytorch_distributed_training_example_tpu.models import nemotron_h
    from pytorch_distributed_training_example_tpu.utils.config import (
        from_preset)

    config = _config()
    bundle = trainer_lib.build_model(from_preset(
        config["preset"], **config["overrides"]))
    module = bundle.module
    assert nemotron_h.num_params(module) == 666_962_944
    shapes = weights.flatten(jax.eval_shape(lambda: module.init(
        jax.random.key(0), *bundle.input_template, train=False))["params"])
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == 666_962_944
    assert shapes["block_1/moe/w_up"].shape == (8, 2688, 1856)
    assert shapes["block_1/moe/w_down"].shape == (8, 1856, 2688)
    assert shapes["block_0/mamba/in_proj/kernel"].shape == (2688, 10304)
    assert bundle.fwd_flops_per_example == pytest.approx(
        run_lib.load_module([BENCH], "references", "nemotron3_nano")
        .forward_flops(config["model"], {"seq_len": 8192}), rel=1e-12)
    small = {p: jax.ShapeDtypeStruct((2, 2), s.dtype)
             for p, s in shapes.items()}
    made = weights.make_flat(small, config["init"], weights.seed_key(1))
    kinds = {p: (float(np.abs(v).max()), float(np.asarray(v).std()))
             for p, v in made.items()}
    assert kinds["block_0/mamba/dt_bias"] == (3.0, 0.0)
    assert kinds["block_0/mamba/D"] == kinds["block_4/norm/scale"] == (1.0, 0.0)
    assert kinds["block_0/mamba/conv_bias"] == (0.0, 0.0)
    for leaf in ("block_0/mamba/out_proj/kernel", "block_5/attn/out/kernel",
                 "block_1/moe/shared/down/kernel", "block_1/moe/w_down"):
        assert 0 < kinds[leaf][0] < 3e-3, leaf        # the closing kernels
    for leaf in ("block_1/moe/router", "block_1/moe/w_up",
                 "block_0/mamba/in_proj/kernel", "lm_head/kernel"):
        assert 5e-3 < kinds[leaf][0] < 0.1, leaf
    assert kinds["embed/embedding"][0] > 0.3


def test_manifest_gained_one_configuration_one_cell_and_eight_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    assert manifest["configs"][-1]["name"] == "nemotron3_nano"
    assert manifest["configs"][-1]["reduced"] == _config()["reduced"]
    assert manifest["configs"][-1]["source"] == _config()["source"]
    assert manifest["configs"][-1]["file"] == \
        "chipbench/configs/nemotron3_nano.json"
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        NEW_CELL, "nemotron3_nano", "b1.s8192.v16384", 1)
    assert len(cell["why"]) <= 200 and len(manifest["configs"][-1]["why"]) <= 200
    new = {m["name"]: m for m in manifest["per_layer"][-8:]}
    assert list(new) == NEW_METRICS
    # what was there is where it was, before the new entries
    assert [m["name"] for m in manifest["per_layer"][-14:-8]] == [
        "mla_ms", "mla_proj_ms", "mla_kernels_ms", "mla_kernels_roofline",
        "mtp_ms", "glm_moe_ms"]
    assert [w["name"] for w in manifest["workloads"][:-1]] == [
        "gpt2_124m.b24.s1024", "granite4_h_micro.b1.s4096",
        "trinity_mini.b1.s8192", "smallthinker_21b.b1.s8192.v37984",
        "glm47_flash.b1.s8192.v19360"]
    for metric in new.values():
        assert metric["workloads"] == [NEW_CELL]
        assert metric["moves"] == "examples_per_s_chip"
        assert metric["source"] == "device_trace"
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert {n for n in new if new[n]["unit"] == "%"} == {
        "nemo_ssd_roofline", "nemo_experts_roofline"}
    assert {new[n]["layer"] for n in ("nemo_mamba_ms", "nemo_moe_ms",
                                      "nemo_route_ms")} == {"model step"}
    assert {new[n]["layer"] for n in NEW_METRICS if n not in (
        "nemo_mamba_ms", "nemo_moe_ms", "nemo_route_ms")} == {"kernels"}
    with open(os.path.join(BENCH, "traffic", "b1.s8192.v16384.json")) as fh:
        traffic = json.load(fh)
    assert traffic["overrides"] == {"global_batch_size": 1, "seq_len": 8192}
    assert traffic["data"] == {"kind": "tokens", "seq_len": 8192,
                               "vocab_size": 16384}
    assert (traffic["driver"], traffic["warmup_steps"],
            traffic["trace_seconds"]) == ("train_window", 5, 3.0)
    # the cells that were there report what they reported
    for old in ("granite4_h_micro.b1.s4096", "trinity_mini.b1.s8192",
                "glm47_flash.b1.s8192.v19360"):
        assert not set(new) & set(run_lib.context(old, 1, 1.0, 1)["per_layer"])
    ours = run_lib.context(NEW_CELL, 1, 1.0, 1)["per_layer"]
    assert set(new) <= set(ours)
    # and the metrics without a list report here by themselves
    assert {"step_mfu_pct", "optimizer_ms", "region_coverage_pct",
            "step_mem_gb", "device_idle_pct", "device_step_ms",
            "setup_init_s", "setup_xla_compile_s"} <= set(ours)
    assert not {"moe_ms", "ssd_ms", "mamba_mixer_ms", "attn_kernels_ms",
                "glm_moe_ms"} & set(ours)


# -- the readers, on a hand-made trace ----------------------------------------------

# Two whole steps of 12 ms on device 0 between a first and a last that the
# trace cut short. A step: in a Mamba block the in projection 1.0, the conv
# 0.5, the scan's cumulative sums 0.25 and its forward kernel 0.75, the gated
# norm 0.5; in an expert block under ``mlp/moe``: the router 0.5, a
# conditional that spans 2.0 and is no operation of its own, the gathers
# 0.5, the grouped matmul 1.5, the shared expert 1.0; the attention block's
# forward kernel 1.0; the scan's backward kernel 1.5; the head 0.5, an unnamed
# copy 0.5.
_STEP = [("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 1.5),
         ("fusion.3", 1.5, 1.75), ("ssd_fwd.4", 1.75, 2.5),
         ("fusion.5", 2.5, 3.0), ("fusion.6", 3.0, 3.5),
         ("cond.7", 3.5, 5.5), ("fusion.8", 3.5, 4.0),
         ("grouped_matmul.9", 4.0, 5.5), ("fusion.10", 5.5, 6.5),
         ("flash_fwd_online.11", 6.5, 7.5), ("ssd_bwd.12", 7.5, 9.0),
         ("fusion.13", 9.0, 9.5), ("copy.14", 9.5, 10.0)]
_PRE = "jit(train_step)/jvp(NemotronH)/checkpoint/"
_BWD = "jit(train_step)/transpose(jvp(NemotronH))/checkpoint/"
_KERNEL = 'custom_call_target="tpu_custom_call", '


def _line(name, scope, kernel=False):
    return (f'  %{name} = bf16[8]{{0}} {"custom-call" if kernel else "fusion"}'
            f'(%p), {_KERNEL if kernel else ""}'
            f'metadata={{op_name="{scope}"}}')


STEP_TEXT = "\n".join(["ENTRY %main {"] + [
    _line("fusion.1", _PRE + "block_0/mamba/in_proj/dot_general"),
    _line("fusion.2", _PRE + "block_0/mamba/conv1d/add"),
    _line("fusion.3", _PRE + "block_0/mamba/ssd/dot_general"),
    _line("ssd_fwd.4", _PRE + "block_0/mamba/ssd/ssd_fwd/pallas_call",
          kernel=True),
    _line("fusion.5", _PRE + "block_0/mamba/gated_norm/norm/mul"),
    _line("fusion.6", _PRE + "block_1/mlp/moe/moe_router/top_k"),
    _line("cond.7", _PRE + "block_1/mlp/moe/cond"),
    _line("fusion.8", _PRE + "block_1/mlp/moe/cond/branch_0_fun/moe_dispatch/"
          "gather"),
    _line("grouped_matmul.9", _PRE + "block_1/mlp/moe/cond/branch_0_fun/"
          "moe_experts/grouped_matmul/pallas_call", kernel=True),
    _line("fusion.10", _PRE + "block_1/mlp/moe/moe_shared/shared/up/"
          "dot_general"),
    _line("flash_fwd_online.11", _PRE + "block_5/attn/flash_fwd_online/"
          "pallas_call", kernel=True),
    _line("ssd_bwd.12", _BWD + "block_0/mamba/ssd/ssd_bwd/pallas_call",
          kernel=True),
    _line("fusion.13", "jit(train_step)/jvp(NemotronH)/head_loss/lm_head/"
          "dot_general"),
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
    ("nemo_mamba_ms", 4.5), ("nemo_ssd_ms", 2.5), ("nemo_moe_ms", 3.5),
    ("nemo_route_ms", 1.0), ("nemo_experts_ms", 1.5),
    ("nemo_attn_kernels_ms", 1.0)])
def test_readers_sum_their_scopes_or_their_kernels(metric, want):
    """``nemo_mamba_ms``: the projection, the conv, the scan both ways and
    the gated norm; ``nemo_ssd_ms``: the sums and both kernels;
    ``nemo_moe_ms``: the expert block without its ``cond``;
    ``nemo_route_ms``: router and gathers."""
    assert _reader(metric).read(_trace(), {}, _ctx()) == pytest.approx(want)


def test_readers_give_nothing_without_their_names(capsys):
    """The parent's step has no such scope and a dense model's has none at
    all: no value and no exception, with a trace and without one."""
    ctx = {**_ctx(), "step_text": STEP_TEXT.replace("mamba", "mixer")
           .replace("ssd", "scan").replace("moe", "ffn")
           .replace("flash_", "splash_")}
    for metric in NEW_METRICS:
        assert _reader(metric).read(_trace(), {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, ctx) is None, metric
        assert _reader(metric).read(None, {}, _ctx()) is None, metric
    assert '"missing"' in capsys.readouterr().out


def test_rooflines_count_a_group_and_two_matrices():
    """The scan's least time counts ``C B^T`` once a group (8 x 128 columns,
    not 128) and the bytes of eight groups' B and C; the experts' counts two
    matrices over the expected 3,072 rows of four layers: 0.736 TFLOP."""
    model = _config()["model"]
    scan = _reader("nemo_ssd_roofline")
    least = scan.least_seconds(model, {"seq_len": 8192}, 1, PEAK)
    macs = 129 / 2 * (8 * 128 + 4096) + 2 * 4096 * 128
    assert least["flops"] == 4 * 8192 * 3 * 2.0 * macs
    assert least["bytes"] == 4 * 8192 * 2 * (2 * 4096 * 2 + 2 * 1024 * 2
                                             + 64 * 4)
    one_group = scan.least_seconds({**model, "n_groups": 1},
                                   {"seq_len": 8192}, 1, PEAK)
    assert one_group["flops"] < least["flops"]
    assert scan.read(_trace(), {}, _ctx()) == pytest.approx(
        100 * least["seconds"] / 2.5e-3)
    experts = _reader("nemo_experts_roofline")
    least = experts.least_seconds(model, {"seq_len": 8192}, 1, PEAK)
    assert least["rows"] == 3072
    assert least["flops"] == 4 * 3072 * 2 * 3 * 2.0 * 2688 * 1856
    assert least["flops"] == pytest.approx(0.7356e12, rel=1e-3)
    assert least["bytes"] == 4 * 2 * (3 * 8 * 2 * 2688 * 1856
                                      + 4 * 3072 * 2688)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(3.734e-3, rel=1e-3)
    assert experts.read(_trace(), {}, _ctx()) == pytest.approx(
        100 * least["seconds"] / 1.5e-3)

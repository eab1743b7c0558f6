"""What PR 28 added to the benchmark, off the chip: the Granite configuration's
plain reference through the whole harness at toy size (the rehearsal twin
``tiny_granite``), its control, the three readers of the mixer's scopes and
the two collective readers on a hand-made trace, and the new entries of the
manifest. No number here comes from a device."""

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
CELL = "tiny_granite.b8.s36"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E = xplane.Event
MS = 1_000_000


def _reader(name):
    return run_lib.load_module([BENCH], "layer_metrics", name)


def _config():
    with open(os.path.join(BENCH, "configs", "granite4_h_micro.json")) as fh:
        return json.load(fh)


# -- the twin through the harness ------------------------------------------------


def test_twin_runs_through_the_command():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "CHIPBENCH_REHEARSAL": os.path.join("tests", "chipbench",
                                               "rehearsal")}
    env.pop("BENCH_RUN", None)
    done = subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"), "--workload",
         CELL, "--seed", "2147489999", "--seconds", "4", "--trace", "1"],
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
    ctx = run_lib.context(CELL, 2147484123, 3.0, 0, REHEARSAL)
    driver = run_lib.load_module(ctx["search"], "drivers",
                                 ctx["traffic"]["driver"])
    result, extra = driver.measure(ctx, None)
    return ctx, result, extra


def test_twin_agrees_with_the_plain_reference(sound):
    _, result, _ = sound
    assert result["correct"], result["compared"]
    assert {r["number"] for r in result["compared"]} == set(compare.NUMBERS)


def test_twin_control_fails_the_limits(sound):
    import jax

    ctx, _, extra = sound
    config = ctx["config"]
    reference = run_lib.load_module(ctx["search"], "references",
                                    config["reference"])
    params = jax.jit(lambda k: weights.make_flat(
        extra["shapes"], config["init"], k))(extra["key"])
    low = reference.run(config, params, extra["batches"],
                        precision=config["control_precision"])
    ok, rows = compare.judge(compare.readings(low, extra["reference"]),
                             config["limits"])
    assert not ok, rows


# -- the configuration's file and the manifest's new entries -----------------------


def test_configuration_keeps_every_published_key():
    """Against the catalog row where the guide is installed; the file's own
    two copies of the keys (top level for the driver's check, ``model`` for
    the harness) against each other everywhere."""
    config = _config()
    model = config["model"]
    assert {k: config[k] for k in model} == model
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (model["num_hidden_layers"], model["vocab_size"]) == (10, 12544)
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["vocab_size"] == 100352 == 8 * 12544
    assert len(model["layer_types"]) == 40
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["source_url"] == config["source"])
    changed = {k for k, v in row["config"].items() if model.get(k) != v}
    assert changed == set(config["reduced"])


@pytest.mark.parametrize("path,kind,value", [
    ("block_0/mamba/dt_bias", "const", -3.0),
    ("block_0/mamba/conv_bias", "const", 0.0),
    ("block_0/mamba/D", "const", 1.0),
    ("block_0/mamba/norm/scale", "const", 1.0),
    ("block_0/mamba/A_log", "normal", 1.0),
    ("block_0/mamba/conv_kernel", "normal", 0.3),
    ("block_5/attn/query/kernel", "normal", 0.02),
    ("embed/embedding", "normal", 0.02)])
def test_init_rules_reach_the_leaves_they_name(path, kind, value):
    import re

    rule = next(r for r in _config()["init"] if re.search(r[0], path))
    assert rule[1:] == [kind, value]


def test_manifest_gained_one_cell_and_three_metrics():
    """The four-chip GPT-2 cell is not here: a run of it outlasts the
    driver's limit for one run (PERF.md, Open questions)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cells = {c["name"]: c for c in manifest["workloads"]}
    assert cells["granite4_h_micro.b1.s4096"]["chips"] == 1
    assert all(c["chips"] == 1 for c in cells.values())
    new = {m["name"]: m["workloads"] for m in manifest["per_layer"][-3:]}
    assert new == {
        "mamba_mixer_ms": ["granite4_h_micro.b1.s4096"],
        "ssd_ms": ["granite4_h_micro.b1.s4096"],
        "ssd_roofline": ["granite4_h_micro.b1.s4096"]}
    with open(os.path.join(BENCH, "traffic", "b1.s4096.json")) as fh:
        traffic = json.load(fh)
    assert traffic["overrides"] == {"global_batch_size": 1, "seq_len": 4096}
    assert traffic["data"] == {"kind": "tokens", "seq_len": 4096,
                               "vocab_size": 12544}
    assert (traffic["driver"], traffic["warmup_steps"]) == ("train_window", 5)


# -- the readers, on a hand-made trace ----------------------------------------------

# Two whole steps of 10 ms on device 0 between a first and a last that the
# trace cut short. A step: in_proj 1.0, conv 0.5, scan 2.0 + 1.0 (backward),
# gated norm 0.5, out_proj 1.0 (all under mamba: 6.0 with the backward), attention 1.0, mlp 1.5,
# an unnamed copy 0.5.
_STEP = [("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 1.5), ("fusion.3", 1.5, 3.5),
         ("fusion.4", 3.5, 4.0), ("fusion.5", 4.0, 5.0), ("fusion.6", 5.0, 6.0),
         ("fusion.7", 6.0, 7.5), ("fusion.8", 7.5, 8.5), ("copy.9", 8.5, 9.0)]
_PRE = "jit(train_step)/jvp(GraniteHybrid)/checkpoint/block_0/"
_BWD = "jit(train_step)/transpose(jvp(GraniteHybrid))/checkpoint/block_0/"
STEP_TEXT = "\n".join(
    ["ENTRY %main {"] + [
        f'  %{name} = bf16[8]{{0}} fusion(%p), metadata={{op_name="{scope}"}}'
        for name, scope in [
            ("fusion.1", _PRE + "mamba/in_proj/dot_general"),
            ("fusion.2", _PRE + "mamba/conv1d/add"),
            ("fusion.3", _PRE + "mamba/ssd/dot_general"),
            ("fusion.4", _PRE + "mamba/gated_norm/mul"),
            ("fusion.5", _PRE + "mamba/out_proj/dot_general"),
            ("fusion.6", _PRE + "attn/query/dot_general"),
            ("fusion.7", _PRE + "mlp/gate/dot_general"),
            ("fusion.8", _BWD + "mamba/transpose(jvp(ssd))/dot_general")]]
    + ["  %copy.9 = bf16[8]{0} copy(%p)", "}"])


def _trace():
    ops, modules = [], []
    for base in (90, 100, 110, 120):
        modules.append(E("jit_train_step(1)", base * MS, (base + 10) * MS))
        ops += [E(n, int((base + a) * MS), int((base + b) * MS))
                for n, a, b in _STEP]
    return xplane.Trace([xplane.Device("/device:TPU:0", ops, modules, [])],
                        [])


def _ctx():
    config = _config()
    return {"step_text": STEP_TEXT, "config": config, "peaks": PEAK,
            "traffic": {"seq_len": 4096}, "global_batch": 1, "chips": 1}


@pytest.mark.parametrize("metric,want", [("mamba_mixer_ms", 6.0),
                                         ("ssd_ms", 3.0)])
def test_scope_readers_sum_their_scope(metric, want):
    assert _reader(metric).read(_trace(), {}, _ctx()) == pytest.approx(want)


def test_the_mixers_row_splits_it_by_inner_scope(capsys):
    _reader("mamba_mixer_ms").read(_trace(), {}, _ctx())
    row = next(json.loads(l) for l in capsys.readouterr().out.splitlines()
               if l.startswith('{"row": "mamba"'))
    assert row["by_scope_ms"] == pytest.approx(
        {"ssd": 3.0, "in_proj": 1.0, "out_proj": 1.0, "conv1d": 0.5,
         "gated_norm": 0.5})
    assert row["top_ops"][0]["op"] == "fusion.3"
    assert row["top_ops"][0]["ms"] == pytest.approx(2.0)
    assert row["top_ops"][0]["result"] == "bf16[8]{0}"


def test_scope_readers_give_nothing_without_the_scope(capsys):
    """The parent's step has no such scope: no value and no exception."""
    ctx = {**_ctx(), "step_text": STEP_TEXT.replace("mamba", "mixer")
           .replace("ssd", "scan")}
    for metric in ("mamba_mixer_ms", "ssd_ms", "ssd_roofline"):
        assert _reader(metric).read(_trace(), {}, ctx) is None
        assert _reader(metric).read(None, {}, ctx) is None
    assert '"missing"' in capsys.readouterr().out


def test_ssd_roofline_counts_what_cannot_be_avoided():
    least = _reader("ssd_roofline").least_seconds(
        _config()["model"], {"seq_len": 4096}, 1, PEAK)
    # nine Mamba layers, forward x 3: 13.04 GFLOP a layer forward
    assert least["flops"] == pytest.approx(9 * 3 * 13.04e9, rel=1e-3)
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(1.787e-3, rel=1e-2)
    share = _reader("ssd_roofline").read(_trace(), {}, _ctx())
    assert share == pytest.approx(100 * 1.787 / 3.0, rel=1e-2)
    # a scan as fast as its matmuls alone at the peak reads 100, never more:
    # the count holds nothing an implementation could skip
    assert least["flops"] / PEAK["bf16_flops_per_s"] == least["seconds"]

"""The step's map (``utils/stepmap.py``): a compiled step's text read by pass
and by the scopes the program declares. A two-block ``nn.remat`` model
compiled on the CPU for what the CPU can make; a canned text for what it
cannot (the compiler's clones, a Pallas call, a ``conditional``); the
package's ``jax.named_scope`` literals against ``SCOPES``; and the operator's
path, ``Trainer._stop_profile``."""

import json
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_training_example_tpu.utils import stepmap

PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(stepmap.__file__)))
STEPMAP = "pytorch_distributed_training_example_tpu.utils.stepmap"


# -- a step the CPU compiles --------------------------------------------------------


class Block(nn.Module):
    @nn.compact
    def __call__(self, x):
        with jax.named_scope("norm"):
            h = nn.LayerNorm()(x)
        with jax.named_scope("mlp"):
            h = nn.Dense(64, name="up")(h)
            h = nn.Dense(16, name="down")(nn.gelu(h))
        return x + h


class M(nn.Module):
    @nn.compact
    def __call__(self, x):
        for i in range(2):
            x = nn.remat(Block, prevent_cse=False)(name=f"block_{i}")(x)
        with jax.named_scope("head_loss"):
            return jnp.mean(x ** 2)


@pytest.fixture(scope="module")
def toy():
    """``(text, map)`` of a jitted step of the toy: loss, gradients, and an
    update under the ``optimizer`` scope."""
    model, x = M(), jnp.ones((8, 16))
    params = model.init(jax.random.PRNGKey(0), x)

    def step(params, x):
        loss, grads = jax.value_and_grad(lambda p: model.apply(p, x))(params)
        with jax.named_scope("optimizer"):
            params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
        return params, loss

    text = jax.jit(step).lower(params, x).compile().as_text()
    return text, stepmap.step_map(text)


def _entries(entries, fragment):
    return [e for e in entries.values() if fragment in e.path]


@pytest.mark.parametrize("block", ["block_0", "block_1"])
@pytest.mark.parametrize("layer", ["up", "down"])
def test_the_forwards_matmuls_are_forward(toy, block, layer):
    hits = _entries(toy[1], f"jit(step)/jvp(M)/{block}/mlp/{layer}/dot_general")
    assert hits and {(e.pass_, e.by, e.scope) for e in hits} == {
        ("forward", None, "mlp")}


@pytest.mark.parametrize("block", ["block_0", "block_1"])
@pytest.mark.parametrize("layer", ["up", "down"])
def test_weight_and_input_gradients_are_backward(toy, block, layer):
    """Under ``transpose(jvp(M))/jvp(M)/checkpoint/<block>``: the path holds a
    ``jvp(`` too, and the backward wins."""
    at = f"transpose(jvp(M))/jvp(M)/checkpoint/{block}/mlp/{layer}/"
    hits = _entries(toy[1], at)
    assert len(hits) >= 2                    # the weight's and the input's
    assert {e.pass_ for e in hits} == {"backward"}
    assert all(e.scopes == ("mlp",) for e in hits)


def test_the_recomputed_norm_is_the_programs_recompute(toy):
    hits = [e for e in _entries(toy[1], "rematted_computation/block_1/norm/")]
    assert hits, "the CPU compiler merged the whole recomputation away"
    assert {(e.pass_, e.by, e.scope) for e in hits} == {
        ("recompute", "program", "norm")}
    # its forward twin stayed too: the norm really runs twice
    assert {e.pass_ for e in _entries(toy[1], "jit(step)/jvp(M)/block_1/norm/")
            } == {"forward"}


def test_the_update_is_the_optimizers(toy):
    hits = _entries(toy[1], "jit(step)/optimizer/")
    assert hits and {(e.pass_, e.scope) for e in hits} == {
        ("optimizer", "optimizer")}
    # a parameter's update reads its gradient inside one fusion: flagged
    assert any(e.mixed and ("optimizer", "optimizer") in e.inner for e in hits)


def test_summary_and_the_written_map_agree_with_the_text(toy, tmp_path):
    text, entries = toy
    said = stepmap.write(text, str(tmp_path / "step_map.json"))
    assert said == stepmap.summary(entries)
    assert sum(said["instructions"].values()) == len(entries)
    assert said["instructions"]["recompute"] >= 1
    assert said["instructions"]["other"] == 0
    assert said["compiler_clones"] == 0 and said["kernel_calls"] == {}
    with open(tmp_path / "step_map.json") as fh:
        written = json.load(fh)
    assert set(written) == set(entries)
    in_text = set(re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", text, re.M))
    assert set(written) <= in_text
    name, e = next((n, e) for n, e in entries.items() if e.inner)
    assert written[name] == {
        "path": e.path, "scopes": list(e.scopes), "pass": e.pass_, "by": e.by,
        "kernel": None, "wrapper": False, "result": e.result,
        "inner": sorted(([s, p] for s, p in e.inner),
                        key=lambda pair: (pair[0] or "", pair[1]))}
    assert stepmap.step_map(text) is entries          # cached on the text


# -- what the CPU cannot make: a canned text ------------------------------------------

_J = "jit(train_step)"
_BWD = f"{_J}/transpose(jvp(GraniteHybrid))/jvp(GraniteHybrid)/checkpoint"
CANNED = "\n".join([
    "HloModule jit_train_step, is_scheduled=true",
    "",
    "%fused_computation.7 (param_0.1: bf16[8,64]) -> bf16[8,64] {",
    "  %param_0.1 = bf16[8,64]{1,0} parameter(0)",
    f'  ROOT %mul.1 = bf16[8,64]{{1,0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{_J}/jvp(GraniteHybrid)/block_0/norm/mul"}}',
    "}",
    "",
    "%fused_computation.9 (param_0.2: bf16[8,64]) -> bf16[8,64] {",
    "  %param_0.2 = bf16[8,64]{1,0} parameter(0)",
    f'  %mul.2 = bf16[8,64]{{1,0}} multiply(%param_0.2, %param_0.2), metadata={{op_name="{_BWD}/block_0/norm/mul"}}',
    f'  ROOT %dot.3 = bf16[8,64]{{1,0}} convolution(%mul.2, %param_0.2), metadata={{op_name="{_BWD}/block_0/mamba/in_proj/dot_general"}}',
    "}",
    "",
    "%branch_a (p.1: bf16[8,64]) -> bf16[8,64] {",
    "  %p.1 = bf16[8,64]{1,0} parameter(0)",
    f'  ROOT %negate.5 = bf16[8,64]{{1,0}} negate(%p.1), metadata={{op_name="{_J}/jvp(GraniteHybrid)/block_0/mlp/moe/cond/branch_0_fun/neg"}}',
    "}",
    "",
    "ENTRY %main.40 (p0: bf16[8,64]) -> (bf16[8,64], bf16[8,64]) {",
    "  %p0 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)",
    f'  %fusion.7 = bf16[8,64]{{1,0:T(8,128)(2,1)}} fusion(%p0), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="{_J}/jvp(GraniteHybrid)/block_0/norm/mul"}}',
    f'  %cond.3 = bf16[8,64]{{1,0}} conditional(%p0, %fusion.7, %fusion.7), branch_computations={{%branch_a, %branch_a}}, metadata={{op_name="{_J}/jvp(GraniteHybrid)/block_0/mlp/moe/cond"}}',
    f'  %ssd_bwd.4 = (bf16[8,64]{{1,0}}, bf16[8,64]{{1,0:T(8,128)(2,1)S(1)}}) custom-call(%cond.3), custom_call_target="tpu_custom_call", metadata={{op_name="{_BWD}/block_0/mamba/ssd/jit(_bwd_call)/ssd_bwd/pallas_call"}}',
    f'  %fusion.7.remat = bf16[8,64]{{1,0:T(8,128)(2,1)}} fusion(%p0), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="{_J}/jvp(GraniteHybrid)/block_0/norm/mul"}}',
    f'  %fusion.7.remat2.1 = bf16[8,64]{{1,0:T(8,128)(2,1)}} fusion(%p0), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="{_J}/jvp(GraniteHybrid)/block_0/norm/mul"}}',
    f'  %fusion.9 = bf16[8,64]{{1,0}} fusion(%fusion.7.remat), kind=kOutput, calls=%fused_computation.9, metadata={{op_name="{_BWD}/block_0/mamba/in_proj/dot_general"}}',
    f'  %ssd_fwd.6 = bf16[8,64]{{1,0}} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={{op_name="{_BWD}/rematted_computation/block_1/mamba/ssd/jit(_fwd_call)/ssd_fwd/pallas_call"}}',
    "  %copy.10 = bf16[8,64]{1,0} copy(%fusion.9)",
    "  ROOT %tuple.11 = (bf16[8,64]{1,0}, bf16[8,64]{1,0}) tuple(%copy.10, %ssd_fwd.6)",
    "}"])


@pytest.fixture(scope="module")
def canned():
    return stepmap.step_map(CANNED)


def test_the_canned_map_holds_what_a_trace_can_show(canned):
    assert sorted(canned) == [
        "cond.3", "copy.10", "fusion.7", "fusion.7.remat",
        "fusion.7.remat2.1", "fusion.9", "negate.5", "ssd_bwd.4", "ssd_fwd.6"]


@pytest.mark.parametrize("name", ["fusion.7.remat", "fusion.7.remat2.1"])
def test_a_compilers_clone_is_recompute_although_its_path_says_jvp(canned, name):
    clone = canned[name]
    assert (clone.pass_, clone.by) == ("recompute", "compiler")
    assert clone.path == canned["fusion.7"].path
    assert (canned["fusion.7"].pass_, canned["fusion.7"].by) == ("forward", None)
    assert clone.scopes == ("norm",) and not clone.mixed


def test_a_pallas_call_has_its_kernels_name_and_the_backward(canned):
    call = canned["ssd_bwd.4"]
    assert (call.kernel, call.pass_, call.by) == ("ssd_bwd", "backward", None)
    assert call.scopes == ("mamba", "ssd") and call.scope == "ssd"
    assert call.result.startswith("(bf16[8,64]{1,0}, bf16[8,64]")
    assert not call.wrapper and call.inner is None


def test_a_conditional_is_a_wrapper_and_its_body_is_there_by_itself(canned):
    assert canned["cond.3"].wrapper and canned["cond.3"].scopes == ("mlp", "moe")
    assert not canned["negate.5"].wrapper
    assert canned["negate.5"].pass_ == "forward"


def test_a_fusion_of_two_scopes_is_flagged_and_booked_to_its_root(canned):
    fusion = canned["fusion.9"]
    assert fusion.inner == {("norm", "backward"), ("in_proj", "backward")}
    assert fusion.mixed
    assert (fusion.scope, fusion.pass_) == ("in_proj", "backward")
    assert canned["fusion.7"].inner == {("norm", "forward")}
    assert not canned["fusion.7"].mixed


def test_a_recomputation_with_no_forward_twin_is_the_forward_itself(canned):
    """CSE merged block_1's scan with its recomputation and kept the
    recomputation's path: one call, once a step."""
    call = canned["ssd_fwd.6"]
    assert (call.kernel, call.pass_, call.by) == ("ssd_fwd", "forward", "merged")
    assert stepmap.read_path(call.path)[1:3] == ("recompute", "program")
    twin = CANNED.replace(
        "  %copy.10", f'  %ssd_fwd.12 = bf16[8,64]{{1,0}} custom-call(%p0), '
        f'custom_call_target="tpu_custom_call", metadata={{op_name="{_J}/'
        'jvp(GraniteHybrid)/block_1/mamba/ssd/jit(_fwd_call)/ssd_fwd/'
        'pallas_call"}\n  %copy.10')
    both = stepmap.step_map(twin)
    assert (both["ssd_fwd.6"].pass_, both["ssd_fwd.6"].by) == (
        "recompute", "program")
    assert both["ssd_fwd.12"].pass_ == "forward"
    assert stepmap.summary(both)["kernel_calls"]["ssd_fwd"] == {
        "forward": 1, "recompute": 1, "backward": 0}


def test_a_fusion_whose_root_has_no_path_is_its_last_named_instructions():
    """The compiler closes a fusion with a bitcast or a convert of its own:
    the fusion's line then carries no ``op_name``, its computation does."""
    path = f"{_J}/jvp(GraniteHybrid)/block_0/mamba/gated_norm/square"
    text = "\n".join([
        "%fused_computation.2 (p: f32[8]) -> f32[2,4] {",
        "  %p = f32[8]{0} parameter(0)",
        f'  %square.1 = f32[8]{{0}} multiply(%p, %p), metadata={{op_name="{path}"}}',
        "  ROOT %bitcast.2 = f32[2,4]{1,0} bitcast(%square.1)",
        "}",
        "ENTRY %main (p0: f32[8]) -> f32[2,4] {",
        "  %p0 = f32[8]{0} parameter(0)",
        "  %multiply_bitcast_fusion.1 = f32[2,4]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.2",
        "  ROOT %copy.3 = f32[2,4]{0,1} copy(%multiply_bitcast_fusion.1)",
        "}"])
    entries = stepmap.step_map(text)
    fusion = entries["multiply_bitcast_fusion.1"]
    assert (fusion.path, fusion.scope, fusion.pass_) == (
        path, "gated_norm", "forward")
    assert fusion.inner == {("gated_norm", "forward")}
    assert (entries["copy.3"].path, entries["copy.3"].pass_) == ("", "other")


def test_the_canned_summary(canned):
    assert stepmap.summary(canned) == {
        "instructions": {"forward": 4, "recompute": 2, "backward": 2,
                         "optimizer": 0, "other": 1},
        "kernel_calls": {
            "ssd_bwd": {"forward": 0, "recompute": 0, "backward": 1},
            "ssd_fwd": {"forward": 1, "recompute": 0, "backward": 0}},
        "compiler_clones": 2, "mixed_fusions": 1}
    assert canned["copy.10"].pass_ == "other" and canned["copy.10"].path == ""


@pytest.mark.parametrize("path,expected", [
    ("jit(f)/jvp(M)/block_1/attn/norm/x", (("attn", "norm"), "forward")),
    ("jit(f)/transpose(jvp(head_loss))/mul", (("head_loss",), "backward")),
    ("jit(f)/optimizer/add", (("optimizer",), "optimizer")),
    ("jit(f)/telemetry_health/reduce_max", (("telemetry_health",), "other")),
    ("jit(f)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/"
     "block_1/mlp/up/dot_general", (("mlp",), "recompute")),
    ("", ((), "other")),
])
def test_a_path_alone(path, expected):
    assert stepmap.read_path(path)[:2] == expected


# -- the program keeps the list -----------------------------------------------------


def test_every_named_scope_of_the_package_is_declared():
    found = {}
    for base, _, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(base, name)) as fh:
                for scope in re.findall(
                        r"named_scope\(\s*f?[\"']([^\"']+)[\"']", fh.read()):
                    found.setdefault(scope, name)
    assert len(found) >= 35
    missing = {s: f for s, f in found.items() if s not in stepmap.SCOPES}
    assert not missing, f"add to stepmap.SCOPES: {missing}"
    assert len(set(stepmap.SCOPES)) == len(stepmap.SCOPES)


# -- the operator's path ------------------------------------------------------------


def _tiny_lm(**kw):
    from pytorch_distributed_training_example_tpu.utils.config import Config

    return Config(**{**dict(
        model="gpt2_tiny", dataset="lm", seq_len=32, epochs=1,
        global_batch_size=8, steps_per_epoch=3, log_every=100, workers=0,
        precision="fp32", warmup_epochs=0.0, telemetry=False,
        eval_every_epochs=100, checkpoint_every_epochs=100), **kw})


def test_a_profiled_run_leaves_the_steps_map_beside_its_trace(tmp_path):
    from pytorch_distributed_training_example_tpu.core.trainer import Trainer
    from pytorch_distributed_training_example_tpu.utils import telemetry

    rec = telemetry.recorder()
    rec.clear()
    trainer = Trainer(_tiny_lm(profile_steps="1:2",
                               profile_dir=str(tmp_path / "prof")))
    trainer.train_epoch(0)
    with open(tmp_path / "prof" / "step_map.json") as fh:
        written = json.load(fh)
    records = rec.records()
    (made,) = [r for r in records if r.name == "step_map"]
    assert made.kind == "compile" and made.t0 == made.t1
    assert made.name in telemetry.COMPILE_RECORDS
    (stop,) = [r for r in records
               if r.kind == "span" and r.name == "stop_profile"]
    assert made.parent == stop.id           # under the span that was open
    assert made.step == stop.step == 1      # the last profiled step
    assert sum(made.value["instructions"].values()) == len(written)
    assert made.value["instructions"]["forward"] > 0
    assert made.value["instructions"]["backward"] > 0
    assert made.value["instructions"]["optimizer"] > 0
    passes = {e["pass"] for e in written.values()}
    assert {"forward", "backward", "optimizer"} <= passes
    assert any("attn" in e["scopes"] for e in written.values())
    # the trace it lies beside
    assert any(name.endswith(".xplane.pb") for _, _, names in
               os.walk(tmp_path / "prof") for name in names)


def test_the_maps_names_are_the_compiled_steps(tmp_path):
    """``_write_step_map`` on a trainer that stepped once: every name of the
    file is an instruction of the text the trainer's own step compiles to."""
    from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
    from pytorch_distributed_training_example_tpu.core.trainer import Trainer

    trainer = Trainer(_tiny_lm(profile_dir=str(tmp_path / "prof")))
    batch = next(trainer._make_step_iter(0, 0))
    with mesh_lib.use_mesh(trainer.mesh):
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            (trainer.state, batch))
        text = trainer.train_step.lower(*shapes).compile().as_text()
        trainer._write_step_map(batch)
    with open(tmp_path / "prof" / "step_map.json") as fh:
        written = json.load(fh)
    assert set(written) == set(stepmap.step_map(text))
    assert len(written) > 100


def test_a_run_without_the_option_imports_and_records_nothing(tmp_path):
    """In a process of its own: this one has imported ``stepmap`` already."""
    import subprocess
    import textwrap

    script = textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {os.path.dirname(PACKAGE)!r})
        sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
        import conftest  # the suite's CPU devices and compile cache
        from pytorch_distributed_training_example_tpu.core.trainer import Trainer
        from pytorch_distributed_training_example_tpu.utils import telemetry
        from pytorch_distributed_training_example_tpu.utils.config import Config
        trainer = Trainer(Config(
            model="gpt2_tiny", dataset="lm", seq_len=32, epochs=1,
            global_batch_size=8, steps_per_epoch=3, log_every=100, workers=0,
            precision="fp32", warmup_epochs=0.0, telemetry=False,
            eval_every_epochs=100, checkpoint_every_epochs=100))
        trainer.train_epoch(0)
        names = {{r.name for r in telemetry.recorder().records()}}
        assert "dispatch" in names, names
        assert not names & {{"step_map", "stop_profile"}}, names
        assert {STEPMAP!r} not in sys.modules
        print("untouched")
        """)
    done = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip().endswith("untouched")
    assert not os.path.exists(tmp_path / "step_map.json")

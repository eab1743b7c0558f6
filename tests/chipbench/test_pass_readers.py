"""The step by pass and by declared scope (``chipbench/step_passes.py`` and the
five ``layer_metrics`` files on it), on a hand-made ``xplane.Trace`` and a
planted step text, as ``test_program_readers.py`` builds them; the manifest's
five entries; and the same readers through the whole harness off the chip. No
number here comes from a device."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as run_lib, step_passes, xplane  # noqa: E402

E = xplane.Event
MS = 1_000_000
FIVE = ["step_fwd_ms", "step_bwd_ms", "step_recompute_ms", "scope_mixed_pct",
        "scope_coverage_pct"]
CELLS = ["gpt2_124m.b24.s1024", "granite4_h_micro.b1.s4096",
         "trinity_mini.b1.s8192", "smallthinker_21b.b1.s8192.v37984",
         "glm47_flash.b1.s8192.v19360", "nemotron3_nano.b1.s8192.v16384",
         "lfm2_8b_a1b.b1.s8192.v16384", "qwen3_next_80b.b1.s8192.v18992"]
STEPMAP = "pytorch_distributed_training_example_tpu.utils.stepmap"


def _reader(name):
    return run_lib.load_module([os.path.join(ROOT, "chipbench")],
                               "layer_metrics", name)


# Two whole steps of 10 ms on device 0 between a first and a last that the
# trace cut short. A step: a norm 0.5 and an mlp matmul 1.0 forward, the scan's
# forward kernel 1.0; a ``while`` of the dispatch's recomputation over 2.5..4.5
# whose body (a fusion 1.0, an unnamed sort 0.5) shows by itself; the
# compiler's clone of the norm 0.5; a fusion that holds the norm's and the
# projection's backward 1.5; the scan's backward kernel 1.0; an unnamed copy
# 0.25; the update 0.75; an operation the step's text does not have 0.25; and
# 0.5 idle.
_STEP = [("fusion.1", 0.0, 0.5), ("fusion.2", 0.5, 1.5), ("ssd_fwd.3", 1.5, 2.5),
         ("while.4", 2.5, 4.5), ("fusion.5", 2.75, 3.75), ("sort.6", 3.75, 4.25),
         ("fusion.1.remat", 4.5, 5.0), ("fusion.8", 5.0, 6.5),
         ("ssd_bwd.9", 6.5, 7.5), ("copy.10", 7.5, 7.75),
         ("fusion.11", 8.25, 9.0), ("mystery.12", 9.0, 9.25)]
BUSY = 2.5 + 2.0 + 0.5 + 1.5 + 1.0 + 0.25 + 0.75 + 0.25   # 8.75: the union
LEAVES = BUSY - 2.0 + 1.5                                  # without the while


def _trace():
    ops, modules = [], []
    for base in (90, 100, 110, 120):
        modules.append(E("jit_train_step(1)", base * MS, (base + 10) * MS))
        ops += [E(n, int((base + a) * MS), int((base + b) * MS))
                for n, a, b in _STEP]
    return xplane.Trace([xplane.Device("/device:TPU:0", ops, modules, [])], [])


_J = "jit(train_step)"
_B = f"{_J}/transpose(jvp(M))/jvp(M)/checkpoint"
_meta = lambda path: f'metadata={{op_name="{path}"}}'
STEP_TEXT = "\n".join([
    "HloModule jit_train_step",
    "%fused_computation.1 (p: bf16[8]) -> bf16[8] {",
    f"  ROOT %mul.1 = bf16[8]{{0}} multiply(%p, %p), {_meta(_J + '/jvp(M)/block_0/norm/mul')}",
    "}",
    "%fused_computation.8 (p: bf16[8]) -> bf16[8] {",
    f"  %mul.2 = bf16[8]{{0}} multiply(%p, %p), {_meta(_B + '/block_0/norm/mul')}",
    f"  ROOT %dot.3 = bf16[8]{{0}} convolution(%mul.2, %p), {_meta(_B + '/block_0/mamba/in_proj/dot_general')}",
    "}",
    "%body.4 (p: bf16[8]) -> bf16[8] {",
    f"  %fusion.5 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.5, {_meta(_B + '/rematted_computation/block_0/mlp/moe/moe_dispatch/while/body/add')}",
    "  ROOT %sort.6 = bf16[8]{0} sort(%fusion.5), dimensions={0}",
    "}",
    "%fused_computation.5 (p: bf16[8]) -> bf16[8] {",
    f"  ROOT %add.5 = bf16[8]{{0}} add(%p, %p), {_meta(_B + '/rematted_computation/block_0/mlp/moe/moe_dispatch/while/body/add')}",
    "}",
    "ENTRY %main (p0: bf16[8]) -> bf16[8] {",
    "  %p0 = bf16[8]{0} parameter(0)",
    f"  %fusion.1 = bf16[8]{{0}} fusion(%p0), kind=kLoop, calls=%fused_computation.1, {_meta(_J + '/jvp(M)/block_0/norm/mul')}",
    f"  %fusion.2 = bf16[8,8]{{1,0}} fusion(%p0), kind=kOutput, calls=%fused_computation.2, {_meta(_J + '/jvp(M)/block_0/mlp/up/dot_general')}",
    f'  %ssd_fwd.3 = bf16[8]{{0}} custom-call(%p0), custom_call_target="tpu_custom_call", {_meta(_J + "/jvp(M)/block_0/mamba/ssd/jit(_fwd_call)/ssd_fwd/pallas_call")}',
    f"  %add.0 = bf16[8]{{0}} add(%p0, %p0), {_meta(_J + '/jvp(M)/block_0/mlp/moe/moe_dispatch/while/body/add')}",
    f"  %while.4 = bf16[8]{{0}} while(%p0), condition=%cond.4, body=%body.4, {_meta(_B + '/rematted_computation/block_0/mlp/moe/moe_dispatch/while')}",
    f"  %fusion.1.remat = bf16[8]{{0}} fusion(%p0), kind=kLoop, calls=%fused_computation.1, {_meta(_J + '/jvp(M)/block_0/norm/mul')}",
    f"  %fusion.8 = bf16[8]{{0}} fusion(%p0), kind=kOutput, calls=%fused_computation.8, {_meta(_B + '/block_0/mamba/in_proj/dot_general')}",
    f'  %ssd_bwd.9 = (bf16[8]{{0}}, bf16[8]{{0}}) custom-call(%p0), custom_call_target="tpu_custom_call", {_meta(_B + "/block_0/mamba/ssd/jit(_bwd_call)/ssd_bwd/pallas_call")}',
    "  %copy.10 = bf16[8]{0} copy(%p0)",
    f"  ROOT %fusion.11 = f32[8]{{0}} fusion(%p0), kind=kLoop, calls=%fused_computation.11, {_meta(_J + '/optimizer/add')}",
    "}"])


@pytest.fixture
def CTX():
    """What the driver hands every reader of a run (the table is kept in it)."""
    return {"step_text": STEP_TEXT}


def _row(capsys, row="passes"):
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()
             if l.startswith("{")]
    return [l for l in lines if l["row"] == row]


def test_the_passes_add_up_to_the_operations_without_the_wrappers(capsys, CTX):
    trace = _trace()
    values = {name: _reader(name).read(trace, {}, CTX) for name in FIVE}
    (row,) = _row(capsys)                     # printed once for the five
    assert row["steps"] == 2
    assert row["busy_ms"] == pytest.approx(BUSY)
    assert row["wrapper_ms"] == pytest.approx(2.0)   # the while, once, apart
    assert row["total_ms"] == pytest.approx(LEAVES)
    assert sum(row["by_pass_ms"].values()) == pytest.approx(row["total_ms"])
    assert row["by_pass_ms"] == pytest.approx({
        "forward": 0.5 + 1.0 + 1.0, "recompute": 1.0 + 0.5,
        "backward": 1.5 + 1.0, "optimizer": 0.75,
        "other": 0.5 + 0.25 + 0.25})
    assert values["step_fwd_ms"] == pytest.approx(2.5)
    assert values["step_bwd_ms"] == pytest.approx(2.5)
    assert values["step_recompute_ms"] == pytest.approx(1.5)
    assert row["recompute_ms"] == pytest.approx(
        {"program": 1.0, "compiler": 0.5})
    assert row["merged_forward_ms"] == 0.0
    assert row["unmapped_ms"] == pytest.approx(0.25)


def test_scopes_kernels_and_what_took_most(capsys, CTX):
    _reader("step_recompute_ms").read(_trace(), {}, CTX)
    (row,) = _row(capsys)
    scopes = row["by_scope_ms"]
    assert scopes["norm"] == pytest.approx(
        {"forward": 0.5, "recompute": 0.5, "backward": 0.0})
    # the mixed fusion's 1.5 is booked whole to its root's scope and pass
    assert scopes["in_proj"] == pytest.approx(
        {"forward": 0.0, "recompute": 0.0, "backward": 1.5})
    assert scopes["ssd"] == pytest.approx(
        {"forward": 1.0, "recompute": 0.0, "backward": 1.0})
    assert scopes["moe_dispatch"]["recompute"] == pytest.approx(1.0)
    assert scopes["optimizer"] == pytest.approx(0.75)
    assert scopes["other"] == pytest.approx(1.0)
    assert row["kernel_calls"] == {
        "ssd_bwd": {"forward": 0, "recompute": 0, "backward": 1},
        "ssd_fwd": {"forward": 1, "recompute": 0, "backward": 0}}
    assert [r["op"] for r in row["top_recomputed"]] == [
        "fusion.5", "fusion.1.remat"]
    assert row["top_recomputed"][0]["path"].endswith("moe_dispatch/while/body/add")
    assert row["top_recomputed"][1]["result"] == "bf16[8]{0}"
    (mixed,) = row["top_mixed"]
    assert (mixed["op"], mixed["ms"], mixed["pass"]) == (
        "fusion.8", pytest.approx(1.5), "backward")
    assert mixed["inner"] == ["in_proj:backward", "norm:backward"]
    assert row["unnamed_ms"] == pytest.approx(
        {"sort": 0.5, "copy": 0.25, "mystery": 0.25})
    assert [(r["op"], r.get("path")) for r in row["top_unnamed"]] == [
        ("sort.6", ""), ("copy.10", ""), ("mystery.12", None)]


def test_a_mixed_fusion_is_in_the_mixed_share_and_unnamed_time_not_covered(CTX):
    trace = _trace()
    assert _reader("scope_mixed_pct").read(trace, {}, CTX) == \
        pytest.approx(100 * 1.5 / BUSY)
    # the sort in the loop's body, the copy and the unknown operation have no
    # name; the while's own interval covers nothing
    assert _reader("scope_coverage_pct").read(trace, {}, CTX) == \
        pytest.approx(100 * (BUSY - 1.0 - 0.5) / BUSY)


def test_a_wrappers_time_is_not_counted_twice(capsys, CTX):
    """With the loop's body gone from the trace, the while's 2.0 ms are still
    no operation's: the passes lose the body's 1.5 and nothing else."""
    trace = _trace()
    dev = trace.devices[0]
    dev.ops[:] = [e for e in dev.ops if e.name not in ("fusion.5", "sort.6")]
    assert _reader("step_recompute_ms").read(trace, {}, CTX) == \
        pytest.approx(0.5)
    (row,) = _row(capsys)
    assert row["total_ms"] == pytest.approx(LEAVES - 1.5)
    assert row["busy_ms"] == pytest.approx(BUSY)
    assert row["wrapper_ms"] == pytest.approx(2.0)


@pytest.mark.parametrize("metric", FIVE)
def test_no_stepmap_in_the_program_gives_none_and_no_line(metric, monkeypatch,
                                                          capsys, CTX):
    """The parent commit: the import fails, the reader reports nothing and the
    traced run goes on."""
    import pytorch_distributed_training_example_tpu.utils as utils

    monkeypatch.setitem(sys.modules, STEPMAP, None)
    monkeypatch.delattr(utils, "stepmap", raising=False)
    assert step_passes.stepmap() is None
    assert _reader(metric).read(_trace(), {}, CTX) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("metric", FIVE)
def test_off_the_chip_or_without_names_gives_none_never_zero(metric, capsys, CTX):
    assert _reader(metric).read(None, {}, CTX) is None
    (row,) = _row(capsys)                     # what the text alone says
    assert row["steps"] == 0 and row["compiler_clones"] == 1
    assert row["kernel_calls"]["ssd_fwd"]["forward"] == 1
    bare = {"step_text": "\n".join(
        line.split(", metadata=")[0] for line in STEP_TEXT.splitlines())}
    assert _reader(metric).read(_trace(), {}, bare) is None
    (said,) = _row(capsys, "names")
    assert said["missing"]


def test_the_manifest_gained_five_entries_for_the_eight_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    assert [w["name"] for w in manifest["workloads"]] == CELLS
    new = manifest["per_layer"][-5:]
    assert [m["name"] for m in new] == FIVE
    assert manifest["per_layer"][-6]["name"] == "qwen3n_attn_kernels_roofline"
    for metric in new:
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["workloads"] == CELLS
        assert (metric["source"], metric["layer"], metric["moves"]) == (
            "device_trace", "model step", "examples_per_s_chip")
        assert metric["unit"] == ("ms" if metric["name"].endswith("_ms")
                                  else "%")
        assert metric["better"] == (
            "higher" if metric["name"] == "scope_coverage_pct" else "lower")
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layer_metrics", metric["name"] + ".py"))
    for cell in CELLS:
        ctx = run_lib.context(cell, 1, 20.0, 1)
        assert ctx["per_layer"][-5:] == FIVE
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "step_passes.py"))


def test_all_five_run_in_a_rehearsal_and_stay_out_of_a_cpu_runs_numbers():
    """The whole harness off the chip, the five readers added to a rehearsal
    cell's list (the manifest gives them to its eight cells): each imports the
    program's ``stepmap`` and reads the step the CPU compiled; with no device
    in the trace none reports a number, and the text's own counts are printed
    once."""
    script = textwrap.dedent(f"""
        import json, os, sys
        sys.path.insert(0, {ROOT!r})
        from chipbench import run as run_lib
        ctx = run_lib.context("tiny_gpt2.b16.s64", 2147484052, 4.0, 1,
                              os.environ["CHIPBENCH_REHEARSAL"])
        ctx["per_layer"] = ctx["per_layer"] + {FIVE!r}
        driver = run_lib.load_module(ctx["search"], "drivers",
                                     ctx["traffic"]["driver"])
        print(json.dumps(driver.run(ctx)), flush=True)
        """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "CHIPBENCH_REHEARSAL": os.path.join("tests", "chipbench",
                                               "rehearsal")}
    env.pop("BENCH_RUN", None)
    done = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(l) for l in done.stdout.splitlines()
            if l.startswith('{"row"')]
    (passes,) = [r for r in rows if r["row"] == "passes"]
    assert passes["steps"] == 0
    counts = passes["instructions"]
    assert min(counts["forward"], counts["backward"], counts["optimizer"]) > 0
    assert counts["recompute"] == 0 and passes["compiler_clones"] == 0
    computed = set(next(r for r in rows if r["row"] == "rehearsal")["computed"])
    assert not set(FIVE) & computed
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is False and last["metrics"] == {}

"""The comparison that decides ``correct``, at a size a test run can hold.

The rehearsal configurations state float32, so the precision below is
bfloat16. For each: the program's own step agrees with the plain reference
inside the limits; the program run in bfloat16 does not; the control (the
reference itself computed in bfloat16, put in the program's place) does not;
and a run with the timed path broken underneath comes out ``correct: false``.
Also the benchmark's own FLOP counts.
"""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, run as run_lib, weights  # noqa: E402

REHEARSAL = os.path.join(ROOT, "tests", "chipbench", "rehearsal")
SEARCH = [os.path.join(ROOT, "chipbench"), REHEARSAL]
CELLS = ["tiny_gpt2.b16.s64", "micro_resnet.b16"]
SEED = 2147484123  # past 2**31 - 1, as the driver's seeds are


def _measure(cell, edit=None):
    ctx = run_lib.context(cell, SEED, 4.0, 0, REHEARSAL)
    if edit:
        ctx["config"] = copy.deepcopy(ctx["config"])
        edit(ctx["config"])
    driver = run_lib.load_module(ctx["search"], "drivers",
                                 ctx["traffic"]["driver"])
    result, extra = driver.measure(ctx, None)
    return ctx, result, extra


@pytest.fixture(scope="module")
def sound():
    cache = {}

    def get(cell):
        if cell not in cache:
            cache[cell] = _measure(cell)
        return cache[cell]

    return get


@pytest.mark.parametrize("cell", CELLS)
def test_program_agrees_with_plain_reference(sound, cell):
    _, result, _ = sound(cell)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert {r["number"] for r in result["compared"]} == set(compare.NUMBERS)


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_program_fails(cell):
    def bf16(config):
        config["overrides"]["precision"] = "bf16"

    _, result, _ = _measure(cell, bf16)
    assert not result["correct"]
    assert any(not r["ok"] for r in result["compared"])


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(sound, cell):
    import jax

    ctx, _, extra = sound(cell)
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


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from pytorch_distributed_training_example_tpu.core import train_state

    def frozen(self, grads, **updates):
        _, opt_state = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(step=self.step + 1, opt_state=opt_state, **updates)

    monkeypatch.setattr(train_state.TrainState, "apply_gradients", frozen)
    _, result, _ = _measure(CELLS[0])
    assert not result["correct"]
    failed = {r["number"] for r in result["compared"] if not r["ok"]}
    assert "dparam_leaf" in failed


def test_rows_come_from_the_seed_and_all_differ():
    import numpy as np

    for data in ({"kind": "tokens", "seq_len": 16, "vocab_size": 50257},
                 {"kind": "images", "image_size": 16, "num_classes": 10}):
        key = "tokens" if data["kind"] == "tokens" else "image"
        rows = run_lib.load_module(SEARCH, "rows", data["kind"])
        a, again, other = (rows.Rows(data, seed, 64) for seed in
                           (2 ** 31 + 5, 2 ** 31 + 5, 2 ** 31 + 6))
        assert len(a) == 64
        assert np.array_equal(a[3][key], again[3][key])
        assert not np.array_equal(a[3][key], other[3][key])
        assert len({a[i][key].tobytes() for i in range(64)}) == 64


def test_worst_leaf_uses_the_median_as_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    gap, where = compare.worst_leaf({"a": 1.0, "b": 2.2, "c": 2e-9}, ref)
    assert where == "b" and gap == pytest.approx(0.1)
    gap, where = compare.worst_leaf({"a": 1.0, "b": 2.0, "c": float("nan")},
                                    ref)
    assert where == "c" and gap != gap


def test_flop_counts():
    gpt2 = {"n_layer": 12, "n_embd": 768, "n_head": 12, "vocab_size": 50257}
    gpt2_flops = run_lib.load_module(SEARCH, "references",
                                     "gpt2_124m").forward_flops
    per_token = gpt2_flops(gpt2, {"seq_len": 1024}) / 1024
    # matmuls only, causal half of the square; the program's 2N + 4LSd is
    # 286.6 M, and the same with half the square 267.8 M
    assert per_token == pytest.approx(265.96e6, rel=1e-4)
    assert 0.99 < per_token / 267.8e6 < 1.0
    resnet = {"block": "bottleneck", "stage_sizes": [3, 4, 6, 3],
              "num_filters": 64, "num_classes": 1000}
    # torchvision's ~4.09 G multiply-accumulates, times two
    resnet_flops = run_lib.load_module(SEARCH, "references",
                                       "resnet").forward_flops
    assert resnet_flops(resnet, {"image_size": 224}) == pytest.approx(
        2 * 4.089e9, rel=2e-3)
    least = run_lib.load_module(
        SEARCH, "layer_metrics", "flash_attn_roofline").least_seconds(
        gpt2, {"seq_len": 1024}, 24,
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "flops"
    assert least["seconds"] == pytest.approx(8.25e-3, rel=1e-2)

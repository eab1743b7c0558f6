"""Golden-metric regression gate (SURVEY.md §4.5) — pure-python unit tests."""

import json
import subprocess
import sys
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import check_regression as cr  # noqa: E402

GOLDEN = {"TPU v5 lite": {
    "resnet50_imagenet_train_throughput": {"value": 2200.0},
    "gpt2_lm1024_train_throughput": {"value": 100.0},
}}


def _result(resnet=2250.0, lm=105.0, device="TPU v5 lite"):
    return {
        "metric": "resnet50_imagenet_train_throughput", "value": resnet,
        "extra": {"device": device,
                  "lm": {"metric": "gpt2_lm1024_train_throughput",
                         "value": lm, "unit": "s"}},
    }


def test_ok_within_tolerance():
    failures, report = cr.check(_result(), GOLDEN)
    assert not failures
    assert sum(line.startswith("OK") for line in report) == 2


def test_headline_regression_fails():
    failures, _ = cr.check(_result(resnet=1800.0), GOLDEN)
    assert len(failures) == 1 and "resnet50" in failures[0]


def test_lm_row_regression_fails():
    failures, _ = cr.check(_result(lm=80.0), GOLDEN)
    assert len(failures) == 1 and "gpt2" in failures[0]


def test_unknown_device_never_fails():
    failures, report = cr.check(_result(resnet=1.0, device="TPU v9"), GOLDEN)
    assert not failures
    assert all(line.startswith("NO-GOLDEN") for line in report)


def test_cli_handles_driver_wrapper(tmp_path):
    """The driver's BENCH_r{N}.json wraps the line under 'parsed' and is
    pretty-printed (multi-line). Values track the REAL golden file (the
    subprocess loads it): the test is about wrapper parsing, not numbers."""
    golden = cr.load_golden()["TPU v5 lite"]
    wrapper = {"rc": 0, "parsed": _result(
        resnet=golden["resnet50_imagenet_train_throughput"]["value"],
        lm=golden["gpt2_lm1024_train_throughput"]["value"])}
    f = tmp_path / "bench.json"
    f.write_text(json.dumps(wrapper, indent=2))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "check_regression.py"),
         str(f)], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "resnet50" in proc.stdout


def test_real_golden_file_loads():
    golden = cr.load_golden()
    assert "TPU v5 lite" in golden


# ---- --aot-bytes: per-region AOT modeled-byte gate (r8) ----

AOT_GOLDEN = {"aot_regions": {"llama_moe b4 s2048 gather": {
    "backend_lowering": "cpu",
    "attribution": "proportional_bytes",
    "regions": {"moe_router": 50.0, "moe_experts": 170.0},
}}}


def _aot_result(router=50.0, experts=170.0, backend="cpu",
                attribution="proportional_bytes"):
    return {
        "mode": "aot_hlo_model", "attribution": attribution,
        "backend_lowering": backend, "model": "llama_moe",
        "per_chip_batch": 4, "seq_len": 2048,
        "moe_dispatch_impl": "gather",
        "regions": {"moe_router": {"gbytes_modeled": router},
                    "moe_experts": {"gbytes_modeled": experts}},
    }


def test_aot_bytes_ok_and_shrink_pass():
    failures, report = cr.check_aot_bytes(_aot_result(router=20.0),
                                          AOT_GOLDEN)
    assert not failures
    assert sum(line.startswith("OK") for line in report) == 2


def test_aot_bytes_growth_fails():
    """Bytes regress UPWARD: +10% is the gate, +20% must fail."""
    failures, _ = cr.check_aot_bytes(_aot_result(router=60.0), AOT_GOLDEN)
    assert len(failures) == 1 and "moe_router" in failures[0]
    failures, _ = cr.check_aot_bytes(_aot_result(router=54.9), AOT_GOLDEN)
    assert not failures


def test_aot_bytes_no_golden_reports_not_fails():
    res = _aot_result()
    res["moe_dispatch_impl"] = "sort"  # different key -> no golden entry
    failures, report = cr.check_aot_bytes(res, AOT_GOLDEN)
    assert not failures
    assert report and report[0].startswith("NO-GOLDEN")


def test_aot_bytes_skips_on_model_mismatch():
    """Goldens are lowering- and attribution-model-specific: numbers from
    a different backend or byte-attribution scheme never compare."""
    for kw in ({"backend": "tpu"}, {"attribution": "line_majority"}):
        failures, report = cr.check_aot_bytes(
            _aot_result(router=999.0, **kw), AOT_GOLDEN)
        assert not failures
        assert report and report[0].startswith("SKIP")


def test_aot_bytes_record_then_check_cli(tmp_path):
    """--record writes the golden, a second invocation gates against it;
    a grown region then fails with exit code 1."""
    golden_path = tmp_path / "golden.json"
    golden_path.write_text(json.dumps({"_comment": "test"}))
    import importlib
    res_file = tmp_path / "aot.json"
    res_file.write_text(json.dumps(_aot_result()))
    cr.record_aot_golden(json.loads(res_file.read_text()), str(golden_path))
    golden = json.loads(golden_path.read_text())
    assert "_comment" in golden  # comment keys survive the rewrite
    key = "llama_moe b4 s2048 gather"
    assert golden["aot_regions"][key]["regions"]["moe_router"] == 50.0
    ok, _ = cr.check_aot_bytes(_aot_result(),
                               cr.load_golden(str(golden_path)))
    assert not ok
    bad, _ = cr.check_aot_bytes(_aot_result(router=70.0),
                                cr.load_golden(str(golden_path)))
    assert len(bad) == 1


# ---- proportional fusion attribution (profile_step.build_op_moe_weights) --

SYNTH_HLO = """\
HloModule synth

%fused_computation.1 (param_0: f32[8]) -> f32[24] {
  %param_0 = f32[8]{0} parameter(0)
  %a.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/moe_router/add"}
  ROOT %b.1 = f32[24]{0} multiply(%a.1, %a.1), metadata={op_name="jit(f)/other"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[24]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/other"}
  ROOT %t = f32[8]{0} tanh(%p), metadata={op_name="jit(f)/moe_aux/tanh"}
}
"""


def test_moe_weights_split_mixed_fusion():
    """A fusion whose interior is 25% router bytes (32 of 128) charges the
    router exactly that fraction; the untagged remainder is unassigned.
    Tagged non-fusion ops keep weight 1.0. The winner-take-all map
    (build_op_moe_tags) would have charged this fusion 100% to the router
    — the r7 mega-fusion misattribution this model corrects."""
    import profile_step as ps

    w = ps.build_op_moe_weights(SYNTH_HLO)
    assert w["fusion.1"] == {"moe_router": 32.0 / 128.0}
    assert w["t"] == {"moe_aux": 1.0}
    # the interior tagged line is itself weighted (its own op_bytes exist)
    assert w["a.1"] == {"moe_router": 1.0}
    # contrast: the line-majority map attributes the whole fusion
    tags = ps.build_op_moe_tags(SYNTH_HLO)
    assert tags["fusion.1"] == "moe_router"


def _goodput(tmp_path, history):
    path = tmp_path / "goodput.json"
    path.write_text(json.dumps({"ttfs_history": history}))
    return str(path)


def _ttfs(mode, s, attempt=0):
    return {"attempt": attempt, "mode": mode, "ttfs_s": s}


def test_ttfs_warm_beats_cold_passes(tmp_path):
    failures, report = cr.check_ttfs(_goodput(tmp_path, [
        _ttfs("cold", 8.0), _ttfs("warm", 1.5, 1), _ttfs("cold", 9.0, 2)]))
    assert not failures
    assert any("OK" in line and "x0.19" in line for line in report)


def test_ttfs_slow_warm_fails(tmp_path):
    # Every warm attempt must beat the SLOWEST cold by the floor; warm at
    # 0.9x cold means the executable cache is not paying for itself.
    failures, report = cr.check_ttfs(
        _goodput(tmp_path, [_ttfs("cold", 8.0), _ttfs("warm", 7.2, 1)]))
    assert failures and "not paying for itself" in failures[0]
    assert any(line.startswith("REGRESSION") for line in report)
    # A looser floor admits the same history.
    failures, _ = cr.check_ttfs(
        _goodput(tmp_path, [_ttfs("cold", 8.0), _ttfs("warm", 7.2, 1)]),
        max_ratio=0.95)
    assert not failures


def test_ttfs_neutral_without_a_pair(tmp_path):
    # All-cold (cache missing/corrupt -> quarantined) is the cache layer
    # behaving correctly, not a regression.
    for history in ([_ttfs("cold", 8.0), _ttfs("cold", 8.2, 1)],
                    [_ttfs("warm", 1.0)], []):
        failures, report = cr.check_ttfs(_goodput(tmp_path, history))
        assert not failures
        assert any("neutral" in line for line in report)


def test_ttfs_malformed_goodput_fails_loudly(tmp_path):
    failures, report = cr.check_ttfs(str(tmp_path / "missing.json"))
    assert failures and any("MALFORMED" in line for line in report)
    bad = tmp_path / "goodput.json"
    bad.write_text('{"ttfs_history": [{"mode": "warm", "ttfs_s": "fast"}]}')
    failures, _ = cr.check_ttfs(str(bad))
    assert failures and "malformed ttfs_history entry" in failures[0]


def test_ttfs_cli_gate(tmp_path):
    path = _goodput(tmp_path, [_ttfs("cold", 6.0), _ttfs("warm", 1.0, 1)])
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "check_regression.py"),
         "--ttfs", path], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "check_regression.py"),
         "--ttfs", path, "--ttfs-max-ratio", "0.1"],
        capture_output=True, text=True)
    assert proc.returncode == 1
    assert "REGRESSION ttfs" in proc.stdout

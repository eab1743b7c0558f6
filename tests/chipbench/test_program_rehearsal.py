"""The program's own spans through the whole harness, off the chip: a
rehearsal cell with two loader threads (files under ``tests/chipbench/
rehearsal`` only) computes every host-side reader from the recorder's ring;
the device-side readers, and the idle time's attribution, report nothing."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSAL = os.path.join("tests", "chipbench", "rehearsal")


def test_host_side_readers_are_computed_in_a_rehearsal():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "CHIPBENCH_REHEARSAL": REHEARSAL}
    env.pop("BENCH_RUN", None)
    done = subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"), "--workload",
         "tiny_gpt2.b16.s64.w2", "--seed", "2147484001", "--seconds", "3",
         "--trace", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(l) for l in done.stdout.splitlines()
            if l.startswith('{"row"')]
    by_row = {r["row"]: r for r in rows}
    computed = set(by_row["rehearsal"]["computed"])
    assert {"loader_wait_pct", "device_put_pct", "loader_ready_depth",
            "loop_self_ms"} <= computed
    # beside the wrappers that time the same layers from outside
    assert {"input_wait_pct", "dispatch_ms"} <= computed
    # device numbers stay out of a CPU run: no device plane, no idle time
    assert not {"idle_attributed_pct", "region_coverage_pct", "optimizer_ms",
                "flash_fwd_ms", "head_loss_ms"} & computed
    # the idle reader's host half still ran: the two clocks were matched
    assert by_row["clock"]["pairs"] > 0
    assert by_row["clock"]["residual_ms"] < 1.0
    spans = by_row["spans"]
    assert {"iteration", "input_wait", "loader_wait", "device_put",
            "dispatch", "make_batch"} <= set(spans["count"])
    assert spans["count"]["dispatch"] >= by_row["window"]["steps"]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is False and last["metrics"] == {}

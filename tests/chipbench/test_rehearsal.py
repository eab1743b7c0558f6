"""The command end to end, off the chip: with the rehearsal switch a cell the
manifest does not list (files under ``tests/chipbench/rehearsal`` only, nothing
under ``chipbench/`` touched) runs through the whole harness; without it the
command refuses to measure on a CPU."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSAL = os.path.join("tests", "chipbench", "rehearsal")


def _run(workload, trace, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    full.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"), "--workload",
         workload, "--seed", "2147483999", "--seconds", "4", "--trace",
         str(trace)], cwd=ROOT, env=full, capture_output=True, text=True,
        timeout=600)


def test_rehearsal_cell_runs_through_the_harness():
    done = _run("tiny_gpt2.b16.s64", 1, CHIPBENCH_REHEARSAL=REHEARSAL)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [l for l in done.stdout.splitlines() if l.strip()]
    last = json.loads(lines[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is False          # no chip, no claim
    assert last["metrics"] == {}             # and no device metric
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    rows = [json.loads(l) for l in lines[:-1] if l.startswith('{"row"')]
    rehearsal = next(r for r in rows if r["row"] == "rehearsal")
    assert rehearsal["compared_ok"] is True  # the comparison itself held
    assert {"input_wait_pct", "dispatch_ms"} <= set(rehearsal["computed"])
    compared = next(r for r in rows if r["row"] == "comparison")["compared"]
    assert all("limit" in c and "value" in c for c in compared)


def test_without_the_switch_a_cpu_gets_no_result():
    done = _run("gpt2_124m.b24.s1024", 0)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout

"""Elastic resume (ISSUE r11): batch policies, stream remap, supervisor shrink.

The pure layer (utils/elastic.py, the sampler stream helpers) is tested
directly at world sizes 1/2/4; the supervisor tests run the real launch.py
restart loop against a jax-free fake job, in the style of the
test_resilience.py supervisor tests — the elastic drill with the *real*
trainer lives in the dryrun gauntlet (__graft_entry__.py leg 11).
"""

import importlib.util
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.data import sampler as sampler_lib
from pytorch_distributed_training_example_tpu.utils import elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _launch_module():
    spec = importlib.util.spec_from_file_location(
        "launch_under_test", os.path.join(REPO, "launch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# rescale: both policies across world sizes 1/2/4
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("old,new,accum,want_accum", [
    (2, 1, 1, 2), (4, 2, 1, 2), (4, 1, 1, 4),
    (2, 4, 2, 1), (1, 2, 2, 1), (4, 4, 2, 2),
])
def test_keep_global_batch_scales_accum(old, new, accum, want_accum):
    plan = elastic.rescale(elastic.KEEP_GLOBAL_BATCH, old_world=old,
                           new_world=new, global_batch=16, grad_accum=accum)
    assert plan.global_batch_size == 16  # the defining property
    assert plan.grad_accum_steps == want_accum
    assert plan.lr_scale == 1.0
    # Total microbatch work per update is conserved (or rounded up).
    assert plan.grad_accum_steps * new >= accum * old
    assert 16 % (new * plan.grad_accum_steps) == 0
    assert "elastic [keep_global_batch]" in plan.describe()


def test_keep_global_batch_non_integral_ratio_rounds_up():
    plan = elastic.rescale(elastic.KEEP_GLOBAL_BATCH, old_world=3,
                           new_world=2, global_batch=12)
    assert plan.global_batch_size == 12
    assert plan.grad_accum_steps == 2  # ceil(3/2), and 12 % (2*2) == 0
    assert "rounded up" in plan.note


@pytest.mark.parametrize("old,new,want_gb,want_lr", [
    (2, 1, 8, 0.5), (4, 2, 8, 0.5), (4, 1, 4, 0.25),
    (1, 2, 32, 2.0), (2, 4, 32, 2.0),
])
def test_scale_lr_linear_scaling(old, new, want_gb, want_lr):
    plan = elastic.rescale(elastic.SCALE_LR, old_world=old, new_world=new,
                           global_batch=16)
    assert plan.global_batch_size == want_gb
    assert plan.grad_accum_steps == 1
    assert plan.lr_scale == want_lr
    # Per-device batch is preserved exactly.
    assert want_gb // new == 16 // old


def test_rescale_rejects_bad_inputs():
    with pytest.raises(ValueError, match="unknown elastic policy"):
        elastic.rescale("frobnicate", old_world=2, new_world=1,
                        global_batch=16)
    with pytest.raises(ValueError, match="world sizes"):
        elastic.rescale(elastic.SCALE_LR, old_world=0, new_world=1,
                        global_batch=16)
    with pytest.raises(ValueError, match="not divisible"):
        elastic.rescale(elastic.KEEP_GLOBAL_BATCH, old_world=4, new_world=2,
                        global_batch=10)
    with pytest.raises(ValueError, match="not divisible"):
        elastic.rescale(elastic.SCALE_LR, old_world=3, new_world=2,
                        global_batch=16)


# ---------------------------------------------------------------------------
# step-offset / step-count remap: exact sample positions only
# ---------------------------------------------------------------------------


def test_remap_step_offset_preserves_sample_position():
    assert elastic.remap_step_offset(6, 16, 8) == 12
    assert elastic.remap_step_offset(6, 16, 32) == 3
    assert elastic.remap_step_offset(0, 16, 8) == 0
    assert elastic.remap_step_count(8, 16, 4) == 32


def test_remap_step_offset_rejects_partial_batches():
    with pytest.raises(ValueError, match="sample-exact"):
        elastic.remap_step_offset(3, 16, 32)


# ---------------------------------------------------------------------------
# sampler stream invariance: the property that makes resume sample-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("num_examples", [64, 70])
def test_global_sample_stream_world_size_invariant(num_examples):
    ref = sampler_lib.global_sample_stream(num_examples, 16, 1, seed=3)
    for shards in (2, 4):
        got = sampler_lib.global_sample_stream(num_examples, 16, shards,
                                               seed=3)
        np.testing.assert_array_equal(got, ref)
    # Same number of full batches for every world size (drop_last math).
    assert len(ref) == (num_examples // 16) * 16


def test_global_sample_stream_epochs_differ():
    a = sampler_lib.global_sample_stream(64, 16, 1, seed=3, epoch=0)
    b = sampler_lib.global_sample_stream(64, 16, 1, seed=3, epoch=1)
    assert not np.array_equal(a, b)


def test_shard_batch_stream_partitions_each_global_batch():
    per_shard = sampler_lib.shard_batch_stream(64, 16, 2, 0, seed=3)
    other = sampler_lib.shard_batch_stream(64, 16, 2, 1, seed=3)
    flat = sampler_lib.global_sample_stream(64, 16, 1, seed=3)
    assert len(per_shard) == len(other) == 4
    for b, (mine, theirs) in enumerate(zip(per_shard, other)):
        assert len(mine) == len(theirs) == 8
        union = np.sort(np.concatenate([mine, theirs]))
        np.testing.assert_array_equal(union, np.sort(flat[b * 16:(b + 1) * 16]))


# ---------------------------------------------------------------------------
# recorded geometry -> plan
# ---------------------------------------------------------------------------


def test_plan_from_record_builds_plan_on_world_change():
    recorded = {"mesh_shape": {"data": 2, "fsdp": 1}, "global_batch_size": 16,
                "grad_accum": 1}
    plan = elastic.plan_from_record(recorded,
                                    policy=elastic.KEEP_GLOBAL_BATCH,
                                    new_world=1, fallback_global_batch=999)
    assert plan is not None
    assert (plan.old_world, plan.new_world) == (2, 1)
    assert plan.global_batch_size == 16 and plan.grad_accum_steps == 2


def test_plan_from_record_none_when_unchanged_or_unrecorded():
    recorded = {"mesh_shape": {"data": 2, "fsdp": 2}}
    assert elastic.plan_from_record(recorded, policy=elastic.SCALE_LR,
                                    new_world=4,
                                    fallback_global_batch=16) is None
    assert elastic.plan_from_record({}, policy=elastic.SCALE_LR, new_world=2,
                                    fallback_global_batch=16) is None


def test_recorded_world_reads_mesh_shape_and_fallback():
    assert elastic.recorded_world({"mesh_shape": {"data": 2, "fsdp": 2,
                                                  "model": 2}}) == 4
    assert elastic.recorded_world({"world": 3}) == 3
    assert elastic.recorded_world({}) is None


# ---------------------------------------------------------------------------
# dead-host protocol: append-only jsonl, corruption-tolerant reads
# ---------------------------------------------------------------------------


def test_dead_hosts_round_trip_tolerates_corruption(tmp_path):
    assert elastic.read_dead_hosts(str(tmp_path)) == set()
    elastic.record_dead_host(str(tmp_path), 1, world=2, step=5, reason="test")
    elastic.record_dead_host(str(tmp_path), 0, world=1)
    path = os.path.join(str(tmp_path), elastic.DEAD_HOSTS_FILE)
    with open(path, "a") as fh:
        fh.write('{"host": trunc')  # a host died mid-write
    assert elastic.read_dead_hosts(str(tmp_path)) == {0, 1}
    rows = [json.loads(line) for line
            in open(path).read().splitlines()[:2]]
    assert rows[0] == {"host": 1, "world": 2, "step": 5, "reason": "test"}


def test_returned_hosts_cancel_dead_records(tmp_path):
    d = str(tmp_path)
    assert elastic.effective_dead_hosts(d) == set()
    elastic.record_dead_host(d, 1, world=2, reason="kill")
    elastic.record_dead_host(d, 3, world=2, reason="kill")
    assert elastic.effective_dead_hosts(d) == {1, 3}
    elastic.record_host_return(d, 1, reason="repaired")
    assert elastic.read_returned_hosts(d) == {1}
    assert elastic.effective_dead_hosts(d) == {3}
    # Count-based, not set difference: die -> return -> die again is dead.
    elastic.record_dead_host(d, 1, world=2, reason="kill again")
    assert elastic.effective_dead_hosts(d) == {1, 3}
    # read_dead_hosts keeps its historical "ever died" semantics.
    assert elastic.read_dead_hosts(d) == {1, 3}


def test_returned_hosts_tolerate_torn_tail_and_read_errors(tmp_path):
    """The grow-side ledger gets the same degradation contract as the dead
    side: a torn tail (host died mid-append) skips the bad line, and an
    OSError on open (ESTALE/EIO, not just a missing file) degrades to "no
    records seen" — never a crash in the supervisor's planning path."""
    d = str(tmp_path)
    elastic.record_host_return(d, 1, reason="repaired")
    elastic.record_host_return(d, 4, reason="repaired")
    path = os.path.join(d, elastic.RETURNED_HOSTS_FILE)
    with open(path, "a") as fh:
        fh.write('{"host": 9, "reas')  # torn tail: no newline, no close brace
    assert elastic.read_returned_hosts(d) == {1, 4}
    # Torn records must not cancel dead ones they never finished recording.
    elastic.record_dead_host(d, 9, reason="kill")
    assert elastic.effective_dead_hosts(d) == {9}
    # Non-ENOENT OSError (IsADirectoryError here) degrades to empty, same
    # as the dead-host reader.
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / elastic.RETURNED_HOSTS_FILE).mkdir()
    assert elastic.read_returned_hosts(str(bad)) == set()


# ---------------------------------------------------------------------------
# mesh: elastic_resolve degrades pinned axes instead of refusing
# ---------------------------------------------------------------------------


def test_elastic_resolve_degrades_fixed_axes(caplog):
    mesh_lib = pytest.importorskip(
        "pytorch_distributed_training_example_tpu.core.mesh")
    cfg = mesh_lib.MeshConfig(fsdp=4)
    with pytest.raises(ValueError):
        cfg.resolve(2)
    with caplog.at_level("WARNING", logger="pdtx"):
        shape = cfg.elastic_resolve(2)
    assert shape == (1, 2, 1, 1, 1, 1)
    assert any("degraded axes" in r.message for r in caplog.records)
    # When the strict resolve works, elastic_resolve is a pass-through.
    assert mesh_lib.MeshConfig().elastic_resolve(4) == (4, 1, 1, 1, 1, 1)
    assert cfg.elastic_resolve(8) == (2, 4, 1, 1, 1, 1)


# ---------------------------------------------------------------------------
# launch.py helpers (imported from the file, not via subprocess)
# ---------------------------------------------------------------------------


def test_parse_elastic_and_find_flag():
    launch = _launch_module()
    assert launch.parse_elastic("2") == (2, 1 << 30)
    assert launch.parse_elastic("1:4") == (1, 4)
    for junk in ("0", "4:2", "0:3"):
        with pytest.raises(ValueError):
            launch.parse_elastic(junk)
    cmd = ["main.py", "--checkpoint-dir", "/a", "--checkpoint-dir", "/b"]
    assert launch.find_flag(cmd, "--checkpoint-dir") == "/b"
    assert launch.find_flag(cmd, "--nope") is None


def test_coordinator_port_falls_back_when_held(capsys):
    launch = _launch_module()
    with socket.socket() as held:
        held.bind(("", 0))
        held.listen(1)
        taken = held.getsockname()[1]
        assert not launch.probe_port(taken)
        port = launch.coordinator_port(taken)
        assert port != taken
        assert launch.probe_port(port)
    assert "not bindable" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# launch.py supervisor: elastic shrink loop (jax-free fake job)
# ---------------------------------------------------------------------------


def _write_elastic_script(tmp_path):
    """Fake gang member: on the first attempt the highest rank records itself
    dead and dies abruptly with the host-loss code; the relaunched attempt
    writes what world it came back at."""
    script = tmp_path / "fake_elastic_job.py"
    script.write_text(
        "import json, os, sys, time\n"
        "args = sys.argv[1:]\n"
        "ckdir = args[args.index('--checkpoint-dir') + 1]\n"
        "os.makedirs(ckdir, exist_ok=True)\n"
        "if '--resume' in args:\n"
        "    with open(os.path.join(ckdir, 'resumed.txt'), 'w') as fh:\n"
        "        fh.write(os.environ.get('NUM_PROCESSES', '?') + '|'\n"
        "                 + ' '.join(args))\n"
        "    sys.exit(0)\n"
        "rank = int(os.environ.get('PROCESS_ID', '0'))\n"
        "world = int(os.environ.get('NUM_PROCESSES', '1'))\n"
        "if rank == world - 1 and world > 1:\n"
        "    with open(os.path.join(ckdir, 'dead_hosts.jsonl'), 'a') as fh:\n"
        "        fh.write(json.dumps({'host': rank, 'world': world}) + '\\n')\n"
        "    os._exit(76)\n"
        "time.sleep(30)\n"  # survivor blocks 'in a collective' until torn down
        "sys.exit(1)\n")
    return script


def _run_launch(tmp_path, script, *launch_flags):
    ckdir = tmp_path / "ck"
    res = subprocess.run(
        [sys.executable, "launch.py", "--nprocs", "2",
         "--restart-policy", "on-failure", "--max-restarts", "2",
         "--restart-backoff", "0.05", "--log-dir", str(tmp_path),
         *launch_flags, "--", str(script), "--checkpoint-dir", str(ckdir)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    return res, ckdir


def test_supervisor_shrinks_world_after_host_loss(tmp_path):
    script = _write_elastic_script(tmp_path)
    res, ckdir = _run_launch(tmp_path, script, "--elastic", "1")
    assert res.returncode == 0, res.stderr
    assert "elastic — host(s) [1] lost, relaunching at world size 1" \
        in res.stderr, res.stderr
    world, argv = (ckdir / "resumed.txt").read_text().split("|", 1)
    assert world == "1"  # relaunched one host smaller
    assert "--resume auto" in argv


def _write_grow_script(tmp_path):
    """Fake gang member for the shrink-then-grow drill. Attempt 1 (world 2):
    the highest rank records itself dead and dies abruptly. Attempt 2 (world
    1): the survivor records the host's RETURN and exits preempted. Attempt
    3 must therefore come back at world 2; rank 0 writes the final marker."""
    script = tmp_path / "fake_grow_job.py"
    script.write_text(
        "import json, os, sys, time\n"
        "args = sys.argv[1:]\n"
        "ckdir = args[args.index('--checkpoint-dir') + 1]\n"
        "os.makedirs(ckdir, exist_ok=True)\n"
        "rank = int(os.environ.get('PROCESS_ID', '0'))\n"
        "world = int(os.environ.get('NUM_PROCESSES', '1'))\n"
        "returned = os.path.exists(os.path.join(ckdir, 'returned.txt'))\n"
        "if world > 1 and not returned:\n"  # attempt 1: lose the last host
        "    if rank == world - 1:\n"
        "        with open(os.path.join(ckdir, 'dead_hosts.jsonl'), 'a') as fh:\n"
        "            fh.write(json.dumps({'host': rank, 'world': world}) + '\\n')\n"
        "        os._exit(76)\n"
        "    time.sleep(30)\n"
        "    sys.exit(1)\n"
        "if world == 1:\n"  # attempt 2: the lost host came back repaired
        "    with open(os.path.join(ckdir, 'returned.txt'), 'w') as fh:\n"
        "        fh.write('1')\n"
        "    with open(os.path.join(ckdir, 'returned_hosts.jsonl'), 'a') as fh:\n"
        "        fh.write(json.dumps({'host': 1, 'reason': 'repaired'}) + '\\n')\n"
        "    sys.exit(75)\n"
        "with open(os.path.join(ckdir, f'final.r{rank}.txt'), 'w') as fh:\n"
        "    fh.write(str(world) + '|' + ' '.join(args))\n"
        "sys.exit(0)\n")
    return script


def test_supervisor_grows_world_on_host_return(tmp_path):
    script = _write_grow_script(tmp_path)
    res, ckdir = _run_launch(tmp_path, script, "--elastic", "1")
    assert res.returncode == 0, res.stderr
    assert "elastic — host(s) [1] lost, relaunching at world size 1" \
        in res.stderr, res.stderr
    assert "elastic — host(s) [1] returned, relaunching at world size 2" \
        in res.stderr, res.stderr
    world, argv = (ckdir / "final.r0.txt").read_text().split("|", 1)
    assert world == "2"  # grew back to the launch-time size
    assert "--resume auto" in argv
    assert (ckdir / "final.r1.txt").exists()  # the returned host ran again


def test_supervisor_gives_up_below_elastic_min(tmp_path):
    script = _write_elastic_script(tmp_path)
    res, ckdir = _run_launch(tmp_path, script, "--elastic", "2")
    assert res.returncode == 76, res.stderr
    assert "elastic give-up" in res.stderr, res.stderr
    assert not (ckdir / "resumed.txt").exists()


def test_elastic_requires_restart_policy(tmp_path):
    res = subprocess.run(
        [sys.executable, "launch.py", "--nprocs", "1", "--elastic", "1",
         "--", "whatever.py"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert res.returncode == 2  # argparse error
    assert "--elastic needs a restart policy" in res.stderr


def test_supervisor_coordinator_port_probe(tmp_path):
    script = tmp_path / "port_echo.py"
    script.write_text(
        "import os, sys\n"
        "open(sys.argv[1], 'w').write(os.environ['MASTER_PORT'])\n"
        "sys.exit(0)\n")
    marker = tmp_path / "port.txt"
    with socket.socket() as held:
        held.bind(("", 0))
        held.listen(1)
        taken = held.getsockname()[1]
        res = subprocess.run(
            [sys.executable, "launch.py", "--nprocs", "1",
             "--coordinator-port", str(taken), "--log-dir", str(tmp_path),
             "--", str(script), str(marker)],
            cwd=REPO, capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    assert f"coordinator port {taken} is not bindable" in res.stderr
    assert marker.read_text() != str(taken)


def _write_preempt_script(tmp_path):
    """Fake gang member that is preempted on every attempt — exercises the
    supervisor's backoff/budget ledger with no elastic machinery in play."""
    script = tmp_path / "fake_preempt_job.py"
    script.write_text("import sys\nsys.exit(75)\n")
    return script


def test_supervisor_backoff_doubles_until_budget_exhausted(tmp_path):
    script = _write_preempt_script(tmp_path)
    res, _ = _run_launch(tmp_path, script, "--restart-policy", "on-preempt",
                         "--restart-backoff", "0.2")
    assert res.returncode == 75, res.stderr
    err = res.stderr
    assert "restart 1/2 with --resume auto in 0.2s" in err, err
    assert "restart 2/2 with --resume auto in 0.4s" in err, err  # doubled
    assert "restart budget exhausted (2); last exit code 75" in err, err
    assert err.count("-> restart") == 2  # budget, not one-more-than-budget


def _write_repeat_kill_script(tmp_path):
    """The SAME host dies abruptly on every attempt — a genuinely bad node,
    not a transient preemption. The supervisor must shrink exactly once
    (absolute dead-host accounting: the second record of host 1 is not a
    NEW loss) and then burn the restart budget with doubling backoff,
    rather than shrinking again or restarting forever."""
    script = tmp_path / "fake_repeat_kill_job.py"
    script.write_text(
        "import json, os, sys\n"
        "args = sys.argv[1:]\n"
        "ckdir = args[args.index('--checkpoint-dir') + 1]\n"
        "os.makedirs(ckdir, exist_ok=True)\n"
        "rank = int(os.environ.get('PROCESS_ID', '0'))\n"
        "world = int(os.environ.get('NUM_PROCESSES', '1'))\n"
        "path = os.path.join(ckdir, 'dead_hosts.jsonl')\n"
        "if rank == 0:\n"
        "    with open(path, 'a') as fh:\n"
        "        fh.write(json.dumps({'host': 1, 'world': world}) + '\\n')\n"
        "else:\n"
        "    # the launcher tears the gang down at the first exit: die only\n"
        "    # once rank 0's record of this (the first) attempt is on disk\n"
        "    import time\n"
        "    deadline = time.time() + 30\n"
        "    while time.time() < deadline and not (\n"
        "            os.path.exists(path) and os.path.getsize(path)):\n"
        "        time.sleep(0.02)\n"
        "os._exit(76)\n")
    return script


def test_supervisor_repeated_same_host_loss_exhausts_budget(tmp_path):
    script = _write_repeat_kill_script(tmp_path)
    res, ckdir = _run_launch(tmp_path, script, "--elastic", "1",
                             "--restart-backoff", "0.2")
    assert res.returncode == 76, res.stderr
    err = res.stderr
    # One shrink for the first loss; re-recording the same host is not news.
    assert err.count("relaunching at world size 1") == 1, err
    assert "host(s) [1] lost" in err, err
    assert "restart 1/2 with --resume auto in 0.2s" in err, err
    assert "restart 2/2 with --resume auto in 0.4s" in err, err
    assert "restart budget exhausted (2); last exit code 76" in err, err
    # Every attempt recorded the host: the ledger holds three records but
    # only ever one effectively-dead host.
    recs = [json.loads(line) for line in
            (ckdir / "dead_hosts.jsonl").read_text().splitlines()]
    assert len(recs) == 3 and {r["host"] for r in recs} == {1}
    assert elastic.effective_dead_hosts(str(ckdir)) == {1}


# ---------------------------------------------------------------------------
# goodput coverage gate (benchmarks/check_regression.py --goodput)
# ---------------------------------------------------------------------------


def _check_regression(*argv):
    spec = importlib.util.spec_from_file_location(
        "check_regression_under_test",
        os.path.join(REPO, "benchmarks", "check_regression.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main(list(argv))


def test_goodput_gate_accepts_merged_multi_attempt(tmp_path, capsys):
    path = tmp_path / "goodput.json"
    path.write_text(json.dumps({
        "coverage": 0.97, "wall_s": 12.0, "attempts": 2,
        "categories_s": {"step": 10.0, "restart": 1.5}}))
    assert _check_regression("--goodput", str(path)) == 0
    out = capsys.readouterr().out
    assert "OK goodput" in out and "2 attempt(s)" in out


def test_goodput_gate_fails_below_coverage_floor(tmp_path, capsys):
    path = tmp_path / "goodput.json"
    path.write_text(json.dumps({"coverage": 0.5, "wall_s": 12.0}))
    assert _check_regression("--goodput", str(path)) == 1
    assert "REGRESSION goodput" in capsys.readouterr().out
    path.write_text("{not json")
    assert _check_regression("--goodput", str(path)) == 1


def test_clear_stale_run_id_removes_torn_keeps_healthy(tmp_path, capsys):
    launch = _launch_module()
    d = str(tmp_path)
    path = os.path.join(d, "run_id.json")

    launch.clear_stale_run_id(None)  # no checkpoint dir: no-op
    launch.clear_stale_run_id(d)  # no file yet: no-op

    # A healthy survivor is the shared identity — never cleared.
    with open(path, "w") as fh:
        json.dump({"run_id": "r-abc", "host": "h0"}, fh)
    launch.clear_stale_run_id(d)
    assert json.load(open(path))["run_id"] == "r-abc"
    assert capsys.readouterr().err == ""

    # A torn file (attempt killed mid-write) is cleared LOUDLY, so the
    # relaunch's rank 0 re-establishes identity instead of poll-reading
    # its own wreck to the deadline on every restart.
    with open(path, "w") as fh:
        fh.write('{"run_id": "r-kil')
    launch.clear_stale_run_id(d)
    assert not os.path.exists(path)
    assert "torn" in capsys.readouterr().err

    # Valid JSON missing the key is just as unusable.
    with open(path, "w") as fh:
        json.dump({"host": "h0"}, fh)
    launch.clear_stale_run_id(d)
    assert not os.path.exists(path)

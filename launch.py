#!/usr/bin/env python
"""Multi-process launcher — the ``torchrun`` equivalent (SURVEY.md §2b N8).

On a TPU host ONE process drives all of the host's chips: a chip belongs to
one process at a time, and the launcher has no way to hand each child a chip
of its own. So on the chip run ``main.py`` alone, or, where the supervisor
(``--restart-policy``, ``--elastic``) is wanted,

    python launch.py --nprocs 1 -- main.py --config gpt2_124m ...

(the launcher itself never initialises a jax backend, so it does not take
the chip from its child). ``--nprocs N`` with N > 1 is for local
multi-process CPU pods (``--cpu-devices``) and for jax-free jobs:

    python launch.py --nprocs 2 --cpu-devices 4 -- main.py --distributed ...

spawns N processes with COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID set
(plus per-process CPU device partitioning when --cpu-devices is given),
streams rank-0 output, and propagates the first non-zero exit — torchrun's
contract, including elasticity (``--elastic``, below).

Supervisor mode (``--restart-policy``): when a run exits with the distinct
preemption code (resilience.PREEMPTED_EXIT_CODE — the trainer's
graceful-shutdown path after a SIGTERM took its emergency checkpoint), or
with any failure under ``on-failure``, the whole gang is relaunched with
``--resume auto`` appended, up to ``--max-restarts`` times with exponential
backoff. This is the "gang-scheduled slices get preempted and restart from
the latest checkpoint" recovery loop, run locally.

Elastic mode (``--elastic MIN[:MAX]``): before each restart the supervisor
reads the dead-host records (``dead_hosts.jsonl`` in the child's
``--checkpoint-dir``, written by an abruptly dying attempt — chaos
``kill_host`` or a real hard failure) and relaunches at the surviving world
size instead of the original one. The abrupt host-loss exit code
(resilience.HOST_LOST_EXIT_CODE) is restartable under any restart policy
when ``--elastic`` is set. Below MIN the supervisor gives up; the trainer
side (``main.py --elastic``) rebuilds the mesh at the new size and rescales
the batch geometry under ``--elastic-policy`` (utils/elastic.py).

The world also grows back: a host-return record (``returned_hosts.jsonl``,
written by whoever notices the repair — a node manager, a probe, the host
itself) cancels its dead record, and the next relaunch runs at
``base_world - |currently dead|``, capped by MAX and the launch-time size.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

try:
    # The package __init__ imports jax (core.mesh, core.precision), but
    # importing initialises no backend and resilience.py / elastic.py never
    # touch one: the launcher cannot take the chip from its children
    # (tests/test_elastic.py checks it).
    from pytorch_distributed_training_example_tpu.utils.resilience import (
        HOST_LOST_EXIT_CODE, PREEMPTED_EXIT_CODE, retriable_io)
    from pytorch_distributed_training_example_tpu.utils.elastic import (
        effective_dead_hosts)
except ImportError:  # stripped deployments: keep the launcher standalone
    PREEMPTED_EXIT_CODE = 75
    HOST_LOST_EXIT_CODE = 76

    def effective_dead_hosts(directory):
        return set()

    def retriable_io(fn, *args, _what="io", _attempts=4,
                     _base_delay_s=0.05, **kwargs):
        return fn(*args, **kwargs)


def _read_json(path):
    with open(path) as fh:
        return json.load(fh)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def probe_port(port: int) -> bool:
    """True when ``port`` is actually bindable right now."""
    try:
        with socket.socket() as s:
            s.bind(("", port))
        return True
    except OSError:
        return False


def coordinator_port(preferred: int | None) -> int:
    """Pick a bindable coordinator port, preferring the configured one.

    A supervisor restart previously burned a whole restart-budget attempt on
    EADDRINUSE when the preferred port (or the freshly allocated one, in a
    rare close-to-spawn race) was still held — e.g. the dying attempt's
    socket lingering outside TIME_WAIT, or another job grabbing it. Probe
    before spawning children and fall back to a fresh port with a warning
    instead.
    """
    candidates = ([preferred] if preferred else []) + \
        [free_port() for _ in range(3)]
    for i, port in enumerate(candidates):
        if probe_port(port):
            if i > 0 and preferred:
                print(f"launch.py: coordinator port {preferred} is not "
                      f"bindable — using {port} instead", file=sys.stderr)
            return port
    raise OSError(
        f"no bindable coordinator port found (tried {candidates})")


_interrupted = False


def run_once(args, cmd) -> int:
    """Spawn the gang once, poll all ranks, return the first failure code."""
    # Fresh port per attempt: the previous attempt's coordinator socket can
    # linger in TIME_WAIT and wedge the rendezvous of a restart. Probed for
    # bindability so a held port costs a warning, not a restart attempt.
    port = coordinator_port(args.coordinator_port)
    procs = []
    for rank in range(args.nprocs):
        env = os.environ.copy()
        env["COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["NUM_PROCESSES"] = str(args.nprocs)
        env["PROCESS_ID"] = str(rank)
        # torchrun-compatible aliases
        env["MASTER_ADDR"], env["MASTER_PORT"] = "127.0.0.1", str(port)
        env["WORLD_SIZE"], env["RANK"] = str(args.nprocs), str(rank)
        if args.cpu_devices:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count={args.cpu_devices}").strip()
        if rank == 0:
            out = err = None
        else:
            out = err = retriable_io(
                open, os.path.join(args.log_dir, f"launch_rank{rank}.log"),
                "w", _what="rank log open")
        procs.append(subprocess.Popen([sys.executable, *cmd], env=env,
                                      stdout=out, stderr=err))

    def kill_all(*signal_args):
        if signal_args:
            # Operator-initiated teardown (Ctrl-C / SIGTERM to the launcher):
            # the supervisor must NOT restart what the human just killed.
            global _interrupted
            _interrupted = True
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()

    signal.signal(signal.SIGINT, kill_all)
    signal.signal(signal.SIGTERM, kill_all)

    # Poll ALL ranks: the first failure tears the job down immediately
    # (a dead rank would otherwise leave the rest blocked in a collective
    # and the launcher hung in a serial wait()).
    code = None
    while code is None:
        time.sleep(0.2)
        rcs = [pr.poll() for pr in procs]
        failed = [rc for rc in rcs if rc not in (None, 0)]
        if failed:
            code = failed[0]
            kill_all()
        elif all(rc == 0 for rc in rcs):
            code = 0
    for pr in procs:
        try:
            pr.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pr.kill()
    return code


def parse_elastic(spec: str) -> tuple[int, int]:
    """``MIN`` or ``MIN:MAX`` -> (min_world, max_world)."""
    lo, _, hi = spec.partition(":")
    min_world = int(lo)
    max_world = int(hi) if hi else 1 << 30
    if min_world < 1 or max_world < min_world:
        raise ValueError(f"--elastic expects MIN[:MAX] with 1 <= MIN <= MAX, "
                         f"got {spec!r}")
    return min_world, max_world


def find_flag(cmd: list[str], flag: str) -> str | None:
    """Value of ``flag <value>`` in the child command line (last wins)."""
    value = None
    for i, tok in enumerate(cmd[:-1]):
        if tok == flag:
            value = cmd[i + 1]
    return value


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--nprocs", type=int, default=2)
    p.add_argument("--coordinator-port", type=int, default=None)
    p.add_argument("--cpu-devices", type=int, default=0,
                   help="fake CPU devices per process (testing without TPUs)")
    p.add_argument("--log-dir", default="/tmp",
                   help="directory for non-rank-0 stdout/stderr logs "
                        "(launch_rankN.log)")
    p.add_argument("--restart-policy", default="never",
                   choices=["never", "on-preempt", "on-failure"],
                   help="supervisor mode: relaunch the gang with --resume "
                        "auto after a preemption exit (code "
                        f"{PREEMPTED_EXIT_CODE}; on-preempt) or after any "
                        "non-zero exit (on-failure)")
    p.add_argument("--max-restarts", type=int, default=3,
                   help="restart budget for the supervisor (per launcher run)")
    p.add_argument("--restart-backoff", type=float, default=1.0,
                   help="base seconds between restarts; doubles per restart")
    p.add_argument("--elastic", default=None, metavar="MIN[:MAX]",
                   help="elastic supervisor: on restart, shrink the world to "
                        "the surviving host set (dead_hosts.jsonl in the "
                        "child's --checkpoint-dir) instead of relaunching "
                        "the full gang; give up below MIN hosts. Makes the "
                        f"abrupt host-loss exit ({HOST_LOST_EXIT_CODE}) "
                        "restartable under any restart policy")
    p.add_argument("--trace-merge", default="auto", choices=["auto", "off"],
                   help="after the gang exits (any code), merge per-rank "
                        "telemetry in the child's --checkpoint-dir into one "
                        "fleet trace/goodput/straggler report "
                        "(benchmarks/trace_merge.py); auto = when artifacts "
                        "exist")
    p.add_argument("--fleet", default=None, metavar="JOBS_JSON",
                   help="multi-job control plane: run the utils/scheduler.py "
                        "loop over the jobs in JOBS_JSON sharing one device "
                        "pool — priorities, SIGTERM preemption (exit "
                        f"{PREEMPTED_EXIT_CODE} requeues without burning the "
                        "restart budget), doubling backoff, and backfill of "
                        "devices freed by dead hosts; ignores the "
                        "single-gang flags")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="with --fleet: serve cluster + per-job pdtx_fleet_* "
                        "gauges on one /metrics endpoint (0 = ephemeral)")
    p.add_argument("--fleet-poll", type=float, default=0.05,
                   help="with --fleet: scheduler loop poll interval seconds")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="-- script.py args...")
    args = p.parse_args(argv)
    if args.fleet is not None:
        retriable_io(os.makedirs, args.log_dir, exist_ok=True,
                     _what="log dir create")
        return run_fleet(args)
    cmd = args.cmd[1:] if args.cmd and args.cmd[0] == "--" else args.cmd
    if not cmd:
        p.error("no command given; usage: launch.py --nprocs N -- main.py ...")
    elastic = None
    if args.elastic is not None:
        if args.restart_policy == "never":
            p.error("--elastic needs a restart policy (on-preempt or "
                    "on-failure): shrinking happens at relaunch")
        try:
            elastic = parse_elastic(args.elastic)
        except ValueError as e:
            p.error(str(e))
    retriable_io(os.makedirs, args.log_dir, exist_ok=True,
                 _what="log dir create")
    code = supervise(args, cmd, elastic)
    if args.trace_merge == "auto":
        # Post-mortem-friendly: the merge runs after EVERY terminal outcome
        # — success, budget exhaustion, elastic give-up — because the fleet
        # view matters most when the run died. Best-effort by design.
        merge_traces(cmd)
    return code


def merge_traces(cmd: list[str]) -> None:
    """Merge the attempt's telemetry artifacts into the fleet view (one
    subprocess call of ``benchmarks/trace_merge.py``; skipped quietly when
    there is nothing to merge or the script is absent)."""
    ckdir = find_flag(cmd, "--checkpoint-dir")
    if not ckdir or not os.path.isdir(ckdir):
        return
    try:
        names = retriable_io(os.listdir, ckdir, _what="trace merge scan")
    except OSError:
        return
    if not any(n.startswith("trace_events") and n.endswith(".json")
               for n in names):
        return
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmarks", "trace_merge.py")
    if not os.path.exists(script):
        return
    try:
        res = subprocess.run([sys.executable, script, ckdir],
                             capture_output=True, text=True, timeout=120)
        out = (res.stdout or res.stderr or "").strip()
        tag = "" if res.returncode == 0 else f" (exit {res.returncode})"
        print(f"launch.py: trace merge{tag}:\n{out}", file=sys.stderr)
    except (OSError, subprocess.TimeoutExpired) as e:
        print(f"launch.py: trace merge failed ({e})", file=sys.stderr)


def start_reshard(ckdir: str, world: int):
    """Kick off the background checkpoint re-shard (core/reshard.py).

    The supervisor knows the surviving world the moment it reads the dead
    host records — *before* the restart backoff ends — so the consolidation
    of the newest committed checkpoint overlaps the backoff window instead
    of the relaunch's restore path. Best-effort: a failure to even spawn
    just means the relaunch restores the original layout.
    """
    mod = "pytorch_distributed_training_example_tpu.core.reshard"
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", mod, "--checkpoint-dir", ckdir,
             "--world", str(world)],
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)
    except OSError as e:
        print(f"launch.py: background re-shard failed to start ({e})",
              file=sys.stderr)
        return None
    print(f"launch.py: background re-shard started for world {world} "
          f"(pid {proc.pid})", file=sys.stderr)
    return proc


def finish_reshard(proc, ckdir: str, timeout_s: float = 60.0) -> None:
    """Join the background re-shard before relaunching.

    A hung or failed re-shard must never block the restart — the relaunch
    simply restores the original (un-consolidated) layout. Killing it is
    safe at any instant: reshard.py commits via the same ``.old`` set-aside
    swap as checkpoint.py, so a committed copy of the step always exists;
    only the ``.saving.reshard`` attempt dir can be left behind, and we
    sweep those here (the Checkpointer never prunes that suffix).
    """
    try:
        _, err = proc.communicate(timeout=timeout_s)
        code = proc.returncode
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        code = None
    if code == 0:
        print("launch.py: background re-shard ready — the relaunch restores "
              "a consolidated checkpoint", file=sys.stderr)
        return
    if code is None:
        print(f"launch.py: background re-shard overran the {timeout_s:.0f}s "
              "restart window — killed; the relaunch restores the original "
              "layout", file=sys.stderr)
    else:
        tail = (err or "").strip().splitlines()
        detail = f": {tail[-1]}" if tail else ""
        print(f"launch.py: background re-shard exit {code}{detail}",
              file=sys.stderr)
    try:
        for name in retriable_io(os.listdir, ckdir, _what="reshard sweep"):
            if name.startswith("step_") and name.endswith(".saving.reshard"):
                shutil.rmtree(os.path.join(ckdir, name), ignore_errors=True)
    except OSError:
        pass


def clear_stale_run_id(ckdir: str | None) -> None:
    """Remove a torn ``run_id.json`` before relaunching.

    An attempt killed mid-write (host loss, preemption during startup) can
    leave the shared run-identity file truncated. Rank 0 of the relaunch
    refuses to trust it and every rank would fall back to per-process ids —
    telemetry artifacts from the same logical run would then never merge.
    The supervisor owns the restart boundary, so it clears the wreck here,
    loudly; a *healthy* file is preserved (attempt counters must keep
    monotonically increasing across restarts).
    """
    if not ckdir:
        return
    path = os.path.join(ckdir, "run_id.json")
    if not os.path.exists(path):
        return
    try:
        str(retriable_io(_read_json, path, _what="run_id check")["run_id"])
        return  # healthy: keep the shared identity
    except (OSError, ValueError, KeyError, TypeError):
        pass
    print(f"launch.py: {path} is torn (an earlier attempt died mid-write) — "
          "clearing it so the relaunch re-establishes a shared run identity",
          file=sys.stderr)
    try:
        retriable_io(os.unlink, path, _what="run_id clear")
    except OSError as e:
        print(f"launch.py: could not clear torn run_id.json ({e})",
              file=sys.stderr)


def supervise(args, cmd, elastic) -> int:
    """The restart loop: run the gang until a terminal exit code."""
    # The elastic "world" is whichever knob actually multiplexes hosts in
    # this launch: real processes when --nprocs > 1, else fake CPU devices
    # (the single-process local pod used by tests and dryrun drills).
    world_attr = "nprocs" if args.nprocs > 1 else "cpu_devices"
    dead_seen: set[int] = set()
    base_world: int | None = None  # launch-time size: the grow ceiling
    reshard_proc = None  # background checkpoint consolidation, one at a time

    restarts = 0
    while True:
        code = run_once(args, cmd)
        if code == 0 or _interrupted:
            return code
        restartable = (args.restart_policy == "on-failure"
                       or (args.restart_policy == "on-preempt"
                           and code == PREEMPTED_EXIT_CODE)
                       or (elastic is not None
                           and code == HOST_LOST_EXIT_CODE))
        if args.restart_policy == "never" or not restartable:
            return code
        if restarts >= args.max_restarts:
            print(f"launch.py: restart budget exhausted "
                  f"({args.max_restarts}); last exit code {code}",
                  file=sys.stderr)
            return code
        if elastic is not None:
            ckdir = find_flag(cmd, "--checkpoint-dir")
            # Absolute accounting, not incremental: the next world size is
            # always base_world minus the hosts dead RIGHT NOW (dead minus
            # returned, count-based), so a host-return record GROWS the
            # world back — capped by the launch-time size and --elastic MAX.
            dead_now = effective_dead_hosts(ckdir) if ckdir else set()
            new_dead = dead_now - dead_seen
            returned = dead_seen - dead_now
            if new_dead or returned:
                dead_seen = dead_now
                world = getattr(args, world_attr) or 1
                if base_world is None:
                    # First size change: ``world`` is still the launch size.
                    base_world = world
                min_world, max_world = elastic
                new_world = min(max(base_world - len(dead_now), 0), max_world)
                if new_world < min_world:
                    print(f"launch.py: elastic give-up — {len(new_dead)} "
                          f"host(s) {sorted(new_dead)} lost, surviving world "
                          f"{new_world} is below --elastic min {min_world}",
                          file=sys.stderr)
                    return code
                if new_dead:
                    print(f"launch.py: elastic — host(s) {sorted(new_dead)} "
                          f"lost, relaunching at world size {new_world} "
                          f"(was {world})", file=sys.stderr)
                if returned:
                    print(f"launch.py: elastic — host(s) {sorted(returned)} "
                          f"returned, relaunching at world size {new_world} "
                          f"(was {world})", file=sys.stderr)
                setattr(args, world_attr, new_world)
                if ckdir and new_world and reshard_proc is None:
                    # Overlap the backoff: consolidate the newest committed
                    # checkpoint for the surviving world while nothing runs.
                    reshard_proc = start_reshard(ckdir, new_world)
        restarts += 1
        delay = args.restart_backoff * 2 ** (restarts - 1)
        print(f"launch.py: exit code {code} -> restart {restarts}/"
              f"{args.max_restarts} with --resume auto in {delay:.1f}s",
              file=sys.stderr)
        time.sleep(delay)
        if reshard_proc is not None:
            finish_reshard(reshard_proc,
                           find_flag(cmd, "--checkpoint-dir") or "")
            reshard_proc = None
        clear_stale_run_id(find_flag(cmd, "--checkpoint-dir"))
        if _interrupted:  # Ctrl-C during the backoff window
            return code
        if "--resume" not in cmd:
            # argparse last-wins makes appending safe even if a later restart
            # re-appends; guard anyway to keep the command line readable.
            cmd = [*cmd, "--resume", "auto"]


def write_cluster_goodput(sched, log_dir: str) -> dict | None:
    """Fold each job's merged ``goodput.json`` into one cluster summary
    (``cluster_goodput.json`` in the fleet log dir) — distinct run_ids by
    construction, which is what ``check_regression.py --goodput --cluster``
    gates. Best-effort: jobs without telemetry just don't contribute."""
    from pytorch_distributed_training_example_tpu.utils import fleetobs
    from pytorch_distributed_training_example_tpu.utils import (
        scheduler as scheduler_lib)

    per_job = {}
    for name in sorted(sched.jobs):
        ckdir = sched.state(name).spec.checkpoint_dir
        if not ckdir:
            continue
        path = os.path.join(ckdir, "goodput.json")
        if not os.path.exists(path):
            continue
        try:
            per_job[name] = retriable_io(_read_json, path,
                                         _what="fleet goodput read")
        except (OSError, ValueError):
            print(f"launch.py: fleet — unreadable goodput for {name} "
                  f"({path})", file=sys.stderr)
    if not per_job:
        return None
    cluster = fleetobs.aggregate_cluster_goodput(per_job)
    fleetobs.write_json_atomic(
        os.path.join(log_dir, scheduler_lib.CLUSTER_GOODPUT_FILE), cluster)
    return cluster


def run_fleet(args) -> int:
    """The multi-job control plane: spawn/preempt/relaunch what the
    scheduler decides, over one shared pool of fake CPU devices.

    Each job runs as one local process whose ``world`` is its fake-device
    count (the same local-pod shape ``--nprocs 1 --cpu-devices N`` uses and
    the dryrun drills test); on a real pod the worlds would map to hosts.
    Preemption is a SIGTERM — the trainer's resilience path takes its
    emergency checkpoint and exits PREEMPTED_EXIT_CODE, and the scheduler
    requeues it; relaunches append ``--resume auto``.
    """
    from pytorch_distributed_training_example_tpu.utils import fleetobs
    from pytorch_distributed_training_example_tpu.utils import (
        scheduler as scheduler_lib)

    pool, specs = scheduler_lib.load_jobs(args.fleet)
    sched = scheduler_lib.FleetScheduler(pool, specs, log_dir=args.log_dir)
    print(f"launch.py: fleet — {len(specs)} job(s) over a pool of "
          f"{pool} device(s)", file=sys.stderr)
    procs: dict[str, subprocess.Popen] = {}
    logs: dict[str, object] = {}
    metrics = None
    if args.metrics_port is not None:
        metrics = fleetobs.MetricsServer(port=args.metrics_port).start()
        print(f"launch.py: fleet metrics on :{metrics.port}", file=sys.stderr)

    def stop_fleet(*_sig):
        global _interrupted
        _interrupted = True
        for pr in procs.values():
            if pr.poll() is None:
                pr.terminate()

    signal.signal(signal.SIGINT, stop_fleet)
    signal.signal(signal.SIGTERM, stop_fleet)

    def spawn(name: str, world: int) -> None:
        st = sched.state(name)
        cmd = list(st.spec.cmd)
        if st.attempts > 1 and "--resume" not in cmd:
            # argparse last-wins; same relaunch contract as supervise().
            cmd = [*cmd, "--resume", "auto"]
        port = coordinator_port(None)
        env = os.environ.copy()
        env.update(dict(st.spec.env))
        env["PDTX_JOB_KIND"] = st.spec.kind
        env["COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["NUM_PROCESSES"], env["PROCESS_ID"] = "1", "0"
        env["MASTER_ADDR"], env["MASTER_PORT"] = "127.0.0.1", str(port)
        env["WORLD_SIZE"], env["RANK"] = "1", "0"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={world}").strip()
        if name not in logs:
            logs[name] = retriable_io(
                open, os.path.join(args.log_dir, f"fleet_{name}.log"), "a",
                _what="fleet log open")
        print(f"launch.py: fleet — launch {name} at world {world} "
              f"(attempt {st.attempts})", file=sys.stderr)
        procs[name] = subprocess.Popen([sys.executable, *cmd], env=env,
                                       stdout=logs[name], stderr=logs[name])

    last_obs_push = float("-inf")
    while not _interrupted:
        for name, pr in list(procs.items()):
            rc = pr.poll()
            if rc is not None:
                procs.pop(name)
                row = sched.on_exit(name, rc, time.monotonic())
                print(f"launch.py: fleet — {name} exited {rc}: "
                      f"{row['reason']}", file=sys.stderr)
        now = time.monotonic()
        decisions = sched.plan(now)
        for d in decisions:
            if d["action"] == "launch":
                spawn(d["job"], d["world"])
            elif d["action"] == "preempt":
                print(f"launch.py: fleet — preempt {d['job']}: "
                      f"{d['reason']}", file=sys.stderr)
                pr = procs.get(d["job"])
                if pr is not None and pr.poll() is None:
                    pr.send_signal(signal.SIGTERM)
        if metrics is not None:
            metrics.update(**sched.gauges())
            if now - last_obs_push >= 2.0:
                # Per-job artifact gauges at a gentle cadence: straggler
                # flag counts (r12 detection, previously write-only) so a
                # slow host is scrapeable while the fleet runs.
                last_obs_push = now
                for name in sched.jobs:
                    ckdir = sched.state(name).spec.checkpoint_dir
                    if not ckdir:
                        continue
                    rows = fleetobs.read_jsonl_tolerant(
                        os.path.join(ckdir, fleetobs.STRAGGLER_FILE))
                    if rows:
                        metrics.update(**fleetobs.straggler_gauges(
                            rows, prefix=f"fleet_straggler_{name}"))
        if sched.finished():
            break
        deadline = sched.next_deadline_s()
        if (not procs and not decisions
                and (deadline is None or deadline <= now)):
            # Whole pool free, every backoff expired, still nothing
            # placeable — the leftovers are permanently stuck (dependency
            # died checkpoint-less, or dead hosts pinned a range shut).
            for row in sched.mark_starved():
                print(f"launch.py: fleet — give up on {row['job']}: "
                      f"{row['reason']}", file=sys.stderr)
            break
        time.sleep(args.fleet_poll)

    for pr in procs.values():
        if pr.poll() is None:
            pr.terminate()
    for pr in procs.values():
        try:
            pr.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pr.kill()
    for fh in logs.values():
        fh.close()
    cluster = write_cluster_goodput(sched, args.log_dir)
    if cluster:
        print(f"launch.py: fleet — cluster goodput "
              f"{cluster.get('goodput_fraction')} coverage "
              f"{cluster.get('coverage')} over {len(cluster.get('jobs', []))}"
              f" job(s), {cluster.get('attempts')} attempt(s)",
              file=sys.stderr)
        if metrics is not None:
            metrics.update(
                fleet_goodput_fraction=cluster.get("goodput_fraction") or 0.0,
                fleet_goodput_coverage=cluster.get("coverage") or 0.0)
    states = {name: sched.state(name).status for name in sorted(sched.jobs)}
    print(f"launch.py: fleet — final states {states}", file=sys.stderr)
    if metrics is not None:
        metrics.update(**sched.gauges())
        metrics.stop()
    if _interrupted:
        return 130
    return 0 if all(s == scheduler_lib.DONE for s in states.values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())

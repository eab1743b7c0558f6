#!/usr/bin/env python
"""Chip smoke: the quickest proof that the trainer still starts on the TPU.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the sharded path across four chips

Drives the training main path through ``main.main(argv)`` in THIS process
(no children: a chip belongs to one process at a time), at the published
widths of the models, with random weights from a seed and synthetic data:

one chip
  1. GPT-2 124M (12 layers, d=768, 12 heads, S=1024, vocab 50257; bf16,
     ``fsdp`` rules on a one-device mesh, AdamW, grad clip), global batch 24:
     a handful of steps, eval, checkpoint save;
  2. the same command again with ``--resume auto``: must log
     ``resumed from step N`` and take more steps (hits the compile cache);
  3. ResNet-50 at batch 128 on synthetic images, three steps (conv/BN,
     ``dp``, the image loader).

``--chips 4`` (run by the builder; the driver has one chip)
  GPT-2 124M through ``main.main`` with ``--mesh fsdp=4`` against the same
  four steps on a one-device mesh: per-step losses agree, the parameters
  really occupy four devices, collectives counted in the compiled step.

Earlier lines are JSON rows worth keeping (versions, per phase the steps,
losses, compile seconds and cache hits, step time, peak bytes, the count of
``tpu_custom_call`` in the compiled step, which input pipeline ran). The LAST
line is the contract's: ``{"ok": ..., "device": {...}}``. Exits non-zero,
with ``"ok": false``, when jax finds no TPU or when any phase fails; there
is no CPU mode. Checkpoints and metrics go under ``chiprun_out/chip_smoke``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import logging
import math
import os
import shutil
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")

# Constant LR: the cosine schedule's horizon (epochs x steps) is a constant of
# the compiled step, so the resume phase (--epochs 2) would compile a
# different program than the first (--epochs 1) and never show a warm start.
GPT2_ARGV = ["--config", "gpt2_124m", "--batch-size", "24", "--log-every", "1",
             "--lr-schedule", "constant"]
#: |loss_fsdp4 - loss_1dev| per step, relative. Same init bits and batches;
#: what differs is the reduction order of bf16 matmuls/collectives. Measured
#: on a v5e 2x2: at most 7.6e-6 over four steps (CHANGES.md, PR 23); the
#: bound leaves a decade of room.
FSDP_LOSS_RTOL = 1e-4


def say(**row):
    print(json.dumps(row, default=float), flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _recorder():
    from pytorch_distributed_training_example_tpu.utils import telemetry

    return telemetry.recorder()


def _newest_record():
    """The largest ``id`` in the program's span recorder: a phase's compile
    records are those with a larger one."""
    return max((r.id for r in _recorder().records()), default=0)


def _compile_row(since):
    """Backend seconds (XLA compiles and executables the persistent cache
    served) and the cache's hits and misses of the records after ``since``,
    from the recorder that hears jax's compile pipeline."""
    found = [r for r in _recorder().records()
             if r.kind == "compile" and r.id > since]
    return {"compile_s": round(sum(r.seconds for r in found
                                   if r.name in ("compile", "cache_load")), 2),
            "cache_hits": sum(r.name == "cache_load" for r in found),
            "cache_misses": sum(r.name == "cache_miss" for r in found)}


class _LogTap(logging.Filter):
    """Collects the trainer's log lines a phase is judged by (a filter, not
    a handler: ``setup_logging`` resets the handlers on every Trainer)."""

    def __init__(self):
        super().__init__()
        self.lines: list[str] = []

    def filter(self, record):
        self.lines.append(record.getMessage())
        return True

    def find(self, needle):
        return next((l for l in self.lines if needle in l), None)


@contextlib.contextmanager
def _capture_trainer(box: list):
    """``main.main`` returns an exit code, not its Trainer: note the instance
    as ``train()`` is entered so the phase can inspect what it trained."""
    from pytorch_distributed_training_example_tpu.core import trainer as tr

    orig = tr.Trainer.train

    def train(self):
        box.append(self)
        return orig(self)

    tr.Trainer.train = train
    try:
        yield
    finally:
        tr.Trainer.train = orig


def _train_rows(ckpt_dir):
    with open(os.path.join(ckpt_dir, "metrics.jsonl")) as fh:
        rows = [json.loads(l) for l in fh if l.strip()]
    return [r for r in rows if r.get("kind") == "train"]


def _abstract(tree):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


def _compiled_step(trainer, batch):
    """The compiled train step, for its HLO and the compiler's byte counts
    (jit's own cache serves it: the trainer compiled these avals already)."""
    from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib

    with mesh_lib.use_mesh(trainer.mesh):
        return trainer.train_step.lower(
            _abstract(trainer.state), _abstract(batch)).compile()


def _one_batch(trainer):
    from pytorch_distributed_training_example_tpu.data import prefetch

    trainer.train_loader.set_epoch(0)
    trainer.train_loader.start_batch = 0
    it = prefetch.device_prefetch(trainer.train_loader, trainer.batch_sharding)
    try:
        return next(it)
    finally:
        it.close()


def _timed_steps(trainer, batch, n=5):
    """Median seconds of ``n`` more steps on a device-resident batch, each
    timed on the host clock around ``block_until_ready``."""
    import jax

    from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib

    times = []
    with mesh_lib.use_mesh(trainer.mesh):
        for _ in range(n):
            t0 = time.perf_counter()
            trainer.state, metrics = trainer.train_step(trainer.state, batch)
            jax.block_until_ready((trainer.state, metrics))
            times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _peak_bytes():
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()]


def run_main(name, argv, ckpt_dir, *, expect_steps):
    """One ``main.main(argv)`` phase; returns (row, trainer)."""
    import main
    from pytorch_distributed_training_example_tpu.utils import (
        metrics as metrics_lib)
    from pytorch_distributed_training_example_tpu.utils.logging import log

    tap, box = _LogTap(), []
    log.addFilter(tap)
    since = _newest_record()
    t0 = time.perf_counter()
    try:
        with _capture_trainer(box):
            rc = main.main([*argv, "--checkpoint-dir", ckpt_dir])
    finally:
        log.removeFilter(tap)
    wall = time.perf_counter() - t0
    check(rc == 0 and len(box) == 1, f"{name}: main.main returned {rc}")
    trainer = box[0]
    rows = _train_rows(ckpt_dir)[-expect_steps:]
    losses = [r["loss"] for r in rows]
    check(len(losses) == expect_steps,
          f"{name}: expected {expect_steps} logged steps, got {len(losses)}")
    check(all(math.isfinite(x) for x in losses),
          f"{name}: non-finite loss in {losses}")
    gaps = [b["time"] - a["time"] for a, b in zip(rows[1:], rows[2:])]
    row = {"phase": name, "steps": [r["step"] for r in rows],
           "losses": losses, "wall_s": round(wall, 2), **_compile_row(since),
           # host clock between consecutive per-step metric fetches (each a
           # blocking device_get): the loop's step time, input included
           "loop_step_s": statistics.median(gaps) if gaps else None,
           "loader": type(trainer.train_loader).__name__,
           "native_engine": tap.find("using native C++ batch engine") is not None,
           "resumed": tap.find("resumed from step"),
           "mesh": {k: v for k, v in trainer.mesh.shape.items() if v > 1}}
    batch = _one_batch(trainer)
    compiled = _compiled_step(trainer, batch)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    row["tpu_custom_calls"] = text.count("tpu_custom_call")
    # per device, by the compiler's count (memory_stats() below is the
    # allocator's view)
    row["step_bytes"] = {"arguments": mem.argument_size_in_bytes,
                         "temp": mem.temp_size_in_bytes}
    row["collectives"] = {
        op: text.count(f" {op}(") + text.count(f" {op}-start(")
        for op in ("all-gather", "reduce-scatter", "all-reduce",
                   "all-to-all", "collective-permute")}
    row["step_s"] = _timed_steps(trainer, batch)
    row["mfu_at_step_s"] = metrics_lib.mfu(
        trainer.cfg.global_batch_size / row["step_s"] / trainer.mesh.size,
        trainer.bundle.fwd_flops_per_example)
    row["peak_bytes_in_use"] = _peak_bytes()
    say(**row)
    return row, trainer


def one_chip():
    from pytorch_distributed_training_example_tpu.core import (
        checkpoint as checkpoint_lib)

    gdir = os.path.join(OUT, "gpt2_124m")
    first, trainer = run_main(
        "gpt2_train", [*GPT2_ARGV, "--epochs", "1", "--steps-per-epoch", "6"],
        gdir, expect_steps=6)
    check(first["tpu_custom_calls"] > 0,
          "gpt2_train: no tpu_custom_call in the compiled step — the XLA "
          "attention fallback was taken")
    check(6 in checkpoint_lib.all_checkpoints(gdir),
          "gpt2_train: no committed checkpoint at step 6")
    del trainer
    gc.collect()

    second, trainer = run_main(
        "gpt2_resume",
        [*GPT2_ARGV, "--epochs", "2", "--steps-per-epoch", "6",
         "--resume", "auto"],
        gdir, expect_steps=6)
    check(second["resumed"] and "resumed from step 6" in second["resumed"],
          f"gpt2_resume: did not restore (log: {second['resumed']!r})")
    check(second["steps"][0] == 6,
          f"gpt2_resume: continued at step {second['steps'][0]}, not 6")
    check(second["losses"][-1] < first["losses"][0],
          f"GPT-2 loss did not fall: first {first['losses'][0]}, "
          f"last {second['losses'][-1]}")
    del trainer
    gc.collect()

    run_main("resnet50_train",
             ["--config", "resnet50_imagenet", "--batch-size", "128",
              "--epochs", "1", "--steps-per-epoch", "3", "--log-every", "1"],
             os.path.join(OUT, "resnet50"), expect_steps=3)


def _param_bytes_per_device(params):
    import jax

    per = {}
    for leaf in jax.tree.leaves(params):
        for s in leaf.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return per


def four_chips():
    import jax

    from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
    from pytorch_distributed_training_example_tpu.core.trainer import Trainer
    import main

    check(len(jax.devices()) == 4, f"--chips 4 found {len(jax.devices())}")
    argv = [*GPT2_ARGV, "--epochs", "1", "--steps-per-epoch", "4"]

    sharded_row, sharded = run_main(
        "gpt2_fsdp4", [*argv, "--mesh", "fsdp=4"],
        os.path.join(OUT, "gpt2_fsdp4"), expect_steps=4)
    check(sharded_row["tpu_custom_calls"] > 0,
          "gpt2_fsdp4: the flash kernel is not in the compiled step")

    # Really spread? Code that never ran on more than one chip may put
    # everything on the first.
    leaves = jax.tree.leaves(sharded.state.params)
    split = [l for l in leaves if not l.sharding.is_fully_replicated]
    check(split, "gpt2_fsdp4: no parameter is sharded")
    for leaf in split:
        devs = {s.device.id for s in leaf.addressable_shards}
        check(len(devs) == 4, f"sharded param {leaf.shape} sits on {devs}")
    per_dev = _param_bytes_per_device(sharded.state.params)
    peaks = sharded_row["peak_bytes_in_use"]
    check(len(per_dev) == 4 and all(p > 0 for p in peaks),
          f"devices unused: param bytes {per_dev}, peak bytes {peaks}")
    del sharded, leaves, split
    gc.collect()

    # The same four steps on a one-device mesh (same seed, same batches).
    ref_dir = os.path.join(OUT, "gpt2_one_device")
    cfg = main.config_from_args(main.build_parser().parse_args(
        [*argv, "--checkpoint-dir", ref_dir]))
    since = _newest_record()
    ref = Trainer(cfg, mesh=mesh_lib.single_device_mesh(jax.devices()[0]))
    ref.train()
    ref_losses = [r["loss"] for r in _train_rows(ref_dir)][-4:]
    ref_bytes = _param_bytes_per_device(ref.state.params)
    check(len(ref_bytes) == 1, f"one-device run used {ref_bytes}")
    total = next(iter(ref_bytes.values()))
    say(phase="gpt2_one_device", losses=ref_losses, **_compile_row(since),
        param_bytes=total)

    rel = [abs(a - b) / abs(b)
           for a, b in zip(sharded_row["losses"], ref_losses)]
    say(phase="fsdp4_vs_one_device", losses_fsdp4=sharded_row["losses"],
        losses_one_device=ref_losses, rel_diff=rel, rtol=FSDP_LOSS_RTOL,
        param_bytes_per_device=per_dev, param_bytes_one_device=total,
        collectives=sharded_row["collectives"])
    check(len(ref_losses) == 4 and max(rel) <= FSDP_LOSS_RTOL,
          f"fsdp=4 losses differ from one device: rel diff {rel}")
    check(all(0.2 * total <= b <= 0.35 * total for b in per_dev.values()),
          f"per-device param bytes {per_dev} are not ~1/4 of {total}")
    check(sharded_row["collectives"]["all-gather"] > 0,
          "no all-gather in the compiled fsdp=4 step")


def main_(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=[1, 4], default=1,
                   help="4 = only the fsdp=4 path and its one-device twin")
    args = p.parse_args(argv)

    # The chip first, before any other work: no TPU, no result.
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        say(phase="device", error="jax found no TPU; chip_smoke.py has no "
            "CPU mode")
        print(json.dumps({"ok": False, "device": device}))
        return 1

    ok = False
    try:
        import importlib.metadata

        import jaxlib

        from pytorch_distributed_training_example_tpu.core import xcache

        try:
            libtpu = importlib.metadata.version("libtpu")
        except importlib.metadata.PackageNotFoundError:
            libtpu = None
        shutil.rmtree(OUT, ignore_errors=True)
        os.makedirs(OUT)
        say(phase="versions", jax=jax.__version__, jaxlib=jaxlib.__version__,
            libtpu=libtpu, device_kind=dev.device_kind,
            compile_cache=xcache.place_compile_cache())
        if args.chips == 4:
            four_chips()
        else:
            check(device["count"] == 1,
                  f"one-chip run found {device['count']} devices; use "
                  "--chips 4")
            one_chip()
        ok = True
    except (Exception, SystemExit) as e:  # a failed phase fails the run
        import traceback

        traceback.print_exc()
        say(phase="failed", error=f"{type(e).__name__}: {e}")
    finally:
        # Keep the metrics, drop the checkpoints (GPT-2's is 1.5 GB a save).
        for root, dirs, _ in os.walk(OUT):
            for d in [d for d in dirs if d.startswith("step_")]:
                shutil.rmtree(os.path.join(root, d), ignore_errors=True)
                dirs.remove(d)
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main_())

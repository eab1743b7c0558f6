#!/usr/bin/env python
"""Convergence artifact.

The reference's implicit acceptance test is "ResNet converges to known
accuracy" (SURVEY.md §4.4). Real CIFAR/ImageNet files and network access
don't exist in this environment, so this is the longest-horizon proxy
available: train the reference dev config (ResNet-18, 32px, 10 classes —
the CIFAR-10 preset's synthetic fallback, a deterministic pattern+noise
task) and record the full accuracy-vs-epoch curve as CONVERGENCE.json.

r5 hardening (the r4 artifact was a 2-point curve on an eval split that
reused the train noise stream):

- the eval split draws a DISJOINT per-sample noise stream (genuinely
  held-out; ``SyntheticImageDataset.noise_seed``);
- train-time augmentation is ON (reflect-pad-4 crop + flip — the CIFAR
  recipe), so the run measures learning under the reference transform,
  not memorization of fixed tensors;
- the curve runs the FULL horizon (no early stop): >= 5 points;
- a seen-samples/no-augment evaluation accompanies every epoch, and the
  final train/eval generalization gap is recorded and bounded.

A-priori acceptance (asserted by tests/test_convergence.py): held-out
top-1 >= 0.90 by the final epoch, and |seen - heldout| <= 0.10.

``--task lm`` (r17) is the LM counterpart with an ENTROPY-FLOOR gate
instead of an accuracy threshold. The synthetic LM stream
(``SyntheticTokenDataset``) draws tokens i.i.d. uniform over the vocab,
so the best achievable next-token loss is exactly ``ln(vocab_size)``
nats/token (6.2383 for llama_tiny's vocab of 512) — no model can beat
it without cheating. The gate is two-sided:

- final eval loss <= floor + margin: the optimizer actually drove the
  randomly-initialized logits down to the entropy floor (training and
  the loss plumbing work);
- final eval loss >= floor - eps: a loss BELOW the floor on i.i.d.
  uniform data is impossible except through target leakage — a broken
  causal mask (attention peeking at position t+1) or shifted-target
  misalignment. This is the cheap, always-on canary for that bug class.

    python benchmarks/convergence.py --out CONVERGENCE.json
    python benchmarks/convergence.py --task lm --out CONVERGENCE_LM.json

Runs on CPU fake devices by default (CI-runnable, no TPU needed).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time


def run_lm(args):
    """LM entropy-floor leg: train ``--model`` (llama_tiny default) on the
    uniform synthetic token stream and gate the final eval loss against
    ``ln(vocab_size)``."""
    import jax

    from pytorch_distributed_training_example_tpu.core.trainer import Trainer
    from pytorch_distributed_training_example_tpu.utils.config import Config

    model = args.model if args.model != "resnet18" else "llama_tiny"
    cfg = Config(
        model=model, dataset="lm", seq_len=args.seq_len,
        global_batch_size=args.batch_size, epochs=args.epochs,
        steps_per_epoch=args.steps_per_epoch, lr=args.lr,
        warmup_epochs=0.0, optimizer="adamw", weight_decay=0.0,
        precision="fp32", workers=0, evaluate=True, eval_every_epochs=1,
        checkpoint_dir=tempfile.mkdtemp(prefix="conv_lm_ck_"))
    t = Trainer(cfg)
    vocab = getattr(t.bundle.module, "vocab_size", None)
    assert vocab, f"{model} exposes no vocab_size; cannot place the floor"
    floor = math.log(vocab)

    curve = []
    t0 = time.time()
    for epoch in range(cfg.epochs):
        t.train_epoch(epoch)
        avg = t.evaluate(epoch)
        row = {"epoch": epoch, "step": int(t.state.step),
               "loss": round(avg.get("loss", float("nan")), 4),
               "wall_s": round(time.time() - t0, 1)}
        curve.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    t.metric_logger.close()

    final_loss = curve[-1]["loss"] if curve else float("nan")
    out = {
        "task": ("synthetic LM, tokens i.i.d. uniform over the vocab "
                 "(data/datasets.py SyntheticTokenDataset) — entropy floor "
                 "= ln(vocab) exactly; loss below the floor implies target "
                 "leakage (causal mask / target shift)"),
        "model": model,
        "vocab_size": vocab,
        "entropy_floor_nats": round(floor, 4),
        "floor_margin": args.floor_margin,
        "floor_eps": args.floor_eps,
        "seq_len": args.seq_len,
        "global_batch": args.batch_size,
        "steps_per_epoch": args.steps_per_epoch,
        "epochs": args.epochs,
        "lr": args.lr,
        "devices": jax.device_count(),
        "backend": jax.default_backend(),
        "final_loss": final_loss,
        "ok": (final_loss == final_loss  # NaN guard
               and floor - args.floor_eps <= final_loss
               <= floor + args.floor_margin),
        "curve": curve,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in
                      ("final_loss", "entropy_floor_nats", "ok")}))
    return 0 if out["ok"] else 1


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--task", default="vision", choices=["vision", "lm"],
                   help="vision: ResNet accuracy-threshold artifact; lm: "
                        "LM entropy-floor gate on the uniform token stream")
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--steps-per-epoch", type=int, default=30)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--model", default="resnet18")
    p.add_argument("--threshold", type=float, default=0.9)
    p.add_argument("--max-gap", type=float, default=0.10)
    p.add_argument("--seq-len", type=int, default=64,
                   help="--task lm: sequence length")
    p.add_argument("--floor-margin", type=float, default=0.10,
                   help="--task lm: final loss may sit this far ABOVE "
                        "ln(vocab) (optimizer still closing in)")
    p.add_argument("--floor-eps", type=float, default=1e-3,
                   help="--task lm: loss below floor - eps fails (target "
                        "leakage; fp sum tolerance only)")
    p.add_argument("--out", default=None)
    p.add_argument("--tpu", action="store_true",
                   help="run on the default backend instead of CPU fakes")
    args = p.parse_args(argv)
    if args.out is None:
        args.out = ("CONVERGENCE_LM.json" if args.task == "lm"
                    else "CONVERGENCE.json")

    if not args.tpu:
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if args.task == "lm":
        if args.epochs == 10 and args.steps_per_epoch == 30:
            # vision defaults are oversized for the floor gate; the LM leg
            # converges to ln(V) in a few hundred small-batch steps
            args.epochs, args.steps_per_epoch = 5, 40
        if args.batch_size == 128:
            args.batch_size = 16
        if args.lr == 0.05:
            args.lr = 1e-3
        return run_lm(args)

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
    from pytorch_distributed_training_example_tpu.core.trainer import Trainer
    from pytorch_distributed_training_example_tpu.data import (
        datasets as datasets_lib, loader as loader_lib, prefetch)
    from pytorch_distributed_training_example_tpu.utils import (
        metrics as metrics_lib)
    from pytorch_distributed_training_example_tpu.utils.config import from_preset

    # Record every consumed train index (the mid-epoch-resume debug hook)
    # so the seen-samples probe scores indices the optimizer REALLY
    # trained on — with a 51,200-sample shuffled pool and 30×128 consumed
    # per epoch, fixed probe indices would be mostly never-trained and the
    # gap bound near-vacuous (r5 review finding).
    idx_log = os.path.join(tempfile.mkdtemp(prefix="conv_idx_"), "idx.jsonl")

    cfg = from_preset(
        "resnet18_cifar10", model=args.model, global_batch_size=args.batch_size,
        epochs=args.epochs, steps_per_epoch=args.steps_per_epoch,
        lr=args.lr, workers=0, evaluate=True, eval_every_epochs=1,
        checkpoint_dir=tempfile.mkdtemp(prefix="conv_ck_"))
    t = Trainer(cfg)
    assert getattr(t.train_data, "augment", False), \
        "convergence run must train under augmentation"
    assert t.eval_data.noise_seed != t.train_data.noise_seed, \
        "eval split must be disjoint from the train noise stream"

    # Un-augmented view of the train distribution for the probe (the gap
    # is measured under eval transforms, like CIFAR practice).
    seen_ds = datasets_lib.SyntheticImageDataset(
        len(t.train_data), cfg.image_size, cfg.num_classes, cfg.seed,
        augment=False)

    def trained_indices():
        """Unique sample indices consumed by TRAINED steps (the loader
        overfetches a few batches past the steps-per-epoch cap; batches
        beyond the cap are dropped here)."""
        seen = []
        have = set()
        with open(idx_log) as fh:
            for line in fh:
                row = json.loads(line)
                if row["batch"] >= args.steps_per_epoch:
                    continue
                for i in row["indices"]:
                    if i not in have:
                        have.add(i)
                        seen.append(i)
        return seen

    def eval_seen(max_samples=2048):
        idx = trained_indices()[-max_samples:]
        sums = {}
        with mesh_lib.use_mesh(t.mesh):
            batches = (loader_lib.collate([seen_ds[i] for i in
                                           idx[j: j + t.local_batch]])
                       for j in range(0, len(idx) - t.local_batch + 1,
                                      t.local_batch))
            for batch in prefetch.device_prefetch(batches, t.batch_sharding):
                stats = t.eval_step(t.state, batch)
                for k, v in jax.device_get(stats).items():
                    sums[k] = sums.get(k, 0.0) + float(v)
        return metrics_lib.finalize_eval_sums(sums)

    curve = []
    t0 = time.time()
    reached = None
    for epoch in range(cfg.epochs):
        # The index log must record TRAIN consumption only — every
        # DataLoader in the process honors the env var, and evaluate()'s
        # eval-split batches would otherwise pollute trained_indices()
        # with never-trained samples (r5 review finding). Toggle it
        # around the phases; all loaders here are consumed synchronously.
        # try/finally so a raising train_epoch (OOM, fault injection)
        # can't leak the env var into the eval phase or the next run.
        os.environ[loader_lib.INDEX_LOG_ENV] = idx_log
        try:
            t.train_epoch(epoch)
        finally:
            os.environ.pop(loader_lib.INDEX_LOG_ENV, None)
        avg = t.evaluate(epoch)
        seen = eval_seen()
        row = {"epoch": epoch, "step": int(t.state.step),
               "acc_top1": round(avg.get("acc_top1", 0.0), 4),
               "acc_top5": round(avg.get("acc_top5", 0.0), 4),
               "loss": round(avg.get("loss", 0.0), 4),
               "seen_acc_top1": round(seen.get("acc_top1", 0.0), 4),
               "gap": round(seen.get("acc_top1", 0.0)
                            - avg.get("acc_top1", 0.0), 4),
               "wall_s": round(time.time() - t0, 1)}
        curve.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
        if reached is None and row["acc_top1"] >= args.threshold:
            reached = epoch
    t.metric_logger.close()

    final = curve[-1] if curve else {}
    out = {
        "task": ("synthetic CIFAR-10-shaped 10-class pattern+noise, "
                 "augmented train (pad-4 crop + flip), eval on a DISJOINT "
                 "noise stream of the same pattern distribution "
                 "(data/datasets.py SyntheticImageDataset noise_seed)"),
        "model": args.model,
        "global_batch": args.batch_size,
        "steps_per_epoch": args.steps_per_epoch,
        "epochs": args.epochs,
        "lr": args.lr,
        "devices": jax.device_count(),
        "backend": jax.default_backend(),
        "threshold": args.threshold,
        "max_gap": args.max_gap,
        "reached_at_epoch": reached,
        "final_acc_top1": final.get("acc_top1", 0.0),
        "final_seen_acc_top1": final.get("seen_acc_top1", 0.0),
        "generalization_gap": final.get("gap", 1.0),
        # acceptance = the stated a-priori rule: held-out accuracy at the
        # FINAL epoch (late regression must fail, matching the artifact
        # test), plus the bounded train/eval gap.
        "ok": (final.get("acc_top1", 0.0) >= args.threshold
               and abs(final.get("gap", 1.0)) <= args.max_gap),
        "curve": curve,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in
                      ("final_acc_top1", "generalization_gap",
                       "reached_at_epoch", "ok")}))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())

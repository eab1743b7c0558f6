"""Optimizer + LR schedule construction (optax chains).

Reference parity: SGD-momentum with step/cosine decay for the vision configs,
AdamW for ViT/GPT/Llama; warmup + cosine is the modern default for all five
presets. Gradient clipping folds into the optax chain (the reference would
call ``clip_grad_norm_`` between unscale and step).
"""

from __future__ import annotations

import optax

from pytorch_distributed_training_example_tpu.utils.config import Config


def build_schedule(cfg: Config, steps_per_epoch: int) -> optax.Schedule:
    total_steps = max(int(cfg.epochs * steps_per_epoch), 1)
    warmup_steps = min(int(cfg.warmup_epochs * steps_per_epoch), total_steps - 1)
    if cfg.lr_schedule == "step":
        # The reference ImageNet recipe (StepLR): lr * gamma^(epoch //
        # step_epochs), evaluated on the GLOBAL step grid — decay epochs
        # must not shift with warmup. join_schedules hands the post-warmup
        # schedule (step - boundary), so shift it back by warmup_steps.
        stair = optax.exponential_decay(
            cfg.lr, transition_steps=max(cfg.lr_step_epochs, 1)
            * steps_per_epoch, decay_rate=cfg.lr_gamma, staircase=True)
        main = ((lambda step: stair(step + warmup_steps))
                if warmup_steps > 0 else stair)
    elif cfg.lr_schedule == "constant":
        main = optax.constant_schedule(cfg.lr)
    elif cfg.lr_schedule == "cosine":
        main = optax.cosine_decay_schedule(
            cfg.lr, decay_steps=max(total_steps - warmup_steps, 1))
    else:
        raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r} "
                         "(cosine | step | constant)")
    if warmup_steps > 0:
        return optax.join_schedules(
            [optax.linear_schedule(0.0, cfg.lr, warmup_steps), main],
            boundaries=[warmup_steps])
    return main


def adam_b2(task: str) -> float:
    """AdamW's second-moment decay, chosen by the model's task and not by a
    substring of its name: 0.95 for every language model (the GPT-3 / Llama
    recipe), optax's 0.999 for the rest (ViT)."""
    return 0.95 if task == "lm" else 0.999


def build_optimizer(cfg: Config, steps_per_epoch: int, task: str | None = None):
    """Returns ``(tx, schedule)``; schedule is also used for logging lr.

    ``task`` is the model bundle's (``core/trainer.build_step_program`` has
    it in hand); a caller with a ``Config`` alone leaves it out and AdamW
    asks the registry."""
    schedule = build_schedule(cfg, steps_per_epoch)
    parts = []
    if cfg.grad_clip and cfg.grad_clip > 0:
        parts.append(optax.clip_by_global_norm(cfg.grad_clip))
    if cfg.optimizer == "sgd":
        parts += [
            optax.sgd(schedule, momentum=cfg.momentum, nesterov=True),
        ]
        if cfg.weight_decay:
            # Decoupled WD on >=2D params only (skip BN/bias), torch-style.
            parts.insert(-1, optax.add_decayed_weights(
                cfg.weight_decay, mask=_wd_mask))
    elif cfg.optimizer == "adamw":
        if task is None:
            from pytorch_distributed_training_example_tpu.models import registry

            task = registry.create_model(cfg.model).task
        parts.append(optax.adamw(
            schedule, b1=0.9, b2=adam_b2(task),
            weight_decay=cfg.weight_decay, mask=_wd_mask,
        ))
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    return optax.chain(*parts), schedule


def _wd_mask(params):
    import jax

    return jax.tree.map(lambda p: p.ndim >= 2, params)

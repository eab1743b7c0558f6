"""What the plain references share: operand rounding for the control, AdamW
written out, and the three training steps every reference follows.

Nothing here imports the program. Everything is float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul runs
in bfloat16 passes unless that is set).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rounder(precision: str):
    """Returns ``q(x)``: ``x`` rounded to ``precision`` with a
    straight-through gradient; a reference wraps the operands of every matmul
    and convolution in it. ``"highest"`` is the reference itself. The others
    are controls, the reference computed one precision below the
    configuration's: ``"bf16"``, and ``"fp8"`` (e4m3 with one scale per
    tensor, as fp8 training recipes do).
    """
    if precision == "highest":
        return lambda x: x
    if precision == "bf16":
        low = lambda x: x.astype(jnp.bfloat16).astype(F32)
    elif precision == "fp8":
        def low(x):
            scale = 256.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
            return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return lambda x: x + jax.lax.stop_gradient(low(x) - x)


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in tree.items()}


def decayed(leaf):
    return leaf.ndim >= 2  # weight decay skips norms and biases


def clip(grads: dict, max_norm: float):
    if not max_norm:
        return grads
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    factor = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return {k: g * factor for k, g in grads.items()}


def adamw(opt: dict):
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["lr"], opt["weight_decay"]

    def init(params):
        zeros = lambda: {k: jnp.zeros_like(v) for k, v in params.items()}
        return {"m": zeros(), "v": zeros()}

    def step(params, grads, state, t):
        grads = clip(grads, opt.get("grad_clip", 0.0))
        m = {k: b1 * state["m"][k] + (1 - b1) * g for k, g in grads.items()}
        v = {k: b2 * state["v"][k] + (1 - b2) * g * g for k, g in grads.items()}
        new = {}
        for k, p in params.items():
            upd = (m[k] / (1 - b1 ** t)) / (jnp.sqrt(v[k] / (1 - b2 ** t)) + eps)
            if decayed(p):
                upd = upd + wd * p
            new[k] = p - lr * upd
        return new, {"m": m, "v": v}, m

    return init, step


def three_steps(loss_and_grads, params: dict, batches: list, optimizer,
                scale: float) -> dict:
    """Follow the first steps of training from ``params`` over ``batches``.

    ``loss_and_grads(params, batch) -> (loss, grads)`` and ``optimizer``, an
    ``(init, step)`` pair such as ``adamw(opt)``, are the reference's own.
    Returns what the comparison reads: each step's loss, the per-leaf norm of
    the optimizer's first moment after step one times ``scale`` (the
    configuration's ``first_moment_scale``; for Adam the product is the
    clipped gradient), and the per-leaf norm of the parameters' change after
    the last.
    """
    init, step = optimizer
    step = jax.jit(step, static_argnums=3, donate_argnums=(0, 2))
    state = init(params)
    start = {k: jnp.copy(v) for k, v in params.items()}
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, 1):
            loss, grads = loss_and_grads(params, batch)
            params, state, moment = step(params, grads, state, t)
            out["loss"].append(float(loss))
            if t == 1:
                out["moment_norms"] = {
                    k: float(v) * scale
                    for k, v in jax.jit(leaf_norms)(moment).items()}
        diff = jax.jit(lambda a, b: leaf_norms(
            {k: a[k] - b[k] for k in a}))(params, start)
    out["dparam_norms"] = {k: float(v) for k, v in diff.items()}
    return out

"""Plain reference for the GPT-2 configurations: loss and gradients of the
published architecture (Radford et al. 2019; HF ``gpt2``): learned token and
position embeddings, pre-LN blocks, causal softmax attention, tanh-GELU MLP
at 4x width, biased projections, tied head, mean next-token cross-entropy.

float32 ``jax.numpy``, no kernels, no sharding, no cache; imports nothing of
the program. Weights come as a flat ``{path: array}`` in the checkpoint
layout the benchmark generates (query/key/value kernels ``[d, heads, hd]``,
out kernel ``[heads, hd, d]``). Gradients are the mean over equal
micro-batches so that the float32 activations fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.references import _plain


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one sequence, as the benchmark counts them: 2 per
    multiply-accumulate, matmuls only (bias adds, norms and activations are
    not counted, so a share of a peak computed from this can only come out
    low), nothing recomputed.

    Per token: the block matmuls (qkv 3d^2, out d^2, mlp 2*r*d^2), the tied
    head (d*V), and causal attention: QK^T and PV over the S*(S+1)/2 pairs
    the mask keeps, i.e. 2 * d * (S+1)/2 multiply-accumulates per token and
    layer.
    """
    d, L, V = model["n_embd"], model["n_layer"], model["vocab_size"]
    r = model.get("mlp_ratio", 4)
    S = traffic["seq_len"]
    block_macs = (4 + 2 * r) * d * d
    attn_macs = 2 * d * (S + 1) / 2
    return 2.0 * (L * (block_macs + attn_macs) + d * V) * S


def _ln(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias


def _block(x, w, q):
    S = x.shape[1]
    h = _ln(x, w["ln_1/scale"], w["ln_1/bias"])
    proj = lambda n: jnp.einsum("bsd,dhk->bshk", q(h),
                                q(w[f"attn/{n}/kernel"])) \
        + w[f"attn/{n}/bias"]
    qh, kh, vh = proj("query"), proj("key"), proj("value")
    scores = jnp.einsum("bqhk,bthk->bhqt", q(qh), q(kh)) / jnp.sqrt(
        jnp.float32(qh.shape[-1]))
    mask = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqt,bthk->bqhk", q(probs), q(vh))
    x = x + jnp.einsum("bshk,hkd->bsd", q(att), q(w["attn/out/kernel"])) \
        + w["attn/out/bias"]
    h = _ln(x, w["ln_2/scale"], w["ln_2/bias"])
    h = jax.nn.gelu(q(h) @ q(w["mlp_up/kernel"]) + w["mlp_up/bias"],
                    approximate=True)
    return x + q(h) @ q(w["mlp_down/kernel"]) + w["mlp_down/bias"]


def loss_fn(params, batch, model, precision="highest"):
    q = _plain.rounder(precision)
    tokens, targets = batch["tokens"], batch["targets"]
    wte = params["wte/embedding"]
    x = wte[tokens] + params["wpe"][None, : tokens.shape[1]]
    for i in range(model["n_layer"]):
        pre = f"block_{i}/"
        w = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = jax.checkpoint(functools.partial(_block, q=q))(x, w)
    x = _ln(x, params["ln_f/scale"], params["ln_f/bias"])
    logits = q(x) @ q(wte).T
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def run(config: dict, params: dict, batches: list, precision="highest") -> dict:
    """Three steps from ``params`` over ``batches`` (host arrays).

    The micro-batches of a step go round the local devices, each holding a
    copy of the parameters, and their gradients are added up on the first:
    independent devices, no sharding, so a four-chip cell's batch takes no
    longer than a one-chip cell's."""
    model, micro = config["model"], config["reference_micro_batch"]
    grad = jax.jit(jax.value_and_grad(
        functools.partial(loss_fn, model=model, precision=precision)))
    devices = jax.local_devices()
    add = functools.partial(jax.tree.map, jnp.add)

    def loss_and_grads(p, batch):
        n = batch["tokens"].shape[0]
        if n % micro:
            raise ValueError(f"batch {n} is not a multiple of {micro}")
        copies = [jax.device_put(p, d) for d in devices]
        sums = [None] * len(devices)
        for j, s in enumerate(range(0, n, micro)):
            d = j % len(devices)
            mb = {k: jax.device_put(v[s:s + micro], devices[d])
                  for k, v in batch.items()}
            out = grad(copies[d], mb)
            sums[d] = out if sums[d] is None else add(sums[d], out)
        loss, grads = functools.reduce(add, [
            jax.device_put(part, devices[0]) for part in sums
            if part is not None])
        k = n // micro
        return loss / k, {name: g / k for name, g in grads.items()}

    opt = config["optimizer"]
    return _plain.three_steps(loss_and_grads, params, batches,
                              _plain.adamw(opt), opt["first_moment_scale"])

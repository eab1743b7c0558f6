"""Plain reference for a ResNet configuration, with its own optimizer and its
own FLOP count: what a later PR brings for a new family of model, as files
and with nothing under ``chipbench/`` touched. Loss and gradients of the
published architecture (He et al. 2015, the v1.5 variant torchvision ships:
the stride of a bottleneck sits on its 3x3 convolution): 7x7/2 stem, 3x3/2
max pool, four stages of residual blocks, batch normalisation with the
batch's own statistics (training mode, biased variance, eps 1e-5), global
average pool, linear head, mean cross-entropy.

float32, NHWC, SAME padding (which for these shapes is torchvision's explicit
padding), no kernels, no sharding; imports nothing of the program. Weights
come as a flat ``{path: array}``. The whole batch goes through at once, since
batch normalisation ties the rows together; each block is rematerialised so
that the float32 activations fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.references import _plain


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one image (v1.5: the stride sits on the 3x3
    convolution), from every convolution's and the head's shape, 2 per
    multiply-accumulate."""
    size = traffic["image_size"]
    width = model["num_filters"]
    macs = 0.0
    out_size = lambda size, stride: -(-size // stride)  # SAME padding

    def conv(hw_out, k, cin, cout):
        nonlocal macs
        macs += hw_out * hw_out * k * k * cin * cout

    if model.get("small_images"):
        conv(size, 3, 3, width)
    else:
        size = out_size(size, 2)
        conv(size, 7, 3, width)
        size = out_size(size, 2)  # max pool
    cin = width
    bottleneck = model["block"] == "bottleneck"
    for i, blocks in enumerate(model["stage_sizes"]):
        f = width * 2 ** i
        cout = 4 * f if bottleneck else f
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = out_size(size, stride)
            if bottleneck:
                conv(size, 1, cin, f)
                conv(out, 3, f, f)
                conv(out, 1, f, cout)
            else:
                conv(out, 3, cin, f)
                conv(out, 3, f, f)
            if stride != 1 or cin != cout:
                conv(out, 1, cin, cout)
            cin, size = cout, out
    macs += cin * model["num_classes"]
    return 2.0 * macs


def sgd_nesterov(opt: dict):
    mom, lr, wd = opt["momentum"], opt["lr"], opt["weight_decay"]

    def init(params):
        return {"trace": {k: jnp.zeros_like(v) for k, v in params.items()}}

    def step(params, grads, state, t):
        grads = _plain.clip(grads, opt.get("grad_clip", 0.0))
        grads = {k: g + wd * params[k] if _plain.decayed(params[k]) else g
                 for k, g in grads.items()}
        trace = {k: g + mom * state["trace"][k] for k, g in grads.items()}
        new = {k: p - lr * (grads[k] + mom * trace[k])
               for k, p in params.items()}
        return new, {"trace": trace}, trace

    return init, step


def _conv(x, kernel, stride, q):
    return jax.lax.conv_general_dilated(
        q(x), q(kernel), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, w, name):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * w[f"{name}/scale"] \
        + w[f"{name}/bias"]


def _block(x, w, stride, n_convs, q):
    y = x
    for c in range(n_convs):
        # v1.5: the first conv of a basic block, the 3x3 of a bottleneck
        s = stride if c == (1 if n_convs == 3 else 0) else 1
        y = _bn(_conv(y, w[f"Conv_{c}/kernel"], s, q), w, f"BatchNorm_{c}")
        if c < n_convs - 1:
            y = jax.nn.relu(y)
    if "downsample_conv/kernel" in w:
        x = _bn(_conv(x, w["downsample_conv/kernel"], stride, q), w,
                "downsample_norm")
    return jax.nn.relu(x + y)


def loss_fn(params, batch, model, precision="highest"):
    q = _plain.rounder(precision)
    x = batch["image"].astype(jnp.float32)
    small = model.get("small_images", False)
    x = _conv(x, params["conv_init/kernel"], 1 if small else 2, q)
    x = jax.nn.relu(_bn(x, params, "bn_init"))
    if not small:
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
    kind = "Bottleneck" if model["block"] == "bottleneck" else "BasicBlock"
    n_convs = 3 if model["block"] == "bottleneck" else 2
    index = 0
    for i, blocks in enumerate(model["stage_sizes"]):
        for j in range(blocks):
            pre = f"{kind}_{index}/"
            w = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
            stride = 2 if i > 0 and j == 0 else 1
            x = jax.checkpoint(functools.partial(
                _block, stride=stride, n_convs=n_convs, q=q))(x, w)
            index += 1
    x = jnp.mean(x, (1, 2))
    logits = q(x) @ q(params["head/kernel"]) + params["head/bias"]
    picked = jnp.take_along_axis(logits, batch["label"][:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def run(config: dict, params: dict, batches: list, precision="highest") -> dict:
    """Three steps from ``params`` over ``batches`` (host arrays)."""
    grad = jax.jit(jax.value_and_grad(functools.partial(
        loss_fn, model=config["model"], precision=precision)))

    def loss_and_grads(p, batch):
        return grad(p, {k: jnp.asarray(v) for k, v in batch.items()})

    opt = config["optimizer"]
    return _plain.three_steps(loss_and_grads, params, batches,
                              sgd_nesterov(opt), opt["first_moment_scale"])

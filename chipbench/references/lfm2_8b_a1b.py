"""Plain reference for the ``lfm2_moe`` configurations (HF ``model_type:
lfm2_moe``, ``LiquidAI/LFM2-8B-A1B``): loss, gradients, AdamW and the router's
bias update of the published architecture, for the share of it that one chip
of the stated deployment holds.

Per token, hidden ``d`` (``u`` a token's normed vector):

- ``h = E[id]``, no multiplier; a layer: ``h += Op(RMS(h; g_op)); h +=
  FFN(RMS(h; g_ffn))``, eps ``norm_eps``; ``logits = RMS(h; g_f) E^T`` (the
  embedding tied); mean next-token cross-entropy over the rows of the
  vocabulary that are held. No bias in any linear map.
- ``conv`` layers (``layer_types``): ``[B; C; x] = W_in u`` (``d -> 3d``, the
  three chunks in that order); ``v = B * x``; ``c_t = sum_k w[k] * v_{t -
  (K-1) + k}`` (depthwise, causal, ``K = conv_L_cache`` taps, zero history, no
  bias); ``y = C * c``; ``W_out y``. No activation anywhere.
- ``full_attention`` layers: ``q = u W_q`` (``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``), ``k``, ``v`` (``num_key_value_heads``
  heads); ``q`` and ``k`` RMS-normed over the head, each head by itself, with
  one scale vector for all heads; rotary positions over the whole head
  (``rope_theta``, rotate-half); ``softmax(q k^T / sqrt(head)) v`` under the
  causal mask, a KV head serving ``num_attention_heads /
  num_key_value_heads`` query heads; ``W_o``.
- ``FFN`` of the first ``num_dense_layers`` layers: ``(silu(u W_gate) * u
  W_up) W_down`` of width ``intermediate_size``. Of the others: ``s =
  sigmoid(u W_r)`` over ``routed_experts``; ``I`` = the
  ``num_experts_per_tok`` largest of ``s + b``; ``w_i = routed_scaling_factor
  * s_i / (sum_{j in I} s_j + 1e-6)``; ``y = sum_{i in I, i held} w_i
  Expert_i(u)``, SwiGLU of width ``moe_intermediate_size``, no shared expert.
  ``b`` has no gradient; after a step, with ``c`` the tokens that chose each
  of the ``routed_experts``: ``b += d - mean(d)``, ``d = load_balance_coeff *
  sign(mean(c) - c)``.

**The share.** ``num_experts`` experts are held, ``held_experts_start``
onwards, of the router's ``routed_experts``: the router scores and chooses
over all of them, and what an absent expert would have added is left out.
``held_layers`` names the published layers that the blocks are, and a block's
kind is ``layer_types`` at its held layer; the first ``num_dense_layers``
blocks are dense.

float32 ``jax.numpy``, no kernels, no sharding, no cache; imports nothing of
the program. Weights come as a flat ``{path: array}`` in the layout the
benchmark generates (attention kernels ``[d, heads, hd]``, out kernel
``[heads, hd, d]``, conv kernel ``[K, d]``, the held experts stacked ``[held,
d, f]`` / ``[held, f, d]``).

Departures from the published description, each of form and not of value:

- The conv is a sum of ``K`` shifted products of one padded array.
- Every held expert is computed for every token and multiplied by the token's
  weight for it (zero where the token did not choose it).
- Attention is mapped over the query heads, the head and the loss over blocks
  of ``LOSS_ROWS`` tokens: for memory only.
- :func:`run` differentiates layer by layer (a layer's forward again from its
  kept input, then its transpose), through one jitted pair a *kind* of layer,
  so that each kind compiles once and not once a layer; :func:`loss_fn` is
  the same sum as one function, for ``jax.grad``.
- The control (``precision`` below ``highest``) rounds the operands of every
  matmul and of the convolution but the router's, which the configuration
  states in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references import _plain

LOSS_ROWS = 1024
KINDS = ("conv", "full_attention")
#: the released router's normaliser
ROUTE_NORM_EPS = 1e-6


def _sizes(model: dict) -> dict:
    blocks = model["num_hidden_layers"]
    kinds = [model["layer_types"][j] for j in model["held_layers"]]
    if len(kinds) != blocks or set(kinds) - set(KINDS):
        raise ValueError(f"layers {kinds} of {blocks}")
    return {
        "d": model["hidden_size"], "heads": model["num_attention_heads"],
        "hd": model["hidden_size"] // model["num_attention_heads"],
        "kv": model["num_key_value_heads"], "K": model["conv_L_cache"],
        "held": model["num_experts"], "first": model["held_experts_start"],
        "routed": model["routed_experts"], "k": model["num_experts_per_tok"],
        "kinds": [(kind, i < model["num_dense_layers"])
                  for i, kind in enumerate(kinds)],
        "eps": model["norm_eps"],
    }


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one sequence, as the benchmark counts them: 2 per
    multiply-accumulate, matmuls only (the conv's taps and gates, norms,
    rotary terms and the router's sort are not counted, so a share of a peak
    computed from this can only come out low), nothing recomputed. Per token:
    every projection; the tied head once; causal attention's QK^T and PV over
    the (S+1)/2 pairs a token keeps; the router; and the routed rows this
    chip *expects*: ``num_experts_per_tok * num_experts / routed_experts`` a
    token."""
    z = _sizes(model)
    d, S = z["d"], traffic["seq_len"]
    op = {"conv": 4 * d * d,
          "full_attention": 2 * d * z["hd"] * (z["heads"] + z["kv"])
          + 2 * z["heads"] * z["hd"] * (S + 1) / 2}
    dense = 3 * d * model["intermediate_size"]
    moe = d * z["routed"] + 3 * d * model["moe_intermediate_size"] \
        * z["k"] * z["held"] / z["routed"]
    macs = sum(op[kind] + (dense if is_dense else moe)
               for kind, is_dense in z["kinds"])
    return 2.0 * (macs + d * model["vocab_size"]) * S


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """Rotate-half rotary positions on ``[b, S, heads, hd]``."""
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, gate, up, down, q):
    return q(jax.nn.silu(q(h) @ q(gate)) * (q(h) @ q(up))) @ q(down)


def _gated_conv(bcx, kernel, q):
    """``C * conv(B * x)`` over ``[b, S, 3d]``: a sum of ``K`` shifted
    products, the oldest tap first."""
    S, K = bcx.shape[1], kernel.shape[0]
    B, C, x = jnp.split(bcx, 3, axis=-1)
    padded = jnp.pad(q(B * x), ((0, 0), (K - 1, 0), (0, 0)))
    taps = q(kernel)
    return C * sum(padded[:, k:k + S] * taps[k] for k in range(K))


def _short_conv(u, w, z, q):
    kernel = w["short_conv/conv_kernel"]
    if kernel.shape[0] != z["K"]:
        raise ValueError(f"{kernel.shape[0]} taps, conv_L_cache {z['K']}")
    bcx = q(u) @ q(w["short_conv/in_proj/kernel"])
    return q(_gated_conv(bcx, kernel, q)) @ q(w["short_conv/out_proj/kernel"])


def _qk_normed(qh, kh, w, z, model):
    """The query and key heads RMS-normed over the head, then rotated."""
    qh = _rms(qh, w["attn/q_norm/scale"], z["eps"])
    kh = _rms(kh, w["attn/k_norm/scale"], z["eps"])
    return _rope(qh, model["rope_theta"]), _rope(kh, model["rope_theta"])


def _attention(u, w, z, model, q):
    S = u.shape[1]
    proj = lambda n: jnp.einsum("bsd,dhk->bshk", q(u), q(w[f"attn/{n}/kernel"]))
    qh, kh = _qk_normed(proj("query"), proj("key"), w, z, model)
    vh = proj("value")
    seen = jnp.tril(jnp.ones((S, S), bool))
    rep = z["heads"] // z["kv"]

    @jax.checkpoint
    def head(args):
        qs, ks, vs = args                                   # [b, S, hd] each
        scores = jnp.einsum("bqk,btk->bqt", q(qs), q(ks)) / math.sqrt(z["hd"])
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqt,btk->bqk", q(probs), q(vs))

    per_head = lambda a: a.transpose(2, 0, 1, 3)            # [heads, b, S, hd]
    out = jax.lax.map(head, (per_head(qh),
                             jnp.repeat(per_head(kh), rep, axis=0),
                             jnp.repeat(per_head(vh), rep, axis=0)))
    return jnp.einsum("bshk,hkd->bsd", q(out.transpose(1, 2, 0, 3)),
                      q(w["attn/out/kernel"]))


def _experts(h, w, bias, z, model, q):
    """``(y, c)``: the expert layer's output and the tokens that chose each
    of the routed experts."""
    scores = jax.nn.sigmoid(h @ w["moe/router"])            # [b, S, routed]
    chosen_by = scores + bias if model["use_expert_bias"] else scores
    _, chosen = jax.lax.top_k(chosen_by, z["k"])
    weight = jnp.take_along_axis(scores, chosen, -1)
    if model["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + ROUTE_NORM_EPS)
    weight = model["routed_scaling_factor"] * weight
    # [b, S, routed]: a token's weight for each expert, zero where not chosen
    spread = jnp.sum(jax.nn.one_hot(chosen, z["routed"]) * weight[..., None],
                     axis=-2)
    held = spread[..., z["first"]:z["first"] + z["held"]]

    @jax.checkpoint
    def expert(args):
        gate, up, down, mine = args
        return _swiglu(h, gate, up, down, q) * mine[..., None]

    y, _ = jax.lax.scan(
        lambda total, args: (total + expert(args), None), jnp.zeros_like(h),
        (w["moe/w_gate"], w["moe/w_up"], w["moe/w_down"],
         jnp.moveaxis(held, -1, 0)))
    counts = jnp.sum(jax.nn.one_hot(chosen, z["routed"]), axis=(0, 1, 2))
    return y, counts


def _layer(x, w, bias, kind, dense, z, model, q):
    """``(x + Op(RMS(x)) + FFN(RMS(.)), counts [routed])``; ``bias`` is the
    router's (an expert layer reads it, a dense one counts nothing)."""
    u = _rms(x, w["operator_norm/scale"], z["eps"])
    if kind == "conv":
        x = x + _short_conv(u, w, z, q)
    else:
        x = x + _attention(u, w, z, model, q)
    h = _rms(x, w["ffn_norm/scale"], z["eps"])
    if dense:
        m, counts = _swiglu(h, w["gate/kernel"], w["up/kernel"],
                            w["down/kernel"], q), jnp.zeros((z["routed"],))
    else:
        m, counts = _experts(h, w, bias, z, model, q)
    return x + m, counts


def _of_block(params, i):
    pre = f"block_{i}/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden_fn(params, biases, tokens, model, precision="highest"):
    """``(h [b, S, d] after the last layer, counts [blocks, routed])``."""
    q, z = _plain.rounder(precision), _sizes(model)
    x = params["embed/embedding"][tokens]
    counts = []
    for i, (kind, dense) in enumerate(z["kinds"]):
        x, c = jax.checkpoint(functools.partial(
            _layer, kind=kind, dense=dense, z=z, model=model, q=q))(
                x, _of_block(params, i), biases[i])
        counts.append(c)
    return x, jnp.stack(counts)


def _head_loss(x, scale, embedding, targets, eps, q):
    """Mean next-token cross-entropy of ``RMS(x; scale) embedding^T``; the
    head and the loss in blocks of ``LOSS_ROWS`` tokens."""
    x = _rms(x, scale, eps)
    d = x.shape[-1]
    rows = min(LOSS_ROWS, x.shape[0] * x.shape[1])
    x, targets = x.reshape(-1, rows, d), targets.reshape(-1, rows)

    @jax.checkpoint
    def block(args):
        h, t = args
        logits = q(h) @ q(embedding).T
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    return jnp.sum(jax.lax.map(block, (x, targets))) / targets.size


def logits_fn(params, biases, tokens, model, precision="highest"):
    q = _plain.rounder(precision)
    x, _ = hidden_fn(params, biases, tokens, model, precision)
    x = _rms(x, params["final_norm/scale"], model["norm_eps"])
    return q(x) @ q(params["embed/embedding"]).T


def loss_fn(params, biases, batch, model, precision="highest"):
    """``(mean next-token cross-entropy, counts)`` as one function."""
    x, counts = hidden_fn(params, biases, batch["tokens"], model, precision)
    return _head_loss(x, params["final_norm/scale"], params["embed/embedding"],
                      batch["targets"], model["norm_eps"],
                      _plain.rounder(precision)), counts


def layerwise(model: dict, precision="highest"):
    """``loss_and_grads(params, biases, batch) -> ((loss, counts), grads)``:
    :func:`loss_fn`'s value and gradient, a layer at a time. Forward: each
    layer's input is kept. Backward: the layer's forward again inside its
    transpose. One jitted forward and one jitted transpose a kind of layer
    (an operator with a dense or an expert FFN), whatever the depth, all of
    them and the head compiled side by side on the first call. The tied
    embedding's gradient is the head's and the lookup's together."""
    q, z = _plain.rounder(precision), _sizes(model)
    eps = z["eps"]

    def pair(kind, dense):
        layer = functools.partial(_layer, kind=kind, dense=dense, z=z,
                                  model=model, q=q)

        def transpose(x, w, bias, dy):
            _, vjp, _ = jax.vjp(lambda x, w: layer(x, w, bias), x, w,
                                has_aux=True)
            return vjp(dy)

        return jax.jit(layer), jax.jit(transpose)

    pairs = {kind: pair(*kind) for kind in sorted(set(z["kinds"]))}
    head = jax.jit(jax.value_and_grad(
        lambda x, scale, E, targets: _head_loss(x, scale, E, targets, eps, q),
        argnums=(0, 1, 2)))
    embed = jax.jit(lambda E, tokens: E[tokens])
    embed_t = jax.jit(lambda dE, tokens, dx: dE.at[tokens].add(dx),
                      donate_argnums=0)
    compiled = {}

    def compile_all(params, biases, batch):
        shape = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        x = jax.ShapeDtypeStruct(batch["tokens"].shape + (z["d"],),
                                 jnp.float32)
        jobs = {"head": (head, (x, shape(params["final_norm/scale"]),
                                shape(params["embed/embedding"]),
                                shape(batch["targets"])))}
        for kind in pairs:
            w = shape(_of_block(params, z["kinds"].index(kind)))
            jobs["forward", kind] = (pairs[kind][0], (x, w, shape(biases[0])))
            jobs["transpose", kind] = (pairs[kind][1],
                                       (x, w, shape(biases[0]), x))
        compiled.update(_side_by_side(jobs))

    def loss_and_grads(params, biases, batch):
        if not compiled:
            compile_all(params, biases, batch)
        tokens = batch["tokens"]
        E = params["embed/embedding"]
        inputs, counts, x = [], [], embed(E, tokens)
        for i, kind in enumerate(z["kinds"]):
            inputs.append(x)
            x, c = compiled["forward", kind](x, _of_block(params, i),
                                             biases[i])
            counts.append(c)
        loss, (dx, dscale, dE) = compiled["head"](
            x, params["final_norm/scale"], E, batch["targets"])
        grads = {"final_norm/scale": dscale}
        for i in reversed(range(len(z["kinds"]))):
            dx, dw = compiled["transpose", z["kinds"][i]](
                inputs.pop(), _of_block(params, i), biases[i], dx)
            grads.update({f"block_{i}/{k}": g for k, g in dw.items()})
        grads["embed/embedding"] = embed_t(dE, tokens, dx)
        return (loss, jnp.stack(counts)), grads

    return loss_and_grads


def _side_by_side(jobs: dict) -> dict:
    """``{name: compiled}`` for ``{name: (jitted, argument shapes)}``, each
    traced, lowered and compiled on a thread of its own (XLA compiles
    outside the interpreter's lock) at matmul precision ``highest``, which
    like every jax configuration context is a thread's own."""
    import concurrent.futures

    def one(job):
        jitted, shapes = job
        with jax.default_matmul_precision("highest"):
            return jitted.lower(*shapes).compile()

    with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
        return dict(zip(jobs, pool.map(one, jobs.values())))


def next_biases(biases, counts, model):
    """The router's bias after a step in which ``counts [blocks, routed]``
    tokens chose each expert (dense blocks count nothing and stay at zero)."""
    mean = jnp.mean(counts, axis=-1, keepdims=True)
    delta = model["load_balance_coeff"] * jnp.sign(mean - counts)
    moved = biases + delta - jnp.mean(delta, axis=-1, keepdims=True)
    return jnp.where(jnp.sum(counts, -1, keepdims=True) > 0, moved, biases)


def run(config: dict, params: dict, batches: list, precision="highest") -> dict:
    """Three steps from ``params`` over ``batches`` (host arrays), on one
    device: what ``_plain.three_steps`` returns. Written out here, as the
    Granite reference's, because the parameters, gradients and both of Adam's
    moments (16 bytes a parameter) all but fill the chip: the starting
    parameters wait on the host, and only the first moment's norms leave the
    optimizer step. The biases start at zero and follow their own rule."""
    model, opt = config["model"], config["optimizer"]
    if any(len(b["tokens"]) % config["reference_micro_batch"]
           for b in batches):
        raise ValueError("this reference takes a batch whole")
    grad = layerwise(model, precision)
    init, adam = _plain.adamw(opt)

    def step(p, g, state, t):
        new, state, moment = adam(p, g, state, t)
        return new, state, _plain.leaf_norms(moment)

    step = jax.jit(step, static_argnums=3, donate_argnums=(0, 2))
    start = {k: np.asarray(v) for k, v in params.items()}
    state = init(params)
    biases = jnp.zeros((model["num_hidden_layers"], model["routed_experts"]))
    out = {"loss": [], "counts": []}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, 1):
            (loss, counts), grads = grad(
                params, biases, {k: jnp.asarray(v) for k, v in batch.items()})
            params, state, norms = step(params, grads, state, t)
            del grads
            biases = next_biases(biases, counts, model)
            out["loss"].append(float(loss))
            out["counts"].append(np.asarray(counts))
            if t == 1:
                out["moment_norms"] = {
                    k: float(v) * opt["first_moment_scale"]
                    for k, v in norms.items()}
        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        out["dparam_norms"] = {k: float(norm(params[k], start[k]))
                               for k in params}
    out["biases"] = np.asarray(biases)
    return out

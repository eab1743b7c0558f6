"""Plain reference for the ``afmoe`` configurations (HF ``model_type: afmoe``,
``arcee-ai/Trinity-Mini``): loss, gradients, AdamW and the router's bias
update of the published architecture, for the share of it that one chip of
the stated deployment holds.

Per token, hidden ``d`` (``x`` a token's vector):

- ``h = E[id] * sqrt(d)`` (``mup_enabled``); ``logits = RMS(h; g_f) W_head``
  (untied); mean next-token cross-entropy over the rows of the vocabulary
  that are held.
- A layer: ``a = Attn(RMS(h; g1)); h += RMS(a; g2); m = FFN(RMS(h; g3)); h +=
  RMS(m; g4)``.
- ``Attn``: ``q = x W_q`` (``num_attention_heads x head_dim``), ``k = x W_k``,
  ``v = x W_v`` (``num_key_value_heads x head_dim``), ``gate = x W_g``; ``q``
  and ``k`` RMS-normed over ``head_dim``, each head by itself, with one scale
  vector for all heads; in ``sliding_attention`` layers rotary positions
  (``rope_theta``, all of ``head_dim``, rotate-half) and the mask ``0 <= i - j
  < sliding_window``, in ``full_attention`` layers no positional term and ``j
  <= i`` alone; ``o = softmax(q k^T / sqrt(head_dim)) v``, a KV head serving
  ``num_attention_heads / num_key_value_heads`` query heads; ``(o *
  sigmoid(gate)) W_o``.
- ``FFN`` of the first ``num_dense_layers`` layers: ``(silu(x W_gate) * x
  W_up) W_down`` of width ``intermediate_size``. Of the others: ``s =
  sigmoid(x W_r)`` over ``routed_experts``; ``I`` = the ``num_experts_per_tok``
  largest of ``s + b``; ``w_i = route_scale * s_i / (sum_{j in I} s_j +
  1e-20)``; ``y = Shared(x) + sum_{i in I, i held} w_i Expert_i(x)``, all
  SwiGLU of width ``moe_intermediate_size``. ``b`` has no gradient; after a
  step, with ``c`` the tokens that chose each of the ``routed_experts``: ``b
  += d - mean(d)``, ``d = load_balance_coeff * sign(mean(c) - c)``.

**The share.** ``num_experts`` experts are held, ``held_experts_start``
onwards, of the router's ``routed_experts``: the router scores and chooses
over all of them, and what an absent expert would have added is left out.
``held_layers`` names the published layers that the blocks are.

float32 ``jax.numpy``, no kernels, no sharding, no cache; imports nothing of
the program. Weights come as a flat ``{path: array}`` in the layout the
benchmark generates (projection kernels ``[d, heads, hd]``, out kernel
``[heads, hd, d]``, the held experts stacked ``[held, d, f]`` / ``[held, f,
d]``).

Departures from the published description, each of form and not of value:

- Every held expert is computed for every token and multiplied by the token's
  weight for it (zero where the token did not choose it): the same sum as
  gathering each expert's tokens, with nothing to sort.
- Attention is mapped over the query heads, the head and the loss over blocks
  of ``LOSS_ROWS`` tokens, every layer is checkpointed: for memory only.
- The control (``precision`` below ``highest``) rounds the operands of every
  matmul but the router's, which the configuration states in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references import _plain

LOSS_ROWS = 1024


def _sizes(model: dict) -> dict:
    blocks = model["num_hidden_layers"]
    return {
        "d": model["hidden_size"], "hd": model["head_dim"],
        "heads": model["num_attention_heads"],
        "kv": model["num_key_value_heads"],
        "held": model["num_experts"], "first": model["held_experts_start"],
        "routed": model["routed_experts"], "k": model["num_experts_per_tok"],
        "kinds": [model["layer_types"][j] for j in model["held_layers"]],
        "dense": [i < model["num_dense_layers"] for i in range(blocks)],
    }


def _keys_seen(kind: str, S: int, window: int) -> float:
    """Keys a row sees on average: the causal half, or the window's part."""
    if kind == "full_attention":
        return (S + 1) / 2
    W = min(window, S)
    return W - W * (W - 1) / (2 * S)


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one sequence, as the benchmark counts them: 2 per
    multiply-accumulate, matmuls only (norms, gates, rotary terms and the
    router's sort are not counted, so a share of a peak computed from this
    can only come out low), nothing recomputed. Per token: every projection;
    the head once; attention's QK^T and PV over the keys a row sees (the
    causal half in a full layer, the window's part in a window layer); the
    router; the shared expert; and the routed rows this chip *expects*:
    ``num_experts_per_tok * num_experts / routed_experts`` a token."""
    z = _sizes(model)
    d, S = z["d"], traffic["seq_len"]
    proj = d * z["hd"] * (3 * z["heads"] + 2 * z["kv"])
    swiglu = lambda width: 3 * d * width
    moe = d * z["routed"] + swiglu(model["moe_intermediate_size"]) * (
        model["num_shared_experts"] + z["k"] * z["held"] / z["routed"])
    macs = sum(
        proj + 2 * z["heads"] * z["hd"] * _keys_seen(
            kind, S, model["sliding_window"])
        + (swiglu(model["intermediate_size"]) if dense else moe)
        for kind, dense in zip(z["kinds"], z["dense"]))
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


def _attention(h, w, kind, z, model, q):
    b, S, _ = h.shape
    eps = model["rms_norm_eps"]
    proj = lambda n: jnp.einsum("bsd,dhk->bshk", q(h), q(w[f"attn/{n}/kernel"]))
    qh = _rms(proj("query"), w["attn/q_norm/scale"], eps)
    kh = _rms(proj("key"), w["attn/k_norm/scale"], eps)
    vh, gate = proj("value"), proj("gate")
    i = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = i >= 0
    if kind == "sliding_attention":
        qh, kh = _rope(qh, model["rope_theta"]), _rope(kh, model["rope_theta"])
        seen &= i < model["sliding_window"]
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
    out = out.transpose(1, 2, 0, 3) * jax.nn.sigmoid(gate)
    return jnp.einsum("bshk,hkd->bsd", q(out), q(w["attn/out/kernel"]))


def _experts(h, w, bias, z, model, q):
    """``(y, c)``: the expert layer's output and the tokens that chose each
    of the routed experts."""
    scores = jax.nn.sigmoid(h @ w["moe/router"])            # [b, S, routed]
    _, chosen = jax.lax.top_k(scores + bias, z["k"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    weight = model["route_scale"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20)
    # [b, S, routed]: a token's weight for each expert, zero where not chosen
    spread = jnp.sum(jax.nn.one_hot(chosen, z["routed"]) * weight[..., None],
                     axis=-2)
    held = spread[..., z["first"]:z["first"] + z["held"]]

    @jax.checkpoint
    def expert(args):
        gate, up, down, mine = args
        return _swiglu(h, gate, up, down, q) * mine[..., None]

    shared = jnp.zeros_like(h)
    if model["num_shared_experts"]:
        shared = _swiglu(h, w["moe/shared/gate/kernel"],
                         w["moe/shared/up/kernel"],
                         w["moe/shared/down/kernel"], q)
    y, _ = jax.lax.scan(
        lambda total, args: (total + expert(args), None), shared,
        (w["moe/w_gate"], w["moe/w_up"], w["moe/w_down"],
         jnp.moveaxis(held, -1, 0)))
    counts = jnp.sum(jax.nn.one_hot(chosen, z["routed"]), axis=(0, 1, 2))
    return y, counts


def _layer(x, w, bias, kind, dense, z, model, q):
    eps = model["rms_norm_eps"]
    a = _attention(_rms(x, w["attn_norm/scale"], eps), w, kind, z, model, q)
    x = x + _rms(a, w["post_attn_norm/scale"], eps)
    h = _rms(x, w["ffn_norm/scale"], eps)
    if dense:
        m, counts = _swiglu(h, w["gate/kernel"], w["up/kernel"],
                            w["down/kernel"], q), jnp.zeros((z["routed"],))
    else:
        m, counts = _experts(h, w, bias, z, model, q)
    return x + _rms(m, w["post_ffn_norm/scale"], eps), counts


def hidden_fn(params, biases, tokens, model, precision="highest"):
    """``(h [b, S, d] after the last layer, counts [blocks, routed])``."""
    q, z = _plain.rounder(precision), _sizes(model)
    x = params["embed/embedding"][tokens]
    if model["mup_enabled"]:
        x = x * math.sqrt(z["d"])
    counts = []
    for i, (kind, dense) in enumerate(zip(z["kinds"], z["dense"])):
        pre = f"block_{i}/"
        w = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x, c = jax.checkpoint(functools.partial(
            _layer, kind=kind, dense=dense, z=z, model=model, q=q))(
                x, w, biases[i])
        counts.append(c)
    return x, jnp.stack(counts)


def loss_fn(params, biases, batch, model, precision="highest"):
    """``(mean next-token cross-entropy, counts)``; the head and the loss in
    blocks of ``LOSS_ROWS`` tokens."""
    q = _plain.rounder(precision)
    x, counts = hidden_fn(params, biases, batch["tokens"], model, precision)
    x = _rms(x, params["final_norm/scale"], model["rms_norm_eps"])
    d = x.shape[-1]
    rows = min(LOSS_ROWS, x.shape[0] * x.shape[1])
    x, targets = x.reshape(-1, rows, d), batch["targets"].reshape(-1, rows)

    @jax.checkpoint
    def block(args):
        h, t = args
        logits = q(h) @ q(params["lm_head/kernel"])
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    return jnp.sum(jax.lax.map(block, (x, targets))) / targets.size, counts


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
    grad = jax.jit(jax.value_and_grad(functools.partial(
        loss_fn, model=model, precision=precision), has_aux=True))
    init, adam = _plain.adamw(opt)

    def step(p, g, state, t):
        new, state, moment = adam(p, g, state, t)
        return new, state, _plain.leaf_norms(moment)

    step = jax.jit(step, static_argnums=3, donate_argnums=(0, 2))
    start = {k: np.asarray(v) for k, v in params.items()}
    state = init(params)
    biases = jnp.zeros((model["num_hidden_layers"], model["routed_experts"]))
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, 1):
            (loss, counts), grads = grad(
                params, biases, {k: jnp.asarray(v) for k, v in batch.items()})
            params, state, norms = step(params, grads, state, t)
            del grads
            biases = next_biases(biases, counts, model)
            out["loss"].append(float(loss))
            if t == 1:
                out["moment_norms"] = {
                    k: float(v) * opt["first_moment_scale"]
                    for k, v in norms.items()}
        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        out["dparam_norms"] = {k: float(norm(params[k], start[k]))
                               for k in params}
    return out

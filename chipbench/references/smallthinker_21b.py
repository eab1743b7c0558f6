"""Plain reference for the ``smallthinker`` configurations
(``PowerInfer/SmallThinker-21BA3B-Instruct``): loss, gradients and AdamW of
the published architecture, for the share of it that one chip of the stated
deployment holds.

Per token, hidden ``d`` (``x`` a token's vector; the configuration file's
``assumed`` says which lines the published ``config.json`` states and which
are the published modelling code's):

- ``h = E[id]``, no multiplier; ``logits = RMS(h; g_f) W_head`` (untied);
  mean next-token cross-entropy over the rows of the vocabulary that are held.
- A layer, two norms: ``x = RMS(h; g1); r = x; h += Attn(x); y = RMS(h; g2);
  h += MoE(r, y)``: the router reads the attention's input, ahead of
  attention.
- ``Attn``: ``q = x W_q`` (``num_attention_heads x head_dim``), ``k = x W_k``,
  ``v = x W_v`` (``num_key_value_heads x head_dim``); no biases, no head
  norms, no gate. Where the layer's ``rope_layout`` is 1: rotary positions
  (``rope_theta``, all of ``head_dim``, rotate-half) on q and k; where its
  ``sliding_window_layout`` is 1: the mask ``0 <= i - j <
  sliding_window_size``, else ``j <= i`` alone. ``o = softmax(q k^T /
  sqrt(head_dim)) v``, a KV head serving ``num_attention_heads /
  num_key_value_heads`` query heads; ``o W_o``.
- ``MoE(r, y)``: ``z = r W_r`` over ``routed_experts``; ``I`` = the
  ``moe_num_active_primary_experts`` largest of ``z``; ``w`` = softmax over
  the chosen logits (``moe_primary_router_apply_softmax``), then divided by
  their sum (``norm_topk_prob``: the identity after a softmax, kept); ``m =
  sum_{i in I, i held} w_i Expert_i(y)``, ``Expert_i(y) = (relu(y W_gate,i) *
  y W_up,i) W_down,i`` of width ``moe_ffn_hidden_size``. No shared expert, no
  bias, no auxiliary loss term, no buffer.

**The share.** ``moe_num_primary_experts`` experts are held,
``held_experts_start`` onwards, of the router's ``routed_experts``: the
router scores and chooses over all of them, and what an absent expert would
have added is left out, so a token none of whose choices is held gets zero
from the layer. ``held_layers`` names the published layers that the blocks
are; the two layouts are indexed by them.

float32 ``jax.numpy``, no kernels, no sharding, no cache; imports nothing of
the program. Weights come as a flat ``{path: array}`` in the layout the
benchmark generates (projection kernels ``[d, heads, hd]``, out kernel
``[heads, hd, d]``, the held experts stacked ``[held, d, f]`` / ``[held, f,
d]``, the router ``[d, routed_experts]``).

Departures from the published code, each of form and not of value:

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
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references import _plain

LOSS_ROWS = 1024


def _sizes(model: dict) -> dict:
    layers = model["held_layers"]
    return {
        "d": model["hidden_size"], "hd": model["head_dim"],
        "heads": model["num_attention_heads"],
        "kv": model["num_key_value_heads"],
        "f": model["moe_ffn_hidden_size"],
        "held": model["moe_num_primary_experts"],
        "first": model["held_experts_start"],
        "routed": model["routed_experts"],
        "k": model["moe_num_active_primary_experts"],
        "windowed": [bool(model["sliding_window_layout"][j]) for j in layers],
        "rotary": [bool(model["rope_layout"][j]) for j in layers],
    }


def window_pairs(S: int, window: int) -> float:
    """(row, key) pairs inside the window mask of one head: row i sees
    ``min(i + 1, W)`` keys."""
    W = min(window, S)
    return W * S - W * (W - 1) / 2


def causal_pairs(S: int) -> float:
    return S * (S + 1) / 2


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one sequence, as the benchmark counts them: 2 per
    multiply-accumulate, matmuls only (norms, the gate, rotary terms and the
    router's top-k are not counted, so a share of a peak computed from this
    can only come out low), nothing recomputed. Every projection; the head
    once; attention's QK^T and PV over the (row, key) pairs inside each
    layer's own mask (the causal half in a full layer, the window's pairs in
    a window layer); the router; and the routed rows this chip *expects*:
    ``moe_num_active_primary_experts * moe_num_primary_experts /
    routed_experts`` a token."""
    z = _sizes(model)
    d, S = z["d"], traffic["seq_len"]
    proj = 2 * d * z["hd"] * (z["heads"] + z["kv"])
    moe = d * z["routed"] + 3 * d * z["f"] * z["k"] * z["held"] / z["routed"]
    macs = S * len(z["windowed"]) * (proj + moe) + sum(
        2 * z["heads"] * z["hd"] * (
            window_pairs(S, model["sliding_window_size"]) if windowed
            else causal_pairs(S)) for windowed in z["windowed"])
    return 2.0 * (macs + S * d * model["vocab_size"])


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


def _attention(x, w, windowed, rotary, z, model, q):
    S = x.shape[1]
    proj = lambda n: jnp.einsum("bsd,dhk->bshk", q(x), q(w[f"attn/{n}/kernel"]))
    qh, kh, vh = proj("query"), proj("key"), proj("value")
    if rotary:
        qh, kh = _rope(qh, model["rope_theta"]), _rope(kh, model["rope_theta"])
    i = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = i >= 0
    if windowed:
        seen &= i < model["sliding_window_size"]
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


def route(r, kernel, k):
    """``(chosen [.., k], weights [.., k])`` of the router on ``r``: the
    ``k`` largest logits, the softmax over them alone, divided by their sum."""
    top, chosen = jax.lax.top_k(r @ kernel, k)
    weight = jax.nn.softmax(top, axis=-1)
    return chosen, weight / jnp.sum(weight, -1, keepdims=True)


def experts(r, y, w, z, q):
    """``(m, counts)``: the held experts' part of the layer's sum for tokens
    ``y`` routed on ``r``, and the tokens that chose each routed expert."""
    chosen, weight = route(r, w["moe_router/kernel"], z["k"])
    # [b, S, routed]: a token's weight for each expert, zero where not chosen
    spread = jnp.sum(jax.nn.one_hot(chosen, z["routed"]) * weight[..., None],
                     axis=-2)
    held = spread[..., z["first"]:z["first"] + z["held"]]

    @jax.checkpoint
    def expert(args):
        gate, up, down, mine = args
        return (q(jax.nn.relu(q(y) @ q(gate)) * (q(y) @ q(up))) @ q(down)
                * mine[..., None])

    m, _ = jax.lax.scan(
        lambda total, args: (total + expert(args), None), jnp.zeros_like(y),
        (w["moe/w_gate"], w["moe/w_up"], w["moe/w_down"],
         jnp.moveaxis(held, -1, 0)))
    counts = jnp.sum(jax.nn.one_hot(chosen, z["routed"]), axis=(0, 1, 2))
    return m, counts


def _layer(h, w, windowed, rotary, z, model, q):
    eps = model["rms_norm_eps"]
    x = _rms(h, w["attn_norm/scale"], eps)
    r = x                                   # the router's input, ahead of attention
    h = h + _attention(x, w, windowed, rotary, z, model, q)
    y = _rms(h, w["ffn_norm/scale"], eps)
    m, counts = experts(r, y, w, z, q)
    return h + m, counts


def hidden_fn(params, tokens, model, precision="highest"):
    """``(h [b, S, d] after the last layer, counts [blocks, routed])``."""
    q, z = _plain.rounder(precision), _sizes(model)
    h = params["embed/embedding"][tokens]
    counts = []
    for i, (windowed, rotary) in enumerate(zip(z["windowed"], z["rotary"])):
        pre = f"block_{i}/"
        w = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        h, c = jax.checkpoint(functools.partial(
            _layer, windowed=windowed, rotary=rotary, z=z, model=model,
            q=q))(h, w)
        counts.append(c)
    return h, jnp.stack(counts)


def loss_fn(params, batch, model, precision="highest"):
    """``(mean next-token cross-entropy, counts)``; the head and the loss in
    blocks of ``LOSS_ROWS`` tokens."""
    q = _plain.rounder(precision)
    x, counts = hidden_fn(params, batch["tokens"], model, precision)
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


def run(config: dict, params: dict, batches: list, precision="highest") -> dict:
    """Three steps from ``params`` over ``batches`` (host arrays), on one
    device: what ``_plain.three_steps`` returns. Written out here, as the
    Granite and Trinity references', because the parameters, gradients and
    both of Adam's moments (16 bytes a parameter) all but fill the chip: the
    starting parameters wait on the host, and only the first moment's norms
    leave the optimizer step. Prints the rows that fell on the held experts,
    a layer and a step (``row: "reference_held_rows"``)."""
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
    out, held_rows, held_peak, z = {"loss": []}, [], [], _sizes(model)
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, 1):
            (loss, counts), grads = grad(
                params, {k: jnp.asarray(v) for k, v in batch.items()})
            params, state, norms = step(params, grads, state, t)
            del grads
            out["loss"].append(float(loss))
            mine = counts[:, z["first"]:z["first"] + z["held"]]
            held_rows.append([float(v) for v in jnp.sum(mine, -1)])
            held_peak.append([float(v) for v in jnp.max(mine, -1)
                              / jnp.maximum(jnp.mean(mine, -1), 1.0)])
            if t == 1:
                out["moment_norms"] = {
                    k: float(v) * opt["first_moment_scale"]
                    for k, v in norms.items()}
        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        out["dparam_norms"] = {k: float(norm(params[k], start[k]))
                               for k in params}
    # the work the seed gave the held experts: rows a layer, a step, and the
    # fullest held expert's rows over their mean (the program's moe_held_peak)
    expected = (batches[0]["tokens"].size * z["k"] * z["held"] / z["routed"])
    print(json.dumps({"row": "reference_held_rows", "precision": precision,
                      "expected": expected, "by_step_and_layer": held_rows,
                      "fullest_over_mean": held_peak}), flush=True)
    return out

"""Plain reference for the ``glm4_moe_lite`` configurations (HF ``model_type:
glm4_moe_lite``, ``zai-org/GLM-4.7-Flash``): loss, gradients, AdamW and the
router's bias update of the published architecture with its
multi-token-prediction module, for the share of it that one chip of the
stated deployment holds.

Per token, hidden ``d`` (``x`` a token's vector):

- ``h = E[id]``; ``logits = RMS(h_L; g_f) W_head`` (untied); ``L_main`` the
  mean next-token cross-entropy over the rows of the vocabulary that are held.
- A layer: ``h += MLA(RMS(h; g1)); h += FFN(RMS(h; g2))``.
- ``MLA``: ``c_q = RMS(x W_qa; g_q)`` (``q_lora_rank``); ``q = c_q W_qb``, a
  head ``[q_nope (qk_nope_head_dim); q_rope (qk_rope_head_dim)]``; ``[c_kv
  (kv_lora_rank); k_r (qk_rope_head_dim)] = x W_kva``; ``c_kv = RMS(c_kv;
  g_kv)``; ``[k_nope; v (v_head_dim)]`` a head ``= c_kv W_kvb``. ``q_h =
  [q_nope_h; RoPE(q_rope_h)]``, ``k_h = [k_nope_h; RoPE(k_r)]``, the one
  ``k_r`` shared by all heads; rotary positions over all of
  ``qk_rope_head_dim`` (``rope_theta``, rotate-half). ``o_h = softmax(q_h
  k_h^T / sqrt(qk_nope_head_dim + qk_rope_head_dim)) v_h``, ``j <= i``; all
  heads through ``W_o``. No bias.
- ``FFN`` of the first ``first_k_dense_replace`` layers: ``(silu(x W_gate) * x
  W_up) W_down`` of width ``intermediate_size``. Of the others: ``s =
  sigmoid(x W_r)`` over ``routed_experts``; ``I`` = the ``num_experts_per_tok``
  largest of ``s + b``; ``w_i = routed_scaling_factor * s_i / (sum_{j in I}
  s_j + 1e-20)``; ``y = Shared(x) + sum_{i in I, i held} w_i Expert_i(x)``,
  all SwiGLU of width ``moe_intermediate_size``. ``b`` has no gradient; after
  a step, with ``c`` the tokens that chose each of the ``routed_experts``:
  ``b += d - mean(d)``, ``d = load_balance_coeff * sign(mean(c) - c)``.
- The prediction module (``num_nextn_predict_layers`` 1): with ``n = RMS(h_L;
  g_f)``, the main model's output after its last norm, and ``t'`` the tokens
  rolled left by one with id 0 behind the last, ``h'_i = [RMS(E[t'_i]; g_e);
  RMS(n_i; g_h)] W_eh`` at all ``S`` positions, one expert layer of its own
  (its own bias), ``logits' = RMS(.; g_s) W_head`` through the same ``E`` and
  ``W_head``; ``L_mtp`` the mean cross-entropy against ``t_{i+2}`` over the
  ``S - 2`` positions ``0..S-3`` a sequence. ``loss = L_main + mtp_loss_coeff
  * L_mtp``.

**The share.** ``n_routed_experts`` experts are held, ``held_experts_start``
onwards, of the router's ``routed_experts``: the router scores and chooses
over all of them, and what an absent expert would have added is left out.
``held_layers`` names the published layers that the blocks are; the module
is the published layer ``mtp_layer`` and reads the last block.

float32 ``jax.numpy``, no kernels, no sharding, no cache; imports nothing of
the program. Weights come as a flat ``{path: array}`` in the layout the
benchmark generates (``q_b`` / ``kv_b`` kernels ``[rank, heads, width]``, out
kernel ``[heads, v_head_dim, d]``, the held experts stacked ``[held, d, f]`` /
``[held, f, d]``; the module's leaves under ``mtp/``, its layer under
``mtp/mtp_block/``).

Departures from the published description, each of form and not of value:

- Every held expert is computed for every token and multiplied by the token's
  weight for it (zero where the token did not choose it): the same sum as
  gathering each expert's tokens, with nothing to sort.
- Attention is mapped over the heads, both heads and losses over blocks of
  ``LOSS_ROWS`` tokens, every layer is checkpointed: for memory only.
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
        "d": model["hidden_size"], "heads": model["num_attention_heads"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "v": model["v_head_dim"], "q_rank": model["q_lora_rank"],
        "kv_rank": model["kv_lora_rank"],
        "held": model["n_routed_experts"],
        "first": model["held_experts_start"],
        "routed": model["routed_experts"], "k": model["num_experts_per_tok"],
        "dense": [i < model["first_k_dense_replace"] for i in range(blocks)],
        "mtp": model["num_nextn_predict_layers"],
    }


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one sequence, as the benchmark counts them: 2 per
    multiply-accumulate, matmuls only (norms, gates, rotary terms and the
    router's sort are not counted, so a share of a peak computed from this
    can only come out low), nothing recomputed. Per token and layer: the five
    low-rank projections; attention's QK^T at the query/key width and PV at
    the value width over the causal half of the keys; a dense FFN, or the
    router, the shared expert and the routed rows this chip *expects*
    (``num_experts_per_tok * n_routed_experts / routed_experts`` a token).
    The head once; and for the prediction module its merge, its layer and
    the head again (it runs at all S positions)."""
    z = _sizes(model)
    d, S, H = z["d"], traffic["seq_len"], z["heads"]
    qk = z["nope"] + z["rope"]
    attn = (d * z["q_rank"] + z["q_rank"] * H * qk + d * (z["kv_rank"]
            + z["rope"]) + z["kv_rank"] * H * (z["nope"] + z["v"])
            + H * z["v"] * d + H * (qk + z["v"]) * (S + 1) / 2)
    swiglu = lambda width: 3 * d * width
    moe = d * z["routed"] + swiglu(model["moe_intermediate_size"]) * (
        model["n_shared_experts"] + z["k"] * z["held"] / z["routed"])
    head = d * model["vocab_size"]
    macs = sum(attn + (swiglu(model["intermediate_size"]) if dense else moe)
               for dense in z["dense"]) + head
    macs += z["mtp"] * (2 * d * d + attn + moe + head)
    return 2.0 * macs * S


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """Rotate-half rotary positions on ``[b, S, heads, width]``."""
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, gate, up, down, q):
    return q(jax.nn.silu(q(h) @ q(gate)) * (q(h) @ q(up))) @ q(down)


def latent(h, w, z, model, q):
    """``(c_kv [b, S, kv_rank] normed, k_r [b, S, rope] rotated)``: all that
    attention keeps of a past token."""
    down = q(h) @ q(w["attn/kv_a/kernel"])
    c_kv = _rms(down[..., :z["kv_rank"]], w["attn/kv_norm/scale"],
                model["rms_norm_eps"])
    k_r = _rope(down[..., None, z["kv_rank"]:], model["rope_theta"])
    return c_kv, k_r[..., 0, :]


def expand(c_kv, k_r, w, z, q):
    """``(k [b, S, heads, nope + rope], v [b, S, heads, v])`` from the
    latent pair."""
    kv = jnp.einsum("bsr,rhk->bshk", q(c_kv), q(w["attn/kv_b/kernel"]))
    k_r = jnp.broadcast_to(k_r[..., None, :], (*kv.shape[:-1], z["rope"]))
    return (jnp.concatenate([kv[..., :z["nope"]], k_r], -1),
            kv[..., z["nope"]:])


def _attention(h, w, z, model, q):
    S = h.shape[1]
    c_q = _rms(q(h) @ q(w["attn/q_a/kernel"]), w["attn/q_norm/scale"],
               model["rms_norm_eps"])
    qh = jnp.einsum("bsr,rhk->bshk", q(c_q), q(w["attn/q_b/kernel"]))
    qh = jnp.concatenate([qh[..., :z["nope"]],
                          _rope(qh[..., z["nope"]:], model["rope_theta"])], -1)
    kh, vh = expand(*latent(h, w, z, model, q), w, z, q)
    seen = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scale = 1.0 / math.sqrt(z["nope"] + z["rope"])

    @jax.checkpoint
    def head(args):
        qs, ks, vs = args                                   # [b, S, width]
        scores = jnp.einsum("bqk,btk->bqt", q(qs), q(ks)) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqt,btk->bqk", q(probs), q(vs))

    per_head = lambda a: a.transpose(2, 0, 1, 3)         # [heads, b, S, width]
    out = jax.lax.map(head, (per_head(qh), per_head(kh), per_head(vh)))
    return jnp.einsum("bshk,hkd->bsd", q(out.transpose(1, 2, 0, 3)),
                      q(w["attn/out/kernel"]))


def _experts(h, w, bias, z, model, q):
    """``(y, c)``: the expert layer's output and the tokens that chose each
    of the routed experts."""
    scores = jax.nn.sigmoid(h @ w["moe/router"])            # [b, S, routed]
    _, chosen = jax.lax.top_k(scores + bias, z["k"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    weight = model["routed_scaling_factor"] * picked / (
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
    if model["n_shared_experts"]:
        shared = _swiglu(h, w["moe/shared/gate/kernel"],
                         w["moe/shared/up/kernel"],
                         w["moe/shared/down/kernel"], q)
    y, _ = jax.lax.scan(
        lambda total, args: (total + expert(args), None), shared,
        (w["moe/w_gate"], w["moe/w_up"], w["moe/w_down"],
         jnp.moveaxis(held, -1, 0)))
    counts = jnp.sum(jax.nn.one_hot(chosen, z["routed"]), axis=(0, 1, 2))
    return y, counts


def _layer(x, w, bias, dense, z, model, q):
    eps = model["rms_norm_eps"]
    x = x + _attention(_rms(x, w["attn_norm/scale"], eps), w, z, model, q)
    h = _rms(x, w["ffn_norm/scale"], eps)
    if dense:
        m, counts = _swiglu(h, w["gate/kernel"], w["up/kernel"],
                            w["down/kernel"], q), jnp.zeros((z["routed"],))
    else:
        m, counts = _experts(h, w, bias, z, model, q)
    return x + m, counts


def _under(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _run_layer(x, params, prefix, bias, dense, z, model, q):
    return jax.checkpoint(functools.partial(
        _layer, dense=dense, z=z, model=model, q=q))(
            x, _under(params, prefix), bias)


def hidden_fn(params, biases, tokens, model, precision="highest"):
    """``(RMS(h_L; g_f) [b, S, d], counts [blocks, routed])``: the main
    model's output after its last norm."""
    q, z = _plain.rounder(precision), _sizes(model)
    x = params["embed/embedding"][tokens]
    counts = []
    for i, dense in enumerate(z["dense"]):
        x, c = _run_layer(x, params, f"block_{i}/", biases[i], dense, z,
                          model, q)
        counts.append(c)
    return (_rms(x, params["final_norm/scale"], model["rms_norm_eps"]),
            jnp.stack(counts))


def ahead(tokens, n):
    """``tokens`` rolled left by ``n`` with id 0 behind the last."""
    return jnp.concatenate([tokens[:, n:], jnp.zeros_like(tokens[:, :n])], 1)


def mtp_hidden_fn(params, bias, normed, tokens, model, precision="highest"):
    """``(the module's output after its own last norm, counts [routed])``."""
    q, z = _plain.rounder(precision), _sizes(model)
    eps = model["rms_norm_eps"]
    merged = jnp.concatenate(
        [_rms(params["embed/embedding"][ahead(tokens, 1)],
              params["mtp/enorm/scale"], eps),
         _rms(normed, params["mtp/hnorm/scale"], eps)], -1)
    x = q(merged) @ q(params["mtp/eh_proj/kernel"])
    x, counts = _run_layer(x, params, "mtp/mtp_block/", bias, False, z, model,
                           q)
    return _rms(x, params["mtp/head_norm/scale"], eps), counts


def head_loss(x, targets, weight, kernel, q):
    """``sum(weight * CE(x W_head, targets)) / sum(weight)``, the head and the
    loss in blocks of ``LOSS_ROWS`` tokens."""
    d = x.shape[-1]
    rows = min(LOSS_ROWS, x.shape[0] * x.shape[1])
    blocks = lambda a: a.reshape(-1, rows, *a.shape[2:])

    @jax.checkpoint
    def block(args):
        h, t, w = args
        logits = q(h) @ q(kernel)
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, -1) - picked) * w)

    total = jnp.sum(jax.lax.map(block, (x.reshape(-1, rows, d),
                                        blocks(targets), blocks(weight))))
    return total / jnp.sum(weight)


def loss_fn(params, biases, batch, model, precision="highest"):
    """``(L_main + mtp_loss_coeff * L_mtp, (counts [blocks + mtp, routed],
    L_main, L_mtp))``; ``biases`` has the module's row last."""
    q, z = _plain.rounder(precision), _sizes(model)
    tokens = batch["tokens"]
    normed, counts = hidden_fn(params, biases, tokens, model, precision)
    ones = jnp.ones(tokens.shape, jnp.float32)
    main = head_loss(normed, batch["targets"], ones, params["lm_head/kernel"],
                     q)
    if not z["mtp"]:
        return main, (counts, main, jnp.zeros(()))
    x, c = mtp_hidden_fn(params, biases[-1], normed, tokens, model, precision)
    S = tokens.shape[1]
    scored = jnp.broadcast_to(
        (jnp.arange(S) < S - 2).astype(jnp.float32), tokens.shape)
    mtp = head_loss(x, ahead(tokens, 2), scored, params["lm_head/kernel"], q)
    return (main + model["mtp_loss_coeff"] * mtp,
            (jnp.concatenate([counts, c[None]]), main, mtp))


def next_biases(biases, counts, model):
    """The router's bias after a step in which ``counts [rows, routed]``
    tokens chose each expert (dense blocks count nothing and stay at zero)."""
    mean = jnp.mean(counts, axis=-1, keepdims=True)
    delta = model["load_balance_coeff"] * jnp.sign(mean - counts)
    moved = biases + delta - jnp.mean(delta, axis=-1, keepdims=True)
    return jnp.where(jnp.sum(counts, -1, keepdims=True) > 0, moved, biases)


def bias_rows(model: dict) -> int:
    return model["num_hidden_layers"] + model["num_nextn_predict_layers"]


def run(config: dict, params: dict, batches: list, precision="highest") -> dict:
    """Three steps from ``params`` over ``batches`` (host arrays), on one
    device: what ``_plain.three_steps`` returns. Written out here, as the
    Trinity reference's, because the parameters, gradients and both of Adam's
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
    biases = jnp.zeros((bias_rows(model), model["routed_experts"]))
    out = {"loss": [], "loss_main": [], "loss_mtp": []}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, 1):
            (loss, (counts, main, mtp)), grads = grad(
                params, biases, {k: jnp.asarray(v) for k, v in batch.items()})
            params, state, norms = step(params, grads, state, t)
            del grads
            biases = next_biases(biases, counts, model)
            out["loss"].append(float(loss))
            out["loss_main"].append(float(main))
            out["loss_mtp"].append(float(mtp))
            if t == 1:
                out["moment_norms"] = {
                    k: float(v) * opt["first_moment_scale"]
                    for k, v in norms.items()}
        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        out["dparam_norms"] = {k: float(norm(params[k], start[k]))
                               for k in params}
    out["biases"] = np.asarray(biases)
    return out

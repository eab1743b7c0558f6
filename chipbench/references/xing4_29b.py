"""Plain reference for the ``xing4_0`` configurations (HF ``model_type:
xing4_0``, ``XingChen-AGI/Xing4.0-29B-A4B``): loss, gradients, AdamW and the
router's bias update of the published architecture, for the share of it that
one chip of the stated deployment holds.

Per token, hidden ``d``, ``n = hc_mult`` streams, ``X`` the token's ``[n, d]``
stream:

- ``X_0 = [E[id]] * n``; ``h = sum_i X_L[i]``; ``logits = RMS(h; g_f)
  W_head`` (untied); the loss is the mean next-token cross-entropy over the
  rows of the vocabulary that are held.
- A layer is two sub-layers, ``F = MLA(RMS(u; g1))`` then ``F = FFN(RMS(u;
  g2))``, each under its own hyper-connection (manifold-constrained, mHC):
  ``x' = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)`` over all ``n d``
  entries; ``H^_pre = a_pre (x' phi_pre) + b_pre``, ``H^_post = a_post (x'
  phi_post) + b_post`` (``R^n``), ``H^_res = a_res mat(x' phi_res) + b_res``
  (``R^{n x n}``, row-major); ``H_pre = sigmoid(H^_pre)``, ``H_post = 2
  sigmoid(H^_post)``; ``M = exp(clip(H^_res, mhc_h_res_clamp_min,
  mhc_h_res_clamp_max))`` and then ``hc_sinkhorn_iters`` times ``M[:, j] /=
  sum_i M[i, j] + hc_eps`` for every column ``j``, ``M[i, :] /= sum_j M[i, j]
  + hc_eps`` for every row ``i``: ``H_res = M``. ``u = sum_i H_pre[i] X[i]``;
  ``y = F(u)``; ``X[i] <- sum_j H_res[i, j] X[j] + H_post[i] y``.
- ``MLA``: ``c_q = RMS(x W_qa; g_q)``; ``q = c_q W_qb``, a head ``[q_nope
  (qk_nope_head_dim); q_rope (qk_rope_head_dim)]``; ``[c_kv (kv_lora_rank);
  k_r (qk_rope_head_dim)] = x W_kva``; ``c_kv = RMS(c_kv; g_kv)``; ``[k_nope;
  v (v_head_dim)]`` a head ``= c_kv W_kvb``. ``q_h = [q_nope_h;
  RoPE(q_rope_h)]``, ``k_h = [k_nope_h; RoPE(k_r)]``, the one ``k_r`` shared
  by all heads; rotate-half pairing; the rotary columns' inverse frequencies
  are YaRN's (``rope_scaling``): ``f_i = rope_theta^(-2i / r)``, ``r =
  qk_rope_head_dim``; ``low, high`` the floor / ceiling of ``r ln(original /
  (2 pi beta)) / (2 ln rope_theta)`` at ``beta_fast`` / ``beta_slow``,
  clipped to ``[0, r - 1]``; ``m_i = 1 - clip((i - low) / (high - low), 0,
  1)``; ``(f_i / factor) (1 - m_i) + f_i m_i``; cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, ``mscale(s, m)
  = 0.1 m ln s + 1``. ``o_h = softmax(q_h k_h^T (qk_nope_head_dim +
  qk_rope_head_dim)^-1/2 mscale(factor, mscale_all_dim)^2) v_h``, ``j <= i``;
  all heads through ``W_o``. No bias.
- ``FFN`` of the first ``first_k_dense_replace`` layers: ``(silu(x W_gate) * x
  W_up) W_down`` of width ``intermediate_size``. Of the others: ``s =
  sigmoid(x W_r)`` over ``routed_experts``; ``I`` = the ``num_experts_per_tok``
  largest of ``s + b``; ``w_i = routed_scaling_factor * s_i / (sum_{j in I}
  s_j + 1e-20)``; ``y = Shared(x) + sum_{i in I, i held} w_i Expert_i(x)``,
  all SwiGLU of width ``moe_intermediate_size``. ``b`` has no gradient; after
  a step, with ``c`` the tokens that chose each of the ``routed_experts``:
  ``b += d - mean(d)``, ``d = load_balance_coeff * sign(mean(c) - c)``.

**The share.** ``n_routed_experts`` experts are held, ``held_experts_start``
onwards, of the router's ``routed_experts``: the router scores and chooses
over all of them, and what an absent expert would have added is left out.
``held_layers`` names the published layers that the blocks are. No
prediction module (``num_nextn_predict_layers`` 0; another value is refused).

float32 ``jax.numpy``, no kernels, no sharding, no cache; imports nothing of
the program. Weights come as a flat ``{path: array}`` in the layout the
benchmark generates (``q_b`` / ``kv_b`` kernels ``[rank, heads, width]``, out
kernel ``[heads, v_head_dim, d]``, the held experts stacked ``[held, d, f]`` /
``[held, f, d]``; a hyper-connection's leaves under ``hc_attn/`` and
``hc_ffn/``: ``phi_pre [n d, n]``, ``phi_post [n d, n]``, ``phi_res [n d, n
n]``, ``alpha_* []``, ``b_pre [n]``, ``b_post [n]``, ``b_res [n n]``).

Departures from the published description, each of form and not of value:

- Every held expert is computed for every token and multiplied by the token's
  weight for it (zero where the token did not choose it): the same sum as
  gathering each expert's tokens, with nothing to sort.
- Attention is mapped over the heads, the head and loss over blocks of
  ``LOSS_ROWS`` tokens, every layer is checkpointed: for memory only.
- The control (``precision`` below ``highest``) rounds the operands of every
  matmul but the router's and the hyper-connections' ``x' phi``, which the
  configuration states in float32.
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
    if model["num_nextn_predict_layers"]:
        raise ValueError("this reference has no prediction module: the "
                         "source does not say how it joins the streams")
    return {
        "d": model["hidden_size"], "heads": model["num_attention_heads"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "v": model["v_head_dim"], "q_rank": model["q_lora_rank"],
        "kv_rank": model["kv_lora_rank"], "n": model["hc_mult"],
        "held": model["n_routed_experts"],
        "first": model["held_experts_start"],
        "routed": model["routed_experts"], "k": model["num_experts_per_tok"],
        "dense": [i < model["first_k_dense_replace"] for i in range(blocks)],
    }


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one sequence, as the benchmark counts them: 2 per
    multiply-accumulate, matmuls only (norms, gates, rotary terms, the
    router's sort, the Sinkhorn iterations and the streams' reads, mixes and
    writes are not counted, so a share of a peak computed from this can only
    come out low), nothing recomputed. Per token and layer: the five low-rank
    projections; attention's QK^T at the query/key width and PV at the value
    width over the causal half of the keys; the two hyper-connections' ``x'
    phi`` (``n d`` by ``2 n + n n`` each); a dense FFN, or the router, the
    shared expert and the routed rows this chip *expects*
    (``num_experts_per_tok * n_routed_experts / routed_experts`` a token).
    The head once."""
    z = _sizes(model)
    d, S, H, n = z["d"], traffic["seq_len"], z["heads"], z["n"]
    qk = z["nope"] + z["rope"]
    attn = (d * z["q_rank"] + z["q_rank"] * H * qk + d * (z["kv_rank"]
            + z["rope"]) + z["kv_rank"] * H * (z["nope"] + z["v"])
            + H * z["v"] * d + H * (qk + z["v"]) * (S + 1) / 2
            + 2 * n * d * (2 * n + n * n))
    swiglu = lambda width: 3 * d * width
    moe = d * z["routed"] + swiglu(model["moe_intermediate_size"]) * (
        model["n_shared_experts"] + z["k"] * z["held"] / z["routed"])
    macs = sum(attn + (swiglu(model["intermediate_size"]) if dense else moe)
               for dense in z["dense"]) + d * model["vocab_size"]
    return 2.0 * macs * S


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(model: dict):
    """``(inv_freq [r / 2], the cos/sin factor, the scores' factor)`` of the
    configuration's rotary scaling; a plain rope where ``rope_scaling`` is
    null."""
    r, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    i = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / r)
    scale = 1.0 / math.sqrt(model["qk_nope_head_dim"] + r)
    group = model.get("rope_scaling")
    if not group:
        return f, 1.0, scale
    if group["type"] != "yarn":
        raise ValueError(f"rope_scaling type {group['type']!r}")
    turns = lambda beta: (r * math.log(
        group["original_max_position_embeddings"] / (2 * math.pi * beta))
        / (2 * math.log(theta)))
    low = max(math.floor(turns(group["beta_fast"])), 0)
    high = min(math.ceil(turns(group["beta_slow"])), r - 1)
    keep = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = f / group["factor"] * (1.0 - keep) + f * keep
    all_dim = _mscale(group["factor"], group["mscale_all_dim"])
    return (inv_freq, _mscale(group["factor"], group["mscale"]) / all_dim,
            scale * all_dim * all_dim)


def _rope(x, inv_freq, factor):
    """Rotate-half rotary positions on ``[b, S, heads, width]``."""
    S, half = x.shape[1], x.shape[-1] // 2
    angles = (jnp.arange(S, dtype=jnp.float32)[:, None]
              * jnp.asarray(inv_freq, jnp.float32))
    cos = jnp.cos(angles)[:, None, :] * factor
    sin = jnp.sin(angles)[:, None, :] * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, gate, up, down, q):
    return q(jax.nn.silu(q(h) @ q(gate)) * (q(h) @ q(up))) @ q(down)


def _attention(h, w, z, model, q):
    S, eps = h.shape[1], model["rms_norm_eps"]
    inv_freq, trig, scale = yarn(model)
    c_q = _rms(q(h) @ q(w["attn/q_a/kernel"]), w["attn/q_norm/scale"], eps)
    qh = jnp.einsum("bsr,rhk->bshk", q(c_q), q(w["attn/q_b/kernel"]))
    qh = jnp.concatenate([qh[..., :z["nope"]],
                          _rope(qh[..., z["nope"]:], inv_freq, trig)], -1)
    down = q(h) @ q(w["attn/kv_a/kernel"])
    c_kv = _rms(down[..., :z["kv_rank"]], w["attn/kv_norm/scale"], eps)
    k_r = _rope(down[..., None, z["kv_rank"]:], inv_freq, trig)
    kv = jnp.einsum("bsr,rhk->bshk", q(c_kv), q(w["attn/kv_b/kernel"]))
    kh = jnp.concatenate(
        [kv[..., :z["nope"]],
         jnp.broadcast_to(k_r, (*kv.shape[:-1], z["rope"]))], -1)
    vh = kv[..., z["nope"]:]
    seen = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

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


def sinkhorn(logits, model):
    """``[..., n, n]``: ``exp`` of the clipped logits, then
    ``hc_sinkhorn_iters`` times every column (its sum runs over the rows,
    axis -2) and then every row divided by its sum plus ``hc_eps`` (a loop
    of the compiler's, not of Python's: unrolled, the chains of ten
    hyper-connections took the chip's compiler 200 s a run)."""
    eps = model["hc_eps"]

    def iteration(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)

    m = jnp.exp(jnp.clip(logits, model["mhc_h_res_clamp_min"],
                         model["mhc_h_res_clamp_max"]))
    return jax.lax.fori_loop(0, model["hc_sinkhorn_iters"], iteration, m)


def maps(X, w, prefix, model):
    """``(H_pre [b, S, n], H_post [b, S, n], H_res [b, S, n, n])`` of the
    hyper-connection whose leaves lie under ``prefix``."""
    n, d = X.shape[-2:]
    flat = X.reshape(*X.shape[:-2], n * d)
    normed = flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), -1, keepdims=True) + model["rms_norm_eps"])
    at = lambda name: w[prefix + name]
    pre = jax.nn.sigmoid(at("alpha_pre") * (normed @ at("phi_pre"))
                         + at("b_pre"))
    post = 2.0 * jax.nn.sigmoid(at("alpha_post") * (normed @ at("phi_post"))
                                + at("b_post"))
    res = at("alpha_res") * (normed @ at("phi_res")) + at("b_res")
    return pre, post, sinkhorn(res.reshape(*res.shape[:-1], n, n), model)


def sub_layer(X, w, prefix, branch, model):
    """``X <- H_res X + H_post^T branch(H_pre X)``."""
    pre, post, res = maps(X, w, prefix, model)
    u = jnp.einsum("bsn,bsnd->bsd", pre, X)
    y = branch(u)
    return (jnp.einsum("bsij,bsjd->bsid", res, X)
            + post[..., None] * y[..., None, :])


def _layer(X, w, bias, dense, z, model, q):
    eps = model["rms_norm_eps"]
    X = sub_layer(X, w, "hc_attn/", lambda u: _attention(
        _rms(u, w["attn_norm/scale"], eps), w, z, model, q), model)
    counts = [jnp.zeros((z["routed"],))]

    def ffn(u):
        h = _rms(u, w["ffn_norm/scale"], eps)
        if dense:
            return _swiglu(h, w["gate/kernel"], w["up/kernel"],
                           w["down/kernel"], q)
        m, counts[0] = _experts(h, w, bias, z, model, q)
        return m

    return sub_layer(X, w, "hc_ffn/", ffn, model), counts[0]


def _under(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def collapse(X):
    """The streams back to one: their sum."""
    return jnp.sum(X, -2)


def hidden_fn(params, biases, tokens, model, precision="highest"):
    """``(RMS(sum_i X_L[i]; g_f) [b, S, d], counts [blocks, routed])``."""
    q, z = _plain.rounder(precision), _sizes(model)
    x = params["embed/embedding"][tokens]
    X = jnp.broadcast_to(x[..., None, :], (*x.shape[:-1], z["n"], z["d"]))
    counts = []
    for i, dense in enumerate(z["dense"]):
        X, c = jax.checkpoint(functools.partial(
            _layer, dense=dense, z=z, model=model, q=q))(
                X, _under(params, f"block_{i}/"), biases[i])
        counts.append(c)
    return (_rms(collapse(X), params["final_norm/scale"],
                 model["rms_norm_eps"]), jnp.stack(counts))


def head_loss(x, targets, kernel, q):
    """The mean of ``CE(x W_head, targets)``, the head and the loss in blocks
    of ``LOSS_ROWS`` tokens."""
    d = x.shape[-1]
    rows = min(LOSS_ROWS, x.shape[0] * x.shape[1])

    @jax.checkpoint
    def block(args):
        h, t = args
        logits = q(h) @ q(kernel)
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    total = jnp.sum(jax.lax.map(block, (x.reshape(-1, rows, d),
                                        targets.reshape(-1, rows))))
    return total / targets.size


def loss_fn(params, biases, batch, model, precision="highest"):
    """``(loss, counts [blocks, routed])``."""
    normed, counts = hidden_fn(params, biases, batch["tokens"], model,
                               precision)
    return head_loss(normed, batch["targets"], params["lm_head/kernel"],
                     _plain.rounder(precision)), counts


def next_biases(biases, counts, model):
    """The router's bias after a step in which ``counts [rows, routed]``
    tokens chose each expert (dense blocks count nothing and stay at zero)."""
    mean = jnp.mean(counts, axis=-1, keepdims=True)
    delta = model["load_balance_coeff"] * jnp.sign(mean - counts)
    moved = biases + delta - jnp.mean(delta, axis=-1, keepdims=True)
    return jnp.where(jnp.sum(counts, -1, keepdims=True) > 0, moved, biases)


def run(config: dict, params: dict, batches: list, precision="highest") -> dict:
    """Three steps from ``params`` over ``batches`` (host arrays), on one
    device: what ``_plain.three_steps`` returns. Written out here, as the GLM
    reference's, because the parameters, gradients and both of Adam's
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
    out["biases"] = np.asarray(biases)
    return out

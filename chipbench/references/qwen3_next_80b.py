"""Plain reference for the ``qwen3_next`` configurations (HF ``model_type:
qwen3_next``, ``Qwen/Qwen3-Next-80B-A3B-Instruct``): loss, gradients and AdamW
of the published architecture, for the share of it that one chip of the
stated deployment holds.

Per token, hidden ``d`` (``u`` a token's normed vector; the configuration
file's ``assumed`` says which lines the published ``config.json`` states and
which are the Gated Delta Networks paper's and the released modelling code's):

- ``h = E[id]``, no multiplier; a layer: ``h += Mixer(RMS(h; g1)); h +=
  MoE(RMS(h; g2))``, eps ``rms_norm_eps``; ``logits = RMS(h; g_f) W_head``
  (untied); mean next-token cross-entropy over the rows of the vocabulary that
  are held. No bias in any linear map. Published layer ``l`` is
  ``full_attention`` where ``(l + 1) % full_attention_interval == 0`` and
  ``linear_attention`` elsewhere.
- ``linear_attention`` (Gated DeltaNet): ``[q; k; v; z] = W_qkvz u``
  (``linear_num_key_heads x linear_key_head_dim`` each for ``q`` and ``k``,
  ``linear_num_value_heads x linear_value_head_dim`` each for ``v`` and
  ``z``), ``[b; a] = W_ba u`` (a scalar a value head each); ``[q; k; v] <-
  silu(conv([q; k; v]))``, depthwise, causal, ``linear_conv_kernel_dim`` taps,
  zero history, no bias; a key head serves ``value heads / key heads`` value
  heads in a row. Per value head: ``beta_t = sigmoid(b_t)``; ``g_t =
  -exp(A_log) * softplus(a_t + dt_bias)``; ``q_t <- q_t / sqrt(sum q^2 +
  1e-6) / sqrt(key dim)``, ``k_t <- k_t / sqrt(sum k^2 + 1e-6)``; with ``S_0
  = 0`` in R^{key dim x value dim}, **token by token**::

      S' = exp(g_t) S_{t-1};  r_t = S'^T k_t
      S_t = S' + k_t (outer) beta_t (v_t - r_t);  o_t = S_t^T q_t

  ``y = RMS(o; w) * silu(z)`` per value head (norm first, gate after, one
  ``w`` of ``value dim`` for all heads); ``W_out y``.
- ``full_attention``: ``q = u W_q``, ``gate = u W_g`` (``num_attention_heads``
  heads of ``head_dim``), ``k``, ``v`` (``num_key_value_heads`` heads); ``q``
  and ``k`` RMS-normed over the head, each head by itself, with one scale
  vector for all heads; rotary positions over the first
  ``partial_rotary_factor * head_dim`` columns of a head (``rope_theta``,
  rotate-half within them), the other columns pass; ``softmax(q k^T /
  sqrt(head_dim)) v`` under the explicit causal mask, a KV head serving
  ``num_attention_heads / num_key_value_heads`` query heads; ``(out *
  sigmoid(gate)) W_o``.
- ``MoE``, every layer, in the published order: ``p = softmax(u W_r)`` over
  all ``routed_experts``; ``I`` = the ``num_experts_per_tok`` largest; ``w_i =
  p_i / sum_{j in I} p_j`` (``norm_topk_prob``); ``m = sum_{i in I, i held}
  w_i Expert_i(u) + sigmoid(u w_sg) Shared(u)``, both SwiGLU (widths
  ``moe_intermediate_size`` and ``shared_expert_intermediate_size``). No bias
  that chooses, no auxiliary loss term, no buffer.

**The share.** ``num_experts`` experts are held, ``held_experts_start``
onwards, of the router's ``routed_experts``: the router scores and chooses
over all of them, and what an absent expert would have added is left out; the
shared expert and its gate are whole. ``held_layers`` names the published
layers that the blocks are.

float32 ``jax.numpy``, no kernels, no sharding, no cache; imports nothing of
the program. Weights come as a flat ``{path: array}`` in the layout the
benchmark generates (attention kernels ``[d, heads, hd]``, out kernel
``[heads, hd, d]``, conv kernel ``[taps, channels]``, the held experts stacked
``[held, d, f]`` / ``[held, f, d]``, the router ``[d, routed_experts]``).

Departures from the published description, each of form and not of value:

- The delta rule's scan over tokens is nested: segments of ``SEGMENT`` tokens
  under ``jax.checkpoint``, so that its transpose keeps a state a segment and
  not one a token (8,192 states of 2 MB a layer are 17 GB). A
  rematerialisation, not a change of the mathematics: every state is the
  recurrence's own.
- The conv is a sum of shifted products of one padded array.
- Every held expert is computed for every token and multiplied by the token's
  weight for it (zero where the token did not choose it).
- Attention is mapped over the query heads, the head and the loss over blocks
  of ``LOSS_ROWS`` tokens: for memory only.
- :func:`run` differentiates layer by layer (a layer's forward again from its
  kept input, then its transpose), through one jitted pair a *kind* of layer;
  :func:`loss_fn` is the same sum as one function, for ``jax.grad``.
- The control (``precision`` below ``highest``) rounds the operands of every
  matmul, of the convolution and of the recurrence's three products (the
  state among them) but the router's, which the configuration states in
  float32.
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
SEGMENT = 64
KINDS = ("linear_attention", "full_attention")
#: the released rule's constant under the square root of a key's norm
L2_EPS = 1e-6


def _sizes(model: dict) -> dict:
    every = model["full_attention_interval"]
    kinds = [KINDS[(j + 1) % every == 0] for j in model["held_layers"]]
    if len(kinds) != model["num_hidden_layers"]:
        raise ValueError(f"layers {model['held_layers']} of "
                         f"{model['num_hidden_layers']}")
    if model["decoder_sparse_step"] != 1 or model["mlp_only_layers"]:
        raise ValueError("this reference has an expert FFN in every layer")
    hd = model["head_dim"]
    return {
        "d": model["hidden_size"], "heads": model["num_attention_heads"],
        "kv": model["num_key_value_heads"], "hd": hd,
        "rot": int(hd * model["partial_rotary_factor"]),
        "Hk": model["linear_num_key_heads"],
        "Hv": model["linear_num_value_heads"],
        "Dk": model["linear_key_head_dim"],
        "Dv": model["linear_value_head_dim"],
        "taps": model["linear_conv_kernel_dim"],
        "held": model["num_experts"], "first": model["held_experts_start"],
        "routed": model["routed_experts"], "k": model["num_experts_per_tok"],
        "kinds": kinds, "eps": model["rms_norm_eps"],
    }


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one sequence, as the benchmark counts them: 2 per
    multiply-accumulate, matmuls only (the conv's taps, norms, gates, rotary
    terms and the router's sort are not counted, so a share of a peak computed
    from this can only come out low), nothing recomputed. Per token: every
    projection; the head once; causal attention's QK^T and PV over the (S+1)/2
    pairs a token keeps; the delta rule as the recurrence counts it (a value
    head's decayed readout by the key, its write and its readout by the
    query: three products of key dim x value dim; a chunked form's in-chunk
    products are an implementation's); the router; the shared expert and its
    gate; and the routed rows this chip *expects*: ``num_experts_per_tok *
    num_experts / routed_experts`` a token."""
    z = _sizes(model)
    d, S = z["d"], traffic["seq_len"]
    K, V = z["Hk"] * z["Dk"], z["Hv"] * z["Dv"]
    mixer = {
        "linear_attention": d * (2 * K + 2 * V) + d * 2 * z["Hv"] + V * d
        + 3 * z["Hv"] * z["Dk"] * z["Dv"],
        "full_attention": d * z["hd"] * (3 * z["heads"] + 2 * z["kv"])
        + 2 * z["heads"] * z["hd"] * (S + 1) / 2}
    moe = (d * z["routed"] + 3 * d * model["shared_expert_intermediate_size"]
           + d + 3 * d * model["moe_intermediate_size"]
           * z["k"] * z["held"] / z["routed"])
    macs = sum(mixer[kind] + moe for kind in z["kinds"])
    return 2.0 * (macs + d * model["vocab_size"]) * S


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, theta, rot):
    """Rotate-half rotary positions over the first ``rot`` columns of a head
    of ``[b, S, heads, hd]``; the others pass."""
    S, half = x.shape[1], rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _swiglu(h, gate, up, down, q):
    return q(jax.nn.silu(q(h) @ q(gate)) * (q(h) @ q(up))) @ q(down)


def _conv_silu(x, kernel, q):
    """``silu`` of the causal depthwise conv over ``[b, S, C]``: a sum of
    shifted products, the oldest tap first."""
    S, taps = x.shape[1], kernel.shape[0]
    padded = jnp.pad(q(x), ((0, 0), (taps - 1, 0), (0, 0)))
    w = q(kernel)
    return jax.nn.silu(sum(padded[:, t:t + S] * w[t] for t in range(taps)))


def _recurrence(qh, kh, vh, g, beta, q, decay=True, readout=True):
    """The gated delta rule token by token: ``qh``, ``kh`` [b, S, Hv, Dk],
    ``vh`` [b, S, Hv, Dv], ``g``, ``beta`` [b, S, Hv]; ``o`` [b, S, Hv, Dv].
    ``decay`` and ``readout`` are the rule's two pieces that a test leaves
    out."""
    b, S, Hv, Dk = qh.shape
    Dv = vh.shape[-1]
    segment = math.gcd(S, SEGMENT)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        if decay:
            state = jnp.exp(g_t)[..., None, None] * state
        read = (jnp.einsum("bhkv,bhk->bhv", q(state), q(k_t)) if readout
                else 0.0)
        state = state + jnp.einsum("bhk,bhv->bhkv", q(k_t),
                                   q(b_t[..., None] * (v_t - read)))
        return state, jnp.einsum("bhkv,bhk->bhv", q(state), q(q_t))

    @jax.checkpoint
    def run_segment(state, xs):
        return jax.lax.scan(token, state, xs)

    # time first, in segments: [S / segment, segment, b, ...]
    by_segment = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        S // segment, segment, *a.shape[:1], *a.shape[2:])
    _, o = jax.lax.scan(run_segment, jnp.zeros((b, Hv, Dk, Dv), jnp.float32),
                        tuple(map(by_segment, (qh, kh, vh, g, beta))))
    return jnp.moveaxis(o.reshape(S, b, Hv, Dv), 0, 1)


def _norm_gate(o, gate, scale, eps, norm_first=True):
    """``RMS(o; w) * silu(z)`` a head: the norm first, the gate after.
    ``norm_first`` False is Mamba-2's order, ``RMS(o * silu(z); w)``, which a
    test holds against."""
    if norm_first:
        return _rms(o, scale, eps) * jax.nn.silu(gate)
    return _rms(o * jax.nn.silu(gate), scale, eps)


def _delta_net(u, w, z, q):
    b, S, _ = u.shape
    Hk, Hv, Dk, Dv = z["Hk"], z["Hv"], z["Dk"], z["Dv"]
    K, V = Hk * Dk, Hv * Dv
    kernel = w["gated_delta_net/conv_kernel"]
    if kernel.shape != (z["taps"], 2 * K + V):
        raise ValueError(f"conv kernel {kernel.shape}, linear_conv_kernel_dim "
                         f"{z['taps']} over {2 * K + V} channels")
    qkvz = q(u) @ q(w["gated_delta_net/in_proj_qkvz/kernel"])
    ba = q(u) @ q(w["gated_delta_net/in_proj_ba/kernel"])
    qkv, gate = qkvz[..., :2 * K + V], qkvz[..., 2 * K + V:]
    qkv = _conv_silu(qkv, kernel, q)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1,
                                               keepdims=True) + L2_EPS)
    # a key head serves Hv / Hk value heads in a row
    serve = lambda x: jnp.repeat(x.reshape(b, S, Hk, Dk), Hv // Hk, axis=2)
    qh = serve(qkv[..., :K])
    kh = serve(qkv[..., K:2 * K])
    vh = qkv[..., 2 * K:].reshape(b, S, Hv, Dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(w["gated_delta_net/A_log"]) * jax.nn.softplus(
        ba[..., Hv:] + w["gated_delta_net/dt_bias"])
    o = _recurrence(unit(qh) / math.sqrt(Dk), unit(kh), vh, g, beta, q)
    y = _norm_gate(o, gate.reshape(b, S, Hv, Dv),
                   w["gated_delta_net/norm_scale"], z["eps"])
    return q(y.reshape(b, S, V)) @ q(w["gated_delta_net/out_proj/kernel"])


def _attention(u, w, z, model, q):
    S = u.shape[1]
    proj = lambda n: jnp.einsum("bsd,dhk->bshk", q(u), q(w[f"attn/{n}/kernel"]))
    turn = lambda x: _rope(x, model["rope_theta"], z["rot"])
    qh = turn(_rms(proj("query"), w["attn/q_norm/scale"], z["eps"]))
    kh = turn(_rms(proj("key"), w["attn/k_norm/scale"], z["eps"]))
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
    out = out.transpose(1, 2, 0, 3) * jax.nn.sigmoid(proj("gate"))
    return jnp.einsum("bshk,hkd->bsd", q(out), q(w["attn/out/kernel"]))


def route(u, kernel, k):
    """``(chosen [.., k], weight [.., k])`` in the published order: the
    softmax over all the experts, the ``k`` largest, divided by their sum."""
    top, chosen = jax.lax.top_k(jax.nn.softmax(u @ kernel, axis=-1), k)
    return chosen, top / jnp.sum(top, -1, keepdims=True)


def _experts(u, w, z, q, shared_gate=True):
    """``(m, c)``: the expert layer's output and the tokens that chose each
    of the routed experts."""
    chosen, weight = route(u, w["moe_router/kernel"], z["k"])
    # [b, S, routed]: a token's weight for each expert, zero where not chosen
    spread = jnp.sum(jax.nn.one_hot(chosen, z["routed"]) * weight[..., None],
                     axis=-2)
    held = spread[..., z["first"]:z["first"] + z["held"]]

    @jax.checkpoint
    def expert(args):
        gate, up, down, mine = args
        return _swiglu(u, gate, up, down, q) * mine[..., None]

    m, _ = jax.lax.scan(
        lambda total, args: (total + expert(args), None), jnp.zeros_like(u),
        (w["moe/w_gate"], w["moe/w_up"], w["moe/w_down"],
         jnp.moveaxis(held, -1, 0)))
    shared = _swiglu(u, w["shared_expert/gate/kernel"],
                     w["shared_expert/up/kernel"],
                     w["shared_expert/down/kernel"], q)
    if shared_gate:
        shared = jax.nn.sigmoid(q(u) @ q(w["shared_expert_gate/kernel"])) \
            * shared
    counts = jnp.sum(jax.nn.one_hot(chosen, z["routed"]), axis=(0, 1, 2))
    return m + shared, counts


def _layer(x, w, kind, z, model, q):
    """``(x + Mixer(RMS(x)) + MoE(RMS(.)), counts [routed])``."""
    u = _rms(x, w["mixer_norm/scale"], z["eps"])
    if kind == "linear_attention":
        x = x + _delta_net(u, w, z, q)
    else:
        x = x + _attention(u, w, z, model, q)
    m, counts = _experts(_rms(x, w["ffn_norm/scale"], z["eps"]), w, z, q)
    return x + m, counts


def _of_block(params, i):
    pre = f"block_{i}/"
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden_fn(params, tokens, model, precision="highest"):
    """``(h [b, S, d] after the last layer, counts [blocks, routed])``."""
    q, z = _plain.rounder(precision), _sizes(model)
    x = params["embed/embedding"][tokens]
    counts = []
    for i, kind in enumerate(z["kinds"]):
        x, c = jax.checkpoint(functools.partial(
            _layer, kind=kind, z=z, model=model, q=q))(x, _of_block(params, i))
        counts.append(c)
    return x, jnp.stack(counts)


def _head_loss(x, scale, head, targets, eps, q):
    """Mean next-token cross-entropy of ``RMS(x; scale) head``; the head and
    the loss in blocks of ``LOSS_ROWS`` tokens."""
    x = _rms(x, scale, eps)
    d = x.shape[-1]
    rows = min(LOSS_ROWS, x.shape[0] * x.shape[1])
    x, targets = x.reshape(-1, rows, d), targets.reshape(-1, rows)

    @jax.checkpoint
    def block(args):
        h, t = args
        logits = q(h) @ q(head)
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    return jnp.sum(jax.lax.map(block, (x, targets))) / targets.size


def logits_fn(params, tokens, model, precision="highest"):
    q = _plain.rounder(precision)
    x, _ = hidden_fn(params, tokens, model, precision)
    x = _rms(x, params["final_norm/scale"], model["rms_norm_eps"])
    return q(x) @ q(params["lm_head/kernel"])


def loss_fn(params, batch, model, precision="highest"):
    """``(mean next-token cross-entropy, counts)`` as one function."""
    x, counts = hidden_fn(params, batch["tokens"], model, precision)
    return _head_loss(x, params["final_norm/scale"], params["lm_head/kernel"],
                      batch["targets"], model["rms_norm_eps"],
                      _plain.rounder(precision)), counts


def layerwise(model: dict, precision="highest"):
    """``loss_and_grads(params, batch) -> ((loss, counts), grads)``:
    :func:`loss_fn`'s value and gradient, a layer at a time. Forward: each
    layer's input is kept. Backward: the layer's forward again inside its
    transpose. One jitted forward and one jitted transpose a kind of layer,
    whatever the depth, all of them and the head compiled side by side on the
    first call."""
    q, z = _plain.rounder(precision), _sizes(model)
    eps = z["eps"]

    def pair(kind):
        layer = functools.partial(_layer, kind=kind, z=z, model=model, q=q)

        def transpose(x, w, dy):
            _, vjp, _ = jax.vjp(layer, x, w, has_aux=True)
            return vjp(dy)

        return jax.jit(layer), jax.jit(transpose)

    pairs = {kind: pair(kind) for kind in sorted(set(z["kinds"]))}
    head = jax.jit(jax.value_and_grad(
        lambda x, scale, W, targets: _head_loss(x, scale, W, targets, eps, q),
        argnums=(0, 1, 2)))
    embed = jax.jit(lambda E, tokens: E[tokens])
    embed_t = jax.jit(lambda E, tokens, dx: jnp.zeros_like(E).at[tokens].add(
        dx))
    compiled = {}

    def compile_all(params, batch):
        shape = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        x = jax.ShapeDtypeStruct(batch["tokens"].shape + (z["d"],),
                                 jnp.float32)
        jobs = {"head": (head, (x, shape(params["final_norm/scale"]),
                                shape(params["lm_head/kernel"]),
                                shape(batch["targets"])))}
        for kind in pairs:
            w = shape(_of_block(params, z["kinds"].index(kind)))
            jobs["forward", kind] = (pairs[kind][0], (x, w))
            jobs["transpose", kind] = (pairs[kind][1], (x, w, x))
        compiled.update(_side_by_side(jobs))

    def loss_and_grads(params, batch):
        if not compiled:
            compile_all(params, batch)
        tokens = batch["tokens"]
        E = params["embed/embedding"]
        inputs, counts, x = [], [], embed(E, tokens)
        for i, kind in enumerate(z["kinds"]):
            inputs.append(x)
            x, c = compiled["forward", kind](x, _of_block(params, i))
            counts.append(c)
        loss, (dx, dscale, dhead) = compiled["head"](
            x, params["final_norm/scale"], params["lm_head/kernel"],
            batch["targets"])
        grads = {"final_norm/scale": dscale, "lm_head/kernel": dhead}
        for i in reversed(range(len(z["kinds"]))):
            dx, dw = compiled["transpose", z["kinds"][i]](
                inputs.pop(), _of_block(params, i), dx)
            grads.update({f"block_{i}/{k}": g for k, g in dw.items()})
        grads["embed/embedding"] = embed_t(E, tokens, dx)
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


def run(config: dict, params: dict, batches: list, precision="highest") -> dict:
    """Three steps from ``params`` over ``batches`` (host arrays), on one
    device: what ``_plain.three_steps`` returns. Written out here, as the
    SmallThinker and LFM2 references', because the parameters, gradients and
    both of Adam's moments (16 bytes a parameter, 10 GB) leave no room for a
    device copy of the start: the starting parameters wait on the host, and
    only the first moment's norms leave the optimizer step. Prints the rows
    that fell on the held experts, a layer and a step (``row:
    "reference_held_rows"``)."""
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

"""Plain reference for the ``nemotron_h`` configurations (HF ``model_type:
nemotron_h``, ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``): loss,
gradients, AdamW and the router's bias update of the published architecture,
for the share of it that one chip of the stated deployment holds.

Per token, hidden ``d`` (``x`` a token's vector):

- ``h = E[id]``; for each layer ``h += Mixer(RMS(h; g_l))``, the mixer by the
  layer's letter in ``hybrid_override_pattern``; ``logits = RMS(h; g_f)
  W_head`` (untied); mean next-token cross-entropy over the rows of the
  vocabulary that are held. No bias in any linear map.
- ``M`` (Mamba-2, ``n_groups`` B/C groups): ``[z, xBC, dt] = W_in x``; ``xBC_t
  = silu(sum_k w[k] * xBC_{t-(K-1)+k} + b)`` with zero history; ``[x, B, C] =
  split(xBC)``, ``B`` and ``C`` ``n_groups x ssm_state_size``, head ``h``
  reading group ``h // (heads / n_groups)``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
  B_t``, ``y_t = S_t C_t + D x_t``; ``y = GroupRMS(y * silu(z); g)``, each of
  the ``n_groups`` runs of channels normed by itself; ``W_out y``.
- ``*``: causal softmax attention, ``num_key_value_heads`` serving
  ``num_attention_heads``, no positional term, scores over ``sqrt(head_dim)``.
- ``E``: ``s = sigmoid(x W_r)`` over ``routed_experts``; ``I`` = the
  ``num_experts_per_tok`` largest of ``s + b``; ``w_i = routed_scaling_factor
  * s_i / (sum_{j in I} s_j + 1e-20)``; ``y = Shared(x) + sum_{i in I, i held}
  w_i Expert_i(x)``, ``Expert(x) = relu(x W_up)^2 W_down`` of width
  ``moe_intermediate_size``, the shared one of
  ``moe_shared_expert_intermediate_size``. ``b`` has no gradient; after a
  step, with ``c`` the tokens that chose each of the ``routed_experts``: ``b
  += d - mean(d)``, ``d = load_balance_coeff * sign(mean(c) - c)``.

**The share.** ``n_routed_experts`` experts are held, ``held_experts_start``
onwards, of the router's ``routed_experts``: the router scores and chooses
over all of them, and what an absent expert would have added is left out.
``held_layers`` names the published layers that the blocks are.

float32 ``jax.numpy``, no kernels, no sharding, no cache; imports nothing of
the program. Weights come as a flat ``{path: array}`` in the layout the
benchmark generates (attention kernels ``[d, heads, hd]``, out kernel
``[heads, hd, d]``, conv kernel ``[K, channels]``, the held experts stacked
``[held, d, f]`` / ``[held, f, d]``).

Departures from the published description, each of form and not of value:

- The state-space recurrence is computed as its closed form over the whole
  sequence, ``y_t = sum_{s<=t} exp(sum_{r=s+1..t} dt_r A) (C_t . B_s) dt_s x_s
  + D x_t`` (an S x S matrix per head, ``C B^T`` once a group), not in the
  published chunks and not step by step: it is the same sum. Groups and,
  inside a group, heads are mapped one after another under ``jax.checkpoint``.
- Every held expert is computed for every token and multiplied by the token's
  weight for it (zero where the token did not choose it).
- Attention is mapped over the query heads, the head and the loss over blocks
  of ``LOSS_ROWS`` tokens: for memory only.
- :func:`run` differentiates layer by layer (a layer's forward again from its
  kept input, then its transpose), through one jitted pair a *kind* of
  layer, so that each kind compiles once and not once a layer;
  :func:`loss_fn` is the same sum as one function, for ``jax.grad``.
- The control (``precision`` below ``highest``) rounds the operands of every
  matmul and the convolution but the router's, which the configuration states
  in float32.
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
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, G = model["ssm_state_size"], model["n_groups"]
    pattern = model["hybrid_override_pattern"]
    kinds = [pattern[j] for j in model["held_layers"]]
    if len(kinds) != model["num_hidden_layers"] or set(kinds) - set("ME*"):
        raise ValueError(f"layers {kinds} of {model['num_hidden_layers']}")
    return {
        "d": model["hidden_size"], "H": H, "P": P, "N": N, "G": G,
        "inner": H * P, "conv": H * P + 2 * G * N, "K": model["conv_kernel"],
        "hd": model["head_dim"], "heads": model["num_attention_heads"],
        "kv": model["num_key_value_heads"],
        "held": model["n_routed_experts"],
        "first": model["held_experts_start"],
        "routed": model["routed_experts"], "k": model["num_experts_per_tok"],
        "kinds": kinds, "eps": model["layer_norm_epsilon"],
    }


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one sequence, as the benchmark counts them: 2 per
    multiply-accumulate, matmuls only (the conv, norms, gates and the decays'
    exponentials are not counted, so a share of a peak computed from this can
    only come out low), nothing recomputed. Per token: every projection; the
    head once; causal attention's QK^T and PV over the (S+1)/2 pairs a token
    keeps; the state-space mixer at its published chunk ``Q``: the causal half
    of ``C B^T`` (a group) and of ``(L o C B^T)(dt x)`` inside a chunk, the
    chunk's state out (``dt x (x) B``) and in (``C S``); the router; the
    shared expert; and the routed rows this chip *expects*:
    ``num_experts_per_tok * n_routed_experts / routed_experts`` a token."""
    z = _sizes(model)
    d, S = z["d"], traffic["seq_len"]
    Q = min(model["chunk_size"], S)
    mamba = d * (z["inner"] + z["conv"] + z["H"]) + z["inner"] * d \
        + (Q + 1) / 2 * (z["G"] * z["N"] + z["inner"]) \
        + 2 * z["inner"] * z["N"]
    attn = 2 * d * z["hd"] * (z["heads"] + z["kv"]) \
        + 2 * z["heads"] * z["hd"] * (S + 1) / 2
    moe = d * z["routed"] \
        + model["n_shared_experts"] * 2 * d \
        * model["moe_shared_expert_intermediate_size"] \
        + z["k"] * z["held"] / z["routed"] * 2 * d \
        * model["moe_intermediate_size"]
    macs = sum({"M": mamba, "*": attn, "E": moe}[k] for k in z["kinds"])
    return 2.0 * (macs + d * model["vocab_size"]) * S


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _attention(h, w, z, q):
    b, S, _ = h.shape
    proj = lambda n: jnp.einsum("bsd,dhk->bshk", q(h), q(w[f"attn/{n}/kernel"]))
    qh, kh, vh = proj("query"), proj("key"), proj("value")
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


def _mamba(h, w, z, q):
    b, S, _ = h.shape
    H, P, N, G, K = z["H"], z["P"], z["N"], z["G"], z["K"]
    per = H // G                                            # heads a group
    zxbcdt = q(h) @ q(w["mamba/in_proj/kernel"])
    gate, xBC, dt = jnp.split(zxbcdt, [z["inner"], z["inner"] + z["conv"]], -1)
    padded = jnp.pad(q(xBC), ((0, 0), (K - 1, 0), (0, 0)))
    kernel = q(w["mamba/conv_kernel"])
    xBC = jax.nn.silu(sum(padded[:, k:k + S] * kernel[k] for k in range(K))
                      + w["mamba/conv_bias"])
    x, B, C = jnp.split(xBC, [z["inner"], z["inner"] + G * N], -1)
    x = x.reshape(b, S, H, P)
    dt = jax.nn.softplus(dt + w["mamba/dt_bias"])           # [b, S, H]
    A = -jnp.exp(w["mamba/A_log"])
    cum = jnp.cumsum(dt * A, axis=1)                        # [b, S, H]
    lower = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def group(args):
        Bg, Cg, cum_g, xdt_g = args         # [b,S,N] x 2, [per,b,S], [per,b,S,P]
        scores = jnp.einsum("btn,bsn->bts", q(Cg), q(Bg))   # the group's heads'

        @jax.checkpoint
        def head(args):
            cum_h, xdt_h = args                             # [b,S], [b,S,P]
            decay = jnp.exp(jnp.where(
                lower, cum_h[:, :, None] - cum_h[:, None, :], -jnp.inf))
            return jnp.einsum("bts,bsp->btp", q(decay * scores), q(xdt_h))

        return jax.lax.map(head, (cum_g, xdt_g))            # [per, b, S, P]

    by_group = lambda a: a.reshape(b, S, G, N).transpose(2, 0, 1, 3)
    y = jax.lax.map(group, (
        by_group(B), by_group(C),
        cum.reshape(b, S, G, per).transpose(2, 3, 0, 1),
        (x * dt[..., None]).reshape(b, S, G, per, P).transpose(2, 3, 0, 1, 4)))
    y = y.transpose(2, 3, 0, 1, 4).reshape(b, S, H, P) \
        + x * w["mamba/D"][:, None]
    y = y.reshape(b, S, z["inner"]) * jax.nn.silu(gate)
    y = _rms(y.reshape(b, S, G, -1), 1.0, z["eps"]).reshape(y.shape) \
        * w["mamba/norm/scale"]
    return q(y) @ q(w["mamba/out_proj/kernel"])


def _relu2(h, up, down, q):
    return q(jnp.square(jax.nn.relu(q(h) @ q(up)))) @ q(down)


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
        up, down, mine = args
        return _relu2(h, up, down, q) * mine[..., None]

    shared = jnp.zeros_like(h)
    if model["n_shared_experts"]:
        shared = _relu2(h, w["moe/shared/up/kernel"],
                        w["moe/shared/down/kernel"], q)
    y, _ = jax.lax.scan(
        lambda total, args: (total + expert(args), None), shared,
        (w["moe/w_up"], w["moe/w_down"], jnp.moveaxis(held, -1, 0)))
    counts = jnp.sum(jax.nn.one_hot(chosen, z["routed"]), axis=(0, 1, 2))
    return y, counts


def _layer(x, w, bias, kind, z, model, q):
    """``(x + Mixer(RMS(x)), counts [routed])``; ``bias`` is the router's (an
    expert layer reads it, the others count nothing)."""
    h = _rms(x, w["norm/scale"], z["eps"])
    counts = jnp.zeros((z["routed"],))
    if kind == "M":
        m = _mamba(h, w, z, q)
    elif kind == "*":
        m = _attention(h, w, z, q)
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
    for i, kind in enumerate(z["kinds"]):
        x, c = jax.checkpoint(functools.partial(
            _layer, kind=kind, z=z, model=model, q=q))(
                x, _of_block(params, i), biases[i])
        counts.append(c)
    return x, jnp.stack(counts)


def _head_loss(x, scale, kernel, targets, eps, q):
    """Mean next-token cross-entropy of ``RMS(x; scale) kernel``; the head and
    the loss in blocks of ``LOSS_ROWS`` tokens."""
    x = _rms(x, scale, eps)
    d = x.shape[-1]
    rows = min(LOSS_ROWS, x.shape[0] * x.shape[1])
    x, targets = x.reshape(-1, rows, d), targets.reshape(-1, rows)

    @jax.checkpoint
    def block(args):
        h, t = args
        logits = q(h) @ q(kernel)
        picked = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    return jnp.sum(jax.lax.map(block, (x, targets))) / targets.size


def logits_fn(params, biases, tokens, model, precision="highest"):
    q = _plain.rounder(precision)
    x, _ = hidden_fn(params, biases, tokens, model, precision)
    x = _rms(x, params["final_norm/scale"], model["layer_norm_epsilon"])
    return q(x) @ q(params["lm_head/kernel"])


def loss_fn(params, biases, batch, model, precision="highest"):
    """``(mean next-token cross-entropy, counts)`` as one function."""
    x, counts = hidden_fn(params, biases, batch["tokens"], model, precision)
    return _head_loss(x, params["final_norm/scale"], params["lm_head/kernel"],
                      batch["targets"], model["layer_norm_epsilon"],
                      _plain.rounder(precision)), counts


def layerwise(model: dict, precision="highest"):
    """``loss_and_grads(params, biases, batch) -> ((loss, counts), grads)``:
    :func:`loss_fn`'s value and gradient, a layer at a time. Forward: each
    layer's input is kept. Backward: the layer's forward again inside its
    transpose. One jitted forward and one jitted transpose a kind of layer,
    whatever the depth, and the seven of them (three kinds and the head)
    compiled side by side on the first call: one after another they took 90 s
    of a cold run at the published widths, the longest alone 20."""
    q, z = _plain.rounder(precision), _sizes(model)
    eps = z["eps"]

    def pair(kind):
        layer = functools.partial(_layer, kind=kind, z=z, model=model, q=q)

        def transpose(x, w, bias, dy):
            _, vjp, _ = jax.vjp(lambda x, w: layer(x, w, bias), x, w,
                                has_aux=True)
            return vjp(dy)

        return jax.jit(layer), jax.jit(transpose)

    pairs = {kind: pair(kind) for kind in sorted(set(z["kinds"]))}
    head = jax.jit(jax.value_and_grad(
        lambda x, scale, kernel, targets: _head_loss(
            x, scale, kernel, targets, eps, q), argnums=(0, 1, 2)))
    embed = jax.jit(lambda E, tokens: E[tokens])
    embed_t = jax.jit(lambda E, tokens, dx: jnp.zeros_like(E).at[tokens].add(dx))
    compiled = {}

    def compile_all(params, biases, batch):
        shape = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        x = jax.ShapeDtypeStruct(batch["tokens"].shape + (z["d"],),
                                 jnp.float32)
        jobs = {"head": (head, (x, shape(params["final_norm/scale"]),
                                shape(params["lm_head/kernel"]),
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
        loss, (dx, dscale, dkernel) = compiled["head"](
            x, params["final_norm/scale"], params["lm_head/kernel"],
            batch["targets"])
        grads = {"final_norm/scale": dscale, "lm_head/kernel": dkernel}
        for i in reversed(range(len(z["kinds"]))):
            dx, dw = compiled["transpose", z["kinds"][i]](
                inputs.pop(), _of_block(params, i), biases[i], dx)
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


def next_biases(biases, counts, model):
    """The router's bias after a step in which ``counts [blocks, routed]``
    tokens chose each expert (the other blocks count nothing and stay at
    zero)."""
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
    return out

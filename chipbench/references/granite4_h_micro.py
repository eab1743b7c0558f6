"""Plain reference for the Granite 4.0-H configurations (HF
``granitemoehybrid``, dense variant: ``ibm-granite/granite-4.0-h-micro``):
loss, gradients and AdamW of the published architecture.

Per token, hidden ``d``: ``x = embedding_multiplier * E[token]``; for each
layer ``x += residual_multiplier * Mix(RMSNorm(x))`` then ``x +=
residual_multiplier * MLP(RMSNorm(x))``; ``logits = RMSNorm(x) @ E^T /
logits_scaling`` (tied); mean next-token cross-entropy over the rows of ``E``
that are held. ``MLP(h) = W_down (silu(W_gate h) * W_up h)``. ``Mix`` follows
``layer_types``:

- ``attention``: causal softmax attention, grouped queries, no positional
  term (``position_embedding_type: nope``), scores scaled by
  ``attention_multiplier``;
- ``mamba`` (Mamba-2, one B/C group): ``[z, xBC, dt] = W_in h``; ``xBC_t =
  silu(sum_k w[k] * xBC_{t-(K-1)+k} + b)`` with zero history; ``[x, B, C] =
  split(xBC)``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per
  head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D
  x_t``; ``y = RMSNorm(y * silu(z))`` over all inner channels; ``W_out y``.

float32 ``jax.numpy``, no kernels, no sharding, no cache; imports nothing of
the program. Weights come as a flat ``{path: array}`` in the layout the
benchmark generates (query/key/value kernels ``[d, heads, hd]``, out kernel
``[heads, hd, d]``, conv kernel ``[K, channels]``, gate/up/down apart).

Departures from the published description, each of form and not of value:

- The state-space recurrence is computed as its closed form over the whole
  sequence, ``y_t = sum_{s<=t} exp(sum_{r=s+1..t} dt_r A) (C_t . B_s) dt_s x_s
  + D x_t`` (an S x S matrix per head), not in the published chunks of
  ``mamba_chunk_size`` and not step by step: it is the same sum. Heads are
  mapped one after another under ``jax.checkpoint`` so that the matrices fit
  beside the float32 parameters, gradients and Adam state.
- The program folds ``attention_multiplier * sqrt(head_dim)`` (1/8) into the
  queries because its kernels scale by ``1/sqrt(head_dim)``; here the scores
  are scaled by ``attention_multiplier`` as published.
- Attention is mapped over the key/value groups and every layer is
  checkpointed, for memory only.
- HF keeps gate and up in one ``input_linear`` matrix; they are two leaves
  here, as in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references import _plain


def _sizes(model: dict) -> dict:
    H, P = model["mamba_n_heads"], model["mamba_d_head"]
    N, G = model["mamba_d_state"], model["mamba_n_groups"]
    if G != 1:
        raise ValueError("this reference has one B/C group")
    hd = model["hidden_size"] // model["num_attention_heads"]
    return {"H": H, "P": P, "N": N, "inner": H * P, "conv": H * P + 2 * N,
            "K": model["mamba_d_conv"], "hd": hd,
            "heads": model["num_attention_heads"],
            "kv": model["num_key_value_heads"],
            "kinds": model["layer_types"][:model["num_hidden_layers"]]}


def forward_flops(model: dict, traffic: dict) -> float:
    """Forward FLOPs of one sequence, as the benchmark counts them: 2 per
    multiply-accumulate, matmuls only (the conv, norms, gates and the decays'
    exponentials are not counted, so a share of a peak computed from this can
    only come out low), nothing recomputed. Per token: every projection; the
    tied head once; causal attention's QK^T and PV over the (S+1)/2 pairs a
    token keeps; and the state-space mixer at its published chunk ``Q``: the
    causal half of ``C B^T`` and of ``(L o C B^T)(dt x)`` inside a chunk,
    the chunk's state out (``dt x (x) B``) and in (``C S``)."""
    z = _sizes(model)
    d, S, V = model["hidden_size"], traffic["seq_len"], model["vocab_size"]
    Q = min(model["mamba_chunk_size"], S)
    mlp = 3 * d * model["shared_intermediate_size"]
    mamba = d * (z["inner"] + z["conv"] + z["H"]) + z["inner"] * d \
        + (Q + 1) / 2 * (z["N"] + z["inner"]) + 2 * z["inner"] * z["N"]
    attn = 2 * d * z["hd"] * (z["heads"] + z["kv"]) \
        + 2 * z["heads"] * z["hd"] * (S + 1) / 2
    macs = sum(mlp + (mamba if k == "mamba" else attn) for k in z["kinds"])
    return 2.0 * (macs + d * V) * S


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _attention(h, w, z, model, q):
    b, S, _ = h.shape
    proj = lambda n: jnp.einsum("bsd,dhk->bshk", q(h), q(w[f"attn/{n}/kernel"]))
    qh, kh, vh = proj("query"), proj("key"), proj("value")
    rep = z["heads"] // z["kv"]
    qg = qh.reshape(b, S, z["kv"], rep, z["hd"]).transpose(2, 0, 3, 1, 4)
    mask = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def group(args):
        qs, ks, vs = args                      # [b,rep,S,hd], [b,S,hd] x 2
        scores = jnp.einsum("brqk,btk->brqt", q(qs), q(ks)) \
            * model["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("brqt,btk->brqk", q(probs), q(vs))

    out = jax.lax.map(group, (qg, kh.transpose(2, 0, 1, 3),
                              vh.transpose(2, 0, 1, 3)))  # [kv,b,rep,S,hd]
    att = out.transpose(1, 3, 0, 2, 4).reshape(b, S, z["heads"], z["hd"])
    return jnp.einsum("bshk,hkd->bsd", q(att), q(w["attn/out/kernel"]))


def _mamba(h, w, z, model, q):
    b, S, _ = h.shape
    H, P, N, K = z["H"], z["P"], z["N"], z["K"]
    zxbcdt = q(h) @ q(w["mamba/in_proj/kernel"])
    gate, xBC, dt = jnp.split(zxbcdt, [z["inner"], z["inner"] + z["conv"]], -1)
    padded = jnp.pad(q(xBC), ((0, 0), (K - 1, 0), (0, 0)))
    kernel = q(w["mamba/conv_kernel"])
    xBC = jax.nn.silu(sum(padded[:, k:k + S] * kernel[k] for k in range(K))
                      + w["mamba/conv_bias"])
    x, B, C = jnp.split(xBC, [z["inner"], z["inner"] + N], -1)
    x = x.reshape(b, S, H, P)
    dt = jax.nn.softplus(dt + w["mamba/dt_bias"])          # [b,S,H]
    A = -jnp.exp(w["mamba/A_log"])
    cum = jnp.cumsum(dt * A, axis=1)                        # [b,S,H]
    scores = jnp.einsum("btn,bsn->bts", q(C), q(B))         # shared by heads
    lower = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(args):
        cum_h, xdt_h = args                                 # [b,S], [b,S,P]
        decay = jnp.exp(jnp.where(
            lower, cum_h[:, :, None] - cum_h[:, None, :], -jnp.inf))
        return jnp.einsum("bts,bsp->btp", q(decay * scores), q(xdt_h))

    y = jax.lax.map(head, (cum.transpose(2, 0, 1),
                           (x * dt[..., None]).transpose(2, 0, 1, 3)))
    y = y.transpose(1, 2, 0, 3) + x * w["mamba/D"][:, None]
    y = y.reshape(b, S, z["inner"]) * jax.nn.silu(gate)
    y = _rms(y, w["mamba/norm/scale"], model["rms_norm_eps"])
    return q(y) @ q(w["mamba/out_proj/kernel"])


def _layer(x, w, kind, z, model, q):
    eps, r = model["rms_norm_eps"], model["residual_multiplier"]
    mix = _mamba if kind == "mamba" else _attention
    x = x + r * mix(_rms(x, w["mix_norm/scale"], eps), w, z, model, q)
    h = _rms(x, w["mlp_norm/scale"], eps)
    h = jax.nn.silu(q(h) @ q(w["gate/kernel"])) * (q(h) @ q(w["up/kernel"]))
    return x + r * (q(h) @ q(w["down/kernel"]))


def logits_fn(params, tokens, model, precision="highest"):
    q, z = _plain.rounder(precision), _sizes(model)
    E = params["embed/embedding"]
    x = model["embedding_multiplier"] * E[tokens]
    for i, kind in enumerate(z["kinds"]):
        pre = f"block_{i}/"
        w = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = jax.checkpoint(functools.partial(
            _layer, kind=kind, z=z, model=model, q=q))(x, w)
    x = _rms(x, params["final_norm/scale"], model["rms_norm_eps"])
    return q(x) @ q(E).T / model["logits_scaling"]


def loss_fn(params, batch, model, precision="highest"):
    logits = logits_fn(params, batch["tokens"], model, precision)
    picked = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def run(config: dict, params: dict, batches: list, precision="highest") -> dict:
    """Three steps from ``params`` over ``batches`` (host arrays), on one
    device: what ``_plain.three_steps`` returns. Written out here because the
    parameters, gradients and both of Adam's moments (16 bytes a parameter)
    all but fill the chip: the starting parameters wait on the host, and only
    the first moment's norms leave the optimizer step. Batches of more than
    ``reference_micro_batch`` sequences are averaged over equal
    micro-batches."""
    model, micro = config["model"], config["reference_micro_batch"]
    opt = config["optimizer"]
    grad = jax.jit(jax.value_and_grad(
        functools.partial(loss_fn, model=model, precision=precision)))
    add = jax.jit(functools.partial(jax.tree.map, jnp.add), donate_argnums=0)

    def loss_and_grads(p, batch):
        n = batch["tokens"].shape[0]
        if n % micro:
            raise ValueError(f"batch {n} is not a multiple of {micro}")
        total = None
        for s in range(0, n, micro):
            out = grad(p, {k: jnp.asarray(v[s:s + micro])
                           for k, v in batch.items()})
            total = out if total is None else add(total, out)
        k = n // micro
        loss, grads = total
        return loss / k, (grads if k == 1 else
                          {name: g / k for name, g in grads.items()})

    init, adam = _plain.adamw(opt)

    def step(p, g, state, t):
        new, state, moment = adam(p, g, state, t)
        return new, state, _plain.leaf_norms(moment)

    step = jax.jit(step, static_argnums=3, donate_argnums=(0, 2))
    start = {k: np.asarray(v) for k, v in params.items()}
    state = init(params)
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, 1):
            loss, grads = loss_and_grads(params, batch)
            params, state, norms = step(params, grads, state, t)
            del grads
            out["loss"].append(float(loss))
            if t == 1:
                out["moment_norms"] = {
                    k: float(v) * opt["first_moment_scale"]
                    for k, v in norms.items()}
        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
        out["dparam_norms"] = {k: float(norm(params[k], start[k]))
                               for k in params}
    return out

"""Fused single-pass MoE router kernel (Pallas TPU).

The reference router chain (parallel/moe.py, ``router_impl="reference"``)
computes softmax -> ``lax.top_k`` -> gate renormalization -> logsumexp ->
``probs.mean(0)`` as separate XLA ops, each re-reading the fp32 ``[T, E]``
logits/probs from HBM. This kernel makes ONE VMEM-resident pass over a
``[block_tokens, E]`` logits tile and emits everything the MoE block needs
downstream:

- ``gate_vals`` ``[T, k]`` — renormalized top-k gate weights,
- ``expert_idx`` ``[T, k]`` int32 — chosen experts, ``lax.top_k`` order
  (ties broken toward the lower expert index, matching XLA),
- ``lse`` ``[T]`` — logsumexp of the logits (the z-loss input),
- ``probs_mean`` ``[E]`` — mean router probability per expert (the aux-loss
  ``me`` term), accumulated across the sequential grid.

The top-k is k rounds of first-occurrence argmax (max, then min-index among
maxima, then mask) — identical selection and tie order to ``lax.top_k``.

Backward is a plain-XLA ``custom_vjp`` that recomputes the softmax from the
saved logits and composes the gate-renormalization, top-k scatter,
``probs_mean``, logsumexp, and softmax VJPs in one expression — exactly the
cotangent the reference chain's AD produces (equivalence-tested in
tests/test_moe_router.py). A Pallas backward is a chip-A/B follow-up; the
[T, E] recompute is tiny next to the expert FFNs.

On the ``cpu`` platform the kernel runs in interpret mode (numerically the
same program), so CPU tests/dryruns validate the real kernel body
(``ops/backend.py``). Output layouts are kept at
their logical shapes (``[T, k]``, ``[T, 1]``); lane-padding them for Mosaic
is part of the chip A/B, not correctness.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.ops import backend


def _block_tokens(n_tokens: int) -> int:
    """Largest nice power-of-two row block; ragged sizes pad the last block."""
    for bt in (512, 256, 128, 64, 32, 16, 8):
        if n_tokens % bt == 0:
            return bt
    return min(n_tokens, 512)


def _router_kernel(logits_ref, gate_ref, idx_ref, lse_ref, pm_ref, *,
                   top_k: int, n_tokens: int, block_tokens: int,
                   num_experts: int):
    i = pl.program_id(0)
    x = logits_ref[...].astype(jnp.float32)                  # [bt, E]
    m = jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x - m)
    se = jnp.sum(ex, axis=-1, keepdims=True)
    probs = ex / se
    lse_ref[...] = m + jnp.log(se)

    # k rounds of first-occurrence argmax == lax.top_k incl. tie order.
    eidx = jax.lax.broadcasted_iota(jnp.int32, probs.shape, 1)
    avail = probs
    gates, idxs = [], []
    for _ in range(top_k):
        mj = jnp.max(avail, axis=-1, keepdims=True)
        aj = jnp.min(jnp.where(avail == mj, eidx, num_experts),
                     axis=-1, keepdims=True)
        gates.append(mj)
        idxs.append(aj)
        avail = jnp.where(eidx == aj, -jnp.inf, avail)
    g = jnp.concatenate(gates, axis=-1)                      # [bt, k]
    gate_ref[...] = g / jnp.maximum(jnp.sum(g, -1, keepdims=True), 1e-9)
    idx_ref[...] = jnp.concatenate(idxs, axis=-1)

    # probs.mean(0) accumulated across the (sequential) grid; padded rows
    # of a ragged final block are masked out of the sum.
    row = (i * block_tokens
           + jax.lax.broadcasted_iota(jnp.int32, (probs.shape[0], 1), 0))
    contrib = jnp.sum(jnp.where(row < n_tokens, probs, 0.0),
                      axis=0, keepdims=True) / n_tokens

    @pl.when(i == 0)
    def _init():
        pm_ref[...] = jnp.zeros_like(pm_ref)

    pm_ref[...] += contrib


def _fused_router_call(logits, top_k: int):
    T, E = logits.shape
    bt = _block_tokens(T)
    Tp = -(-T // bt) * bt
    logits_p = logits if Tp == T else jnp.zeros(
        (Tp, E), logits.dtype).at[:T].set(logits)
    kernel = functools.partial(_router_kernel, top_k=top_k, n_tokens=T,
                               block_tokens=bt, num_experts=E)
    call = pl.pallas_call(
        kernel,
        name="fused_router",
        grid=(Tp // bt,),
        in_specs=[pl.BlockSpec((bt, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, top_k), lambda i: (i, 0)),
            pl.BlockSpec((bt, top_k), lambda i: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),   # revisited accumulator
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, top_k), jnp.float32),
            jax.ShapeDtypeStruct((Tp, top_k), jnp.int32),
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
        ],
        # The pm accumulator needs the grid walked in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=not backend.on_tpu(),
    )
    # probs_mean accumulates over ALL tokens, so under a mesh every device
    # runs the kernel over the whole (small) [T, E] logits: P() specs.
    gate, idx, lse, pm = mesh_lib.manual_call(
        call, logits_p, in_specs=P(), out_specs=P())
    return gate[:T], idx[:T], lse[:T, 0], pm[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def fused_router(logits, top_k: int):
    """Single-pass router: (gate_vals, expert_idx, lse, probs_mean).

    ``logits``: [T, E] fp32 router logits. Differentiable in ``gate_vals``,
    ``lse`` and ``probs_mean``; ``expert_idx`` is integral.
    """
    return _fused_router_call(logits, top_k)


def _fused_router_fwd(logits, top_k: int):
    out = _fused_router_call(logits, top_k)
    return out, (logits, out[1])


def _fused_router_bwd(top_k: int, res, cts):
    logits, idx = res
    dg, _didx, dlse, dpm = cts
    probs = jax.nn.softmax(logits, axis=-1)                  # [T, E]
    T = logits.shape[0]
    # Gate renormalization VJP: v_j = raw_j / G, G = sum(raw) (the 1e-9
    # clamp is inactive for softmax outputs — top-1 prob >= 1/E).
    raw = jnp.take_along_axis(probs, idx, axis=1)            # [T, k]
    denom = jnp.maximum(raw.sum(-1, keepdims=True), 1e-9)
    v = raw / denom
    draw = (dg - jnp.sum(dg * v, -1, keepdims=True)) / denom
    # top-k selection VJP: scatter the raw-gate cotangents (expert indices
    # are distinct per token, so no collisions)...
    dprobs = jnp.zeros_like(probs).at[
        jnp.arange(T)[:, None], idx].add(draw)
    # ...plus the probs_mean term, then one softmax VJP over the sum.
    dprobs = dprobs + dpm[None, :] / T
    dlogits = probs * (dprobs - jnp.sum(dprobs * probs, -1, keepdims=True))
    # logsumexp VJP: d lse / d logits = probs.
    dlogits = dlogits + probs * dlse[:, None]
    return (dlogits,)


fused_router.defvjp(_fused_router_fwd, _fused_router_bwd)

"""In-step metrics (loss/accuracy) and MFU accounting.

Metric reduction happens *inside* the compiled step over the sharded batch
(reference: ``dist.all_reduce(metric_sum)`` after the fact, SURVEY.md §3.3) —
with GSPMD, ``jnp.sum`` over a batch-sharded array already is the global
reduction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def _integer_ce(logits, labels):
    """Per-element integer-label CE that never materializes fp32 logits.

    The optax formulation upcasts + max-shifts the whole logits tensor
    first; with two consumers (gather and exp-sum) XLA materializes the
    shifted ``f32[B,S,V]`` in HBM — measured 3.3 GB/step and ~9 ms of the
    GPT-2 vocab slice (xplane: ``%fusion.3236`` writing f32[16,1024,50257]).
    Here every large elementwise op has exactly one reduction consumer, so
    each fuses into its reduce and only the bf16 model logits are ever
    resident: the label term uses an iota==label mask (whose gradient is
    elementwise, not a scatter), the lse shift uses a stop-gradient max,
    and fp32 happens per-element inside the fusions.
    """
    f32 = jnp.float32
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1).astype(f32))
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    onehot_mask = iota == labels[..., None]
    label_logit = jnp.sum(
        jnp.where(onehot_mask, logits.astype(f32), 0.0), axis=-1)
    sumexp = jnp.sum(
        jnp.exp(logits.astype(f32) - m[..., None]), axis=-1)
    return jnp.log(sumexp) + m - label_logit


def cross_entropy(logits, labels, label_smoothing: float = 0.0):
    """Mean softmax CE over the (possibly sharded) batch, fp32 accumulation."""
    if label_smoothing > 0.0:
        logits = logits.astype(jnp.float32)
        onehot = optax.smooth_labels(
            jax.nn.one_hot(labels, logits.shape[-1]), label_smoothing
        )
        losses = optax.softmax_cross_entropy(logits, onehot)
    else:
        losses = _integer_ce(logits, labels)
    return losses.mean()


def per_example_cross_entropy(logits, labels):
    """Unreduced CE per example/token (fp32)."""
    return _integer_ce(logits, labels)


def topk_correct(logits, labels, ks=(1, 5), mask=None):
    """Count of top-k correct predictions (summed over the global batch).

    ``mask`` (float [batch]) zeroes out padded examples in the final eval
    batch (the DistributedSampler wrap-around analog).
    """
    out = {}
    maxk = max(ks)
    maxk = min(maxk, logits.shape[-1])
    _, pred = jax.lax.top_k(logits, maxk)
    hit = pred == labels[..., None]
    for k in ks:
        correct = hit[..., : min(k, maxk)].any(-1)
        if mask is not None:
            out[f"top{k}"] = jnp.sum(correct.astype(jnp.float32) * mask)
        else:
            out[f"top{k}"] = jnp.sum(correct)
    return out


# ---------------------------------------------------------------------------
# MFU — the driver metric (BASELINE.json): achieved FLOP/s vs peak.
# ---------------------------------------------------------------------------

#: Peak dense bf16 FLOP/s per chip by device kind (public spec sheets).
#: A TPU kind that is not in the tables is an error, never a default.
PEAK_FLOPS = {
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,  # v5e
    "tpu v5": 459e12,       # v5p
    "tpu v5p": 459e12,
    "tpu v6 lite": 918e12,  # trillium
}

#: HBM bandwidth per chip (GB/s) — the other roofline axis.
PEAK_HBM_GBPS = {
    "tpu v4": 1228.0,
    "tpu v5 lite": 819.0,   # v5e
    "tpu v5": 2765.0,       # v5p
    "tpu v5p": 2765.0,
    "tpu v6 lite": 1640.0,  # trillium
}


def finalize_eval_sums(sums: dict) -> dict:
    """Normalize accumulated eval-step outputs to per-example averages.

    ``eval_step`` emits mask-weighted ``*_sum`` metrics plus a ``count``;
    callers accumulate them across batches and call this once. Shared by
    the trainer's evaluate loop and the convergence harness's
    seen-samples probe so the key convention lives in one place.
    """
    count = max(sums.pop("count", 0.0), 1.0)
    return {k.removesuffix("_sum"): v / count for k, v in sums.items()}


def _peak(table: dict, device) -> float | None:
    """``table``'s entry for ``device``; None on platform ``cpu`` (device
    utilization is not measured there); an unknown kind raises."""
    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    for key, val in table.items():
        if key in kind:
            return val
    raise ValueError(
        f"no published peak for device kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to utils/metrics.py with "
        "its source instead of measuring against a default")


def peak_hbm_gbps(device=None) -> float | None:
    return _peak(PEAK_HBM_GBPS, device)


def peak_flops_per_chip(device=None) -> float | None:
    return _peak(PEAK_FLOPS, device)


def training_flops_per_example(fwd_flops: float) -> float:
    """fwd + bwd ~= 3x forward (bwd is 2x: grads wrt activations and params)."""
    return 3.0 * fwd_flops


def mfu(examples_per_sec_per_chip: float, fwd_flops_per_example: float,
        device=None) -> float | None:
    """Model FLOP/s utilization; None ("not measured") on platform cpu."""
    peak = peak_flops_per_chip(device)
    if peak is None:
        return None
    achieved = examples_per_sec_per_chip * training_flops_per_example(fwd_flops_per_example)
    return achieved / peak


def transformer_flops_per_token(n_params: int, seq_len: int, n_layers: int,
                                d_model: int) -> float:
    """Forward FLOPs/token: 2*N plus attention's 2*2*L*s*d (PaLM appendix-B style)."""
    return 2.0 * n_params + 4.0 * n_layers * seq_len * d_model

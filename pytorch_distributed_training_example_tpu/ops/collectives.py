"""Thin axis-name wrappers over XLA collectives — SURVEY.md §2d.

The communication backend IS the XLA partitioner: there is no user-space
transport (the NCCL replacement is compiled ICI/DCN collectives). These
wrappers exist for ``shard_map`` code (ring attention, pipeline, manual
reductions) so call sites read like the c10d API the reference uses, and for
host-level reductions used by logging/eval.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def axis_size(axis: str) -> int:
    """Static mesh-axis size inside ``shard_map``."""
    return int(jax.lax.axis_size(axis))


def all_reduce(x, axis: str | Sequence[str]):
    """Sum across a mesh axis (reference: ``dist.all_reduce``)."""
    return jax.lax.psum(x, axis)


def all_reduce_mean(x, axis: str | Sequence[str]):
    return jax.lax.pmean(x, axis)


def all_gather(x, axis: str, *, axis_index: int = 0, tiled: bool = True):
    """Concatenate shards along ``axis_index`` (reference: ``all_gather``)."""
    return jax.lax.all_gather(x, axis, axis=axis_index, tiled=tiled)


def reduce_scatter(x, axis: str, *, axis_index: int = 0):
    """Sum then scatter along ``axis_index`` (the ZeRO grad primitive)."""
    return jax.lax.psum_scatter(x, axis, scatter_dimension=axis_index,
                                tiled=True)


def ring_shift(x, axis: str, *, reverse: bool = False):
    """Send to the next ring neighbor over ICI (ppermute convenience)."""
    n = axis_size(axis)
    if reverse:
        perm = [(i, (i - 1) % n) for i in range(n)]
    else:
        perm = [(i, (i + 1) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis, perm)


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int):
    """Transpose sharding between two array dims (Ulysses/MoE primitive)."""
    return jax.lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def all_to_all_blocks(x, axis: str, *, impl: str = "native"):
    """Block all-to-all: ``x[q]`` goes to device q, returns ``out[s]`` from s.

    ``x`` is ``[n, ...]`` with one leading block per destination on the
    ``axis`` mesh axis (size n); the result has the same shape with block
    ``s`` holding what source device s addressed to this device. This is
    the MoE expert-dispatch primitive (GShard's token all-to-all).

    ``impl``:

    - ``"native"`` — ``lax.all_to_all``. Verified to compute correctly
      under the gloo CPU cross-process backend (r12 gangs), so it is the
      default everywhere including host-mesh dryruns.
    - ``"ppermute"`` — decomposed into n-1 ``ppermute`` hops (each shift k
      sends block ``(i+k) mod n`` to peer ``i+k``). Kept as a
      gloo/older-jaxlib safety hatch and as a directly testable oracle for
      the native path (tests/test_moe_dropless.py); byte volume is
      identical, latency is n-1 serialized hops instead of one fused op.

    Must be called inside ``shard_map`` (manual axis context).
    """
    n = axis_size(axis)
    if impl == "native":
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
    if impl != "ppermute":
        raise ValueError(
            f"unknown all_to_all_blocks impl {impl!r}; have ['native', "
            "'ppermute']")
    idx = jax.lax.axis_index(axis)
    # out[idx] = my own block addressed to myself (no hop).
    out = jnp.zeros_like(x)
    out = jax.lax.dynamic_update_slice_in_dim(
        out, jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=0), idx, axis=0)
    for k in range(1, n):
        # Shift k: device i sends its block for peer (i+k) mod n; the block
        # device i receives on this hop therefore came from (i-k) mod n.
        perm = [(i, (i + k) % n) for i in range(n)]
        sent = jax.lax.dynamic_slice_in_dim(x, (idx + k) % n, 1, axis=0)
        recv = jax.lax.ppermute(sent, axis, perm)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, recv, (idx - k) % n, axis=0)
    return out


def broadcast_one_to_all(x, axis: str, *, src: int = 0):
    """Replicate ``src``'s value across the axis (reference: ``broadcast``)."""
    idx = jax.lax.axis_index(axis)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis)


# Host-level (cross-process, outside jit) ----------------------------------


def host_all_reduce_sum(x):
    """Sum a small host value across processes (logging/eval convenience)."""
    from jax.experimental import multihost_utils

    return multihost_utils.process_allgather(jnp.asarray(x)).sum(0)

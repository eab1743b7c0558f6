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

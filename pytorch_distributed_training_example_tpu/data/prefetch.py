"""Host->HBM prefetch: build globally-sharded batches ahead of the step.

Reference parity (SURVEY.md §2b N5/N7): torch overlaps H2D with compute via
pinned memory + CUDA streams. On TPU, ``jax.device_put`` is asynchronous and
the step itself is dispatched ahead, so a small look-ahead window (putting
the next batch while the current step runs) gives the same overlap. Each host
contributes its local slice; ``jax.make_array_from_process_local_data``
assembles the logical global batch across hosts.
"""

from __future__ import annotations

import collections
from typing import Iterable, Iterator

import jax
import numpy as np
from jax.sharding import NamedSharding

from pytorch_distributed_training_example_tpu.utils import telemetry


def pad_batch(batch: dict, target: int) -> dict:
    """Pad a short final batch up to ``target`` rows and attach a 0/1 ``mask``.

    Keeps every batch the same (static) shape — one compiled program, no
    per-remainder recompiles — while eval metrics stay exact via the mask.
    """
    n = next(iter(batch.values())).shape[0]
    mask = batch.get("mask", np.ones(n, np.float32))
    if n == target:
        return {**batch, "mask": mask}
    if n > target:
        raise ValueError(f"batch of {n} exceeds target {target}")
    pad = target - n

    def pad_rows(x):
        reps = np.repeat(x[:1], pad, axis=0)
        return np.concatenate([x, reps], axis=0)

    out = {k: pad_rows(np.asarray(v)) for k, v in batch.items() if k != "mask"}
    out["mask"] = np.concatenate([mask, np.zeros(pad, np.float32)])
    return out


def shard_batch(batch: dict, sharding: NamedSharding) -> dict:
    """Turn a per-host numpy batch into a globally-sharded jax.Array batch."""

    def put(x):
        nd_sharding = sharding
        if x.ndim != len(sharding.spec):
            from jax.sharding import PartitionSpec as P

            spec = list(sharding.spec) + [None] * (x.ndim - len(sharding.spec))
            nd_sharding = NamedSharding(sharding.mesh, P(*spec[: max(x.ndim, 1)]))
        if jax.process_count() == 1:
            return jax.device_put(x, nd_sharding)
        return jax.make_array_from_process_local_data(nd_sharding, x)

    return {k: put(v) for k, v in batch.items()}


def device_prefetch(
    it: Iterable[dict], sharding: NamedSharding, lookahead: int = 2
) -> Iterator[dict]:
    """Yield sharded device batches, keeping ``lookahead`` in flight.

    Where the caller's ``next()`` waits is recorded on the process's span
    recorder: ``loader_wait`` (blocked on the host loader) and ``device_put``
    (the host-to-device put). Both run inside the trainer's ``input_wait``
    span, which is their parent; neither counts toward goodput by itself.
    """
    rec = telemetry.recorder()
    it = iter(it)
    buf: collections.deque = collections.deque()

    def fetch() -> bool:
        with rec.span("loader_wait", bucket=None):
            try:
                batch = next(it)
            except StopIteration:
                return False
        with rec.span("device_put", bucket=None):
            buf.append(shard_batch(batch, sharding))
        return True

    more = True
    for _ in range(lookahead):
        more = more and fetch()
    while buf:
        out = buf.popleft()
        more = more and fetch()
        yield out

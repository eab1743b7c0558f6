"""Threaded batch loader — the ``DataLoader(num_workers=...)`` equivalent.

Reference parity (SURVEY.md §2b N7): torch's loader forks worker *processes*
because Python-side decode is GIL-bound. Here batch assembly is numpy slicing
/ light augmentation, so a thread pool (optionally backed by the C++ prefetch
runtime in ``native/``) suffices: worker threads materialize batches ahead of
the training loop into a bounded queue, and the device prefetcher
(:mod:`prefetch`) overlaps host->HBM transfer with the running step.
"""

from __future__ import annotations

import json
import os
import queue
import threading
from typing import Iterator

import numpy as np

from pytorch_distributed_training_example_tpu.data.sampler import ShardedSampler
from pytorch_distributed_training_example_tpu.utils import telemetry

# Debug/verification hook: when this env var names a file, every loader
# appends one JSON line per YIELDED batch ({"epoch", "batch", "indices"}).
# Used by the mid-epoch-resume test to assert sample-exact continuation
# (no replay, no skip). In multi-process runs every rank would otherwise
# interleave appends into one file, so the path is suffixed ".rankN" when
# jax reports more than one process.
INDEX_LOG_ENV = "PDTX_INDEX_LOG"

def dp_shard(nproc: int, dp: int, process_index: int) -> tuple[int, int]:
    """Loader (shards, rank) for a host in a gang with non-data axes in the
    mesh — the DistributedSampler coordinate contract.

    A process must feed rows for its **data-parallel coordinate**, not its
    process index: with seq/pp/ep/tp axes in the mesh the batch dim
    replicates across some or all processes, and
    ``make_array_from_process_local_data`` assumes every process in a
    replica group supplies IDENTICAL rows. Device order is dp-major, so the
    ``nproc / dp`` processes holding one dp coordinate form a contiguous
    run of process indices — e.g. a 2-process dp1 x seq2 gang maps both
    ranks to coordinate 0 and they read the SAME sample stream.

    ``nproc <= dp`` is the plain multi-host data-parallel case (each host
    feeds its own slice); otherwise ``nproc`` must be a multiple of ``dp``
    so every host maps to exactly one dp replica group.
    """
    if nproc <= dp:
        return nproc, process_index
    if nproc % dp:
        raise ValueError(
            f"process count {nproc} must be a multiple of the data-parallel "
            f"degree {dp} (mesh data x fsdp) so every host maps to one dp "
            "replica group")
    return dp, process_index * dp // nproc


# Process-wide yield-time hook: ``hook(epoch, batch_idx, batch) -> batch``,
# applied by every loader (python and native paths) right after index
# logging. The chaos harness (utils/chaos.py) uses it to poison or stall
# specific batches deterministically — keyed on the batch INDEX, so prefetch
# lookahead does not shift which batch gets hit.
_batch_hook = None


def set_batch_hook(fn) -> None:
    global _batch_hook
    _batch_hook = fn


def _apply_batch_hook(epoch: int, batch: int, item):
    return _batch_hook(epoch, batch, item) if _batch_hook is not None else item


def _log_indices(epoch: int, batch: int, indices) -> None:
    path = os.environ.get(INDEX_LOG_ENV)
    if not path:
        return
    try:  # lazy: the loader is importable (and testable) without jax init
        import jax

        if jax.process_count() > 1:
            path = f"{path}.rank{jax.process_index()}"
    except ImportError:
        pass
    with open(path, "a") as fh:
        fh.write(json.dumps({"epoch": int(epoch), "batch": int(batch),
                             "indices": [int(i) for i in indices]}) + "\n")


class _WorkerError:
    """Wraps a worker-thread exception for re-raise in the consumer
    (torch DataLoader's ExceptionWrapper behavior)."""

    def __init__(self, exc: BaseException):
        self.exc = exc


def collate(samples: list[dict]) -> dict[str, np.ndarray]:
    out = {}
    for key in samples[0]:
        vals = [s[key] for s in samples]
        out[key] = np.stack(vals) if np.ndim(vals[0]) else np.asarray(vals)
    return out


def build_image_loader(dataset, sampler, batch_size: int, workers: int = 0,
                       native: bool = True):
    """Pick the fastest available train loader for a dataset.

    One decision point shared by the trainer and the benchmarks: the native
    C++ engine serves in-memory uint8 arrays (``images_u8``, CIFAR),
    all-JPEG directory trees (``jpeg_paths``, ImageNet), and memmapped token
    files (``tokens`` + ``seq_len``, LM); everything else — including trees
    with non-JPEG files, which the native decoder would zero-fill — falls
    back to the Python :class:`DataLoader`.
    """
    from pytorch_distributed_training_example_tpu.data import native_loader

    augment = bool(getattr(dataset, "augment", False))
    if native and native_loader.available():
        if hasattr(dataset, "images_u8"):
            return native_loader.NativeDataLoader(
                dataset.images_u8, dataset.labels, sampler, batch_size,
                dataset.mean, dataset.std, augment=augment,
                num_threads=max(workers, 1))
        paths = getattr(dataset, "jpeg_paths", None)
        if paths and all(p.lower().endswith((".jpg", ".jpeg")) for p in paths):
            try:
                return native_loader.NativeDataLoader.jpeg(
                    paths, dataset.labels, sampler, batch_size,
                    dataset.image_size, dataset.mean, dataset.std,
                    augment=augment, num_threads=max(workers, 1))
            except RuntimeError:  # engine built without libjpeg
                pass
        if hasattr(dataset, "tokens") and hasattr(dataset, "seq_len"):
            return native_loader.NativeDataLoader.tokens(
                dataset.tokens, dataset.seq_len, sampler, batch_size,
                num_threads=max(workers, 1))
    return DataLoader(dataset, batch_size, sampler, num_workers=workers)


class DataLoader:
    """Iterates per-host batches of stacked numpy arrays.

    ``batch_size`` is the *per-host* batch (global batch / process count);
    the sampler hands this host its index shard, mirroring the reference's
    per-rank ``DistributedSampler`` slice.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        sampler: ShardedSampler | None = None,
        num_workers: int = 0,
        drop_last: bool = True,
        prefetch_batches: int = 4,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = sampler or ShardedSampler(len(dataset), shuffle=False)
        self.num_workers = num_workers
        self.drop_last = drop_last
        self.prefetch_batches = prefetch_batches
        # Mid-epoch resume: skip this many leading batches of the epoch's
        # index stream (never decoded, not just dropped). The trainer sets
        # it for the resumed epoch and resets it to 0 for later epochs.
        self.start_batch = 0

    def set_epoch(self, epoch: int) -> None:
        self.sampler.set_epoch(epoch)
        if hasattr(self.dataset, "epoch"):
            self.dataset.epoch = epoch  # augmentations reseed per epoch

    def _batches_of_indices(self, start: int = 0):
        idx = self.sampler.local_indices()
        n_full = len(idx) // self.batch_size
        for b in range(start, n_full):
            yield idx[b * self.batch_size : (b + 1) * self.batch_size]
        rem = len(idx) - n_full * self.batch_size
        if rem and not self.drop_last and start <= n_full:
            yield idx[n_full * self.batch_size :]

    def __len__(self) -> int:
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def _make_batch(self, indices) -> dict[str, np.ndarray]:
        return collate([self.dataset[int(i)] for i in indices])

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        start = self.start_batch
        if self.num_workers <= 0:
            rec = telemetry.recorder()
            for b, indices in enumerate(self._batches_of_indices(start), start):
                _log_indices(self.sampler.epoch, b, indices)
                with rec.span("make_batch", step=b, bucket=None):
                    batch = self._make_batch(indices)
                yield _apply_batch_hook(self.sampler.epoch, b, batch)
            return
        yield from self._threaded_iter(start)

    def _threaded_iter(self, start: int = 0):
        # Ordered hand-off: each worker owns batch b where b % W == worker_id,
        # writing into a per-batch slot so batch order is deterministic.
        index_batches = list(self._batches_of_indices(start))
        out_q: list[queue.Queue] = [queue.Queue(maxsize=1) for _ in index_batches]
        # Look-ahead window, granted IN BATCH ORDER: a worker may build batch
        # b only while b < consumed + window. An unordered permit pool (a
        # semaphore) deadlocks: workers of later batches can take every
        # permit while the worker of the batch the consumer is blocked on
        # starves. window >= num_workers keeps that batch always admitted.
        window = max(self.prefetch_batches, self.num_workers)
        consumed = 0
        rec = telemetry.recorder()
        cond = threading.Condition()
        stop = threading.Event()

        def worker(wid: int):
            for b in range(wid, len(index_batches), self.num_workers):
                with cond:
                    cond.wait_for(
                        lambda: stop.is_set() or b < consumed + window)
                if stop.is_set():
                    return
                try:
                    # on the worker's own thread; the batch index is its id
                    with rec.span("make_batch", step=start + b, bucket=None):
                        batch = self._make_batch(index_batches[b])
                    out_q[b].put(batch)
                except BaseException as e:  # re-raised in the consumer
                    out_q[b].put(_WorkerError(e))
                    return

        threads = [
            threading.Thread(target=worker, args=(w,), daemon=True)
            for w in range(self.num_workers)
        ]
        for t in threads:
            t.start()
        try:
            for b in range(len(index_batches)):
                # finished batches waiting when the consumer asks for batch
                # b: 0 means it is about to block on the workers
                rec.count("loader.ready_depth",
                          sum(not q.empty() for q in out_q[b:b + window]),
                          step=start + b)
                item = out_q[b].get()
                if isinstance(item, _WorkerError):
                    raise RuntimeError(
                        f"DataLoader worker failed on batch {b}") from item.exc
                _log_indices(self.sampler.epoch, start + b, index_batches[b])
                yield _apply_batch_hook(self.sampler.epoch, start + b, item)
                with cond:
                    consumed = b + 1
                    cond.notify_all()
        finally:
            stop.set()
            with cond:  # unblock any workers parked on the window
                cond.notify_all()

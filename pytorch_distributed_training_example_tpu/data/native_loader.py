"""ctypes bindings for the C++ batch engine (native/batch_engine.cc).

The native path replaces the Python hot loop for memory-resident datasets:
sample gather + augmentation + normalization run on C++ threads with the GIL
released, double-buffered ahead of the train loop. Python keeps orchestration
(index order from :class:`ShardedSampler`) so determinism semantics are
identical to the pure-Python loader — tested against it bit-for-bit in
gather mode (augmentation RNG differs by design).

``available()`` is False, with a loud log line, when ``make`` cannot build
the library; the pure-Python loader is always the reference implementation.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

from pytorch_distributed_training_example_tpu.data import loader as loader_lib

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libbatch_engine.so"))

log = logging.getLogger(__name__)

_lib = None
_lib_lock = threading.Lock()


def _load() -> ctypes.CDLL | None:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        # ALWAYS invoke make (incremental: a no-op when the .so is newer than
        # batch_engine.cc). The library is untracked, so a checkout can leave
        # a stale binary with an old C ABI next to newer sources — loading it
        # would mis-stride gathers instead of erroring. So only a library
        # this process just built, or make found up to date, is ever loaded:
        # a failed build means the Python loader, whatever .so lies there.
        # An flock serializes concurrent ranks (launch.py spawns N processes
        # that would otherwise race the compiler on the same output file).
        try:
            import fcntl

            with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lk:
                fcntl.flock(lk, fcntl.LOCK_EX)
                subprocess.run(["make", "-C", os.path.abspath(_NATIVE_DIR)],
                               check=True, capture_output=True, timeout=120)
        except Exception as e:
            detail = getattr(e, "stderr", b"") or b""
            log.error(
                "native batch engine NOT built (%s: %s) — the input pipeline "
                "runs on the Python loader%s", type(e).__name__, e,
                (": " + detail.decode(errors="replace")[-500:]) if detail
                else "")
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            return None
        try:
            lib.be_abi_version.restype = ctypes.c_int64
            if lib.be_abi_version() != 2:
                return None
        except AttributeError:  # pre-versioning binary
            return None
        lib.be_create_image.restype = ctypes.c_void_p
        lib.be_create_image.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.c_int, ctypes.c_int]
        lib.be_create_gather.restype = ctypes.c_void_p
        lib.be_create_gather.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_int,
                                         ctypes.c_int64]
        lib.be_create_jpeg.restype = ctypes.c_void_p
        lib.be_create_jpeg.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.c_int, ctypes.c_int]
        lib.be_decode_errors.restype = ctypes.c_int64
        lib.be_decode_errors.argtypes = [ctypes.c_void_p]
        lib.be_submit.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                  ctypes.POINTER(ctypes.c_int64),
                                  ctypes.c_int64, ctypes.c_void_p,
                                  ctypes.c_uint64]
        lib.be_wait.restype = ctypes.c_int
        lib.be_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
        lib.be_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class NativeBatchEngine:
    """Thin RAII wrapper; one engine per (dataset, mode)."""

    def __init__(self, handle, lib, sample_shape, out_dtype,
                 num_threads: int = 1, chunked: bool = False):
        self._handle = handle
        self._lib = lib
        self.sample_shape = sample_shape
        self.out_dtype = out_dtype
        self.num_threads = num_threads
        # One engine job runs on ONE worker thread; expensive per-sample work
        # (JPEG decode) must be submitted in per-thread chunks or parallelism
        # caps at the number of in-flight jobs instead of num_threads.
        self.chunked = chunked
        self._keepalive = []  # buffers the C++ side reads from

    @classmethod
    def image(cls, data_u8: np.ndarray, mean, std, augment: bool,
              num_threads: int = 2) -> "NativeBatchEngine":
        lib = _load()
        assert lib is not None
        data_u8 = np.ascontiguousarray(data_u8, np.uint8)
        n, h, w, c = data_u8.shape
        mean_arr = (ctypes.c_float * c)(*[float(m) for m in mean])
        std_arr = (ctypes.c_float * c)(*[float(s) for s in std])
        handle = lib.be_create_image(
            data_u8.ctypes.data_as(ctypes.c_void_p), n, h, w, c,
            mean_arr, std_arr, int(augment), num_threads)
        eng = cls(handle, lib, (h, w, c), np.float32, num_threads=num_threads)
        eng._keepalive.append(data_u8)
        return eng

    @classmethod
    def jpeg(cls, paths: list, image_size: int, mean, std, augment: bool,
             num_threads: int = 2) -> "NativeBatchEngine":
        """File-decode engine (native/batch_engine.cc jpeg mode).

        Raises RuntimeError when the library was built without libjpeg.
        """
        lib = _load()
        assert lib is not None
        encoded = [p.encode("utf-8") for p in paths]
        offsets = np.zeros(len(encoded) + 1, np.int64)
        np.cumsum([len(p) for p in encoded], out=offsets[1:])
        blob = b"".join(encoded)
        mean_arr = (ctypes.c_float * 3)(*[float(m) for m in mean])
        std_arr = (ctypes.c_float * 3)(*[float(s) for s in std])
        handle = lib.be_create_jpeg(
            blob, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(encoded), image_size, mean_arr, std_arr, int(augment),
            num_threads)
        if not handle:
            raise RuntimeError("batch engine built without libjpeg support")
        return cls(handle, lib, (image_size, image_size, 3), np.float32,
                   num_threads=num_threads, chunked=True)

    def decode_errors(self) -> int:
        return int(self._lib.be_decode_errors(self._handle))

    @classmethod
    def gather(cls, data: np.ndarray, num_threads: int = 2) -> "NativeBatchEngine":
        lib = _load()
        assert lib is not None
        data = np.ascontiguousarray(data)
        n = data.shape[0]
        sample_bytes = int(data.nbytes // n)
        handle = lib.be_create_gather(
            data.ctypes.data_as(ctypes.c_void_p), n, sample_bytes, num_threads,
            0)
        eng = cls(handle, lib, data.shape[1:], data.dtype,
                  num_threads=num_threads)
        eng._keepalive.append(data)
        return eng

    @classmethod
    def gather_windows(cls, flat: np.ndarray, num_samples: int,
                       window: int, stride: int,
                       num_threads: int = 2) -> "NativeBatchEngine":
        """Overlapping-window gather over a flat 1-D array (LM token files):
        sample i = flat[i*stride : i*stride + window]."""
        lib = _load()
        assert lib is not None
        assert flat.ndim == 1 and flat.flags["C_CONTIGUOUS"]
        item = flat.dtype.itemsize
        handle = lib.be_create_gather(
            flat.ctypes.data_as(ctypes.c_void_p), num_samples, window * item,
            num_threads, stride * item)
        eng = cls(handle, lib, (window,), flat.dtype, num_threads=num_threads)
        eng._keepalive.append(flat)
        return eng

    def submit(self, batch_id: int, indices: np.ndarray, out: np.ndarray,
               seed: int = 0):
        idx = np.ascontiguousarray(indices, np.int64)
        self._keepalive_batch = idx  # released after wait
        self._lib.be_submit(
            self._handle, batch_id,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(idx),
            out.ctypes.data_as(ctypes.c_void_p), seed & 0xFFFFFFFFFFFFFFFF)

    def wait(self, batch_id: int, timeout_ms: int = 60000):
        rc = self._lib.be_wait(self._handle, batch_id, timeout_ms)
        if rc != 0:
            raise TimeoutError(f"native batch {batch_id} not ready in {timeout_ms}ms")

    def close(self):
        if self._handle:
            self._lib.be_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeDataLoader:
    """DataLoader-compatible iterator backed by the C++ engine.

    Works for array-backed datasets exposing ``.images``/``.labels`` (CIFAR)
    or ``.tokens`` memmaps; double-buffers ``prefetch`` batches ahead.
    """

    def __init__(self, images_u8, labels, sampler, batch_size: int,
                 mean, std, augment: bool, num_threads: int = 2,
                 prefetch: int = 4, drop_last: bool = True, engine=None):
        if not drop_last:
            # The engine writes into fixed-size buffers; a short final batch
            # would leave stale tail rows. Use the Python loader for that.
            raise ValueError("NativeDataLoader requires drop_last=True")
        self.engine = engine if engine is not None else NativeBatchEngine.image(
            images_u8, mean, std, augment, num_threads)
        self.labels = np.asarray(labels)
        self.sampler = sampler
        self.batch_size = batch_size
        self.prefetch = prefetch
        self.epoch = 0
        # Mid-epoch resume: first batch of the epoch to produce (same
        # contract as loader.DataLoader.start_batch — skipped batches are
        # never submitted to the engine).
        self.start_batch = 0
        self._next_id = 0  # globally monotonic: ids never reused across epochs

    @classmethod
    def jpeg(cls, paths: list, labels, sampler, batch_size: int,
             image_size: int, mean, std, augment: bool, num_threads: int = 2,
             prefetch: int = 4) -> "NativeDataLoader":
        """Loader over a FolderDataset's files via the native decode engine."""
        engine = NativeBatchEngine.jpeg(paths, image_size, mean, std, augment,
                                        num_threads)
        return cls(None, labels, sampler, batch_size, None, None, augment,
                   num_threads, prefetch, engine=engine)

    @classmethod
    def tokens(cls, tokens_flat: np.ndarray, seq_len: int, sampler,
               batch_size: int, num_threads: int = 2,
               prefetch: int = 4) -> "NativeTokenDataLoader":
        """Loader over a flat token file via the native window-gather engine."""
        num_samples = (len(tokens_flat) - 1) // seq_len
        engine = NativeBatchEngine.gather_windows(
            np.ascontiguousarray(tokens_flat), num_samples, seq_len + 1,
            seq_len, num_threads)
        return NativeTokenDataLoader(
            None, None, sampler, batch_size, None, None, False,
            num_threads, prefetch, engine=engine)

    def set_epoch(self, epoch: int):
        self.epoch = epoch
        self.sampler.set_epoch(epoch)

    def __len__(self):
        return len(self.sampler) // self.batch_size

    def _emit(self, buf: np.ndarray, bi: np.ndarray) -> dict:
        """Turn a filled engine buffer + its sample indices into a batch."""
        return {"image": buf.copy(),
                "label": self.labels[bi].astype(np.int32)}

    def __iter__(self):
        idx = self.sampler.local_indices()
        nb = len(self)
        bufs = [np.empty((self.batch_size, *self.engine.sample_shape),
                         self.engine.out_dtype)
                for _ in range(self.prefetch)]
        pending: dict[int, tuple[list[int], np.ndarray]] = {}  # b -> (ids, indices)

        # Expensive per-sample engines (JPEG decode) get the batch split
        # into one job per worker thread — a single job runs on a single
        # thread, so batch-granular submission would cap parallelism at the
        # prefetch depth instead of num_threads.
        n_chunks = max(self.engine.num_threads, 1) if self.engine.chunked else 1

        def submit(b):
            lo = b * self.batch_size
            bi = np.ascontiguousarray(idx[lo:lo + self.batch_size], np.int64)
            buf = bufs[b % self.prefetch]
            per = -(-len(bi) // min(n_chunks, len(bi)))
            ids = []
            for j in range(0, len(bi), per):
                cid = self._next_id
                self._next_id += 1
                # Epoch-only seed: the engine keys per-sample RNG on the
                # DATASET index, so augmentation is reproducible across
                # --workers / chunking / batch-size choices.
                self.engine.submit(cid, np.ascontiguousarray(bi[j:j + per]),
                                   buf[j:], seed=self.epoch)
                ids.append(cid)
            pending[b] = (ids, bi)

        start = min(self.start_batch, nb)
        inflight = min(self.prefetch, nb - start)
        for b in range(start, start + inflight):
            submit(b)
        try:
            for b in range(start, nb):
                ids, bi = pending[b]
                for cid in ids:
                    self.engine.wait(cid)
                del pending[b]
                batch = self._emit(bufs[b % self.prefetch], bi)
                if b + inflight < nb:
                    submit(b + inflight)
                loader_lib._log_indices(self.epoch, b, bi)
                yield loader_lib._apply_batch_hook(self.epoch, b, batch)
        finally:
            # Drain in-flight jobs before `bufs` can be garbage-collected:
            # abandoned C++ jobs hold raw pointers into them (use-after-free
            # otherwise when the consumer stops early).
            for ids, _ in pending.values():
                for cid in ids:
                    try:
                        self.engine.wait(cid)
                    except TimeoutError:
                        pass


class NativeTokenDataLoader(NativeDataLoader):
    """Token-file loader on the C++ gather engine (overlapping LM windows).

    Produces the same ``{"tokens", "targets"}`` int32 batches as iterating a
    :class:`~...datasets.TokenFileDataset` through the Python loader — tested
    bit-for-bit — but the window gather runs on engine threads with the GIL
    released, straight off the memmapped file. Construct via
    :meth:`NativeDataLoader.tokens`; all buffering/drain behavior is
    inherited — only batch emission differs.
    """

    def _emit(self, buf: np.ndarray, bi: np.ndarray) -> dict:
        chunk = buf.astype(np.int32)
        return {"tokens": chunk[:, :-1], "targets": chunk[:, 1:]}

"""Sharded checkpoint save/restore with commit markers — the ``torch.save`` /
``--resume`` equivalent (SURVEY.md §3.4, §5).

Reference parity: rank-0 ``torch.save({'model', 'opt', 'epoch'})`` + map_location
restore. TPU-native design (Orbax-style, self-contained implementation):

- every *host* writes only the param shards it addresses (no gather through
  one host — required for FSDP where no host could hold the full model);
- a JSON manifest records each leaf's global shape/dtype and which file holds
  which index-region, so restore works under a *different* sharding/topology
  than save (regions are assembled, then re-placed by ``device_put`` with the
  target NamedSharding);
- a ``COMMIT`` marker is written last (after every host's files are on disk),
  so a crashed half-written checkpoint is never eligible for ``--resume auto``
  (partial-write recovery, SURVEY.md §7 hard part (b));
- file writes run on a background thread (device->host copy is taken
  synchronously first, since the train loop donates state buffers). The
  cross-host commit rendezvous is FILESYSTEM-based (process 0 waits for every
  host's per-host file list to appear) rather than a device collective, so
  multi-host saves stay async too: a device-collective barrier on a
  background thread could interleave with train-step collectives and
  deadlock, and the shared-filesystem assumption is already baked into
  restore's manifest union;
- restore assembles each leaf PER ADDRESSABLE SHARD of the target sharding
  (index-intersecting saved regions with the shard's index) and builds the
  array via ``jax.make_array_from_single_device_arrays`` — peak host memory
  is the host's shard bytes, not the full model (required for FSDP restore
  of models no single host can hold).
"""

from __future__ import annotations

import json
import logging
import os
import re
import shutil
import threading
import time
import zlib
from typing import Any

import jax
import numpy as np

from pytorch_distributed_training_example_tpu.core import distributed
from pytorch_distributed_training_example_tpu.parallel.sharding import param_path
from pytorch_distributed_training_example_tpu.utils import resilience

log = logging.getLogger("pdtx")

COMMIT_FILE = "COMMIT"
MANIFEST_FILE = "manifest.json"
SAVING_SUFFIX = ".saving"  # in-progress attempt dirs (never resume-eligible)
OLD_SUFFIX = ".old"  # prior committed dir set aside during a re-save swap
_STEP_RE = re.compile(r"^step_(\d+)$")


class CheckpointWriteError(RuntimeError):
    """A checkpoint save failed (surfaced by :meth:`Checkpointer.wait`)."""


class CheckpointCorruptError(RuntimeError):
    """A committed checkpoint failed integrity verification on restore."""


def _read_json(path: str):
    """JSON file read, designed to be invoked via ``retriable_io``."""
    with open(path) as fh:
        return json.load(fh)


def _file_crc32(path: str) -> int:
    """Streaming CRC32 of a file's bytes (1 MB chunks).

    File-level (includes the npy header), streamed so integrity verification
    never materializes a full leaf — restore's peak-host-memory contract is
    one SHARD (see ``_assemble_sharded``), and checksumming must not be the
    thing that breaks it.
    """
    crc = 0
    with open(path, "rb") as fh:
        while True:
            chunk = fh.read(1 << 20)
            if not chunk:
                return crc & 0xFFFFFFFF
            crc = zlib.crc32(chunk, crc)


def _is_array_leaf(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray))


def _flatten(state) -> dict[str, Any]:
    flat = {}

    def visit(path, x):
        if _is_array_leaf(x):
            flat[param_path(path)] = x
        return x

    jax.tree_util.tree_map_with_path(visit, state)
    return flat


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        self._thread: threading.Thread | None = None
        self._error: tuple[int, BaseException] | None = None
        #: Step actually restored by the last ``restore()`` call — the caller
        #: asked for "latest usable", this is which one survived verification.
        self.last_restored_step: int | None = None
        if distributed.is_main_process():
            resilience.retriable_io(os.makedirs, directory, exist_ok=True,
                                    _what="ckpt_mkdir")
            self._recover_interrupted_replace()
        if jax.process_count() > 1:
            # Non-main hosts must not race latest_checkpoint() against the
            # heal above: a step_X.old-only directory would look empty to
            # them and desynchronize --resume across hosts. __init__ runs on
            # the main thread (same thread as train-step collectives).
            distributed.barrier("ckpt_init_recover")
            self._validate_shared_filesystem()

    def _validate_shared_filesystem(self):
        """Fail fast if the checkpoint directory is not shared across hosts.

        The multi-host commit rendezvous is filesystem-based (module
        docstring): process 0 polls for every host's ``files.p*.json``
        sentinel before writing COMMIT. On disjoint local disks that
        protocol can never succeed — every save would time out after 600 s
        and no checkpoint would ever commit, silently. Probe at init
        instead: process 0 writes a nonce file, and every host must observe
        it (with a short poll to ride out NFS attribute-cache latency).
        Runs on the main thread; uses host-level collectives only.
        """
        from jax.experimental import multihost_utils

        probe = os.path.join(self.directory, ".fs_probe")
        nonce = np.int32(np.random.randint(1 << 30)
                         if distributed.is_main_process() else 0)
        nonce = int(multihost_utils.broadcast_one_to_all(nonce))
        if distributed.is_main_process():
            with open(probe + ".tmp", "w") as fh:
                fh.write(str(nonce))
            os.replace(probe + ".tmp", probe)
        distributed.barrier("ckpt_fs_probe_written")
        deadline = time.monotonic() + 15.0
        seen = False
        while time.monotonic() < deadline:
            try:
                with open(probe) as fh:
                    seen = fh.read().strip() == str(nonce)
            except OSError:
                seen = False
            if seen:
                break
            time.sleep(0.25)
        all_seen = multihost_utils.process_allgather(
            np.asarray(seen, np.bool_))
        if distributed.is_main_process():
            try:
                os.remove(probe)
            except OSError:
                pass
        if not np.all(all_seen):
            missing = [i for i, ok in enumerate(np.atleast_1d(all_seen))
                       if not ok]
            raise RuntimeError(
                f"checkpoint directory {self.directory!r} is not visible "
                f"from host process(es) {missing}: the multi-host commit "
                f"rendezvous requires a SHARED filesystem (NFS/GCS fuse). "
                f"Point --checkpoint-dir at storage all hosts can read, or "
                f"run single-host.")

    def _recover_interrupted_replace(self):
        """Heal a crash inside save()'s re-save swap: a ``step_X.old`` dir
        without its ``step_X`` means the crash hit between the two renames —
        the set-aside copy is the committed checkpoint; restore its name."""
        for name in os.listdir(self.directory):
            if not name.endswith(OLD_SUFFIX):
                continue
            old = os.path.join(self.directory, name)
            base = os.path.join(self.directory, name[: -len(OLD_SUFFIX)])
            if os.path.isdir(base):
                shutil.rmtree(old, ignore_errors=True)  # swap had completed
            else:
                os.rename(old, base)

    # -- save ---------------------------------------------------------------

    def save(self, state, step: int, extra: dict | None = None, block: bool = False):
        """Snapshot device->host now; write files in the background."""
        self.wait()  # at most one in-flight save
        flat = _flatten(state)
        # Snapshot synchronously: the caller will donate these buffers to the
        # next step. Each host only materializes its addressable shards.
        # np.array (not np.asarray): asarray of a shard is a zero-copy
        # memoryview of the device buffer, and once the caller donates the
        # state XLA recycles that memory for activations — the background
        # thread would then serialize garbage (with a valid CRC, since the
        # checksum is computed over whatever bytes hit disk).
        shards: dict[str, list[tuple[list[list[int]], np.ndarray]]] = {}
        manifest_leaves: dict[str, Any] = {}
        for path, arr in flat.items():
            if isinstance(arr, np.ndarray):
                regions = [([[0, s] for s in arr.shape], np.array(arr))]
            else:
                regions = []
                for sh in arr.addressable_shards:
                    if sh.replica_id != 0:
                        continue  # one copy per replicated region
                    idx = [
                        [s.start or 0, s.stop if s.stop is not None else dim]
                        for s, dim in zip(sh.index, arr.shape)
                    ] or [[0, 0]]
                    regions.append((idx, np.array(sh.data)))
            shards[path] = regions
            manifest_leaves[path] = {
                "shape": list(np.shape(arr)),
                "dtype": str(regions[0][1].dtype) if regions else str(arr.dtype),
            }

        # Source-topology record (elastic resume): which geometry wrote this
        # checkpoint. Restore warns loudly on mismatch instead of silently
        # reassembling across topologies; the elastic trainer reads it via
        # peek_manifest() to plan the batch rescale before building anything.
        geometry: dict[str, Any] = {
            "process_count": jax.process_count(),
            "device_count": jax.device_count(),
        }
        for arr in flat.values():
            mesh = getattr(getattr(arr, "sharding", None), "mesh", None)
            if mesh is not None and hasattr(mesh, "shape"):
                geometry["mesh_shape"] = {
                    str(k): int(v) for k, v in dict(mesh.shape).items()}
                break

        step_dir = os.path.join(self.directory, f"step_{step:08d}")
        attempt_dir = step_dir + SAVING_SUFFIX
        multihost = jax.process_count() > 1
        nproc = jax.process_count()

        # All hosts write into an ATTEMPT dir that is renamed over the final
        # dir only when complete — so a committed checkpoint for this step
        # (e.g. from a run being re-done after --resume to an older step) is
        # never destroyed before its replacement is fully on disk. A crashed
        # earlier attempt may have left stale files.p*.json sentinels in the
        # attempt dir that would satisfy process 0's commit wait early; clear
        # it behind a MAIN-THREAD barrier (same thread as train-step
        # collectives, so no cross-thread collective interleaving).
        if distributed.is_main_process() and os.path.isdir(attempt_dir):
            shutil.rmtree(attempt_dir, ignore_errors=True)
        if multihost:
            distributed.barrier(f"ckpt_clear_{step}")

        def write():
            arrays_dir = os.path.join(attempt_dir, "arrays")
            resilience.retriable_io(os.makedirs, arrays_dir, exist_ok=True,
                                    _what="ckpt_write")
            written: dict[str, list] = {}
            for path, regions in shards.items():
                safe = path.replace("/", ".")
                for i, (idx, data) in enumerate(regions):
                    fname = f"{safe}.p{jax.process_index()}.{i}.npy"
                    fpath = os.path.join(arrays_dir, fname)
                    resilience.retriable_io(np.save, fpath, data,
                                            _what="ckpt_write")
                    # Checksum recorded in the manifest, verified by restore.
                    # Computed right after the write (page-cache hot), over
                    # the file bytes — so restore verifies exactly what the
                    # filesystem durably holds, npy header included.
                    written.setdefault(path, []).append({
                        "file": fname, "index": idx,
                        "crc32": _file_crc32(fpath)})
            if multihost:
                # Per-host file list doubles as the "this host is done"
                # sentinel: written ATOMICALLY (tmp+rename) after the arrays
                # so process 0 commits only once every host's data is on the
                # shared filesystem. No device collective -> async-safe.
                flist = os.path.join(attempt_dir,
                                     f"files.p{jax.process_index()}.json")

                def write_flist():
                    with open(flist + ".tmp", "w") as fh:
                        json.dump({p: f for p, f in written.items()}, fh)
                    os.replace(flist + ".tmp", flist)

                resilience.retriable_io(write_flist, _what="ckpt_write")
            if distributed.is_main_process():
                if multihost and not self._await_hosts(attempt_dir, nproc):
                    # A host died or stalled mid-save: leave uncommitted,
                    # but NEVER silently — the operator must know --resume
                    # will fall back to an older step.
                    log.error(
                        "checkpoint step %d NOT committed: not every host "
                        "finished writing within the timeout (attempt left "
                        "at %s)", step, attempt_dir)
                    return
                manifest = {
                    "step": step,
                    "extra": extra or {},
                    "geometry": geometry,
                    "leaves": {
                        p: {**manifest_leaves[p], "files": written.get(p, [])}
                        for p in shards
                    },
                }
                # NOTE: multi-host file listings are per-host in files.p*.json;
                # restore unions them with the manifest's own list.
                def write_json(path, obj):
                    with open(path, "w") as fh:
                        json.dump(obj, fh)

                resilience.retriable_io(
                    write_json, os.path.join(attempt_dir, MANIFEST_FILE),
                    manifest, _what="ckpt_write")
                # COMMIT is written INSIDE the attempt dir (whose .saving
                # suffix keeps it resume-ineligible), so the rename below
                # publishes a fully-committed dir in one atomic syscall.
                # An existing committed dir for this step is renamed ASIDE,
                # never rmtree'd before its replacement exists: a crash at
                # any point leaves either the old or the new copy intact
                # (the one-syscall gap between the two renames is healed by
                # _recover_interrupted_replace at next startup).
                def write_commit():
                    with open(os.path.join(attempt_dir, COMMIT_FILE),
                              "w") as fh:
                        fh.write(str(step))

                resilience.retriable_io(write_commit, _what="ckpt_commit")
                old_dir = step_dir + OLD_SUFFIX
                if os.path.isdir(step_dir):
                    if os.path.isdir(old_dir):
                        shutil.rmtree(old_dir, ignore_errors=True)
                    os.rename(step_dir, old_dir)
                os.rename(attempt_dir, step_dir)
                shutil.rmtree(old_dir, ignore_errors=True)
                self._prune()

        # attempt dir + rename + COMMIT marker is the atomicity boundary
        if block:
            try:
                write()
            except Exception as e:
                raise CheckpointWriteError(
                    f"checkpoint save for step {step} failed: "
                    f"{type(e).__name__}: {e}") from e
        else:
            def guarded():
                try:
                    write()
                except BaseException as e:  # noqa: BLE001 — surfaced by wait()
                    # A failed background save must NOT die silently with the
                    # daemon thread: the trainer would believe the step is
                    # durable. Stash it; wait() re-raises on the main thread.
                    self._error = (step, e)
                    log.error("background checkpoint write for step %d "
                              "failed: %s: %s", step, type(e).__name__, e)

            self._thread = threading.Thread(target=guarded, daemon=True)
            self._thread.start()

    def _await_hosts(self, step_dir: str, nproc: int,
                     timeout_s: float = 600.0) -> bool:
        """Wait for every host's files.p*.json sentinel; False on timeout."""
        import time

        deadline = time.monotonic() + timeout_s
        want = {f"files.p{i}.json" for i in range(nproc)}
        while time.monotonic() < deadline:
            if want <= set(os.listdir(step_dir)):
                return True
            time.sleep(0.05)
        return False

    def wait(self):
        """Join the in-flight background save, RE-RAISING its failure.

        Before this, a failed background write vanished with its daemon
        thread and the trainer believed the step was durable. Raises
        :class:`CheckpointWriteError` (chained to the original) so callers
        can log-and-retry; the stashed error is cleared once raised.
        """
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            (step, err), self._error = self._error, None
            raise CheckpointWriteError(
                f"background checkpoint write for step {step} failed: "
                f"{type(err).__name__}: {err}") from err

    def quarantine(self, step: int, reason: str = "poisoned") -> None:
        """Set a committed checkpoint aside, permanently resume-ineligible.

        Renamed (not deleted) so the bad state stays inspectable; the suffix
        makes the name fail ``_STEP_RE``, so every discovery path ignores it.
        Used by anomaly rollback when a checkpoint saved after a poisoned
        batch itself contains non-finite params — left in place it would be
        exactly what a later ``--resume auto`` restores.
        """
        src = os.path.join(self.directory, f"step_{step:08d}")
        dst = f"{src}.{reason}"
        if os.path.isdir(src):
            shutil.rmtree(dst, ignore_errors=True)
            os.rename(src, dst)
            log.warning("checkpoint step %d quarantined -> %s", step, dst)

    def _prune(self):
        steps = sorted(all_checkpoints(self.directory))
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)
        # Orphaned attempts from crashed runs. No live attempt can exist
        # here: _prune runs at the end of process 0's write thread, and every
        # host's next save() is gated behind a main-thread barrier that
        # process 0 only reaches after joining this thread.
        for name in resilience.retriable_io(os.listdir, self.directory,
                                            _what="ckpt_prune"):
            if name.endswith(SAVING_SUFFIX):
                shutil.rmtree(os.path.join(self.directory, name),
                              ignore_errors=True)

    # -- restore ------------------------------------------------------------

    def restore(self, state_template, step: int | None = None,
                allow_partial: bool = False):
        """Restore into the shardings of ``state_template`` (a real or abstract
        TrainState whose leaves carry ``.sharding``). Returns (state, extra).

        By default every model parameter must be present in the checkpoint
        with a matching shape — resuming is all-or-nothing, because training
        or evaluating a half-initialized model is silent garbage.
        ``allow_partial=True`` downgrades mismatches to a warning (surgical
        transfer-learning loads).

        With ``step=None`` ("latest usable"): committed steps are tried
        newest-first, and one whose manifest is missing/unparseable or whose
        files fail CRC verification is SKIPPED with a loud warning — a
        corrupted latest checkpoint costs the steps since the previous save,
        not the whole run. An explicit ``step`` is restored exactly or raises.
        ``self.last_restored_step`` records which step actually loaded.
        """
        if step is not None:
            out = self._restore_step(state_template, step, allow_partial)
            self.last_restored_step = step
            return out
        candidates = sorted(all_checkpoints(self.directory), reverse=True)
        if not candidates:
            raise FileNotFoundError(
                f"no committed checkpoint in {self.directory}")
        last_err: BaseException | None = None
        for cand in candidates:
            try:
                out = self._restore_step(state_template, cand, allow_partial)
            except (CheckpointCorruptError, OSError,
                    json.JSONDecodeError, KeyError) as e:
                log.error(
                    "checkpoint step %d is unusable (%s: %s) — falling back "
                    "to the previous committed step", cand,
                    type(e).__name__, e)
                last_err = e
                continue
            if cand != candidates[0]:
                log.warning(
                    "restored step %d instead of latest committed step %d "
                    "(newer checkpoint(s) failed integrity checks)",
                    cand, candidates[0])
            self.last_restored_step = cand
            return out
        raise CheckpointCorruptError(
            f"every committed checkpoint in {self.directory} "
            f"({candidates}) failed to restore") from last_err

    def restore_params(self, params_template, step: int | None = None):
        """Params-only restore for inference/serving. Returns (params, extra).

        ``params_template`` is the model's params pytree (real arrays or
        ``jax.ShapeDtypeStruct``-like leaves; leaves with ``.sharding``
        re-shard exactly as in ``restore``). Only the checkpoint files
        backing model parameters are CRC-verified and read — optimizer
        state, which dominates checkpoint bytes, is never touched, so a
        serving host pays a fraction of the resume-time I/O. The match is
        all-or-nothing like a full restore: serving a half-initialized
        model is the same silent garbage as training one.
        """
        # The manifest namespaces model parameters under "params/..."
        # (TrainState field name); wrapping reproduces that namespace so
        # the integrity pre-pass and assembly skip every other leaf.
        wrapped, extra = self.restore({"params": params_template}, step=step)
        return wrapped["params"], extra

    def _restore_step(self, state_template, step: int,
                      allow_partial: bool = False):
        step_dir = os.path.join(self.directory, f"step_{step:08d}")

        def read_manifest():
            with open(os.path.join(step_dir, MANIFEST_FILE)) as fh:
                return json.load(fh)

        manifest = resilience.retriable_io(read_manifest, _what="ckpt_read")
        _warn_geometry_mismatch(step, manifest)
        # Union per-host file lists when present (multi-host shared fs).
        leaves = manifest["leaves"]
        for fn in resilience.retriable_io(os.listdir, step_dir,
                                          _what="ckpt_read"):
            if fn.startswith("files.p") and fn.endswith(".json"):
                extra_files = resilience.retriable_io(
                    _read_json, os.path.join(step_dir, fn), _what="ckpt_read")
                for p, files in extra_files.items():
                    known = {e["file"] for e in leaves[p]["files"]}
                    leaves[p]["files"] += [e for e in files if e["file"] not in known]

        arrays_dir = os.path.join(step_dir, "arrays")
        flat_template = _flatten(state_template)

        # Integrity pre-pass: verify the recorded CRC32 of every file this
        # restore will read, BEFORE any assembly — a bitflip or truncation
        # must surface as CheckpointCorruptError (fallback-eligible), never
        # as silent garbage weights or an np.load crash mid-assembly.
        # Entries without a checksum (pre-integrity checkpoints) are skipped.
        checked: set[str] = set()
        for path, meta in leaves.items():
            if path not in flat_template:
                continue
            for entry in meta["files"]:
                fname = entry["file"]
                if "crc32" not in entry or fname in checked:
                    continue
                checked.add(fname)
                fpath = os.path.join(arrays_dir, fname)
                got = resilience.retriable_io(_file_crc32, fpath,
                                              _what="ckpt_read")
                if got != entry["crc32"]:
                    raise CheckpointCorruptError(
                        f"CRC mismatch in {fpath!r}: manifest says "
                        f"{entry['crc32']:#010x}, file has {got:#010x} "
                        f"(size {os.path.getsize(fpath)} bytes)")

        restored: dict[str, Any] = {}
        shape_mismatch: list[str] = []
        for path, meta in leaves.items():
            target = flat_template.get(path)
            if target is None:
                continue
            if tuple(meta["shape"]) != tuple(np.shape(target)):
                # Same layer name, different architecture (e.g. resnet18
                # checkpoint into resnet_micro): loading it would blow up
                # later inside flax with a much less useful error.
                shape_mismatch.append(path)
                continue
            if hasattr(target, "sharding"):
                restored[path] = _assemble_sharded(
                    arrays_dir, meta, target.sharding)
            else:
                restored[path] = _assemble_full(arrays_dir, meta)

        want_params = [p for p in flat_template if p.startswith("params")]
        missing = [p for p in want_params if p not in restored]
        if missing:
            detail = (f"{len(missing)}/{len(want_params)} model parameters "
                      f"missing or shape-mismatched (e.g. {missing[:3]}; "
                      f"{len(shape_mismatch)} shape mismatches)")
            if not allow_partial:
                raise ValueError(
                    f"checkpoint at {step_dir!r} does not match this model: "
                    f"{detail} — wrong --model for this --resume path? "
                    f"(allow_partial=True to force a partial load)")
            import logging

            logging.getLogger(__name__).warning(
                "partial restore from %s: %s; unmatched leaves keep their "
                "initialization", step_dir, detail)

        def rebuild(path, x):
            key = param_path(path)
            if _is_array_leaf(x) or hasattr(x, "shape"):
                if key in restored:
                    return restored[key]
            return x

        state = jax.tree_util.tree_map_with_path(rebuild, state_template)
        return state, manifest.get("extra", {})


def _assemble_full(arrays_dir: str, meta: dict) -> np.ndarray:
    """Materialize a whole leaf (host-local numpy targets only)."""
    full = np.empty(meta["shape"], dtype=np.dtype(meta["dtype"]))
    for entry in meta["files"]:
        region = resilience.retriable_io(
            np.load, os.path.join(arrays_dir, entry["file"]),
            _what="ckpt_read")
        if full.ndim == 0:
            full = region.reshape(())
        else:
            full[tuple(slice(a, b) for a, b in entry["index"])] = region
    return full


def _assemble_sharded(arrays_dir: str, meta: dict, sharding) -> jax.Array:
    """Build a jax.Array leaf shard-by-shard under the target ``sharding``.

    For every addressable shard of the target, copy in just the overlapping
    parts of the saved regions (mmap-opened, so only the overlap is read).
    Peak host memory is one shard, not the leaf — FSDP-restore requirement
    (SURVEY.md §3.4/§7(b)); also how a checkpoint saved under one topology
    re-shards onto another.
    """
    shape = tuple(meta["shape"])
    index_map = sharding.addressable_devices_indices_map(shape)
    opened: dict[str, np.ndarray] = {}

    def region(fname):
        if fname not in opened:
            opened[fname] = resilience.retriable_io(
                np.load, os.path.join(arrays_dir, fname), mmap_mode="r",
                _what="ckpt_read")
        return opened[fname]

    def assemble(bounds):
        block = np.empty([b - a for a, b in bounds],
                         dtype=np.dtype(meta["dtype"]))
        for entry in meta["files"]:
            src = entry["index"] if shape else []
            inter = [(max(a, c), min(b, d))
                     for (a, b), (c, d) in zip(bounds, src)]
            if any(a >= b for a, b in inter):
                continue
            dst_sl = tuple(slice(a - o[0], b - o[0])
                           for (a, b), o in zip(inter, bounds))
            src_sl = tuple(slice(a - o[0], b - o[0])
                           for (a, b), o in zip(inter, src))
            if block.ndim == 0:
                block = np.asarray(region(entry["file"])).reshape(())
            else:
                block[dst_sl] = region(entry["file"])[src_sl]
        return block

    # Group devices by shard region: replicated leaves (DP) assemble each
    # region ONCE for all devices holding it, and each host block is freed
    # right after placement so peak host memory stays one shard.
    by_bounds: dict[tuple, list] = {}
    for device, idx in index_map.items():
        bounds = tuple(
            (s.start or 0, s.stop if s.stop is not None else dim)
            for s, dim in zip(idx, shape)
        )
        by_bounds.setdefault(bounds, []).append(device)
    placed = {}
    for bounds, devs in by_bounds.items():
        block = assemble(bounds)
        for device in devs:
            placed[device] = jax.device_put(block, device)
        del block
    pieces = [placed[device] for device in index_map]
    arr = jax.make_array_from_single_device_arrays(shape, sharding, pieces)
    # ``device_put(host_block, device)`` zero-copies aligned numpy memory on
    # the CPU PJRT client, and the train loop DONATES the state:
    # donating a buffer XLA merely borrows frees host memory it does not own
    # — a hard segfault on the first post-resume step (reproduced by
    # tests/test_distributed.py::test_mid_epoch_kill_resume_is_sample_exact).
    # A jitted copy forces fresh XLA-owned buffers; applied per leaf, so peak
    # memory stays one leaf above the state being assembled.
    import jax.numpy as jnp

    return jax.jit(jnp.copy)(arr)


def split_resume_path(path: str) -> tuple[str, int | None]:
    """Parse a ``--resume`` value into (checkpoint root, explicit step|None).

    ``.../ck`` -> ("/.../ck", None); ``.../ck/step_00000007`` ->
    ("/.../ck", 7). Single shared parser for every resume entry point.
    """
    target = path.rstrip("/")
    m = _STEP_RE.match(os.path.basename(target))
    if m:
        return os.path.dirname(target) or ".", int(m.group(1))
    return target, None


def all_checkpoints(directory: str) -> list[int]:
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        m = _STEP_RE.match(name)
        if m and os.path.exists(os.path.join(directory, name, COMMIT_FILE)):
            out.append(int(m.group(1)))
    return sorted(out)


def _manifest_ok(directory: str, step: int) -> bool:
    """True when the committed step's manifest exists and parses."""
    try:
        with open(os.path.join(directory, f"step_{step:08d}",
                               MANIFEST_FILE)) as fh:
            json.load(fh)
        return True
    except (OSError, json.JSONDecodeError):
        return False


def latest_checkpoint(directory: str) -> int | None:
    """Newest committed step whose manifest is present and parseable.

    A COMMIT marker over a missing/garbled manifest (torn write, partial
    sync) previously made ``--resume auto`` crash with a raw JSONDecodeError;
    such a dir is treated as uncommitted and skipped with a warning.
    """
    steps = all_checkpoints(directory)
    for s in reversed(steps):
        if _manifest_ok(directory, s):
            return s
        log.warning(
            "checkpoint step %d in %s has a missing/unparseable manifest — "
            "treating as uncommitted and falling back", s, directory)
    return None


def peek_manifest(directory: str, step: int | None = None) -> dict | None:
    """JSON-only read of a committed step's manifest (no array I/O).

    The elastic resume path calls this *before* the mesh/model/optimizer are
    built, to learn the geometry (``manifest["geometry"]``, ``extra``'s
    ``global_batch_size``/``grad_accum``/``mesh_shape``) the checkpoint was
    written under and plan the batch rescale. ``step=None`` peeks the newest
    usable committed step. Returns None when nothing committed/parseable —
    advisory only, never raises for a missing checkpoint.
    """
    steps = ([step] if step is not None
             else list(reversed(all_checkpoints(directory))))
    for s in steps:
        try:
            with open(os.path.join(directory, f"step_{s:08d}",
                                   MANIFEST_FILE)) as fh:
                return json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
    return None


def _warn_geometry_mismatch(step: int, manifest: dict) -> None:
    """Loud (non-fatal) warning when a checkpoint written under one topology
    is restored under another — previously a changed world size restored
    silently. Cross-topology restore is *supported* (shard-wise reassembly);
    the warning exists so an unintended geometry change can't go unnoticed."""
    geom = manifest.get("geometry") or {}
    if not geom:
        return  # pre-geometry checkpoint: nothing recorded to compare
    mismatches = []
    for key, current in (("process_count", jax.process_count()),
                         ("device_count", jax.device_count())):
        recorded = geom.get(key)
        if recorded is not None and int(recorded) != current:
            mismatches.append(f"{key} {recorded} -> {current}")
    if mismatches:
        log.warning(
            "checkpoint step %d was written under a DIFFERENT topology "
            "(%s; source mesh %s) — restoring cross-topology via shard-wise "
            "reassembly. If this is not an intended elastic/topology change, "
            "stop and check the checkpoint path.", step,
            ", ".join(mismatches), geom.get("mesh_shape"))

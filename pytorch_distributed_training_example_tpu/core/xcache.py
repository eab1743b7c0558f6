"""Persistent executable cache — compile once per topology, restart warm.

The restart tax of an elastic relaunch is dominated by two costs the
checkpoint machinery never touched: re-tracing + re-compiling the train
step, and re-assembling the checkpoint layout (core/reshard.py owns the
second). This module removes the first: the exact
``jit(...).lower(...).compile()`` front-end the trainer and the serve
warmup share is keyed on a **fingerprint** of
everything that can change the lowered program — jax version, backend,
topology (process/device counts), mesh shape, the config knobs that reach
tracing, and the abstract avals+shardings of every input — and the
compiled executable is serialized under ``<ckpt-dir>/xcache/`` with the
same CRC discipline checkpoints use. A relaunched attempt at a previously
seen topology deserializes instead of compiling; any mismatch falls back
to a cold compile with a loud log line, never a stale executable.

Entry layout (one directory per fingerprint)::

    <ckpt-dir>/xcache/<key>/
        executable.bin   pickle of (payload, in_tree, out_tree) from
                         jax.experimental.serialize_executable.serialize
        meta.json        fingerprint fields + crc32 of executable.bin

Corruption handling mirrors ``core/checkpoint.py``: a CRC or unpickle
failure quarantines the entry (rename to ``<key>.corrupt``) and recompiles.
Serialization is backend-dependent; where ``serialize`` is unsupported the
cache degrades to the jax persistent compilation cache
(:func:`place_compile_cache`; with ``--xcache`` and no
``JAX_COMPILATION_CACHE_DIR`` it lives in ``<ckpt-dir>/xcache/jaxcache``),
which ``Lowered.compile()`` consults transparently.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil
import zlib

import jax

from pytorch_distributed_training_example_tpu.utils.resilience import (
    retriable_io)

import logging

log = logging.getLogger("pdtx")

XCACHE_DIRNAME = "xcache"
EXECUTABLE_FILE = "executable.bin"
META_FILE = "meta.json"
SCHEMA_VERSION = 1

#: Config fields that reach tracing/lowering of the train step. Anything
#: here changing MUST miss the cache (a stale executable is silent wrong
#: math); anything not here must not spuriously invalidate it.
TRACED_KNOBS = (
    "model", "dataset", "num_classes", "image_size", "seq_len",
    "global_batch_size", "grad_accum_steps", "precision", "remat",
    "remat_policy", "strategy", "attn_impl", "dropout", "label_smoothing",
    "grad_clip", "optimizer", "weight_decay", "momentum", "telemetry",
    "pp_microbatches",
)


def _abstract_sig(tree) -> list[str]:
    """Stable string per leaf: shape/dtype/sharding spec of the aval."""
    sig = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        shard = getattr(leaf, "sharding", None)
        spec = getattr(shard, "spec", None)
        sig.append(f"{jax.tree_util.keystr(path)}:"
                   f"{tuple(getattr(leaf, 'shape', ()))}:"
                   f"{getattr(getattr(leaf, 'dtype', None), 'name', '?')}:"
                   f"{spec}")
    return sig


def fingerprint(*, mesh, config=None, example_args=(), extra=None) -> dict:
    """Everything that can change the lowered step, as a flat JSON dict."""
    fields = {
        "schema_version": SCHEMA_VERSION,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "process_count": jax.process_count(),
        "device_count": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
        "mesh_shape": {str(k): int(v) for k, v in dict(mesh.shape).items()},
        # The devices the executable runs on, in mesh order: load() hands
        # them to deserialize_and_load, which otherwise assumes every
        # device of the backend.
        "mesh_devices": [int(d.id) for d in mesh.devices.flat],
        "abstract": [s for a in example_args for s in _abstract_sig(a)],
    }
    if config is not None:
        fields["knobs"] = {k: getattr(config, k) for k in TRACED_KNOBS
                           if hasattr(config, k)}
    if extra:
        fields["extra"] = dict(extra)
    return fields


def cache_key(fields: dict) -> str:
    blob = json.dumps(fields, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


def _crc32(path: str) -> int:
    crc = 0
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _read_bytes(path: str) -> bytes:
    with open(path, "rb") as fh:
        return fh.read()


def cache_dir(root: str) -> str:
    return os.path.join(root, XCACHE_DIRNAME)


def _skeleton(tree):
    """JSON-able container skeleton of a plain pytree (leaves become 0.0).

    Only standard containers (dict/list/tuple) are representable — enough
    for the metrics side of the train step's output. Raises TypeError on
    anything fancier, which the caller treats as "trees not
    reconstructible".
    """
    if isinstance(tree, dict):
        if not all(isinstance(k, str) for k in tree):
            raise TypeError("non-string dict key in metrics tree")
        return {"d": {k: _skeleton(v) for k, v in tree.items()}}
    if isinstance(tree, tuple):
        return {"t": [_skeleton(v) for v in tree]}
    if isinstance(tree, list):
        return {"l": [_skeleton(v) for v in tree]}
    return {"x": 0}


def _unskeleton(skel):
    if "d" in skel:
        return {k: _unskeleton(v) for k, v in skel["d"].items()}
    if "t" in skel:
        return tuple(_unskeleton(v) for v in skel["t"])
    if "l" in skel:
        return [_unskeleton(v) for v in skel["l"]]
    return 0.0


def _quarantine(entry: str, reason: str) -> None:
    dst = f"{entry}.{reason}"
    retriable_io(os.replace, entry, dst, _what="xcache quarantine")
    log.warning("xcache: entry %s quarantined -> %s", entry, dst)


def load(root: str, fields: dict, example=None):
    """Deserialize the cached executable for ``fields``, or None (cold).

    Every miss/fallback is loud: the log line names WHY the run compiles
    cold (no entry, fingerprint mismatch, CRC mismatch, deserialize
    failure), because a silent cold path would hide an invalidation bug
    behind a slow restart. A corrupted entry is quarantined like a
    corrupted checkpoint so the recompile can re-save under the same key.

    ``example`` is the live ``(state, batch)`` pair for entries saved in
    ``reconstruct`` tree mode (see :func:`save`): their in/out treedefs
    are rebuilt from the live objects instead of unpickled, because the
    train state's static fields (optax closures) don't pickle.
    """
    entry = os.path.join(cache_dir(root), cache_key(fields))
    meta_path = os.path.join(entry, META_FILE)
    exe_path = os.path.join(entry, EXECUTABLE_FILE)
    if not os.path.isdir(entry):
        log.warning("xcache: MISS — no entry for fingerprint %s (first run "
                    "at this topology, or a knob/topology change "
                    "invalidated the key) — cold compile",
                    os.path.basename(entry))
        return None
    try:
        meta = retriable_io(_read_json, meta_path, _what="xcache meta read")
    except (OSError, ValueError) as e:
        log.warning("xcache: unreadable meta for %s (%s) — quarantining, "
                    "cold compile", entry, e)
        _quarantine(entry, "corrupt")
        return None
    if meta.get("fields") != json.loads(
            json.dumps(fields, sort_keys=True, default=str)):
        # A sha collision would be the only way here; treat as a mismatch.
        log.warning("xcache: fingerprint mismatch under key %s — refusing "
                    "the stale executable, cold compile",
                    os.path.basename(entry))
        return None
    try:
        if retriable_io(_crc32, exe_path, _what="xcache crc") != int(
                meta["crc32"]):
            log.warning("xcache: CRC mismatch for %s — quarantining, cold "
                        "compile", exe_path)
            _quarantine(entry, "corrupt")
            return None
        blob = retriable_io(_read_bytes, exe_path, _what="xcache read")
        if meta.get("tree_mode") == "reconstruct":
            if example is None:
                log.warning("xcache: entry %s needs live example trees and "
                            "none were passed — cold compile", entry)
                return None
            payload = blob
            in_tree = jax.tree_util.tree_structure((tuple(example), {}))
            out_tree = jax.tree_util.tree_structure(
                (example[0], _unskeleton(meta["metrics_skeleton"])))
        else:
            payload, in_tree, out_tree = pickle.loads(blob)
        from jax.experimental.serialize_executable import (
            deserialize_and_load)

        by_id = {d.id: d for d in jax.devices()}
        compiled = deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in fields["mesh_devices"]])
    except Exception as e:  # noqa: BLE001 — any failure means cold compile
        log.warning("xcache: deserialize failed for %s (%s: %s) — "
                    "quarantining, cold compile", entry,
                    type(e).__name__, e)
        try:
            _quarantine(entry, "corrupt")
        except OSError:
            pass
        return None
    log.warning("xcache: HIT — restored compiled executable %s "
                "(jax %s, %d devices), compile skipped",
                os.path.basename(entry), meta["fields"].get("jax_version"),
                meta["fields"].get("device_count"))
    return compiled


def save(root: str, fields: dict, compiled, *, example=None,
         metrics=None) -> bool:
    """Serialize ``compiled`` under the fingerprint key (best-effort).

    Tree handling: the executable payload always serializes, but the
    in/out *treedefs* only pickle when every custom pytree node's static
    data does — the train state's optax closures don't. When ``example``
    (the live ``(state, batch)``) and ``metrics`` (the first step's
    metrics pytree) are passed and their treedefs match the serialized
    ones exactly, the entry is written in ``reconstruct`` mode: raw
    payload plus a JSON skeleton of the metrics tree, and :func:`load`
    rebuilds the treedefs from the caller's live objects.

    Returns False — with a loud line naming the fallback — when neither
    mode works; the jax persistent compilation cache then carries the
    warm restart instead.
    """
    try:
        from jax.experimental.serialize_executable import serialize

        payload, in_tree, out_tree = serialize(compiled)
    except Exception as e:  # noqa: BLE001 — backend-dependent support
        log.warning("xcache: executable serialization unsupported here "
                    "(%s: %s) — relying on the jax persistent compilation "
                    "cache for warm restarts", type(e).__name__, e)
        return False
    tree_mode = None
    skel = None
    try:
        blob = pickle.dumps((payload, in_tree, out_tree))
        tree_mode = "pickle"
    except Exception:  # noqa: BLE001 — unpicklable static treedef data
        if example is not None and metrics is not None:
            try:
                skel = _skeleton(metrics)
                ok = (jax.tree_util.tree_structure((tuple(example), {}))
                      == in_tree
                      and jax.tree_util.tree_structure(
                          (example[0], _unskeleton(skel))) == out_tree)
            except TypeError:
                ok = False
            if ok:
                blob = payload
                tree_mode = "reconstruct"
    if tree_mode is None:
        log.warning("xcache: executable treedefs neither pickle nor "
                    "reconstruct from the train-step contract — relying on "
                    "the jax persistent compilation cache for warm restarts")
        return False
    entry = os.path.join(cache_dir(root), cache_key(fields))
    tmp = f"{entry}.saving.{os.getpid()}"
    retriable_io(os.makedirs, tmp, exist_ok=True, _what="xcache entry dir")
    exe_tmp = os.path.join(tmp, EXECUTABLE_FILE)

    def _write_blob():
        with open(exe_tmp, "wb") as fh:
            fh.write(blob)

    def _write_meta():
        meta = {"schema_version": SCHEMA_VERSION,
                "crc32": _crc32(exe_tmp),
                "tree_mode": tree_mode,
                "fields": json.loads(json.dumps(
                    fields, sort_keys=True, default=str))}
        if skel is not None:
            meta["metrics_skeleton"] = skel
        with open(os.path.join(tmp, META_FILE), "w") as fh:
            json.dump(meta, fh, indent=1, default=str)

    try:
        retriable_io(_write_blob, _what="xcache executable write")
        retriable_io(_write_meta, _what="xcache meta write")
        # Last writer wins: a concurrent attempt racing the same key swaps
        # in an equivalent entry (same fingerprint -> same program).
        shutil.rmtree(entry, ignore_errors=True)
        retriable_io(os.replace, tmp, entry, _what="xcache entry commit")
    except OSError as e:
        log.warning("xcache: save failed (%s) — next restart compiles cold",
                    e)
        shutil.rmtree(tmp, ignore_errors=True)
        return False
    log.info("xcache: saved compiled executable -> %s (%d bytes)",
             entry, len(blob))
    return True


#: Where the jax persistent compilation cache goes when the environment
#: does not place it: one fixed directory inside the checkout (the path is
#: part of what a later run must find again, so it never moves).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache(fallback_dir: str = DEFAULT_COMPILE_CACHE_DIR,
                        min_compile_secs: float = 1.0) -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    The ONE place this repo sets ``jax_compilation_cache_dir`` (main.py,
    chip_smoke.py, the benchmark's driver and tests/conftest.py all call
    it). Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already reads it, and no
    directory is set in code — the cache can be placed from outside.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", fallback_dir)
    return fallback_dir


def compile_cached(lowered, root: str | None, fields: dict):
    """The shared front-end: deserialize on hit, else compile and save.

    Returns ``(compiled, mode)`` where mode is ``"warm"`` (cache hit) or
    ``"cold"``. With ``root=None`` this is exactly ``lowered.compile()``.
    """
    if root:
        compiled = load(root, fields)
        if compiled is not None:
            return compiled, "warm"
    compiled = lowered.compile()
    if root:
        save(root, fields, compiled)
    return compiled, "cold"

"""Unified telemetry: on-device health pack, span timeline, goodput, anomaly guard.

Three pieces, one module (ROADMAP items 1/3/5 all need this to be
interpretable):

1. **Health pack** (device side): ``health_pack`` computes global grad/update/
   param norms and finite flags INSIDE the compiled train step, and
   ``collect_sowed`` folds model-internal diagnostics (the expert layers'
   held rows and layouts, sowed under the ``"telemetry"`` collection) into
   the same metrics dict. Everything rides the existing ``log_every``
   device_get: zero extra host syncs at the default cadence.

2. **Span recorder** (host side): ``SpanRecorder.span("input_wait")`` times
   named phases, mirrors them onto the device timeline via
   ``jax.profiler.TraceAnnotation("pdtx.<name>")`` (so they line up with
   xplane traces), keeps them in a bounded ring with their step, parent and
   thread, and renders a Perfetto-loadable ``trace_events.json`` plus a
   goodput summary — fraction of wall-clock in productive steps vs. each
   badput category (PaLM-style goodput accounting, PAPERS.md). The process
   has ONE recorder (``recorder()``), on whether or not ``cfg.telemetry``
   is: it costs microseconds a step and never touches the compiled step.
   ``cfg.telemetry`` switches on everything else here, and the files.

3. **Anomaly guard**: on a non-finite health scalar, dump a diagnostic
   bundle (step, config, last-K metric rows, trigger row, goodput snapshot)
   and either raise :class:`AnomalyError` or skip-and-continue, per the
   ``--anomaly-action`` knob.

The :class:`Telemetry` facade bundles all three for ``core/trainer.py``
(adopting the process's recorder).
"""

from __future__ import annotations

import atexit
import collections
import dataclasses
import itertools
import json
import logging
import os
import threading
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from pytorch_distributed_training_example_tpu.utils import fleetobs

log = logging.getLogger("pdtx")

#: Span names treated as productive time in the goodput summary. "step" is
#: the training step AND the serving decode step; "prefill" is the serving
#: engine's prompt-ingestion forward (serve/engine.py) — tokens leave the
#: model in both, so both count toward goodput. Trainers never emit
#: "prefill", so training goodput is unchanged.
PRODUCTIVE_SPANS = ("step", "prefill")

#: Badput categories the trainer emits (order is the report order).
#: "restart" is synthesized, not timed by a span: the wall-clock gap between
#: a previous supervisor attempt's last goodput write and this attempt's
#: start (the restart tax of an elastic/preemption relaunch).
BADPUT_SPANS = ("init", "compile", "input_wait", "checkpoint_save",
                "checkpoint_restore", "eval", "anomaly_dump", "restart")


#: Every span is mirrored as ``jax.profiler.TraceAnnotation(PREFIX + name)``.
ANNOTATION_PREFIX = "pdtx."


class AnomalyError(RuntimeError):
    """Raised by the anomaly guard when ``anomaly_action='abort'``."""


# ---------------------------------------------------------------------------
# Device side: the health pack. Pure functions traced into the train step.
# ---------------------------------------------------------------------------


def _global_norm(tree) -> jax.Array:
    import optax

    return optax.global_norm(jax.tree.map(
        lambda x: x.astype(jnp.float32), tree))


def health_pack(loss, grads, old_params, new_params) -> dict[str, jax.Array]:
    """Training-health scalars, computed where the tensors already live.

    ``update_norm`` is the norm of the applied delta (new - old), so it is
    exact under every update rule including the fp16 scaler's skip branch
    (where it is 0: params held). All reductions fuse into the step program;
    the result is a handful of f32 scalars in the metrics dict.
    """
    with jax.named_scope("telemetry_health"):
        update = jax.tree.map(
            lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
            new_params, old_params)
        finite = jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(g.astype(jnp.float32)))
             for g in jax.tree.leaves(grads)]))
        return {
            "update_norm": _global_norm(update),
            "param_norm": _global_norm(new_params),
            "loss_finite": jnp.isfinite(loss).astype(jnp.float32),
            "grads_finite_all": finite.astype(jnp.float32),
        }


def collect_sowed(tele_vars) -> dict[str, jax.Array]:
    """Fold a flax ``"telemetry"`` sow collection into named mean scalars.

    Sow appends one entry per call site per layer (tuples; a leading scan
    dim when layers are scanned): leaves are grouped by their final name and
    averaged.

    The expert layers (``parallel/moe.py``) sow four scalars each, named with
    the enclosing block behind a dot, so a layer keeps its own reading:
    ``moe_held_rows.<block>`` (the (token, choice) pairs that landed on the
    experts this chip holds), ``moe_held_peak.<block>`` (the fullest held
    expert's rows over their mean), ``moe_whole.<block>`` (1.0 where the held
    rows went through the bounded layout whole) and
    ``moe_source_parts.<block>`` (the column parts the padded rows are
    gathered back in). All four come from the router's own ``[E]`` counts,
    which the routine needs anyway.
    """
    out: dict[str, list] = {}
    flat = jax.tree_util.tree_flatten_with_path(tele_vars)[0]
    for path, leaf in flat:
        name = None
        for part in reversed(path):
            key = getattr(part, "key", getattr(part, "name", None))
            if isinstance(key, str) and not key.isdigit():
                name = key
                break
        if name is None:
            name = "telemetry"
        out.setdefault(name, []).append(jnp.mean(jnp.asarray(leaf)))
    return {k: jnp.mean(jnp.stack(v)).astype(jnp.float32)
            for k, v in out.items()}


# ---------------------------------------------------------------------------
# Host side: span recorder + goodput accounting.
# ---------------------------------------------------------------------------


class Span(NamedTuple):
    """One record of the recorder's ring. ``t0``/``t1`` are
    ``time.perf_counter_ns()``; ``step`` is the global step the loop was in
    (a loader-side record carries its batch index); ``parent`` is the ``id``
    of the span that was open on the same thread when this one started."""

    kind: str            # "span" | "counter" | "compile"
    name: str            # of a "compile" record: a key of COMPILE_RECORDS
    t0: int
    t1: int
    step: int | None
    id: int
    parent: int | None
    thread: str
    value: Any = None    # a counter's reading; a compile record's function

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9


#: Ring size: a step leaves about a dozen records, so this holds the last
#: few thousand steps (about 10 MB when full) however long the run.
RING_RECORDS = 1 << 16

_SAME = object()

#: ``jax.monitoring`` duration event -> the stage of the compile pipeline it
#: times. ``SpanRecorder.compile_stage`` turns a stage into records.
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
#: The stages that nest: a traced function traces the jitted functions it
#: calls, and a lowering rule may trace and lower a function of its own.
_NESTING_STAGES = ("trace", "lower")
_NESTING_EVENTS = frozenset(e for e, stage in _COMPILE_STAGES.items()
                            if stage in _NESTING_STAGES)

#: Every name a ``kind="compile"`` record can have. One jitted function
#: leaves, in this order, ``trace`` (Python to jaxpr) and ``lower`` (jaxpr to
#: StableHLO), of the outermost function only, and one backend record: ``compile``
#: where XLA compiled it (with a ``cache_miss`` inside where the persistent
#: cache then stored it), or ``cache_load`` where the persistent cache served
#: the executable (the cache's key, then the ``cache_retrieval`` inside it:
#: read, deserialize, load). ``flash_schedule`` is a record of what was traced,
#: with no duration: an online flash kernel's static schedule (its ``value``
#: a dict: kernel, S, D, blocks, counts; ops/flash_attention.py), once a
#: traced call; ``ssd_plan`` likewise, once a traced call of ``ops.ssd.ssd``
#: (``value``: H, P, N, groups, chunk and the heads a program of the kernels
#: holds, or ``"xla"`` where the shape took the ``jax.numpy`` scan);
#: ``mixer_plan`` likewise, once a traced call of ``ops.ssd.conv_silu`` or
#: ``ops.ssd.gate_norm`` (``value``: stage, rows, channels, groups and the
#: [rows, cols] tile of a program, or ``"xla"``); ``gmm_plan`` likewise, once
#: a traced call of a kernel of ``ops.grouped_matmul`` (``value``: kernel,
#: form (``gated`` / ``plain``), rows, d, f, bt, the column block, how many
#: blocks, and the bytes of VMEM the kernel's blocks take);
#: ``delta_rule_plan`` likewise, once a traced call of
#: ``ops.gated_delta.gated_delta_rule`` (``value``: key and value heads and
#: their widths, the chunk, the chunks, and ``"xla"`` for the scan's body).
#: ``step_map`` is a record of what was compiled, with no duration: once a
#: ``--profile-steps`` run, when its trace has stopped (``value``:
#: ``utils/stepmap.summary`` of the step's text: instructions and kernel
#: calls by pass, the compiler's clones, the mixed fusions).
COMPILE_RECORDS = ("trace", "lower", "compile", "cache_load",
                   "cache_retrieval", "cache_miss", "flash_schedule",
                   "ssd_plan", "mixer_plan", "gmm_plan", "delta_rule_plan",
                   "step_map")
#: The backend's share of them: what the watchdog's dump shows.
BACKEND_RECORDS = ("compile", "cache_load", "cache_miss")


def process_start_ns() -> int | None:
    """This process's start as the operating system has it, on the
    ``perf_counter_ns`` clock, or None where the system does not say. Linux
    counts it in clock ticks (10 ms) since boot: its age by ``CLOCK_BOOTTIME``
    is taken off the present stamp."""
    try:
        with open("/proc/self/stat", "rb") as fh:
            # the fields after "pid (comm)": state is field 3, starttime 22
            ticks = int(fh.read().rsplit(b")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter_ns() - int(age * 1e9) if age >= 0 else None


class _OpenSpan:
    """The context manager ``SpanRecorder.span`` returns. After ``__exit__``
    its ``seconds`` is the span's duration (the loop feeds the fleet layer's
    per-step timings from the spans it has just closed)."""

    __slots__ = ("rec", "name", "step", "bucket", "id", "parent", "t0",
                 "seconds", "_ann", "_accrues")

    def __init__(self, rec, name, step, bucket):
        self.rec, self.name, self.step, self.bucket = rec, name, step, bucket
        self.seconds = 0.0

    def __enter__(self):
        rec = self.rec
        local = rec._local
        stack = getattr(local, "stack", None)
        if stack is None:
            stack = local.stack = []
            local.accruing = 0
        if self.step is None:
            self.step = rec.step
        self.id = next(rec._ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        # Only the OUTERMOST span with a bucket accrues to goodput: a span
        # without one (``iteration``, loader detail) is transparent.
        self._accrues = self.bucket is not None and not local.accruing
        if self._accrues:
            local.accruing += 1
        self._ann = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + self.name,
            **({} if self.step is None else {"step": self.step}))
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        rec = self.rec
        local = rec._local
        local.stack.pop()
        self.seconds = (t1 - self.t0) / 1e9
        rec._events.append(Span(
            "span", self.name, self.t0, t1, self.step, self.id, self.parent,
            threading.current_thread().name))
        if self._accrues:
            local.accruing -= 1
            rec._totals[self.bucket] += self.seconds
            rec._counts[self.bucket] += 1
        return False


class SpanRecorder:
    """Times named host-side phases and renders them two ways.

    ``trace_events()`` is Chrome/Perfetto trace-event JSON (complete "X"
    events, microsecond timestamps); ``goodput()`` is the wall-clock
    decomposition. Records (spans, counter readings, compile events) live in
    a bounded ring, so the recorder can stay on for a run of any length;
    the goodput totals are running sums and do not depend on the ring. Only
    the OUTERMOST span that names a goodput bucket accrues to the totals —
    nested spans (e.g. a checkpoint restore inside init) still appear on
    the timeline but never double-count wall time. Each span also enters a
    ``jax.profiler.TraceAnnotation("pdtx.<name>")`` so the phase shows up,
    beside the device, on xplane traces captured by ``--profile-steps``.

    Appends come from the loop's thread and from loader workers
    (``deque.append`` is atomic); the open-span stack is per thread.
    """

    def __init__(self, run_id: str = "", carry: dict | None = None,
                 meta: dict | None = None, capacity: int = RING_RECORDS,
                 process_t0_ns: int | None = None):
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._local = threading.local()
        self._ids = itertools.count(1)
        #: ``process``: from the operating system's start of this process to
        #: this recorder's creation (interpreter, imports, the backend's
        #: start, the caller's preamble). No goodput bucket; ``adopt`` keeps
        #: it. None where the caller has no start time to give.
        self._process: Span | None = None
        if process_t0_ns is not None:
            self._process = Span(
                "span", "process", process_t0_ns, time.perf_counter_ns(),
                None, next(self._ids), None, threading.current_thread().name)
        #: the global step the training loop is in: the default ``step`` of
        #: every span and event opened until the loop sets the next one
        self.step: int | None = None
        self.adopt(run_id, carry=carry, meta=meta)

    def adopt(self, run_id: str = "", carry: dict | None = None,
              meta: dict | None = None) -> None:
        """(Re)start the accounting: a new origin, empty ring and totals,
        and a previous attempt's goodput carried in. ``Telemetry`` calls it
        on the process's recorder, so that ``goodput.json`` decomposes the
        wall-clock from the telemetry layer's start as it always has."""
        self.run_id = run_id
        # Monotonic<->wall anchor, captured at the same instant: ``ts``
        # values in the trace are microseconds after ``_start`` on THIS
        # host's monotonic clock; ``_wall_origin`` places that origin on the
        # shared wall clock so the merge CLI can align ranks whose monotonic
        # clocks have arbitrary offsets.
        self._start_ns = time.perf_counter_ns()
        self._start = self._start_ns / 1e9
        self._wall_origin = time.time()
        self.meta = dict(meta or {})
        self._run_ids: list[str] = []
        self._attempt_ids: list[str] = []
        self._events.clear()
        if self._process is not None:
            self._events.append(self._process)
        self._totals: collections.defaultdict = collections.defaultdict(float)
        self._counts: collections.defaultdict = collections.defaultdict(int)
        # Cross-attempt carryover (elastic/preemption relaunch): ``carry`` is
        # a previous attempt's goodput.json dict. Its categories/counts/wall
        # seed the cumulative totals, and the gap between its ``ended_at``
        # and now becomes one "restart" badput interval — so the merged
        # goodput.json decomposes the FULL job wall-clock, restart tax
        # included, not just the current attempt.
        self._base_totals: dict[str, float] = {}
        self._base_counts: dict[str, int] = {}
        self._base_wall = 0.0
        self.attempts = 1
        # Time-to-first-step (r21 instant restart): wall from this origin
        # (the telemetry layer's start, inside ``Trainer.__init__``) to the
        # first completed optimizer step, tagged cold/warm by the
        # executable-cache outcome. History carries across attempts so the
        # warm-vs-cold comparison lives in ONE goodput.json. Beside it, the
        # same mark measured from the ``process`` span's start: what a
        # launch costs, imports and the backend's start included.
        self._ttfs: float | None = None
        self._process_ttfs: float | None = None
        self._ttfs_mode: str | None = None
        self._ttfs_history: list[dict] = []
        if carry:
            self._ttfs_history = [dict(h) for h in
                                  (carry.get("ttfs_history") or [])]
            self._base_totals = {k: float(v) for k, v in
                                 (carry.get("categories_s") or {}).items()}
            self._base_counts = {k: int(v) for k, v in
                                 (carry.get("counts") or {}).items()}
            self._base_wall = float(carry.get("wall_s") or 0.0)
            self.attempts = int(carry.get("attempts") or 1) + 1
            # Provenance across attempts: which run/attempt ids this
            # cumulative summary merged (mixed-run detection downstream).
            for rid in (carry.get("run_ids")
                        or ([carry["run_id"]] if carry.get("run_id") else [])):
                if rid and rid not in self._run_ids:
                    self._run_ids.append(rid)
            for aid in (carry.get("attempt_ids")
                        or ([carry["attempt_id"]]
                            if carry.get("attempt_id") else [])):
                if aid and aid not in self._attempt_ids:
                    self._attempt_ids.append(aid)
            ended = carry.get("ended_at")
            if ended is not None:
                gap = max(0.0, time.time() - float(ended))
                self._base_totals["restart"] = (
                    self._base_totals.get("restart", 0.0) + gap)
                self._base_counts["restart"] = (
                    self._base_counts.get("restart", 0) + 1)
                self._base_wall += gap
                # Timeline marker: the gap sits BEFORE this attempt's origin.
                self._events.append(Span(
                    "span", "restart", self._start_ns - int(gap * 1e9),
                    self._start_ns, None, next(self._ids), None,
                    threading.current_thread().name))
        if run_id and run_id not in self._run_ids:
            self._run_ids.append(run_id)
        aid = self.meta.get("attempt_id")
        if aid and aid not in self._attempt_ids:
            self._attempt_ids.append(aid)
        self.meta.setdefault("attempt", self.attempts)

    def span(self, name: str, step: int | None = None, bucket=_SAME):
        """A context manager timing ``name``. ``step`` defaults to the
        loop's current step. ``bucket`` is the goodput category the span
        accrues to (its own name unless given); ``None`` keeps it out of
        goodput and lets the spans inside it accrue."""
        return _OpenSpan(self, name, step,
                         name if bucket is _SAME else bucket)

    def count(self, name: str, value, step: int | None = None) -> None:
        """One reading of the counter ``name``, taken where the work is."""
        now = time.perf_counter_ns()
        self._events.append(Span(
            "counter", name, now, now, self.step if step is None else step,
            next(self._ids), None, threading.current_thread().name, value))

    def compile_event(self, name: str, seconds: float = 0.0,
                      fun_name: str | None = None,
                      t1: int | None = None) -> None:
        """One ``kind="compile"`` record that ended just now (or at ``t1``),
        with the step the loop is in and the span open on this thread."""
        if t1 is None:
            t1 = time.perf_counter_ns()
        stack = getattr(self._local, "stack", None)
        self._events.append(Span(
            "compile", name, t1 - int(seconds * 1e9), t1, self.step,
            next(self._ids), stack[-1] if stack else None,
            threading.current_thread().name, fun_name))

    def stage_started(self) -> None:
        """jax began to trace or to lower a function on this thread
        (``stage_started`` and the ``compile_stage`` of a stage that nests
        come in nested pairs)."""
        local = self._local
        local.nesting = getattr(local, "nesting", 0) + 1

    def compile_stage(self, stage: str, seconds: float,
                      fun_name: str | None = None) -> None:
        """A stage of jax's compile pipeline ended on this thread."""
        local = self._local
        if stage in _NESTING_STAGES:
            # Every jitted function a traced function calls reports its own
            # trace, inside its caller's interval (thousands for one train
            # step), and so do the functions a lowering rule traces: only the
            # outermost is a record, so that these records of a thread do not
            # overlap and add up to wall time.
            depth = getattr(local, "nesting", 0)
            local.nesting = max(depth - 1, 0)
            if depth > 1:
                return
        elif stage == "cache_retrieval":
            # Comes inside the backend's interval and without the function's
            # name: held until the backend event that follows it gives one.
            local.retrieved = (time.perf_counter_ns(), seconds)
            return
        elif stage == "compile":
            retrieved = getattr(local, "retrieved", None)
            if retrieved is not None:
                local.retrieved = None
                self.compile_event("cache_retrieval", retrieved[1], fun_name,
                                   t1=retrieved[0])
                stage = "cache_load"
        self.compile_event(stage, seconds, fun_name)

    def records(self) -> list[Span]:
        """A snapshot of the ring, oldest first."""
        return list(self._events)

    def tail(self, n: int, kind: str | None = None,
             names: tuple | None = None) -> list[dict]:
        """The newest ``n`` records (of ``kind``, called one of ``names``) as
        plain dicts, for the watchdog's dump."""
        picked = [r for r in self.records()
                  if (kind is None or r.kind == kind)
                  and (names is None or r.name in names)]
        return [{"kind": r.kind, "name": r.name, "step": r.step,
                 "ms": round(r.seconds * 1e3, 3), "thread": r.thread,
                 **({} if r.value is None else {"value": r.value})}
                for r in picked[-n:]]

    def clear(self) -> None:
        """Empty the ring (tests; the totals stay)."""
        self._events.clear()

    @property
    def wall_s(self) -> float:
        return time.perf_counter() - self._start

    def mark_first_step(self, mode: str) -> None:
        """Record time-to-first-step once, tagged ``cold``/``warm``."""
        if self._ttfs is not None:
            return
        self._ttfs = self.wall_s
        if self._process is not None:
            self._process_ttfs = (time.perf_counter_ns()
                                  - self._process.t0) / 1e9
        self._ttfs_mode = str(mode)
        self._ttfs_history.append({"attempt": self.attempts,
                                   "ttfs_s": round(self._ttfs, 4),
                                   "mode": self._ttfs_mode})

    def trace_events(self) -> dict:
        # ``fleetobs.trace_doc`` puts otherData FIRST (torn-write salvage
        # contract) and is shared with the serving-side RequestTrace so both
        # kinds of file merge under one clock-alignment rule.
        pid = jax.process_index()
        tids: dict[str, int] = {}
        events = []
        for r in self.records():
            args = {k: v for k, v in (("step", r.step), ("id", r.id),
                                      ("parent", r.parent),
                                      ("value", r.value)) if v is not None}
            events.append({
                "name": r.name, "ph": "C" if r.kind == "counter" else "X",
                "cat": "telemetry" if r.kind == "span" else r.kind,
                "ts": (r.t0 - self._start_ns) // 1000,
                "dur": (r.t1 - r.t0) // 1000,
                "pid": pid, "tid": tids.setdefault(r.thread, len(tids)),
                "args": args})
        return fleetobs.trace_doc(
            run_id=self.run_id, anchor_wall=self._wall_origin,
            anchor_mono=self._start, events=events, meta=self.meta)

    def goodput(self) -> dict:
        """Wall-clock decomposition since construction (plus carried attempts).

        ``goodput_fraction`` is the productive ("step") share; ``coverage``
        is the fraction of wall-clock any top-level span accounts for —
        the acceptance bar asks for >= 0.95, the rest is loop bookkeeping.
        Fractions sum to ``coverage`` <= 1 by construction (top-level spans
        cannot overlap on one thread). With carried attempts the totals and
        wall are CUMULATIVE over every attempt plus the restart gaps;
        ``attempts``/``ended_at`` let the next attempt keep merging.
        """
        wall = max(self._base_wall + self.wall_s, 1e-9)
        totals = dict(self._base_totals)
        for k, v in self._totals.items():
            totals[k] = totals.get(k, 0.0) + v
        counts = dict(self._base_counts)
        for k, v in self._counts.items():
            counts[k] = counts.get(k, 0) + v
        cats = {k: round(v, 4) for k, v in sorted(totals.items())}
        fracs = {k: v / wall for k, v in totals.items()}
        good = sum(fracs.get(k, 0.0) for k in PRODUCTIVE_SPANS)
        out = {
            "schema_version": fleetobs.SCHEMA_VERSION,
            "run_id": self.run_id,
            "run_ids": list(self._run_ids),
            "wall_s": round(wall, 4),
            "categories_s": cats,
            "counts": counts,
            "fractions": {k: round(v, 4) for k, v in sorted(fracs.items())},
            "goodput_fraction": round(good, 4),
            "badput_fraction": round(sum(fracs.values()) - good, 4),
            "coverage": round(sum(fracs.values()), 4),
            "attempts": self.attempts,
            "ended_at": round(time.time(), 3),
        }
        if self._ttfs is not None:
            out["time_to_first_step_s"] = round(self._ttfs, 4)
            out["ttfs_mode"] = self._ttfs_mode
        if self._process_ttfs is not None:
            out["process_to_first_step_s"] = round(self._process_ttfs, 4)
        if self._ttfs_history:
            out["ttfs_history"] = [dict(h) for h in self._ttfs_history]
        if "restart" in totals:
            # The restart tax decomposed: the supervisor gap between
            # attempts plus THIS job's cumulative compile/restore spans —
            # the three costs the executable cache + background re-shard
            # exist to shrink.
            out["restart_breakdown"] = {
                "gap_s": round(totals.get("restart", 0.0), 4),
                "compile_s": round(totals.get("compile", 0.0), 4),
                "restore_s": round(totals.get("checkpoint_restore", 0.0), 4),
            }
        if self.meta:
            out["meta"] = dict(self.meta)
        if self.meta.get("attempt_id"):
            out["attempt_id"] = self.meta["attempt_id"]
            out["attempt_ids"] = list(self._attempt_ids)
        return out

    def write(self, directory: str) -> None:
        """The rank-0 (single-process-compatible) artifact pair."""
        os.makedirs(directory, exist_ok=True)
        with open(os.path.join(directory, "trace_events.json"), "w") as fh:
            json.dump(self.trace_events(), fh)
        fleetobs.write_json_atomic(os.path.join(directory, "goodput.json"),
                                   self.goodput())

    def write_rank(self, directory: str, rank: int, attempt: int) -> None:
        """Per-rank, per-attempt artifact pair — every rank writes its own
        (the plain names above are rank 0's; before this, N ranks clobbered
        one shared file and the merge had nothing to merge)."""
        os.makedirs(directory, exist_ok=True)
        suffix = f"r{rank}.a{attempt}"
        path = os.path.join(directory, f"trace_events.{suffix}.json")
        with open(path, "w") as fh:
            json.dump(self.trace_events(), fh)
        fleetobs.write_json_atomic(
            os.path.join(directory, f"goodput.{suffix}.json"), self.goodput())


# The process's recorder: one, like a logger, whatever ``cfg.telemetry`` says.
# The trainer, the input pipeline and the benchmark's readers all reach it
# through ``recorder()``; ``Telemetry`` adopts it and builds no other.
_process_recorder: SpanRecorder | None = None
_process_lock = threading.Lock()


def recorder() -> SpanRecorder:
    """The process-wide :class:`SpanRecorder`, made on first use: its first
    record is the ``process`` span. From then on it also hears jax's compile
    pipeline (``jax.monitoring``), so the timeline says which span, and which
    step, a trace, a lowering, a compile or a cache load fell into."""
    global _process_recorder
    if _process_recorder is None:
        with _process_lock:
            if _process_recorder is None:
                _process_recorder = SpanRecorder(
                    process_t0_ns=process_start_ns())
                jax.monitoring.register_scalar_listener(_on_compile_start)
                jax.monitoring.register_event_duration_secs_listener(
                    _on_compile_duration)
                jax.monitoring.register_event_listener(_on_cache_event)
    return _process_recorder


# The listeners: one comparison (or lookup) an event, nothing stored for an
# event that is not of the compile pipeline.


def _on_compile_start(event: str, value, **kw) -> None:
    if event in _NESTING_EVENTS:
        _process_recorder.stage_started()


def _on_compile_duration(event: str, seconds: float, **kw) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is not None:
        _process_recorder.compile_stage(stage, seconds, kw.get("fun_name"))


def _on_cache_event(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_misses":
        _process_recorder.compile_event("cache_miss")


def load_goodput(directory: str, rank: int = 0) -> dict | None:
    """Previous attempt's cumulative goodput for ``rank`` (None if absent).

    Rank 0 reads the plain ``goodput.json``; other ranks read their
    highest-attempt suffixed file, falling back to the plain file (resume
    from a run that predates per-rank artifacts)."""
    import re as _re

    if rank:
        best: tuple[int, str] | None = None
        try:
            for name in os.listdir(directory):
                m = _re.fullmatch(rf"goodput\.r{rank}\.a(\d+)\.json", name)
                if m and (best is None or int(m.group(1)) > best[0]):
                    best = (int(m.group(1)), name)
        except OSError:
            best = None
        if best is not None:
            try:
                with open(os.path.join(directory, best[1])) as fh:
                    return json.load(fh)
            except (OSError, ValueError):
                return None
    try:
        with open(os.path.join(directory, "goodput.json")) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


# ---------------------------------------------------------------------------
# Anomaly guard.
# ---------------------------------------------------------------------------


def _nonfinite_keys(row: dict) -> list[str]:
    import math

    bad = []
    for k, v in row.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        if not math.isfinite(v):
            bad.append(k)
    return bad


class AnomalyGuard:
    """Watches fetched metric rows for non-finite training-health scalars.

    ``record`` keeps the last-K rows; ``check`` dumps a diagnostic bundle
    (step, config, trigger row, history, goodput snapshot) into
    ``directory`` on the first non-finite scalar and then either raises
    :class:`AnomalyError` (action="abort") or logs and returns True
    (action="continue"). With an fp16 GradScaler in play, rows whose
    ``grads_finite`` flag is 0 are the scaler's *handled* overflow-skip
    branch — set ``allow_scaler_skips`` so they don't false-trigger.
    """

    def __init__(self, directory: str, action: str = "abort", keep: int = 32,
                 config: Any = None, run_id: str = "",
                 goodput_fn: Callable[[], dict] | None = None,
                 allow_scaler_skips: bool = False):
        if action not in ("abort", "continue", "rollback"):
            raise ValueError(
                f"anomaly_action must be 'abort', 'continue' or 'rollback', "
                f"got {action!r}")
        self.directory = directory
        self.action = action
        self.config = config
        self.run_id = run_id
        self.goodput_fn = goodput_fn
        self.allow_scaler_skips = allow_scaler_skips
        self.history: collections.deque = collections.deque(maxlen=keep)
        self.tripped = False
        self.trips = 0
        self.warnings = 0
        # Optional hook called as ``fn(reason, step=...)`` after a bundle is
        # written — the Telemetry facade points it at the flight recorder.
        # Dumped once per anomaly EPISODE (a run of anomalous checks with no
        # clean row in between), not per anomalous step: under
        # anomaly_action=continue a NaN that sticks in the params would
        # otherwise append a near-identical ring dump every step.
        self.flight_dump_fn: Callable[..., Any] | None = None
        self._in_anomaly_episode = False

    def record(self, step: int, row: dict) -> None:
        self.history.append({"step": int(step), **row})

    def check(self, step: int, row: dict) -> bool:
        """Record the row, then trip on any non-finite scalar in it."""
        self.record(step, row)
        if (self.allow_scaler_skips
                and float(row.get("grads_finite", 1.0)) == 0.0):
            return False  # fp16 overflow-skip: params held, not an anomaly
        bad = _nonfinite_keys(row)
        if not bad:
            self._in_anomaly_episode = False
            return False
        self.tripped = True
        self.trips += 1
        path = self.dump(step, row, bad)
        msg = (f"non-finite health scalar(s) {bad} at step {step}; "
               f"diagnostic bundle: {path}")
        if self.action == "abort":
            raise AnomalyError(msg)
        # "continue" and "rollback" both return True after the dump; for
        # rollback, acting on the trip (restore + iterator re-seed + budget)
        # is the TRAINER's job — the guard only detects and documents.
        log.error("anomaly guard: %s — anomaly_action=%s", msg, self.action)
        return True

    def warn(self, step: int, reason: str) -> None:
        """Warn-only trigger (straggler/skew detection): counted and kept in
        the history ring so the next bundle shows it, but never dumps or
        aborts on its own — a slow host is an operator page, not a rollback.
        """
        self.warnings += 1
        self.history.append({"step": int(step), "warn": reason})
        log.warning("anomaly guard [warn-only] step %d: %s", int(step), reason)

    def dump(self, step: int, row: dict, bad_keys: list[str]) -> str:
        cfg = self.config
        if dataclasses.is_dataclass(cfg) and not isinstance(cfg, type):
            cfg = dataclasses.asdict(cfg)
        bundle = {
            "schema_version": fleetobs.SCHEMA_VERSION,
            "run_id": self.run_id,
            "step": int(step),
            "trigger_keys": bad_keys,
            "trigger_row": row,
            "config": cfg,
            "history": list(self.history),
            "goodput": self.goodput_fn() if self.goodput_fn else None,
            "time": time.time(),
        }
        os.makedirs(self.directory, exist_ok=True)
        path = os.path.join(self.directory, f"anomaly_step{int(step):08d}.json")
        with open(path, "w") as fh:
            json.dump(bundle, fh, indent=1, default=float)
        if self.flight_dump_fn is not None and not self._in_anomaly_episode:
            try:
                self.flight_dump_fn("anomaly", step=int(step))
            except Exception as e:  # diagnostics never mask the anomaly
                log.warning("flight dump on anomaly failed: %s", e)
        self._in_anomaly_episode = True
        return path


# ---------------------------------------------------------------------------
# Facade: what the trainer holds.
# ---------------------------------------------------------------------------


class Telemetry:
    """Span recorder + anomaly guard + last-seen state, as one object.

    ``directory`` receives ``trace_events.json`` / ``goodput.json`` (epoch
    end and shutdown) and anomaly bundles. ``snapshot()`` is the watchdog's
    context hook: last global step, last health row, goodput decomposition.
    """

    def __init__(self, directory: str, run_id: str = "",
                 anomaly_action: str = "abort", config: Any = None,
                 history_keep: int = 32, allow_scaler_skips: bool = False,
                 resume: bool = False, straggler_threshold: float = 2.0,
                 flightrec_steps: int = 256):
        self.directory = directory
        self.rank = jax.process_index()
        self.host = fleetobs.host_identity()
        # ``run_id`` (the MetricLogger per-process uuid) is really the
        # ATTEMPT id; the fleet-stable run id lives in <dir>/run_id.json so
        # every rank and every elastic attempt stamps the same one.
        self.attempt_id = run_id
        self.run_id = fleetobs.ensure_run_id(
            directory, run_id, fresh=not resume, rank=self.rank)
        # ``resume=True`` (a --resume run, e.g. a supervisor relaunch) merges
        # a previous attempt's goodput.json into this one: cumulative
        # categories plus a "restart" badput interval for the gap. The file
        # in ``directory`` then always decomposes the whole job so far.
        carry = load_goodput(directory, rank=self.rank) if resume else None
        if carry and (carry.get("attempt_id") == self.attempt_id
                      or carry.get("run_id") == run_id):
            carry = None  # same attempt rewriting its own file: nothing to merge
        elif (carry and carry.get("schema_version")
              and carry.get("run_id") != self.run_id):
            # Stamped artifact from a DIFFERENT run in the same directory —
            # summing unrelated attempts would fabricate goodput. Refuse.
            log.warning(
                "telemetry: refusing to carry goodput from foreign run %s "
                "into run %s (stale artifacts in %s?)",
                carry.get("run_id"), self.run_id, directory)
            carry = None
        meta = {"host": self.host, "rank": self.rank,
                "attempt_id": self.attempt_id}
        self.recorder = recorder()
        self.recorder.adopt(self.run_id, carry=carry, meta=meta)
        if carry:
            log.info(
                "telemetry: merging goodput across supervisor attempts — "
                "attempt %d, %.1fs of prior wall-clock carried",
                self.recorder.attempts, carry.get("wall_s", 0.0))
        self.guard = AnomalyGuard(
            directory, action=anomaly_action, keep=history_keep,
            config=config, run_id=self.run_id,
            goodput_fn=self.recorder.goodput,
            allow_scaler_skips=allow_scaler_skips)
        self.guard.flight_dump_fn = self.flight_dump
        self.flight = fleetobs.FlightRecorder(flightrec_steps)
        self.monitor = fleetobs.StragglerMonitor(threshold=straggler_threshold)
        self._steprows = (fleetobs.StepRowWriter(
            directory, self.rank, self.recorder.attempts,
            meta={"run_id": self.run_id, "attempt_id": self.attempt_id})
            if directory else None)
        fleetobs.set_active(
            self.flight, directory, self.rank,
            meta={"run_id": self.run_id, "attempt_id": self.attempt_id,
                  "attempt": self.recorder.attempts})
        self.last_step: int | None = None
        self.last_health: dict | None = None
        # Satellite fix (host-loss flush gap): a surviving rank torn down by
        # the launcher after a peer's abrupt death may never reach the
        # trainer's finally — flush the tail spans at interpreter exit so
        # only the genuinely-killed host loses data.
        self._atexit_armed = True
        atexit.register(self._atexit_flush)

    def span(self, name: str):
        return self.recorder.span(name)

    def mark_first_step(self, mode: str) -> None:
        """Time-to-first-step landed (cold/warm) — forwarded to goodput."""
        self.recorder.mark_first_step(mode)

    def observe(self, step: int, row: dict) -> bool:
        """Feed one fetched metrics row; returns True if the guard tripped."""
        self.last_step = int(step)
        self.last_health = dict(row)
        # Into the flight recorder FIRST: if the guard trips on this row its
        # bundle-adjacent flightrec dump must already contain the trigger.
        self.flight.record_health(step, row)
        return self.guard.check(step, row)

    def observe_timing(self, step: int, *, total_s: float,
                       input_wait_s: float = 0.0, checkpoint_s: float = 0.0,
                       epoch: int | None = None) -> str | None:
        """Feed one step's host-side phase timings (every step — pure
        ``perf_counter`` deltas, no device syncs). Returns the warn reason
        when the live straggler monitor flags the step."""
        compute = max(0.0, total_s - input_wait_s - checkpoint_s)
        row = {"step": int(step), "t": round(time.time(), 3),
               "total_s": round(total_s, 6),
               "input_wait_s": round(input_wait_s, 6),
               "compute_s": round(compute, 6),
               "checkpoint_s": round(checkpoint_s, 6)}
        if epoch is not None:
            row["epoch"] = int(epoch)
        self.flight.record_timing(step, **{k: v for k, v in row.items()
                                           if k != "step"})
        if self._steprows is not None:
            self._steprows.add(row)
        reason = self.monitor.observe(step, total_s=total_s,
                                      input_wait_s=input_wait_s)
        if reason:
            self.guard.warn(step, reason)
            if self.directory:
                # Live feed for the fleet scheduler's eviction reader
                # (fleetobs.read_chronic_straggler): the offline
                # detect_stragglers merge only lands after the attempt
                # exits. Same row shape as the merged attribution rows.
                fleetobs.append_straggler_flag(self.directory, {
                    "step": int(step), "slowest_rank": self.rank,
                    "delta_s": round(input_wait_s, 6),
                    "cause": "input_wait_s", "flagged": True,
                    "source": "live", "attempt": self.recorder.attempts})
        return reason

    def flight_dump(self, reason: str, **extra) -> str | None:
        """Dump the flight-recorder ring (anomaly / preempt / shutdown)."""
        return self.flight.dump(
            self.directory, reason=reason, rank=self.rank,
            meta={"run_id": self.run_id, "attempt_id": self.attempt_id,
                  "attempt": self.recorder.attempts, **extra})

    def write_artifacts(self) -> None:
        """Flush every on-disk artifact this rank owns: the per-rank trace/
        goodput pair (all ranks), the legacy plain pair (rank 0 only — N
        ranks used to clobber one shared file), and buffered step rows."""
        self.recorder.write_rank(self.directory, self.rank,
                                 self.recorder.attempts)
        if self.rank == 0:
            self.recorder.write(self.directory)
        if self._steprows is not None:
            self._steprows.flush()

    def _atexit_flush(self) -> None:
        if not self._atexit_armed:
            return
        self._atexit_armed = False
        try:
            self.write_artifacts()
        except Exception:  # interpreter teardown: never raise
            pass

    def snapshot(self) -> dict:
        return {"last_step": self.last_step,
                "last_health": self.last_health,
                "straggler_warnings": self.guard.warnings,
                "goodput": self.recorder.goodput()}

    def emit(self, where: str = "") -> dict:
        """Write the timeline + goodput files and log the one-line summary."""
        self.write_artifacts()
        if where == "shutdown":
            self._atexit_armed = False
        g = self.recorder.goodput()
        log.info(
            "goodput%s: %.1f%% productive over %.1fs (coverage %.1f%%) — %s",
            f" [{where}]" if where else "", 100 * g["goodput_fraction"],
            g["wall_s"], 100 * g["coverage"],
            " ".join(f"{k} {100 * v:.1f}%"
                     for k, v in g["fractions"].items() if k != "step"))
        return g

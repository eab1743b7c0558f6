"""What the readers of the program's own instrumentation share.

The program keeps one span recorder for the whole process
(``utils/telemetry.recorder()``: spans, counter readings and compile events
in a bounded ring, stamped with ``time.perf_counter_ns``), names its kernels
(``pl.pallas_call(name=...)``) and names the regions of its compiled step
(``jax.named_scope``). Three things are done here, once, for every reader:

1. ``records(host)``: the recorder's ring, cut to the measured window with the
   ``perf_counter`` bounds of the rows the driver's own wrappers wrote. The
   recorder outlives the ``Trainer`` that the driver frees. A program that has
   no recorder gives ``None``, and every reader then reports nothing.
2. ``clock(trace, host)``: ``perf_counter`` put on the trace's clock, from the
   pairs the benchmark already has on both: the ``train_step`` rows and the
   ``chipbench.train_step`` annotations, matched from the last backwards.
3. ``regions(step_text)``: HLO instruction name -> region, from the scope path
   that ``compiled.as_text()`` keeps as ``metadata={op_name=...}``; and the
   kernels' instructions by the kernels' own names.

Times are integer nanoseconds. Nothing here knows a cell or a configuration.
"""

from __future__ import annotations

import bisect
import functools
import json
import re
import statistics

from chipbench import xplane

#: the program's region names (``jax.named_scope``; ``attn`` is the module's)
VOCABULARY = ("embed", "attn", "mlp", "norm", "head_loss", "optimizer")
STEP_ANNOTATION = xplane.HOST_PREFIX + "train_step"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def say(**row):
    print(json.dumps(row, default=float), flush=True)


# -- 1. the recorder's ring ----------------------------------------------------


def ring():
    """Every record the program's recorder holds, or None where the program
    has no process-wide recorder (a commit before it had one)."""
    try:
        from pytorch_distributed_training_example_tpu.utils import telemetry
    except ImportError:
        return None
    get = getattr(telemetry, "recorder", None)
    return None if get is None else get().records()


def bounds(host):
    """``(lo, hi)`` in ``perf_counter_ns``: the measured window as the loop
    lived it. ``hi`` is the end of the last row the driver's wrappers wrote,
    ``lo`` the start of the first, but no more than ``window_s`` before
    ``hi``: the loop enqueues steps ahead of the device, so its rows begin
    before the first completion that opens the window."""
    rows = host.get("rows") or []
    if not rows:
        return None
    lo = int(min(r[1] for r in rows) * 1e9)
    hi = int(max(r[2] for r in rows) * 1e9)
    if host.get("window_s"):
        lo = max(lo, hi - int(host["window_s"] * 1e9))
    return lo, hi


def records(host, kind="span", name=None):
    """The window's records of one kind (and name), oldest first."""
    everything, span = ring(), bounds(host)
    if everything is None or span is None:
        return None
    lo, hi = span
    return [r for r in everything
            if r.kind == kind and (name is None or r.name == name)
            and lo <= r.t0 and r.t1 <= hi]


def share_of_window(host, name):
    """Percent of the window the loop spent in spans called ``name``."""
    found = records(host, name=name)
    if not found or not host.get("window_s"):
        return None
    return 100.0 * sum(r.t1 - r.t0 for r in found) / 1e9 / host["window_s"]


def self_ns(span, children):
    """A span's duration less what its child spans cover of it."""
    covered = xplane.clip(xplane.union((c.t0, c.t1) for c in children),
                          span.t0, span.t1)
    return (span.t1 - span.t0) - xplane.total(covered)


# -- 2. perf_counter on the trace's clock ----------------------------------------


def clock(trace, host):
    """``{"offset_ns", "residual_ns", "residual_max_ns", "pairs"}``: add
    ``offset_ns`` to a ``perf_counter_ns`` stamp to get the trace's time. The
    residual is how far the pairs disagree with their median (the median of
    those gaps, and the largest)."""
    if trace is None:
        return None
    rows = [r for r in host.get("rows") or [] if r[0] == "train_step"]
    events = [e for e in trace.host if e.name == STEP_ANNOTATION]
    pairs = [(e.start - int(r[1] * 1e9), e.end - int(r[2] * 1e9))
             for r, e in zip(reversed(rows), reversed(events))]
    if not pairs:
        return None
    # the annotation opens just after the row's first stamp and closes just
    # before its second: the middle of the two gaps is the offset
    offsets = [(a + b) // 2 for a, b in pairs]
    offset = int(statistics.median(offsets))
    gaps = sorted(abs(o - offset) for o in offsets)
    return {"offset_ns": offset, "pairs": len(pairs),
            "residual_ns": gaps[len(gaps) // 2], "residual_max_ns": gaps[-1]}


def innermost(spans, t):
    """The innermost of one thread's ``spans`` (sorted by start) that holds
    the instant ``t``, or None. Spans of one thread nest, so it is the one
    that started last; a top-level span that ended before ``t`` ends the
    search, since nothing before it can reach ``t``."""
    at = bisect.bisect_right(spans, t, key=lambda s: s.t0) - 1
    while at >= 0:
        s = spans[at]
        if s.t0 <= t <= s.t1:
            return s
        if s.parent is None:
            return None
        at -= 1
    return None


# -- 3. the step program's names ---------------------------------------------------


def _component(part):
    """``transpose(jvp(head_loss))`` -> ``head_loss``."""
    return part.rsplit("(", 1)[-1].split(")", 1)[0]


def region_of(op_name):
    """The innermost component of a scope path that is in the vocabulary."""
    for part in reversed(op_name.split("/")):
        if _component(part) in VOCABULARY:
            return _component(part)
    return None


def kernel_of(op_name):
    """``.../flash_fwd_online/pallas_call`` -> ``flash_fwd_online``."""
    parts = op_name.split("/")
    if len(parts) >= 2 and parts[-1].startswith("pallas_call"):
        return _component(parts[-2])
    return None


@functools.lru_cache(maxsize=2)
def names(step_text):
    """``(regions, kernels)``: instruction name -> region for every
    instruction whose scope path holds a name of the vocabulary, and
    instruction name -> kernel name for every Pallas call that has one.
    (Cached: the text runs to megabytes and five readers ask.)"""
    regions, kernels = {}, {}
    for name, rest in _INSTRUCTION.findall(step_text or ""):
        found = _OP_NAME.search(rest)
        if not found:
            continue
        if _KERNEL_TARGET in rest:
            kernel = kernel_of(found.group(1))
            if kernel:
                kernels[name] = kernel
        region = region_of(found.group(1))
        if region:
            regions[name] = region
    return regions, kernels


def whole_steps(trace):
    """``(events, runs, lo, hi)``: device 0's operations inside its whole
    executions of the step program, or None."""
    first = xplane.first_device(trace)
    if first is None:
        return None
    dev, lo, hi, runs = first
    return ([e for e in dev.ops if lo <= e.start and e.end <= hi],
            runs, lo, hi)


def _named_ms(trace, ctx, table, wanted, metric):
    """Per step, the device time of the events whose instruction ``table``
    maps to something ``wanted`` accepts. None, and a line saying so, where
    the step's text holds no such name (an executable served from the
    compilation cache carries the names of whoever compiled it first)."""
    steps = whole_steps(trace)
    if steps is None:
        return None
    hits = {n for n, v in table.items() if wanted(v)}
    if not hits:
        say(row="names", metric=metric,
            missing="no instruction of the step's text carries this name")
        return None
    events, runs, _, _ = steps
    took = sum(e.end - e.start for e in events if e.name in hits)
    return took / runs / 1e6


def region_ms(trace, ctx, region):
    return _named_ms(trace, ctx, names(ctx.get("step_text"))[0],
                     lambda r: r == region, region)


def kernel_ms(trace, ctx, prefix):
    return _named_ms(trace, ctx, names(ctx.get("step_text"))[1],
                     lambda k: k.startswith(prefix), prefix)

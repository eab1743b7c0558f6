"""What the readers of the program's start-up share.

The program's recorder (``utils/telemetry.recorder()``) holds, before the
measured window opens, the start-up as the program lived it: a ``process``
span from the operating system's start of the process to the recorder's
creation, the ``Trainer``'s ``init`` span with a child a layer, the first
``iteration`` with its ``compile`` span, and under whichever span caused it
every stage of jax's compile pipeline as a ``kind="compile"`` record named
``trace``, ``lower``, ``compile`` (XLA compiled it) or ``cache_load`` (the
persistent cache served it), with the function as ``value``.

Two readings are taken here, once, for the eight readers:

1. ``chain(host)``: five stamps cut the set-up, from ``process.t0`` to the
   window's start ``W`` (``program_spans.bounds(host)[0]``), into five parts
   with no gap between them, so they add up to ``W - process.t0``.
2. ``kinds(host, names)``: across the whole set-up, the seconds that compile
   records of some kinds cover, every instant of a thread counted once.

A program without those records (a commit before it had them) gives ``None``
from both, and every reader then reports nothing. The first reader that runs
prints one ``row: "setup"`` line. Times are integer nanoseconds. Nothing here
knows a cell or a configuration.
"""

from __future__ import annotations

from chipbench import program_spans, xplane

CHAIN = ("preinit", "init", "between", "first_step", "warmup")
#: what no cache saves; what the persistent cache served; what XLA compiled
TRACE_LOWER, CACHE_LOAD, XLA_COMPILE = ("trace", "lower"), ("cache_load",), (
    "compile",)
LONGEST = 5

_said = False


def _before_window(host):
    """``(records, W)``: the ring's records that ended before the window
    opened, oldest first, or None."""
    everything, span = program_spans.ring(), program_spans.bounds(host)
    if everything is None or span is None:
        return None
    return [r for r in everything if r.t1 <= span[0]], span[0]


def stamps(records, w):
    """The chain's six stamps, or None where the program does not record its
    own start: ``process.t0``, ``init.t0``, ``init.t1``, the first
    ``iteration.t0``, the end of that iteration's ``compile`` span, ``W``."""
    spans = [r for r in records if r.kind == "span"]
    first = lambda name, ok=lambda r: True: next(
        (r for r in spans if r.name == name and ok(r)), None)
    process, init = first("process"), first("init")
    if process is None or init is None:
        return None
    iteration = first("iteration", lambda r: r.t0 >= init.t1)
    if iteration is None:
        return None
    compiled = first("compile", lambda r: r.parent == iteration.id)
    if compiled is None:
        return None
    found = (process.t0, init.t0, init.t1, iteration.t0, compiled.t1, w)
    return found if list(found) == sorted(found) else None


def chain(host):
    """``{part: nanoseconds}`` for the five parts of ``CHAIN``, or None."""
    before = _before_window(host)
    found = before and stamps(*before)
    if not found:
        return None
    return dict(zip(CHAIN, (b - a for a, b in zip(found, found[1:]))))


def _covered(records):
    """Nanoseconds that ``records`` cover, an instant of a thread once."""
    by_thread = {}
    for r in records:
        by_thread.setdefault(r.thread, []).append((r.t0, r.t1))
    return sum(xplane.total(xplane.union(v)) for v in by_thread.values())


def pipeline(records):
    """The compile records among ``records``, or None where the recorder does
    not tell the kinds apart (it then has no ``trace`` record: every start-up
    traces something)."""
    compiles = [r for r in records if r.kind == "compile"]
    return compiles if any(r.name == "trace" for r in compiles) else None


def covered_s(compiles, names):
    """Seconds that the records called one of ``names`` cover."""
    return _covered(r for r in compiles if r.name in names) / 1e9


def _under(records):
    """By the name of the span that was open (``"-"``: none was), the compile
    records summed by kind, and the longest by function."""
    span_name = {r.id: r.name for r in records if r.kind == "span"}
    out = {}
    for r in records:
        if r.kind != "compile" or r.t1 == r.t0:
            continue
        where = "-" if r.parent is None else span_name.get(r.parent, "open")
        entry = out.setdefault(where, {"s": {}, "n": {}, "longest": []})
        entry["s"][r.name] = entry["s"].get(r.name, 0.0) + (r.t1 - r.t0) / 1e9
        entry["n"][r.name] = entry["n"].get(r.name, 0) + 1
        entry["longest"].append([(r.t1 - r.t0) / 1e9, r.name, r.value])
    for entry in out.values():
        entry["longest"] = sorted(entry["longest"], reverse=True)[:LONGEST]
    return out


def say_once(records, w):
    """The ``row: "setup"`` line: the chain, every start-up span's seconds by
    name (the spans outside an ``iteration``, and the first iteration's),
    ``init``'s children, and under each span its compile records."""
    global _said
    found = None if _said else stamps(records, w)
    if found is None:
        return
    _said = True
    init = next(r for r in records if r.kind == "span" and r.name == "init")
    spans = [r for r in records if r.kind == "span"
             and r.thread == init.thread]      # the loop's thread
    by_id = {r.id: r for r in spans}
    iteration = next(r for r in spans
                     if r.name == "iteration" and r.t0 == found[3])

    def inside(r, top):
        while r is not None and r is not top:
            r = by_id.get(r.parent)
        return r is top

    def seconds(picked):
        out = {}
        for r in picked:
            out[r.name] = out.get(r.name, 0.0) + (r.t1 - r.t0) / 1e9
        return out

    compiles = pipeline(records)
    program_spans.say(
        row="setup",
        chain_s={k: (b - a) / 1e9 for k, a, b in zip(CHAIN, found, found[1:])},
        chain_sum_s=(w - found[0]) / 1e9,
        pipeline_s=compiles and {
            "trace_lower": covered_s(compiles, TRACE_LOWER),
            "cache_load": covered_s(compiles, CACHE_LOAD),
            "xla_compile": covered_s(compiles, XLA_COMPILE)},
        spans_s=seconds(r for r in spans if r.parent is None
                        and r.name != "iteration"),
        init_s=seconds(r for r in spans if r.parent == init.id),
        first_iteration_s=seconds(r for r in spans if inside(r, iteration)),
        warmup_iterations=sum(r.name == "iteration" and r.t0 >= found[4]
                              for r in spans),
        under=_under(records))


def part(host, name):
    """One part of the chain in seconds, or None."""
    before = _before_window(host)
    found = before and stamps(*before)
    if not found:
        return None
    say_once(*before)
    at = CHAIN.index(name)
    return (found[at + 1] - found[at]) / 1e9


def kinds(host, names):
    """The set-up's seconds in compile records of the kinds ``names``."""
    before = _before_window(host)
    compiles = before and pipeline(before[0])
    if not compiles:
        return None
    say_once(*before)
    return covered_s(compiles, names)

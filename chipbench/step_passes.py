"""The step's device time by pass and by the program's own scopes.

The program reads its compiled step's text into a map (``utils/stepmap.py``:
instruction -> scope path, declared scopes, pass, kernel, wrapper, and what a
fusion holds inside); the trace has each instruction's time. ``table`` joins
the two once a run, for the five readers that are a few lines each
(``step_fwd_ms``, ``step_bwd_ms``, ``step_recompute_ms``, ``scope_mixed_pct``,
``scope_coverage_pct``), and prints ``row: "passes"``:

- ``by_pass_ms``: forward, recompute, backward, optimizer, other. They add up
  to ``total_ms``, the summed time of the step's operations without the
  wrappers (a ``while`` or a ``conditional`` spans its body's operations, which
  are counted by themselves); ``busy_ms`` is the union, as ``device_step_ms``.
- ``recompute_ms``: what the program's remat asked for and what the compiler's
  rematerialization cloned, apart; ``merged_forward_ms``: forward operations
  that carry the recomputation's path (CSE merged the twins; run once).
- ``by_scope_ms``: innermost declared scope -> forward / recompute / backward;
  ``optimizer`` and ``other`` as their own keys.
- ``kernel_calls``: each named kernel's calls a step by pass: did a forward
  run twice.
- the largest recomputed operations, mixed fusions and unnamed operations (by
  family, and the eight largest with the path they do have: what
  ``stepmap.SCOPES`` lacks, or what the compiler made and no scope names);
  ``parse_s`` and ``join_s``: what the five readers cost the traced run.

A fusion is booked whole to its root's scope and pass; one that holds more
than one declared scope or pass inside is *flagged* (``scope_mixed_pct``), not
split. A commit whose program has no ``stepmap`` gives nothing and no line.
"""

from __future__ import annotations

import collections
import time

from chipbench import program_spans, xplane

MODEL_PASSES = ("forward", "recompute", "backward")


def stepmap():
    """The program's module, or None on a commit that has none."""
    try:
        from pytorch_distributed_training_example_tpu.utils import stepmap
    except ImportError:
        return None
    return stepmap


def _top(table, entries, n=8):
    per = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return [{"op": name, "ms": ms, "pass": entries[name].pass_,
             "result": entries[name].result.strip()[:60],
             "path": entries[name].path[-90:]} if name in entries else
            {"op": name, "ms": ms} for name, ms in per]


def _join(steps, busy, entries, passes):
    events, runs, _, _ = steps
    ms = lambda ns: ns / runs / 1e6
    by_pass = dict.fromkeys(passes, 0)
    by = {"program": 0, "compiler": 0, "merged": 0}
    by_scope, calls = {}, {}
    recomputed, mixed, unnamed, unnamed_ops = (
        collections.Counter() for _ in range(4))
    named, mixed_spans, wrappers, unmapped = [], [], 0, 0
    for e in events:
        took = e.end - e.start
        entry = entries.get(e.name)
        if entry is not None and entry.wrapper:
            wrappers += took
            continue
        if entry is None or not (entry.scopes or entry.kernel):
            unnamed[e.name.split(".")[0]] += took
            unnamed_ops[e.name] += took
        else:
            named.append((e.start, e.end))
        if entry is None:       # an operation the step's text does not have
            by_pass["other"] += took
            unmapped += took
            continue
        by_pass[entry.pass_] += took
        if entry.by:
            by[entry.by] += took
        if entry.pass_ in MODEL_PASSES:
            by_scope.setdefault(entry.scope or "unnamed", dict.fromkeys(
                MODEL_PASSES, 0))[entry.pass_] += took
        if entry.kernel:
            at = calls.setdefault(entry.kernel, dict.fromkeys(MODEL_PASSES, 0))
            at[entry.pass_] = at.get(entry.pass_, 0) + 1
        if entry.pass_ == "recompute":
            recomputed[e.name] += took
        if entry.mixed:
            mixed[e.name] += took
            mixed_spans.append((e.start, e.end))
    scopes = {k: {p: ms(v) for p, v in at.items()} for k, at in sorted(
        by_scope.items(), key=lambda kv: -sum(kv[1].values()))}
    scopes["optimizer"], scopes["other"] = (ms(by_pass["optimizer"]),
                                            ms(by_pass["other"]))
    per_op = lambda table: {k: ms(v) for k, v in table.items()}
    return {
        "steps": runs, "busy_ms": ms(busy),
        "total_ms": ms(sum(by_pass.values())), "wrapper_ms": ms(wrappers),
        "unmapped_ms": ms(unmapped),
        "by_pass_ms": {k: ms(v) for k, v in by_pass.items()},
        "recompute_ms": {"program": ms(by["program"]),
                         "compiler": ms(by["compiler"])},
        "merged_forward_ms": ms(by["merged"]),
        "by_scope_ms": scopes,
        "kernel_calls": {k: {p: n / runs for p, n in at.items()}
                         for k, at in sorted(calls.items())},
        "mixed_pct": 100.0 * xplane.total(xplane.union(mixed_spans)) / busy,
        "coverage_pct": 100.0 * xplane.total(xplane.union(named)) / busy,
        "top_recomputed": _top(per_op(recomputed), entries),
        "top_mixed": [dict(row, inner=sorted(
            f"{s}:{p}" for s, p in entries[row["op"]].inner))
            for row in _top(per_op(mixed), entries)],
        "unnamed_ms": dict(sorted(per_op(unnamed).items(),
                                  key=lambda kv: -kv[1])[:12]),
        "top_unnamed": _top(per_op(unnamed_ops), entries),
    }


def table(trace, ctx):
    """The join, once a run (``row: "passes"`` is printed with it); None where
    the program has no ``stepmap``, the trace no whole step on a device, or
    the step's text no scope path."""
    program = stepmap()
    if program is None:
        return None
    text = ctx.get("step_text")
    done = ctx.get("step_passes")        # (the trace, its table): once a run
    if done is not None and done[0] is trace:
        return done[1]
    t0 = time.perf_counter()
    entries = program.step_map(text)
    parse_s = time.perf_counter() - t0
    steps = program_spans.whole_steps(trace)
    found = None
    if not any(e.path for e in entries.values()):
        program_spans.say(row="names", metric="step_passes",
                          missing="the step's text holds no scope path")
    elif steps is None:
        # off the chip: what the text alone says
        program_spans.say(row="passes", steps=0, **program.summary(entries))
    else:
        dev, lo, hi, _ = xplane.first_device(trace)
        found = _join(steps, xplane.busy(dev, lo, hi), entries,
                      program.PASSES)
        # what the five readers cost the traced run: one parse, one join
        program_spans.say(row="passes", **found, text_mb=len(text) / 1e6,
                          parse_s=parse_s,
                          join_s=time.perf_counter() - t0 - parse_s)
    ctx["step_passes"] = trace, found
    return found


def pass_ms(trace, ctx, pass_):
    found = table(trace, ctx)
    return None if found is None else found["by_pass_ms"][pass_]

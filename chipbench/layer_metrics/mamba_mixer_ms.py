"""Per step on device 0: the device time of the operations whose scope path
holds ``mamba``, the state-space mixer as a whole (its two projections, the
conv, the scan and the gated norm; forward, recomputation and backward).

``scope_ms`` is shared with the other readers of scopes that are not in
``program_spans.VOCABULARY``. A fusion carries its root's scope, as there. A
program whose step has no such scope gives nothing, and a line saying so. An
earlier line (``row: "mamba"``) splits the time by the mixer's inner scopes and
names the operations that took most, with their result's shape.
"""
import functools

from chipbench import program_spans

INNER = ("conv1d", "ssd", "gated_norm", "in_proj", "out_proj")


@functools.lru_cache(maxsize=4)
def instructions(step_text, scope):
    """``{instruction name: (scope path, result type)}`` for the step's
    instructions whose scope path has the component ``scope``
    (``transpose(jvp(ssd))`` counts as ``ssd``)."""
    hits = {}
    for name, rest in program_spans._INSTRUCTION.findall(step_text or ""):
        found = program_spans._OP_NAME.search(rest)
        if found and any(program_spans._component(part) == scope
                         for part in found.group(1).split("/")):
            hits[name] = (found.group(1), rest.split(" ", 1)[0])
    return hits


def _under(trace, ctx, scope):
    """``(hits, events, runs)``: the scope's instructions and device 0's
    operations inside its whole steps; None where either is missing."""
    steps = program_spans.whole_steps(trace)
    if steps is None:
        return None
    hits = instructions(ctx.get("step_text"), scope)
    if not hits:
        program_spans.say(
            row="names", metric=scope,
            missing="no instruction of the step's text carries this scope")
        return None
    return hits, steps[0], steps[1]


def scope_ms(trace, ctx, scope):
    found = _under(trace, ctx, scope)
    if found is None:
        return None
    hits, events, runs = found
    return sum(e.end - e.start for e in events if e.name in hits) / runs / 1e6


def read(trace, host, ctx):
    found = _under(trace, ctx, "mamba")
    if found is None:
        return None
    hits, events, runs = found
    by_op, by_inner = {}, {}
    for e in events:
        if e.name not in hits:
            continue
        took = (e.end - e.start) / runs / 1e6
        by_op[e.name] = by_op.get(e.name, 0.0) + took
        parts = [program_spans._component(p)
                 for p in hits[e.name][0].split("/")]
        inner = next((p for p in parts if p in INNER), "other")
        by_inner[inner] = by_inner.get(inner, 0.0) + took
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:8]
    program_spans.say(
        row="mamba", steps=runs, by_scope_ms=dict(sorted(
            by_inner.items(), key=lambda kv: -kv[1])),
        top_ops=[{"op": name, "ms": took, "result": hits[name][1],
                  "scope": hits[name][0].split("mamba", 1)[-1][-90:]}
                 for name, took in top])
    return sum(by_op.values())

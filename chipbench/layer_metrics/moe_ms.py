"""Per step on device 0: the device time of the operations whose scope path
holds ``moe``, the expert layer as a whole (router, sort and gathers, the
grouped matmuls of the routed experts, the shared expert, the combine;
forward, recomputation and backward).

Built on ``mamba_mixer_ms``'s reading of scopes. ``scopes_ms`` is shared with
the other readers of the expert layer: it leaves out the instructions that
only wrap others (a ``cond`` and the ``while`` of a loop carry their
body's scope and, where the trace shows them, span their body's operations,
which are counted by themselves). A program whose step has no such scope
gives nothing, and a line saying so. An earlier line (``row: "moe"``) splits
the time by the layer's inner scopes and names the operations that took most.
"""
from chipbench import program_spans
from chipbench.layer_metrics import mamba_mixer_ms

INNER = ("moe_router", "moe_dispatch", "moe_experts", "moe_shared",
         "moe_combine")
WRAPPERS = ("cond", "conditional", "while", "call")


def leaves(trace, ctx, scope):
    """``(hits, events, runs)`` as ``mamba_mixer_ms._under`` gives them,
    without the wrapping instructions; None where the scope is missing."""
    found = mamba_mixer_ms._under(trace, ctx, scope)
    if found is None:
        return None
    hits, events, runs = found
    hits = {name: at for name, at in hits.items()
            if name.split(".")[0] not in WRAPPERS}
    return hits, events, runs


def scopes_ms(trace, ctx, scopes):
    """Per step, the time under any of ``scopes`` (an operation under two of
    them counts once); None where none of them is in the step's text."""
    names, events, runs = set(), None, None
    for scope in scopes:
        found = leaves(trace, ctx, scope)
        if found is not None:
            names |= set(found[0])
            events, runs = found[1], found[2]
    if events is None:
        return None
    return sum(e.end - e.start for e in events if e.name in names) / runs / 1e6


def read(trace, host, ctx):
    found = leaves(trace, ctx, "moe")
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
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    program_spans.say(
        row="moe", steps=runs, by_scope_ms=dict(sorted(
            by_inner.items(), key=lambda kv: -kv[1])),
        top_ops=[{"op": name, "ms": took, "result": hits[name][1],
                  "scope": hits[name][0].split("/moe/", 1)[-1][-90:]}
                 for name, took in top])
    return sum(by_op.values())

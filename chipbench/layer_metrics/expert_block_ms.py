"""Per step on device 0: the device time of the expert layer as a whole where
the router does not live inside it: the operations whose scope path holds
``moe`` (the held experts' module: the sort and gathers, the grouped matmuls,
the combine) **or** ``moe_router`` (the router, which a ``smallthinker`` block
runs on the attention's input, ahead of attention and outside ``moe``);
forward, recomputation and backward. An operation under both counts once.

Built on ``moe_ms``'s reading of scopes (the instructions that only wrap
others, a ``cond`` or a ``while``, are left out: their bodies' operations are
counted by themselves). A program whose step has neither scope gives nothing.
An earlier line (``row: "moe"``) splits the time by the layer's inner scopes
and names the operations that took most.
"""
from chipbench import program_spans
from chipbench.layer_metrics import moe_ms

SCOPES = ("moe", "moe_router")
INNER = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")


def read(trace, host, ctx):
    hits, events, runs = {}, None, None
    for scope in SCOPES:
        found = moe_ms.leaves(trace, ctx, scope)
        if found is not None:
            hits.update(found[0])
            events, runs = found[1], found[2]
    if events is None:
        return None
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
                  "scope": hits[name][0][-90:]} for name, took in top])
    return sum(by_op.values())

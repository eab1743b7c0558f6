"""Per step on device 0: the device time of the operations whose scope path
holds ``gated_delta_net``, the delta-rule mixer of a ``qwen3_next`` model as a
whole (its two input projections, the conv with its silu, the gates and the
norms of ``q`` and ``k``, the chunked gated delta rule, the head norm with its
gate, the output projection; forward, recomputation and backward, every held
``linear_attention`` layer).

Built on ``moe_ms``'s reading of scopes: the rule's chunks run under a
``while`` whose instruction carries the scope of its body and, where the trace
shows it, spans the body's operations, which are counted by themselves. A
program whose step has no such scope gives nothing, and a line saying so. An
earlier line (``row: "gated_delta_net"``) splits the time by the mixer's inner
scopes and names the operations that took most, with their result's shape.
"""
from chipbench import program_spans
from chipbench.layer_metrics import moe_ms

SCOPE = "gated_delta_net"
INNER = ("in_proj", "conv_silu", "delta_rule", "gate_norm", "out_proj")


def read(trace, host, ctx):
    found = moe_ms.leaves(trace, ctx, SCOPE)
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
        row=SCOPE, steps=runs, by_scope_ms=dict(sorted(
            by_inner.items(), key=lambda kv: -kv[1])),
        top_ops=[{"op": name, "ms": took, "result": hits[name][1],
                  "scope": hits[name][0].split(SCOPE, 1)[-1][-90:]}
                 for name, took in top])
    return sum(by_op.values())

"""Per step on device 0: the device time of the operations whose scope path
holds ``mla``, latent attention as a whole in every block that has it (the
prediction module's among them): the two low-rank paths with their latent
norms, both rotary terms and the broadcast of the shared rope key, the flash
kernels and the transposes around them, the out projection; forward,
recomputation and backward.

Built on ``moe_ms``'s reading of scopes (the instructions that only wrap
others are left out). A program whose step has no such scope gives nothing,
and a line saying so. An earlier line (``row: "mla"``) splits the time by the
inner scopes ``mla_q``, ``mla_kv``, ``mla_rope``, ``mla_out``, the kernels by
their own names, and ``other`` (what lies between the scopes and the
kernels), and names the operations that took most.
"""
from chipbench import program_spans
from chipbench.layer_metrics import moe_ms

INNER = ("mla_q", "mla_kv", "mla_rope", "mla_out")


def read(trace, host, ctx):
    found = moe_ms.leaves(trace, ctx, "mla")
    if found is None:
        return None
    hits, events, runs = found
    kernels = program_spans.names(ctx.get("step_text"))[1]
    by_op, by_inner = {}, {}
    for e in events:
        if e.name not in hits:
            continue
        took = (e.end - e.start) / runs / 1e6
        by_op[e.name] = by_op.get(e.name, 0.0) + took
        parts = [program_spans._component(p)
                 for p in hits[e.name][0].split("/")]
        inner = kernels.get(e.name) or next(
            (p for p in parts if p in INNER), "other")
        by_inner[inner] = by_inner.get(inner, 0.0) + took
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    program_spans.say(
        row="mla", steps=runs, by_scope_ms=dict(sorted(
            by_inner.items(), key=lambda kv: -kv[1])),
        top_ops=[{"op": name, "ms": took, "result": hits[name][1],
                  "scope": hits[name][0][-90:]} for name, took in top])
    return sum(by_op.values())

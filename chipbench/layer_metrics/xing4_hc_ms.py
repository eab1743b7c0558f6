"""Per step on device 0: the device time of the operations whose scope path
holds ``hc``, the hyper-connections of an ``xing4`` model as a whole (two a
layer: the norm over the ``hc_mult`` streams, the ``phi`` product, the
sigmoids, the Sinkhorn iterations, the read ``H_pre X``, and after the branch
the write ``H_res X + H_post^T y``; forward, recomputation and backward).

Built on ``moe_ms``'s reading of scopes (the instructions that only wrap
others are left out). A program whose step has no such scope gives nothing,
and a line saying so. An earlier line (``row: "hc"``) splits the time by the
inner scopes ``hc_maps``, ``hc_sinkhorn``, ``hc_read``, ``hc_write`` (and
``other``: what carries ``hc`` and none of the four) and, where the program
has a step map (``utils/stepmap.py``), by pass within each, and names the ten
operations that took most, with their result's shape.
"""
from chipbench import program_spans, step_passes
from chipbench.layer_metrics import moe_ms

SCOPE = "hc"
INNER = ("hc_maps", "hc_sinkhorn", "hc_read", "hc_write")


def read(trace, host, ctx):
    found = moe_ms.leaves(trace, ctx, SCOPE)
    if found is None:
        return None
    hits, events, runs = found
    # PR 52's five readers of the step's map list the cells they had; here the
    # join is asked for by name, for its line (``row: "passes"``: the step by
    # pass and scope, what no scope names) and for each operation's pass
    step_passes.table(trace, ctx)
    program = step_passes.stepmap()
    entries = program.step_map(ctx.get("step_text")) if program else {}
    by_op, by_inner = {}, {}
    for e in events:
        if e.name not in hits:
            continue
        took = (e.end - e.start) / runs / 1e6
        by_op[e.name] = by_op.get(e.name, 0.0) + took
        parts = [program_spans._component(p)
                 for p in hits[e.name][0].split("/")]
        inner = next((p for p in parts if p in INNER), "other")
        pass_ = entries[e.name].pass_ if e.name in entries else "all"
        at = by_inner.setdefault(inner, {})
        at[pass_] = at.get(pass_, 0.0) + took
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    program_spans.say(
        row=SCOPE, steps=runs, by_scope_ms=dict(sorted(
            by_inner.items(), key=lambda kv: -sum(kv[1].values()))),
        top_ops=[{"op": name, "ms": took, "result": hits[name][1],
                  "scope": hits[name][0].split("/hc/", 1)[-1][-90:]}
                 for name, took in top])
    return sum(by_op.values())

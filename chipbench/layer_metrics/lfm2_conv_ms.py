"""Per step on device 0: the device time of the operations whose scope path
holds ``short_conv``, the doubly gated short convolution of an ``lfm2_moe``
model as a whole (its two projections and the gated conv between them;
forward, recomputation and backward, every held conv layer).

Built on ``mamba_mixer_ms``'s reading of scopes (its ``scope_ms`` gives this
sum alone). A program whose step has no such scope gives nothing, and a line
saying so. An earlier line (``row: "short_conv"``) splits the time by the
operator's inner scopes and names the operations that took most, with their
result's shape.
"""
from chipbench import program_spans
from chipbench.layer_metrics import mamba_mixer_ms

SCOPE = "short_conv"
INNER = ("in_proj", "conv_gate", "out_proj")


def read(trace, host, ctx):
    found = mamba_mixer_ms._under(trace, ctx, SCOPE)
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
        row=SCOPE, steps=runs, by_scope_ms=dict(sorted(
            by_inner.items(), key=lambda kv: -kv[1])),
        top_ops=[{"op": name, "ms": took, "result": hits[name][1],
                  "scope": hits[name][0].split(SCOPE, 1)[-1][-90:]}
                 for name, took in top])
    return sum(by_op.values())

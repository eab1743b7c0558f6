"""Share of device 0's busy time per step that falls to an operation with a
name: a region of the program's vocabulary or a named kernel. What is left is
unnamed. An earlier line (``row: "regions"``) gives the time by region and by
kernel, and the unnamed operations that took most."""
from chipbench import program_spans, xplane


def read(trace, host, ctx):
    steps = program_spans.whole_steps(trace)
    if steps is None:
        return None
    regions, kernels = program_spans.names(ctx.get("step_text"))
    if not regions and not kernels:
        program_spans.say(row="names", metric="region_coverage_pct",
                          missing="the step's text holds no name")
        return None
    events, runs, lo, hi = steps
    by_region, by_kernel, unnamed, named = {}, {}, {}, []
    for e in events:
        took = e.end - e.start
        if e.name in kernels:
            by_kernel[kernels[e.name]] = by_kernel.get(kernels[e.name], 0) + took
        if e.name in regions:
            by_region[regions[e.name]] = by_region.get(regions[e.name], 0) + took
        if e.name in kernels or e.name in regions:
            named.append((e.start, e.end))
        else:
            family = e.name.split(".")[0]
            unnamed[family] = unnamed.get(family, 0) + took
    per_step = lambda table: {k: v / runs / 1e6 for k, v in sorted(
        table.items(), key=lambda kv: -kv[1])}
    busy = xplane.busy(trace.devices[0], lo, hi)
    program_spans.say(
        row="regions", steps=runs, busy_ms=busy / runs / 1e6,
        region_ms=per_step(by_region), kernel_ms=per_step(by_kernel),
        unnamed_ms=dict(list(per_step(unnamed).items())[:12]))
    return 100.0 * xplane.total(xplane.union(named)) / busy

"""Share of device 0's idle time in the traced window that has a name: the
middle of the gap lies inside a span of the loop's thread other than
``iteration`` itself, once ``perf_counter`` is on the trace's clock. Earlier
lines give the clock's offset and residual (``row: "clock"``) and the idle
time by innermost span (``row: "idle_by_span"``)."""
from chipbench import program_spans, xplane


def read(trace, host, ctx):
    first = xplane.first_device(trace)
    clock = program_spans.clock(trace, host)
    if clock is not None:
        program_spans.say(row="clock", offset_ns=clock["offset_ns"],
                          pairs=clock["pairs"],
                          residual_ms=clock["residual_ns"] / 1e6,
                          residual_max_ms=clock["residual_max_ns"] / 1e6)
    spans = program_spans.records(host)
    if first is None or clock is None or not spans:
        return None
    dev, lo, hi, _ = first
    loop = {s.thread for s in spans if s.name == "iteration"}
    offset = clock["offset_ns"]
    mine = sorted((s._replace(t0=s.t0 + offset, t1=s.t1 + offset)
                   for s in spans if s.thread in loop), key=lambda s: s.t0)
    loop_end = max(s.t1 for s in mine)
    by_span, idle, after_loop = {}, 0, 0
    for a, b in xplane.gaps(xplane.union(xplane.spans(dev.ops)), lo, hi):
        inside = program_spans.innermost(mine, (a + b) // 2)
        name = inside.name if inside is not None else "outside"
        by_span[name] = by_span.get(name, 0) + b - a
        idle += b - a
        if (a + b) // 2 > loop_end:
            after_loop += b - a
    # the driver lets the device finish the steps the loop enqueued ahead:
    # idle time after the loop's last span belongs to no span by definition
    program_spans.say(
        row="idle_by_span", idle_ms=idle / 1e6, after_loop_ms=after_loop / 1e6,
        ms={k: v / 1e6 for k, v in
            sorted(by_span.items(), key=lambda kv: -kv[1])})
    if not idle:
        return None
    named = sum(v for k, v in by_span.items()
                if k not in ("iteration", "outside"))
    return 100.0 * named / idle

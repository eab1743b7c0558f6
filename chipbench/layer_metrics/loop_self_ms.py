"""Median self time of the loop's ``iteration`` span: its duration less what
its child spans (``input_wait``, ``dispatch``, ``metrics_fetch``,
``checkpoint_save``) cover. An earlier line (``row: "spans"``) counts the
window's spans by name, gives their medians, and says how much longer the
``dispatch`` span took than the benchmark's wrapper, which runs inside it,
over the same calls."""
import statistics

from chipbench import program_spans


def read(trace, host, ctx):
    spans = program_spans.records(host)
    if not spans:
        return None
    by_name, children = {}, {}
    for s in spans:
        by_name.setdefault(s.name, []).append((s.t1 - s.t0) / 1e6)
        children.setdefault(s.parent, []).append(s)
    iterations = [s for s in spans if s.name == "iteration"]
    # the same calls timed by the program's span and, inside it, by the
    # benchmark's wrapper (which the driver sets in ``train_step``'s place),
    # matched from the last call backwards
    wrapped = [r for r in host["rows"] if r[0] == "train_step"]
    enqueues = [s for s in spans if s.name == "dispatch"]
    around = [(s.t1 - s.t0) / 1e6 - 1e3 * (r[2] - r[1])
              for r, s in zip(reversed(wrapped), reversed(enqueues))]
    program_spans.say(
        row="spans", per_iteration=len(spans) / max(len(iterations), 1),
        median_ms={k: statistics.median(v) for k, v in by_name.items()},
        count={k: len(v) for k, v in by_name.items()},
        dispatch_minus_wrapper_ms=(statistics.median(around)
                                   if around else None))
    if not iterations:
        return None
    return statistics.median(
        program_spans.self_ns(s, children.get(s.id, ())) for s in iterations
    ) / 1e6

"""Share of the window the trainer's loop spent inside ``next()`` of its batch
iterator (host clock, the benchmark's own wrapper)."""


def read(trace, host, ctx):
    rows = [r for r in host["rows"] if r[0] == "next"]
    if not rows or not host["window_s"]:
        return None
    return 100.0 * sum(t1 - t0 for _, t0, t1 in rows) / host["window_s"]

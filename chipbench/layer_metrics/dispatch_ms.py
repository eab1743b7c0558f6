"""Median host time inside one ``train_step`` call of the loop: the enqueue
(host clock, the benchmark's own wrapper)."""
import statistics


def read(trace, host, ctx):
    rows = [t1 - t0 for kind, t0, t1 in host["rows"] if kind == "train_step"]
    return 1e3 * statistics.median(rows) if rows else None

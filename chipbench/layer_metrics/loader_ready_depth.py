"""Median over the window's batches of the program's counter
``loader.ready_depth``: how many finished batches waited when the loop asked
for the next (0: it blocked on the workers)."""
import statistics

from chipbench import program_spans


def read(trace, host, ctx):
    readings = program_spans.records(host, kind="counter",
                                     name="loader.ready_depth")
    return statistics.median(r.value for r in readings) if readings else None

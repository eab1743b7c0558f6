"""Across the whole set-up, the seconds in ``trace`` (Python to jaxpr, the
outermost function only) and ``lower`` (jaxpr to StableHLO) records: the part
of compiling that no compilation cache saves."""
from chipbench import setup_spans


def read(trace, host, ctx):
    return setup_spans.kinds(host, setup_spans.TRACE_LOWER)

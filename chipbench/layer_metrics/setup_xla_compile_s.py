"""Across the whole set-up, the seconds in backend-compile records that XLA
compiled: 0 in a warm run, most of a cold one."""
from chipbench import setup_spans


def read(trace, host, ctx):
    return setup_spans.kinds(host, setup_spans.XLA_COMPILE)

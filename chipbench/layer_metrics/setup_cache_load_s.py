"""Across the whole set-up, the seconds in backend-compile records that the
persistent cache served: the key, then reading, deserializing and loading the
executable."""
from chipbench import setup_spans


def read(trace, host, ctx):
    return setup_spans.kinds(host, setup_spans.CACHE_LOAD)

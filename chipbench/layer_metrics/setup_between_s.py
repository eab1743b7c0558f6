"""From the end of ``init`` to the start of the first ``iteration``: what the
caller does between building the ``Trainer`` and entering the loop (here the
benchmark's seeded weights and rows)."""
from chipbench import setup_spans


def read(trace, host, ctx):
    return setup_spans.part(host, "between")

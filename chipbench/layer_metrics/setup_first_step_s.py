"""From the start of the first ``iteration`` to the end of its ``compile`` span:
the first batch, then trace, lower, compile or load, and the first execution
of the step."""
from chipbench import setup_spans


def read(trace, host, ctx):
    return setup_spans.part(host, "first_step")

"""From the end of the program's ``compile`` span to the window's start: the
warm-up steps and the benchmark's captures."""
from chipbench import setup_spans


def read(trace, host, ctx):
    return setup_spans.part(host, "warmup")

"""The ``Trainer``'s ``init`` span: mesh, model, data, the initial state and a
restore. Its children are on the ``row: "setup"`` line."""
from chipbench import setup_spans


def read(trace, host, ctx):
    return setup_spans.part(host, "init")

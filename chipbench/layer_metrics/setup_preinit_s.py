"""From the operating system's start of the process (``process.t0``) to the
start of the ``Trainer``'s ``init`` span: the interpreter, the imports, the
backend's start and the caller's preamble."""
from chipbench import setup_spans


def read(trace, host, ctx):
    return setup_spans.part(host, "preinit")

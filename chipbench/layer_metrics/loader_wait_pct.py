"""Share of the window the trainer's loop spent blocked on the host loader:
the program's ``loader_wait`` spans (``data/prefetch.py``)."""
from chipbench import program_spans


def read(trace, host, ctx):
    return program_spans.share_of_window(host, "loader_wait")

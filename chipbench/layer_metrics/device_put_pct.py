"""Share of the window the trainer's loop spent in the host-to-device put:
the program's ``device_put`` spans (``data/prefetch.py``)."""
from chipbench import program_spans


def read(trace, host, ctx):
    return program_spans.share_of_window(host, "device_put")

"""Per step on device 0: the device time of the operations whose scope path
holds ``optimizer`` (clipping, the update, the gradient norm)."""
from chipbench import program_spans


def read(trace, host, ctx):
    return program_spans.region_ms(trace, ctx, "optimizer")

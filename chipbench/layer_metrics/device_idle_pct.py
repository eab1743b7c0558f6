"""Share of the traced window in which no operation ran on device 0."""
from chipbench import xplane


def read(trace, host, ctx):
    first = xplane.first_device(trace)
    if first is None:
        return None
    dev, lo, hi, _ = first
    return 100.0 * (1.0 - xplane.busy(dev, lo, hi) / (hi - lo))

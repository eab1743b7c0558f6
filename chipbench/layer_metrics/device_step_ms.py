"""Device busy time per execution of the step program, on device 0: the union
of its operations' intervals over the whole executions in the trace."""
from chipbench import xplane


def read(trace, host, ctx):
    first = xplane.first_device(trace)
    if first is None:
        return None
    dev, lo, hi, runs = first
    return xplane.busy(dev, lo, hi) / runs / 1e6

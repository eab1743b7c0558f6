"""Per step on device 0: the summed duration of the Pallas kernels, i.e. of
the events whose HLO instruction in the compiled step is a
``tpu_custom_call`` (the kernels carry no name of their own yet)."""
import re

from chipbench import xplane

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*custom_call_target="tpu_custom_call"',
    re.M)


def kernel_names(step_text):
    return set(_INSTRUCTION.findall(step_text or ""))


def read(trace, host, ctx):
    names = kernel_names(ctx.get("step_text"))
    first = xplane.first_device(trace)
    if not names or first is None:
        return None
    dev, lo, hi, runs = first
    events = [e for e in dev.ops if e.name.lstrip("%") in names
              and lo <= e.start and e.end <= hi]
    if not events:
        return None
    return sum(e.end - e.start for e in events) / runs / 1e6

"""Per step on device 0: the summed duration of the collective operations
(all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute): the
union of their operations on the core's line and of the asynchronous ones
from start to done.

Kept with the rehearsal files, not under ``chipbench/``: tested on a hand-made
trace (``tests/chipbench/test_xplane.py``), read in no cell yet. The four-chip
cell brings it with it once a real trace has proved it.
"""
import re

from chipbench import xplane

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)")


def read(trace, host, ctx):
    first = xplane.first_device(trace)
    if first is None:
        return None
    dev, lo, hi, runs = first
    events = [e for e in dev.ops + dev.flights
              if COLLECTIVE.match(e.name)
              and lo <= e.start and e.end <= hi]
    if not events:
        return None
    return xplane.total(xplane.union(xplane.spans(events))) / runs / 1e6

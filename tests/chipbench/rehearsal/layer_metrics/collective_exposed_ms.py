"""Per step on device 0: the part of the collective operations' time during
which no other operation runs there - communication that compute does not
hide.

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
    inside = lambda events: [e for e in events
                             if lo <= e.start and e.end <= hi]
    comm = [e for e in inside(dev.ops + dev.flights)
            if COLLECTIVE.match(e.name)]
    if not comm:
        return None
    rest = [e for e in inside(dev.ops) if not COLLECTIVE.match(e.name)]
    exposed = xplane.subtract(xplane.spans(comm), xplane.spans(rest))
    return xplane.total(exposed) / runs / 1e6

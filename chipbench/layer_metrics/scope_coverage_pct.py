"""Share of device 0's busy time per step whose operation lies under a name
the program declares (``utils/stepmap.SCOPES``) or is a named kernel; the
wrappers (``while``, ``conditional``) are left out and their bodies' operations
counted by themselves. ``row: "passes"`` names the unnamed operations that
took most."""
from chipbench import step_passes


def read(trace, host, ctx):
    found = step_passes.table(trace, ctx)
    return None if found is None else found["coverage_pct"]

"""Share of device 0's busy time per step in fusions that hold more than one
declared innermost scope or more than one pass inside: how far a reader that
books a fusion to its root's scope can be trusted in this cell. ``row:
"passes"`` names the largest."""
from chipbench import step_passes


def read(trace, host, ctx):
    found = step_passes.table(trace, ctx)
    return None if found is None else found["mixed_pct"]

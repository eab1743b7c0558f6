"""Per step on device 0: the device time of the operations that run a
forward again for the backward: what the blocks' ``remat`` left in the compiled
step (``rematted_computation`` in the scope path) and what the compiler's
rematerialization cloned (``<instruction>.remat``), together. ``row:
"passes"`` has the two apart and the largest of them."""
from chipbench import step_passes


def read(trace, host, ctx):
    return step_passes.pass_ms(trace, ctx, "recompute")

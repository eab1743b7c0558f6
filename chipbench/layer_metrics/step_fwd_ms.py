"""Per step on device 0: the device time of the operations whose pass is
``forward`` (their scope path lies under ``jvp(Model)``, or under a
recomputation's with no forward twin left: ``chipbench/step_passes.py``)."""
from chipbench import step_passes


def read(trace, host, ctx):
    return step_passes.pass_ms(trace, ctx, "forward")

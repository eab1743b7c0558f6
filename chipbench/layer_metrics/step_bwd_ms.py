"""Per step on device 0: the device time of the operations whose pass is
``backward`` (their scope path lies under ``transpose(jvp(Model))`` and not
under a recomputation: ``chipbench/step_passes.py``)."""
from chipbench import step_passes


def read(trace, host, ctx):
    return step_passes.pass_ms(trace, ctx, "backward")

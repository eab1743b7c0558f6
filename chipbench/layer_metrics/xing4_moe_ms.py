"""Per step on device 0: the device time of the operations under ``moe`` in
every expert layer of an ``xing4`` model (router, sort and gathers, the
grouped matmuls of the held experts at 3584 x 1024, the shared expert, the
combine; forward, recomputation and backward). The accepted reader's number
under this cell's own name; its ``row: "moe"`` line splits the time by the
layer's inner scopes."""
from chipbench.layer_metrics import moe_ms


def read(trace, host, ctx):
    return moe_ms.read(trace, host, ctx)

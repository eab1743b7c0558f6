"""Per step on device 0: the device time of a ``nemotron_h`` model's expert
layers under ``moe_router``, ``moe_dispatch`` and ``moe_combine`` together:
the router's product and scores, the choice, the sort, the index arithmetic
and the gathers into and out of the experts' layout; what is not a matmul of
an expert. The accepted reader's number under this cell's own name."""
from chipbench.layer_metrics import moe_route_ms


def read(trace, host, ctx):
    return moe_route_ms.read(trace, host, ctx)

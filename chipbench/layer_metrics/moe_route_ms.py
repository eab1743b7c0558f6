"""Per step on device 0: the device time of the expert layer's operations
under ``moe_router``, ``moe_dispatch`` and ``moe_combine`` together: the
router's product and scores, the choice, the sort, the index arithmetic and
the gathers into and out of the experts' layout, forward, recomputation and
backward. What is not a matmul of an expert."""
from chipbench.layer_metrics import moe_ms


def read(trace, host, ctx):
    return moe_ms.scopes_ms(trace, ctx, ("moe_router", "moe_dispatch",
                                         "moe_combine"))

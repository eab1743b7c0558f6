"""Per step on device 0: the device time of the expert layer's operations
under ``moe_router`` (wherever the block runs it: a ``smallthinker`` block
routes ahead of attention), ``moe_dispatch`` and ``moe_combine`` together:
the router's float32 product, the top-k and its softmax, the counts, the
sorts, the index arithmetic and the gathers into and out of the experts'
layout; forward, recomputation and backward. What is not a matmul of an
expert."""
from chipbench.layer_metrics import moe_route_ms


def read(trace, host, ctx):
    # the same three scopes as the accepted reader: scopes are found
    # wherever they lie in the path, inside ``moe`` or not
    return moe_route_ms.read(trace, host, ctx)

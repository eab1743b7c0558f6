"""Per step on device 0: the device time of a ``qwen3_next`` model's expert
layers under ``moe_router``, ``moe_dispatch`` and ``moe_combine`` together:
the router's float32 product over 512 outputs, the top-10 and its softmax,
the counts, the sorts of 81,920 pairs, the index arithmetic and the gathers
into and out of the experts' layout; what is not a matmul of an expert. The
accepted reader's number under this cell's own name."""
from chipbench.layer_metrics import moe_route_ms


def read(trace, host, ctx):
    return moe_route_ms.read(trace, host, ctx)

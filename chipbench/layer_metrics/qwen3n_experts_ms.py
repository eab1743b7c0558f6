"""Per step on device 0: the device time of the operations under
``moe_experts`` in a ``qwen3_next`` model: the held experts' gated-FFN kernels
(32 experts of 2048 x 512 a layer, 160 rows an expert expected), forward,
recomputation and backward; the shared expert lies under ``moe_shared`` and is
not in it. The accepted reader's number under this cell's own name."""
from chipbench.layer_metrics import moe_experts_ms


def read(trace, host, ctx):
    return moe_experts_ms.read(trace, host, ctx)

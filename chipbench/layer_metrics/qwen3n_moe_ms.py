"""Per step on device 0: the device time of the operations under ``moe`` in
every layer of a ``qwen3_next`` model: the block's whole expert FFN (the
router's float32 product over 512 outputs, its top-10 and softmax, the sorts
and gathers, the held SwiGLU experts' grouped matmuls, the combine, and the
shared expert with its gate; forward, recomputation and backward). The
accepted reader's number under this cell's own name; its ``row: "moe"`` line
splits the time by the layer's inner scopes."""
from chipbench.layer_metrics import moe_ms


def read(trace, host, ctx):
    return moe_ms.read(trace, host, ctx)

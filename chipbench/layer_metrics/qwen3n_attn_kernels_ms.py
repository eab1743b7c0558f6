"""Per step on device 0: the device time of every flash attention kernel of a
``qwen3_next`` model's ``full_attention`` layers, found by the names the
program gives them (``flash_fwd_*``, ``flash_bwd_*``): forward, recomputation
and backward, 16 query heads to 2 at a head of 256. The accepted reader's
number under this cell's own name."""
from chipbench.layer_metrics import attn_kernels_ms


def read(trace, host, ctx):
    return attn_kernels_ms.read(trace, host, ctx)

"""Per step on device 0: the device time of latent attention's flash kernels,
found by the names the program gives them (``flash_fwd_*``, ``flash_bwd_*``;
every attention of a ``glm_moe_lite`` model is latent attention): forward,
recomputation and backward, every block and the prediction module's."""
from chipbench.layer_metrics import attn_kernels_ms


def read(trace, host, ctx):
    return attn_kernels_ms.read(trace, host, ctx)

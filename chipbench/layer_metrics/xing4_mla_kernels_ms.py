"""Per step on device 0: the device time of latent attention's flash kernels
in an ``xing4`` model, found by the names the program gives them
(``flash_fwd_online``, ``flash_bwd_dq``, ``flash_bwd_dkv``: the online
kernels, whose query/key blocks are 192 wide and value blocks 128): forward,
recomputation and backward, every block."""
from chipbench.layer_metrics import mla_kernels_ms


def read(trace, host, ctx):
    return mla_kernels_ms.read(trace, host, ctx)

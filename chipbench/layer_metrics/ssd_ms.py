"""Per step on device 0: the device time of the operations whose scope path
holds ``ssd``: the chunked state-space scan of every Mamba layer (decays,
in-chunk products, chunk states), forward, recomputation and backward. A
kernel the program names ``ssd_*`` runs under that scope and is counted there."""
from chipbench.layer_metrics import mamba_mixer_ms


def read(trace, host, ctx):
    return mamba_mixer_ms.scope_ms(trace, ctx, "ssd")

"""Per step on device 0: the device time of the operations whose scope path
holds ``mtp``, the multi-token-prediction module as a whole: the next
token's embedding, the two norms and the merging projection (``mtp_merge``),
its own expert layer with its latent attention, its last norm, and its head
and loss through the shared head; forward, recomputation and backward. The
instructions that only wrap others are left out (``moe_ms.scopes_ms``). A
program whose step has no such scope gives nothing."""
from chipbench.layer_metrics import moe_ms


def read(trace, host, ctx):
    return moe_ms.scopes_ms(trace, ctx, ("mtp",))

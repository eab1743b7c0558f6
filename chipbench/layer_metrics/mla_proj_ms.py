"""Per step on device 0: the part of latent attention that is not a kernel:
the device time of the operations under ``mla_q`` (down, latent norm, up),
``mla_kv`` (down, latent norm, up), ``mla_rope`` (both rotary terms, the
broadcast of the one rope key to all heads, the joins) and ``mla_out``
together, in every block; forward, recomputation and backward."""
from chipbench.layer_metrics import mla_ms, moe_ms


def read(trace, host, ctx):
    return moe_ms.scopes_ms(trace, ctx, mla_ms.INNER)

"""Per step on device 0: the device time of the operations under
``conv_gate`` in an ``lfm2_moe`` model: the doubly gated conv alone (``C *
conv(B * x)`` between the operator's two projections), forward, recomputation
and backward, every held conv layer. A fusion carries its root's scope: what
the compiler folds into a projection's matmul is that projection's time."""
from chipbench.layer_metrics import mamba_mixer_ms


def read(trace, host, ctx):
    return mamba_mixer_ms.scope_ms(trace, ctx, "conv_gate")

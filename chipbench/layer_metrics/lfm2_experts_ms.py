"""Per step on device 0: the device time of the operations under
``moe_experts`` in an ``lfm2_moe`` model: the held experts' grouped matmuls
(the kernels ``grouped_matmul`` and ``grouped_matmul_dw``) and the SwiGLU
between them, forward, recomputation and backward. The accepted reader's
number under this cell's own name."""
from chipbench.layer_metrics import moe_experts_ms


def read(trace, host, ctx):
    return moe_experts_ms.read(trace, host, ctx)

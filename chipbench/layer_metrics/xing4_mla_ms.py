"""Per step on device 0: the device time of the operations under ``mla``,
latent attention as a whole in every block of an ``xing4`` model (both
low-rank paths with their latent norms, the YaRN-scaled rotary terms and the
broadcast of the shared rope key, the online flash kernels at 192 / 128 and
the transposes around them, the out projection; forward, recomputation and
backward). The accepted reader's number under this cell's own name; its
``row: "mla"`` line splits the time by the inner scopes and the kernels."""
from chipbench.layer_metrics import mla_ms


def read(trace, host, ctx):
    return mla_ms.read(trace, host, ctx)

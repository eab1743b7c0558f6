"""Per step on device 0: the device time of the operations under ``mamba`` in
every Mamba-2 layer of a ``nemotron_h`` model (the two projections, the conv,
the grouped-B/C scan and the per-group gated norm; forward, recomputation and
backward). The accepted reader's number under this cell's own name; its
``row: "mamba"`` line splits the time by the mixer's inner scopes."""
from chipbench.layer_metrics import mamba_mixer_ms


def read(trace, host, ctx):
    return mamba_mixer_ms.read(trace, host, ctx)

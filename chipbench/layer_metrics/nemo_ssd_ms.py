"""Per step on device 0: the device time of the operations under ``ssd`` in
every Mamba-2 layer of a ``nemotron_h`` model: the chunked scan with its B/C
groups (decays, in-chunk products, chunk states; the kernels ``ssd_fwd`` and
``ssd_bwd`` where the plan engaged them), forward, recomputation and backward.
The accepted reader's number under this cell's own name."""
from chipbench.layer_metrics import ssd_ms


def read(trace, host, ctx):
    return ssd_ms.read(trace, host, ctx)

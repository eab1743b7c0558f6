"""Model FLOP/s utilisation of the step on the device: the FLOPs one step
needs by the benchmark's own count (forward x 3, nothing recomputed), over
the device's busy time per step, the published peak and the chips."""
from chipbench.layer_metrics import device_step_ms


def read(trace, host, ctx):
    step_ms = device_step_ms.read(trace, host, ctx)
    if not step_ms:
        return None
    flops = 3.0 * ctx["fwd_flops_per_example"] * ctx["global_batch"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (step_ms / 1e3) / peak

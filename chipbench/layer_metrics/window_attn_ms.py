"""Per step on device 0: the device time of the windowed flash attention
kernels, found by the names the program gives them (``flash_fwd_window``,
``flash_bwd_window_dq``, ``flash_bwd_window_dkv``): forward, recomputation
and backward of every window layer."""
from chipbench import program_spans


def read(trace, host, ctx):
    kernels = program_spans.names(ctx.get("step_text"))[1]
    return program_spans._named_ms(trace, ctx, kernels,
                                   lambda k: "_window" in k, "flash_*_window")

"""Per step on device 0: the device time of every flash attention kernel,
found by the names the program gives them (``flash_fwd_*``, ``flash_bwd_*``):
the window layers' and the full layers' together, forward, recomputation and
backward."""
from chipbench import program_spans


def read(trace, host, ctx):
    kernels = program_spans.names(ctx.get("step_text"))[1]
    return program_spans._named_ms(
        trace, ctx, kernels,
        lambda k: k.startswith(("flash_fwd_", "flash_bwd_")), "flash_*")

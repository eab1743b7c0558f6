"""Per step on device 0: the device time of the flash attention's backward
kernels, found by the names the program gives them (``flash_bwd_*``)."""
from chipbench import program_spans


def read(trace, host, ctx):
    return program_spans.kernel_ms(trace, ctx, "flash_bwd")

"""Per step on device 0: the device time of the operations whose scope path
holds ``head_loss`` (the head's matmul and the loss over its logits, forward
and backward)."""
from chipbench import program_spans


def read(trace, host, ctx):
    return program_spans.region_ms(trace, ctx, "head_loss")

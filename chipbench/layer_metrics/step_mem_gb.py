"""Bytes the compiled step holds on one device, by the compiler's own count:
arguments + temporaries + outputs that alias no argument."""


def read(trace, host, ctx):
    if not ctx.get("step_bytes"):
        return None
    return ctx["step_bytes"] / 1e9

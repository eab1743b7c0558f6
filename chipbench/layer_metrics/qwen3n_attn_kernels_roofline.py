"""Share of its roofline that flash attention reaches in a ``qwen3_next``
model: the least time one chip could take for the causal attention of one
step, forward and backward, in every held ``full_attention`` layer, by the
benchmark's own count and the published peaks, over the time the flash
kernels took (``qwen3n_attn_kernels_ms``).

The count is of work no implementation can avoid (the pairs of the causal
half, once), so the share reads low wherever a kernel computes blocks the mask
half covers or the remat runs a forward twice; it cannot read over 100."""
from chipbench.layer_metrics import (attn_kernels_roofline,
                                     qwen3n_attn_kernels_ms)


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """``attn_kernels_roofline.least_seconds`` (7 products over the pairs
    inside each held layer's mask, ``num_attention_heads`` query heads of
    ``head_dim`` to ``num_key_value_heads``; q, o, do, dq a query head, k, v,
    dk, dv a key/value head and the lse, once) over the held ``full_attention``
    layers alone (published layer ``l`` where ``(l + 1) %
    full_attention_interval == 0``), each under the causal mask: no layer of
    this family has a window."""
    every = model["full_attention_interval"]
    full = [j for j in model["held_layers"] if (j + 1) % every == 0]
    return attn_kernels_roofline.least_seconds({
        **model, "held_layers": full, "sliding_window_size": 0,
        "sliding_window_layout": [0] * (max(full, default=-1) + 1)},
        traffic, examples, peak)


def read(trace, host, ctx):
    took_ms = qwen3n_attn_kernels_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

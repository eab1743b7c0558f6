"""Share of its roofline that flash attention reaches in a model that mixes
window layers with full ones: the least time one chip could take for the
attention of one step, forward and backward, every held layer by its own
mask, by the benchmark's own count and the published peaks, over the time
all the flash kernels took (``attn_kernels_ms``).

The count is of work no implementation can avoid (the pairs inside each
mask, once), so the share reads low wherever a kernel computes blocks the
mask half covers or the remat runs a forward twice; it cannot read over
100."""
from chipbench.layer_metrics import attn_kernels_ms


def pairs(windowed, S: int, window: int) -> float:
    """(row, key) pairs inside one head's mask: the causal half, or in a
    window layer ``min(i + 1, W)`` keys for row i."""
    W = min(window, S) if windowed else S
    return W * S - W * (W - 1) / 2


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for softmax(QK^T)V, forward and
    backward, over ``examples`` sequences and every held layer, each under
    its own mask (``sliding_window_layout`` by ``held_layers``: 1 the window,
    0 the causal half). Forward: QK^T and PV over the pairs inside the mask;
    backward: the scores again and dV, dP, dQ, dK, so 7 products of 2 x pairs
    x D FLOPs a head in all, nothing else recomputed. Bytes: q, o, do, dq a
    query head and k, v, dk, dv a key/value head, moved once in bf16, and the
    float32 lse a query head."""
    S, D = traffic["seq_len"], model["head_dim"]
    H, Hkv = model["num_attention_heads"], model["num_key_value_heads"]
    kinds = [model["sliding_window_layout"][j] for j in model["held_layers"]]
    inside = sum(pairs(kind, S, model["sliding_window_size"])
                 for kind in kinds)
    flops = examples * H * 7 * 2.0 * inside * D
    bytes_ = examples * len(kinds) * S * (2 * D * 4 * (H + Hkv) + 4 * H)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "pairs": inside,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = attn_kernels_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

"""Share of its roofline that windowed flash attention reaches: the least
time one chip could take for the window layers' attention of one step,
forward and backward, by the benchmark's own count and the published peaks,
over the time the window kernels took (``window_attn_ms``)."""
from chipbench.layer_metrics import window_attn_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for softmax(QK^T)V with the window
    mask, forward and backward, over ``examples`` sequences and every window
    layer that is held. Forward: QK^T and PV over the keys inside the window
    only (row i sees min(i + 1, W) of them); backward: the scores again and
    dV, dP, dQ, dK, so 7 products of 2*S*keys*D FLOPs a head in all, nothing
    else recomputed. Bytes: q, o, do, dq a query head and k, v, dk, dv a
    key/value head, moved once in bf16, and the float32 lse a query head."""
    S, D = traffic["seq_len"], model["head_dim"]
    H, Hkv = model["num_attention_heads"], model["num_key_value_heads"]
    W = min(model["sliding_window"], S)
    layers = [model["layer_types"][j] for j in model["held_layers"]].count(
        "sliding_attention")
    pairs = W * S - W * (W - 1) / 2          # (row, key) pairs inside the window
    flops = examples * layers * H * 7 * 2.0 * pairs * D
    bytes_ = examples * layers * S * (2 * D * 4 * (H + Hkv) + 4 * H)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = window_attn_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

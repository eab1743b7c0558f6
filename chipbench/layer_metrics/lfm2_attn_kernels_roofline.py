"""Share of its roofline that flash attention reaches in an ``lfm2_moe``
model: the least time one chip could take for the causal attention of one
step, forward and backward, in every held ``full_attention`` layer, by the
benchmark's own count and the published peaks, over the time the flash
kernels took (``lfm2_attn_kernels_ms``).

The count is of work no implementation can avoid (the pairs of the causal
half, once), so the share reads low wherever a kernel computes blocks the mask
half covers or the remat runs a forward twice; it cannot read over 100."""
from chipbench.layer_metrics import lfm2_attn_kernels_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for softmax(QK^T)V, causal, forward and
    backward, over ``examples`` sequences and every held ``full_attention``
    layer (``layer_types`` by ``held_layers``), ``num_attention_heads`` query
    heads of ``hidden_size / num_attention_heads`` to ``num_key_value_heads``.
    Forward: QK^T and PV over the pairs of the causal half; backward: the
    scores again and dV, dP, dQ, dK, so 7 products of 2 x pairs x D FLOPs a
    head in all, nothing else recomputed. Bytes: q, o, do, dq a query head
    and k, v, dk, dv a key/value head, moved once in bf16, and the float32
    lse a query head."""
    S = traffic["seq_len"]
    H, Hkv = model["num_attention_heads"], model["num_key_value_heads"]
    D = model["hidden_size"] // H
    layers = sum(model["layer_types"][j] == "full_attention"
                 for j in model["held_layers"])
    pairs = S * (S + 1) / 2
    flops = examples * layers * H * 7 * 2.0 * pairs * D
    bytes_ = examples * layers * S * (2 * D * 4 * (H + Hkv) + 4 * H)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "pairs": pairs, "layers": layers,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = lfm2_attn_kernels_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

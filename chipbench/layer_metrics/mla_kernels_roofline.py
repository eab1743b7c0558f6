"""Share of its roofline that flash attention reaches under latent attention
in its expanded form: the least time one chip could take for the causal
attention of one step, forward and backward, in every block and the
prediction module's, by the benchmark's own count and the published peaks,
over the time the flash kernels took (``mla_kernels_ms``).

The count is of work no implementation of the expanded form can avoid (the
pairs of the causal half, once, at the query/key width and at the value
width), so the share reads low wherever a kernel computes blocks the mask half
covers or the remat runs a forward twice; it cannot read over 100. The
absorbed form, which attends over the latent pair and never expands, would
count differently, and is not what this program runs."""
from chipbench.layer_metrics import mla_kernels_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for softmax(QK^T)V, causal, forward
    and backward, over ``examples`` sequences and ``num_hidden_layers +
    num_nextn_predict_layers`` attentions of ``num_attention_heads`` heads.
    Per head and (row, key) pair of the causal half, 2 FLOPs times: forward
    QK^T at the query/key width ``qk_nope_head_dim + qk_rope_head_dim`` and
    PV at ``v_head_dim``; backward the scores again, dQ and dK at the
    query/key width, dV and dP at the value width: 4 products at one width
    and 3 at the other, nothing else recomputed. Bytes: q, k, dq, dk at the
    query/key width and v, o, dv, do at the value width, every head's (the
    expanded form has no shared key), moved once in bf16, and the float32 lse
    a head."""
    S, H = traffic["seq_len"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    v = model["v_head_dim"]
    layers = model["num_hidden_layers"] + model["num_nextn_predict_layers"]
    pairs = S * (S + 1) / 2
    flops = examples * layers * H * 2.0 * pairs * (4 * qk + 3 * v)
    bytes_ = examples * layers * S * H * (2 * 4 * (qk + v) + 4)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "pairs": pairs, "layers": layers,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = mla_kernels_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

"""Share of its roofline that the flash attention (forward and backward, all
layers) reaches: the least time one chip could take for one step's attention,
by the benchmark's own count of its operations and bytes and the published
peaks, over the time the Pallas kernels took."""
from chipbench.layer_metrics import pallas_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for the causal flash forward + backward
    over ``examples`` sequences, all layers: 2 matmuls forward and 5 backward
    (the backward recomputes QK^T) over the causal half, and q, k, v, o, do,
    dq, dk, dv (bf16) and the log-sum-exp (f32) moved once each."""
    d, L, H = model["n_embd"], model["n_layer"], model["n_head"]
    S = traffic["seq_len"]
    flops = examples * L * 7 * 2.0 * d * S * (S + 1) / 2
    bytes_ = examples * L * (8 * S * d * 2 + H * S * 4)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = pallas_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

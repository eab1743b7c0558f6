"""Share of its roofline that the grouped state-space scan reaches: the least
time one chip could take for one step's scans, by the benchmark's own count of
the operations and bytes no implementation can avoid and the published peaks,
over the time the operations under ``ssd`` took (``nemo_ssd_ms``)."""
from chipbench.layer_metrics import nemo_ssd_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for the chunked scan, forward and
    backward, over ``examples`` sequences and every Mamba layer held.

    Multiply-accumulates a token and layer at the published chunk ``Q``,
    ``G`` B/C groups, ``H`` heads of ``P``, state ``N``: the causal half of
    ``C B^T`` once **a group** ((Q+1)/2 * G*N) and of ``(L o C B^T)(dt x)``
    ((Q+1)/2 * H*P) inside the chunk, and the chunk's state out (``dt x (x)
    B``: H*P*N) and in (``C S``: H*P*N). Twice that forward, and the backward
    at twice the forward; nothing recomputed, and the decays' exponentials,
    sums and masks not counted, so the share can only come out low. Bytes: x
    and y (H*P wide), B and C (G*N wide each) and the gradient of each, moved
    once in bf16, and dt and its gradient in float32."""
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, G = model["ssm_state_size"], model["n_groups"]
    S = traffic["seq_len"]
    Q = min(model["chunk_size"], S)
    pattern = model["hybrid_override_pattern"]
    layers = sum(pattern[j] == "M" for j in model["held_layers"])
    macs = (Q + 1) / 2 * (G * N + H * P) + 2 * H * P * N
    flops = examples * layers * S * 3 * 2.0 * macs
    bytes_ = examples * layers * S * 2 * (2 * H * P * 2 + 2 * G * N * 2
                                          + H * 4)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = nemo_ssd_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

"""Share of its roofline that the state-space scan reaches: the least time
one chip could take for one step's scans, by the benchmark's own count of the
operations and bytes no implementation can avoid and the published peaks,
over the time the operations under ``ssd`` took (``ssd_ms``)."""
from chipbench.layer_metrics import ssd_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for the chunked scan, forward and
    backward, over ``examples`` sequences and every Mamba layer.

    Multiply-accumulates a token and layer at the published chunk ``Q``, one
    B/C group, ``H`` heads of ``P``, state ``N``: the causal half of ``C B^T``
    ((Q+1)/2 * N) and of ``(L o C B^T)(dt x)`` ((Q+1)/2 * H*P) inside the
    chunk, and the chunk's state out (``dt x (x) B``: H*P*N) and in (``C S``:
    H*P*N). Twice that forward, and the backward at twice the forward; nothing
    recomputed, and the decays' exponentials, sums and masks not counted, so
    the share can only come out low. Bytes: x, B, C, dt and y and the gradient
    of each, moved once (bf16; dt float32)."""
    H, P = model["mamba_n_heads"], model["mamba_d_head"]
    N = model["mamba_d_state"]
    S = traffic["seq_len"]
    Q = min(model["mamba_chunk_size"], S)
    layers = model["layer_types"][:model["num_hidden_layers"]].count("mamba")
    macs = (Q + 1) / 2 * (N + H * P) + 2 * H * P * N
    flops = examples * layers * S * 3 * 2.0 * macs
    bytes_ = examples * layers * S * 2 * (2 * H * P * 2 + 2 * N * 2 + H * 4)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = ssd_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

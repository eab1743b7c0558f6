"""Share of its roofline that the gated delta rule reaches: the least time one
chip could take for one step's delta rules, by the benchmark's own count of
the operations and bytes no implementation avoids and the published peaks,
over the time the operations under ``delta_rule`` took
(``qwen3n_delta_rule_ms``).

The count is of the chunked form at the published chunk, the same work
whether XLA's scan or a later kernel does it. It reads low while the blocks'
remat runs the forward twice, and cannot read over 100."""
from chipbench.layer_metrics import qwen3n_delta_rule_ms

#: the released chunk
CHUNK = 64


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for the chunked gated delta rule,
    forward and backward, over ``examples`` sequences and every held
    ``linear_attention`` layer.

    Multiply-accumulates a chunk of ``C`` tokens, ``Hk`` key heads and ``Hv``
    value heads of ``Dk`` / ``Dv``: a key head ``K K^T`` and ``Q K^T`` at the
    triangle's half (``C (C + 1) / 2 * Dk`` each); a value head ``T V_b`` and
    ``tril(Q K^T o D) V_new`` at the half (``C (C + 1) / 2 * Dv`` each), ``T
    K_b`` (``C (C + 1) / 2 * Dk``), ``W S``, ``Q S`` and the state's update
    (``C * Dk * Dv`` each). Twice that forward, and the backward at twice the
    forward; nothing recomputed, and the solve ``T``, the exponentials, the
    masks and the norms not counted, so the share can only come out low.
    Bytes: ``q``, ``k`` (``Hk * Dk`` wide), ``v``, ``o`` (``Hv * Dv`` wide) and
    the gradient of each, moved once in bf16, and ``g``, ``beta`` and theirs
    in float32."""
    Hk, Hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    Dk, Dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    every = model["full_attention_interval"]
    layers = sum((j + 1) % every != 0 for j in model["held_layers"])
    S = traffic["seq_len"]
    C = min(CHUNK, S)
    half = C * (C + 1) / 2
    macs = (Hk * 2 * half * Dk
            + Hv * (2 * half * Dv + half * Dk + 3 * C * Dk * Dv))
    flops = examples * layers * (S / C) * 3 * 2.0 * macs
    bytes_ = examples * layers * S * 2 * (2 * Hk * Dk * 2 + 2 * Hv * Dv * 2
                                          + 2 * Hv * 4)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "layers": layers, "macs_per_chunk": macs,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = qwen3n_delta_rule_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

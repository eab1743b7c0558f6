"""Share of its roofline that the routed experts' grouped matmuls reach: the
least time one chip could take for one step's routed-expert matmuls, by the
benchmark's own count of the operations and bytes no implementation can avoid
and the published peaks, over the time the operations under ``moe_experts``
took (``moe_experts_ms``).

The row count is an expectation, not a reading: a token chooses
``num_experts_per_tok`` of the router's ``routed_experts``, of which
``num_experts`` are held here, so ``tokens x num_experts_per_tok x num_experts
/ routed_experts`` rows land on this chip's experts when the router is
balanced. A run whose router sends more does more work than is counted, and
the share then reads low."""
from chipbench.layer_metrics import moe_experts_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for the routed experts' matmuls of one
    step, forward and backward, nothing recomputed.

    FLOPs: three matrices (gate, up, down) of ``hidden_size x
    moe_intermediate_size``, three passes (forward, the rows' gradient, the
    weights' gradient), 2 a multiply-accumulate, over the expected rows of
    every expert layer. Bytes, each moved once in bf16: the held experts'
    weights read forward and backward and their gradients written; the rows
    in and out (``hidden_size`` wide) and the rows' gradients in and out."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    layers = model["num_hidden_layers"] - model["num_dense_layers"]
    tokens = examples * traffic["seq_len"]
    rows = (tokens * model["num_experts_per_tok"] * model["num_experts"]
            / model["routed_experts"])
    flops = layers * rows * 3 * 3 * 2.0 * d * f
    weights = model["num_experts"] * 3 * d * f
    bytes_ = layers * 2 * (3 * weights + 4 * rows * d)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "rows": rows, "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = moe_experts_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

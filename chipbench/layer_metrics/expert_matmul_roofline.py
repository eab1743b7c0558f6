"""Share of its roofline that the held experts' grouped matmuls reach in a
model whose every layer is an expert layer: the least time one chip could
take for one step's held-expert matmuls, by the benchmark's own count of the
operations and bytes no implementation can avoid and the published peaks,
over the time the operations under ``moe_experts`` took
(``expert_matmul_ms``).

The row count is an expectation, not a reading: a token chooses
``moe_num_active_primary_experts`` of the router's ``routed_experts``, of
which ``moe_num_primary_experts`` are held here, so ``tokens x active x held /
routed`` rows land on this chip's experts when the router is level. A run
whose router sends more does more work than is counted. The count is of work
no implementation can avoid, so the share reads low while the blocks' remat
runs the routed forward twice; it cannot read over 100."""
from chipbench.layer_metrics import expert_matmul_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for the held experts' matmuls of one
    step, forward and backward, nothing recomputed.

    FLOPs: three matrices (gate, up, down) of ``hidden_size x
    moe_ffn_hidden_size``, three passes (forward, the rows' gradient, the
    weights' gradient), 2 a multiply-accumulate, over the expected rows of
    every held layer. Bytes, each moved once in bf16: the held experts'
    weights read forward and backward and their gradients written; the rows
    in and out (``hidden_size`` wide) and the rows' gradients in and out."""
    d, f = model["hidden_size"], model["moe_ffn_hidden_size"]
    layers = len(model["held_layers"])        # every layer is an expert layer
    tokens = examples * traffic["seq_len"]
    rows = (tokens * model["moe_num_active_primary_experts"]
            * model["moe_num_primary_experts"] / model["routed_experts"])
    flops = layers * rows * 3 * 3 * 2.0 * d * f
    weights = model["moe_num_primary_experts"] * 3 * d * f
    bytes_ = layers * 2 * (3 * weights + 4 * rows * d)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "rows": rows, "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = expert_matmul_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

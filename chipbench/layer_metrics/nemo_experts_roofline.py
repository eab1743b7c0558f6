"""Share of its roofline that the held ungated experts' grouped matmuls reach:
the least time one chip could take for one step's held-expert matmuls, by the
benchmark's own count of the operations and bytes no implementation can avoid
and the published peaks, over the time the operations under ``moe_experts``
took (``nemo_experts_ms``).

The row count is an expectation, not a reading: a token chooses
``num_experts_per_tok`` of the router's ``routed_experts``, of which
``n_routed_experts`` are held here, so ``tokens x chosen x held / routed``
rows land on this chip's experts when the router is level. A run whose router
sends more does more work than is counted. The count is of work no
implementation can avoid at the published width (a kernel's part-filled last
column block is not counted), so the share reads low while the blocks' remat
runs the routed forward twice; it cannot read over 100."""
from chipbench.layer_metrics import nemo_experts_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for the held experts' matmuls of one
    step, forward and backward, nothing recomputed.

    FLOPs: two matrices (up, down) of ``hidden_size x moe_intermediate_size``,
    three passes (forward, the rows' gradient, the weights' gradient), 2 a
    multiply-accumulate, over the expected rows of every expert layer held.
    Bytes, each moved once in bf16: the held experts' weights read forward and
    backward and their gradients written; the rows in and out (``hidden_size``
    wide) and the rows' gradients in and out. At the cell's 384 rows an
    expert the two lie close: 0.736 TFLOP is 3.73 ms at the v5e's peak and
    2.18 GB is 2.66 ms, so the FLOPs decide (``bound`` says which), by a
    margin that a chip with more rows an expert would widen."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    pattern = model["hybrid_override_pattern"]
    layers = sum(pattern[j] == "E" for j in model["held_layers"])
    tokens = examples * traffic["seq_len"]
    rows = (tokens * model["num_experts_per_tok"] * model["n_routed_experts"]
            / model["routed_experts"])
    flops = layers * rows * 2 * 3 * 2.0 * d * f
    weights = model["n_routed_experts"] * 2 * d * f
    bytes_ = layers * 2 * (3 * weights + 4 * rows * d)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "rows": rows, "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = nemo_experts_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

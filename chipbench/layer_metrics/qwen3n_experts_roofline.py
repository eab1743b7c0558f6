"""Share of its roofline that the held experts' grouped matmuls reach in a
``qwen3_next`` model: the least time one chip could take for one step's
held-expert matmuls, by the benchmark's own count of the operations and bytes
no implementation can avoid and the published peaks, over the time the
operations under ``moe_experts`` took (``qwen3n_experts_ms``).

``expert_matmul_roofline``'s count by this configuration's keys. The row count
is an expectation, not a reading: a token chooses ``num_experts_per_tok`` of
the router's ``routed_experts``, of which ``num_experts`` are held here. At
160 rows an expert the weights' bytes decide, not the FLOPs. The share reads
low while the blocks' remat runs the routed forward twice; it cannot read
over 100."""
from chipbench.layer_metrics import expert_matmul_roofline, qwen3n_experts_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """``expert_matmul_roofline.least_seconds`` (three matrices, three passes,
    the expected rows of every held layer, each an expert layer; the held
    weights read twice and their gradients written, the rows in and out) with
    this configuration's names for the sizes it reads."""
    return expert_matmul_roofline.least_seconds({
        "hidden_size": model["hidden_size"],
        "moe_ffn_hidden_size": model["moe_intermediate_size"],
        "held_layers": model["held_layers"],
        "moe_num_active_primary_experts": model["num_experts_per_tok"],
        "moe_num_primary_experts": model["num_experts"],
        "routed_experts": model["routed_experts"]}, traffic, examples, peak)


def read(trace, host, ctx):
    took_ms = qwen3n_experts_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

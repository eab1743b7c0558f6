"""Share of its roofline that the held experts' grouped matmuls reach in an
``lfm2_moe`` model: ``gmm_roofline``'s least time (three matrices of
``hidden_size x moe_intermediate_size``, three passes, over the expected rows
of every expert layer: ``num_hidden_layers - num_dense_layers`` layers,
``num_experts_per_tok * num_experts / routed_experts`` rows a token; the
configuration's keys are the ones that count reads) over the time the
operations under ``moe_experts`` took (``lfm2_experts_ms``). At 1,024 rows an
expert the FLOPs decide. The row count is an expectation, not a reading, and
the share reads low while the blocks' remat runs the routed forward twice; it
cannot read over 100."""
from chipbench.layer_metrics import gmm_roofline, lfm2_experts_ms


def read(trace, host, ctx):
    took_ms = lfm2_experts_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = gmm_roofline.least_seconds(
        ctx["config"]["model"], ctx["traffic"],
        ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

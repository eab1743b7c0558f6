"""Share of its roofline that flash attention reaches under an ``xing4``
model's latent attention: ``mla_kernels_roofline.least_seconds`` (the pairs
of the causal half, once: 4 products at the query/key width
``qk_nope_head_dim + qk_rope_head_dim`` and 3 at ``v_head_dim``, which differ
here; q, k, dq, dk at the one width and v, o, dv, do at the other, moved once)
over the time the flash kernels took (``xing4_mla_kernels_ms``). It reads low
wherever a kernel computes blocks the mask half covers or the remat runs a
forward twice; it cannot read over 100."""
from chipbench.layer_metrics import mla_kernels_roofline, xing4_mla_kernels_ms


def read(trace, host, ctx):
    took_ms = xing4_mla_kernels_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = mla_kernels_roofline.least_seconds(
        ctx["config"]["model"], ctx["traffic"],
        ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

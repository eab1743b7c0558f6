"""Share of its roofline that the hyper-connections reach: the least time one
chip could take for one step's reads, mixes and writes of the ``hc_mult``
streams, by the benchmark's own count of the bytes and operations no
implementation avoids and the published peaks, over the time the operations
under ``hc`` took (``xing4_hc_ms``).

The count is of the float32 stream moved as few times as the mathematics
allows: it reads the same work whether XLA's fusions or a later kernel do it.
It reads low while the blocks' remat runs the forward twice and wherever a pass
moves the stream more than once; it cannot read over 100 unless the compiler
folds a pass over the stream into a neighbouring operation outside the scope
(the line ``row: "hc"`` of ``xing4_hc_ms`` shows where the time went)."""
from chipbench.layer_metrics import xing4_hc_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for the hyper-connections of one step,
    forward and backward, over ``examples`` sequences and two sub-layers in
    each of ``num_hidden_layers`` layers, nothing recomputed.

    Bytes a token and sub-layer, the ``[hc_mult, hidden_size]`` float32 stream
    ``X`` moved whole: forward ``X`` read once (the norm, the ``phi`` product,
    the read and the mix can share one pass) and the new ``X`` written once;
    backward ``X`` and the new stream's gradient read and ``X``'s gradient
    written: 5 streams of ``4 hc_mult hidden_size`` bytes. The branch's input
    and output (``hidden_size`` wide, a quarter of a stream) and the maps are
    not counted. FLOPs: the ``phi`` product, ``hc_mult hidden_size`` by ``2
    hc_mult + hc_mult^2``, 2 a multiply-accumulate, forward and twice that
    backward, against the bf16 peak though it runs in float32: both choices
    can only lower the share."""
    n, d, S = model["hc_mult"], model["hidden_size"], traffic["seq_len"]
    sublayers = 2 * model["num_hidden_layers"]
    tokens = examples * S
    bytes_ = sublayers * tokens * 5 * n * d * 4
    flops = sublayers * tokens * 3 * 2.0 * n * d * (2 * n + n * n)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "sublayers": sublayers,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = xing4_hc_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

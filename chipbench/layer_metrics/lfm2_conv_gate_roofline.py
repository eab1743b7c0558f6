"""Share of its roofline that the doubly gated conv reaches: the least time
one chip could take for one step's ``C * conv(B * x)`` stages, by the
benchmark's own count of the bytes no implementation of the stage avoids and
the published peak, over the time the operations under ``conv_gate`` took
(``lfm2_conv_gate_ms``).

The count is of the stage's own traffic, once each way: it reads the same
work whether XLA's fusions or a kernel do it. It reads low while the blocks'
remat runs the forward twice and wherever a pass moves a tensor more than
once; it cannot read over 100 unless the compiler folds the stage into a
neighbouring matmul, whose time the scope then does not hold (the line
``row: "short_conv"`` of ``lfm2_conv_ms`` shows where the time went)."""
from chipbench.layer_metrics import lfm2_conv_gate_ms


def least_seconds(model: dict, traffic: dict, examples: int,
                  peak: dict) -> dict:
    """Least time one chip could take for the gated conv of one step, forward
    and backward, over ``examples`` sequences and every held ``conv`` layer,
    nothing recomputed.

    Bytes a token and layer, each moved once in bf16 at hidden size ``d``:
    forward ``bcx`` read (3d) and ``y`` written (d); backward ``bcx`` and
    ``dy`` read (3d + d) and ``d bcx`` written (3d): 11 d elements. The taps
    and their gradient (``conv_L_cache x d`` float32) are not counted. FLOPs
    (for ``bound`` alone): a token and channel, ``1 + 2K + 1`` forward and
    about three times that backward; they are four orders under the peak's
    share."""
    d, K, S = model["hidden_size"], model["conv_L_cache"], traffic["seq_len"]
    layers = sum(model["layer_types"][j] == "conv"
                 for j in model["held_layers"])
    tokens = examples * S
    bytes_ = layers * tokens * 11 * d * 2
    flops = layers * tokens * d * 4.0 * (2 + 2 * K)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops, "bytes": bytes_,
            "layers": layers,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def read(trace, host, ctx):
    took_ms = lfm2_conv_gate_ms.read(trace, host, ctx)
    if not took_ms:
        return None
    least = least_seconds(ctx["config"]["model"], ctx["traffic"],
                          ctx["global_batch"] // ctx["chips"], ctx["peaks"])
    return 100.0 * least["seconds"] / (took_ms / 1e3)

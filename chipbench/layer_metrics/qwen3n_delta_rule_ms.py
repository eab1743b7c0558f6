"""Per step on device 0: the device time of the operations under
``delta_rule`` in a ``qwen3_next`` model: the gated delta rule alone (the
norms of ``q`` and ``k``, the gates, the chunks' solves and products, the scan
over the chunks with its carried state), forward, recomputation and backward,
every held ``linear_attention`` layer. The ``while`` that wraps the scan's
body is left out: its body's operations are counted by themselves."""
from chipbench.layer_metrics import moe_ms


def read(trace, host, ctx):
    return moe_ms.scopes_ms(trace, ctx, ("delta_rule",))

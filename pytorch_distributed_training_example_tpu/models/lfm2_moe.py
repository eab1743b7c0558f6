"""The ``lfm2_moe`` family (Liquid AI LFM2: ``LiquidAI/LFM2-8B-A1B``, 8.3B
total / about 1.5B active): a hybrid whose operator in three layers of four is
a doubly gated short convolution and in the fourth q/k-normed rotary GQA, over
a sigmoid-and-bias routed mixture of experts with no shared expert.

Per token, hidden ``d`` (published ``config.json`` keys in brackets):

- ``h_0 = E[id]``, no multiplier; a layer is ``h += Op(RMS(h; g_op)); h +=
  FFN(RMS(h; g_ffn))`` (``norm_eps``), the operator by ``layer_types``;
  ``logits = RMS(h_L; g_f) E^T``, the embedding tied. No bias in any linear
  map.
- ``conv``: :class:`ShortConv`: ``[B; C; x] = W_in u`` (``d -> 3d``, the
  chunks in that order); ``y = C * conv(B * x)``, the conv depthwise and
  causal over ``conv_L_cache`` taps with zero history and no bias
  (``ops/ssd.gated_conv``); ``W_out y``. No activation anywhere.
- ``full_attention``: :class:`models.afmoe.GatedAttention` without its gate:
  ``num_attention_heads`` query and ``num_key_value_heads`` key/value heads;
  ``q`` and ``k`` RMS-normed per head, then rotary over the whole head
  (``rope_theta``, rotate-half); causal ``softmax(q k^T / sqrt(head_dim)) v``;
  ``W_o``. No window.
- ``FFN`` of the first ``num_dense_layers`` layers: SwiGLU of width
  ``intermediate_size``. Of the others: :class:`parallel.moe.SharedExpertMoE`
  with no shared expert: ``s = sigmoid(x W_r)`` in float32 over
  ``num_experts``; the ``num_experts_per_tok`` largest of ``s + b`` are chosen
  (``use_expert_bias``; ``b`` chooses and nothing more); weights ``s_i / (sum
  of the chosen s + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; SwiGLU experts of ``moe_intermediate_size``. No
  auxiliary loss term.

The residual stream and the router's input are float32 whatever the compute
dtype (``models/afmoe.py`` says why); the matmuls' operands are the compute
dtype's.

Same conventions as ``afmoe.py`` and ``nemotron_h.py``: ``dtype`` /
``param_dtype``, the residual constrained through ``mesh_lib``, ``remat`` per
block (the kinds differ, so there is no stacked ``scan_layers`` form), named
regions ``embed``, ``norm``, ``short_conv`` (inside it ``in_proj``,
``conv_gate``, ``out_proj``), ``attn``, ``mlp`` (inside it ``moe`` with
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``),
``head_loss``.

Training only: serving needs a cache for the conv's rows of history beside
the KV pages. ``dp`` / ``fsdp`` only: the expert layer runs without an exchange
(``held_experts`` says which experts this chip holds), and the family has no
tensor-parallel rule table.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.models import llama
from pytorch_distributed_training_example_tpu.models.afmoe import (
    GatedAttention)
from pytorch_distributed_training_example_tpu.models.llama import RMSNorm
from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

#: The published ``layer_types``: attention at 2, 6, 10, 14, 18 and 21.
KINDS = ("conv", "full_attention")
LAYER_TYPES = tuple("full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
                    for i in range(24))
#: The released router's normaliser: ``s_i / (sum of the chosen s + 1e-6)``.
ROUTE_NORM_EPS = 1e-6


class ShortConv(nn.Module):
    """``W_out (C * conv(B * x))`` with ``[B; C; x] = W_in u``: the doubly
    gated causal convolution over ``taps`` tokens, float32 between the two
    projections' roundings (``ops/ssd.gated_conv``)."""
    taps: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, u):
        d = u.shape[-1]
        dense = lambda feat, name: nn.Dense(
            feat, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        bcx = dense(3 * d, "in_proj")(u)
        with jax.named_scope("conv_gate"):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (self.taps, d), self.param_dtype)
            y = ssd_lib.gated_conv(bcx, kernel)
        return dense(d, "out_proj")(y)


class Lfm2Block(nn.Module):
    """``x += Op(RMS(x)); x += FFN(RMS(x))`` on a float32 stream; ``kind``
    (static) picks the operator and ``experts`` None a leading dense layer,
    so each combination is one compiled body."""
    kind: str           # "conv" | "full_attention"
    conv_taps: int
    attn: dict          # GatedAttention's sizes
    dense_ffn_dim: int
    experts: dict | None
    epsilon: float
    train: bool
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        def rn(name, h, dtype=self.dtype):
            with jax.named_scope("norm"):
                return RMSNorm(self.epsilon, dtype, self.param_dtype,
                               name=name)(h)
        f32 = jnp.float32
        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h = rn("operator_norm", x)
        if self.kind == "conv":
            h = ShortConv(self.conv_taps, **kinds, name="short_conv")(h)
        elif self.kind == "full_attention":
            h = GatedAttention(**self.attn, window=None, gated=False,
                               rotary=True, epsilon=self.epsilon, **kinds,
                               name="attn")(h)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}; have "
                             f"{KINDS}")
        x = mesh_lib.constrain(x + h.astype(f32), llama._seq_rule("residual"))
        with jax.named_scope("mlp"):
            if self.experts is None:
                m = llama.swiglu_mlp(rn("ffn_norm", x), self.dense_ffn_dim,
                                     **kinds)
            else:
                # the router reads the norm unrounded
                m = moe_lib.SharedExpertMoE(
                    **self.experts, route_norm_eps=ROUTE_NORM_EPS, **kinds,
                    name="moe")(rn("ffn_norm", x, f32), self.train)
        return mesh_lib.constrain(x + m.astype(f32),
                                  llama._seq_rule("residual"))


class Lfm2Moe(nn.Module):
    # the published sizes of LFM2-8B-A1B are the defaults
    vocab_size: int = 65536
    layer_types: tuple = LAYER_TYPES
    num_dense_layers: int = 2
    d_model: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    conv_taps: int = 3
    dense_ffn_dim: int = 7168
    expert_ffn_dim: int = 1792
    num_experts: int = 32
    top_k: int = 4
    held_experts: tuple | None = None   # (how many, starting where); None: all
    route_scale: float = 1.0
    balance_coeff: float = 0.001
    rope_theta: float = 1000000.0
    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "nothing"  # key into llama.REMAT_POLICIES
    attn_impl: str = "auto"
    logits_dtype: Any = jnp.float32

    @property
    def num_layers(self):
        return len(self.layer_types)

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 decode_ctx: dict | None = None):
        if decode_ctx is not None:
            raise NotImplementedError(
                "the lfm2_moe family trains only: serving it needs a cache "
                "for the rows of history of every conv layer beside the KV "
                "pages, which serve/kv_cache.py does not have")
        # float32 rows: the residual stream starts unrounded (afmoe.AfmoeBlock)
        emb = nn.Embed(self.vocab_size, self.d_model, dtype=jnp.float32,
                       param_dtype=self.param_dtype, name="embed")
        with jax.named_scope("embed"):
            x = emb(tokens)
        x = mesh_lib.constrain(x, llama._seq_rule("residual"))
        block_cls = Lfm2Block
        if self.remat:
            if self.remat_policy not in llama.REMAT_POLICIES:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    f"have {sorted(llama.REMAT_POLICIES)}")
            block_cls = nn.remat(
                Lfm2Block, prevent_cse=False,
                policy=llama.REMAT_POLICIES[self.remat_policy])
        experts = dict(
            num_experts=self.num_experts, ffn_dim=self.expert_ffn_dim,
            top_k=self.top_k, held_experts=self.held_experts,
            route_scale=self.route_scale, balance_coeff=self.balance_coeff)
        for i, kind in enumerate(self.layer_types):
            x = block_cls(
                kind=kind, conv_taps=self.conv_taps,
                attn=dict(num_heads=self.num_heads,
                          num_kv_heads=self.num_kv_heads,
                          head_dim=self.head_dim, rope_theta=self.rope_theta,
                          attn_impl=self.attn_impl),
                dense_ffn_dim=self.dense_ffn_dim,
                experts=None if i < self.num_dense_layers else experts,
                epsilon=self.epsilon, train=train, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"block_{i}")(x)
        with jax.named_scope("norm"):
            x = RMSNorm(self.epsilon, self.dtype, self.param_dtype,
                        name="final_norm")(x)
        # Tied head: the rows that the stream starts from in float32 are the
        # head's kernel in the compute dtype.
        with jax.named_scope("head_loss"):
            logits = jnp.einsum("bsd,vd->bsv", x,
                                emb.embedding.astype(self.dtype))
            logits = mesh_lib.constrain(logits, llama._seq_rule("logits"))
            return logits.astype(self.logits_dtype)


def lfm2_8b_a1b(**kw) -> Lfm2Moe:
    """The published model: 24 layers, the first 2 dense, 18 conv to 6
    attention."""
    return Lfm2Moe(**kw)


#: The deployment the benchmark states: four chips share every layer.
SHARE_CHIPS = 4
SHARE_LAYERS = slice(1, 8)   # a dense layer, a period of four, two of the next


def chip_share(model: Lfm2Moe, chip: int = 0) -> Lfm2Moe:
    """One chip's share of ``model`` in the deployment the benchmark states:
    four chips share each layer by expert parallelism, so this one holds a
    quarter of the routed experts (``chip`` says which) and of the tied
    vocabulary's rows, and the conv operators, the attention, the routers and
    the dense FFN whole; of the depth, the published layers 1..7: one of the
    leading dense layers, then ``full_attention, conv, conv, conv,
    full_attention, conv`` (the others lie on further pipeline stages). No
    width changes."""
    held = model.num_experts // SHARE_CHIPS
    return model.clone(
        layer_types=model.layer_types[SHARE_LAYERS],
        num_dense_layers=min(model.num_dense_layers, 1),
        held_experts=(held, chip * held),
        vocab_size=model.vocab_size // SHARE_CHIPS)


def lfm2_moe_tiny(**kw) -> Lfm2Moe:
    """Test scale: a dense conv layer and a published period at toy widths,
    two of eight experts held, and a bias step large enough to change the
    choice within three steps."""
    kw.setdefault("vocab_size", 96)
    kw.setdefault("layer_types", ("conv", "full_attention", "conv", "conv",
                                  "conv"))
    kw.setdefault("num_dense_layers", 1)
    kw.setdefault("d_model", 64)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("dense_ffn_dim", 128)
    kw.setdefault("expert_ffn_dim", 32)
    kw.setdefault("num_experts", 8)
    kw.setdefault("top_k", 2)
    kw.setdefault("held_experts", (2, 2))
    kw.setdefault("balance_coeff", 0.05)
    return Lfm2Moe(**kw)


def _layer_macs(cfg: Lfm2Moe, seq_len: int | None, experts: float) -> dict:
    """Multiply-accumulates a token of each operator's and each FFN's matmuls,
    with ``experts`` routed experts a token; with ``seq_len`` None, the
    parameters instead (``experts`` then the experts held, the conv's taps
    and the q/k norms added)."""
    d, hd = cfg.d_model, cfg.head_dim
    conv = 4 * d * d
    attn = 2 * d * hd * (cfg.num_heads + cfg.num_kv_heads)
    dense = 3 * d * cfg.dense_ffn_dim
    moe = d * cfg.num_experts + experts * 3 * d * cfg.expert_ffn_dim
    if seq_len is None:
        conv += cfg.conv_taps * d
        attn += 2 * hd
    else:
        attn += 2 * cfg.num_heads * hd * (seq_len + 1) / 2
    return {"conv": conv, "full_attention": attn, "dense": dense, "moe": moe}


def _layers_sum(cfg: Lfm2Moe, parts: dict) -> float:
    """An operator and an FFN a layer, by the layer's kinds."""
    return sum(parts[kind]
               + parts["dense" if i < cfg.num_dense_layers else "moe"]
               for i, kind in enumerate(cfg.layer_types))


def num_params(cfg: Lfm2Moe) -> int:
    """Parameters held (the routed experts this chip holds; two norms a
    layer and the last; the embedding once: the head is tied)."""
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    d = cfg.d_model
    return (cfg.vocab_size * d + d + cfg.num_layers * 2 * d
            + _layers_sum(cfg, _layer_macs(cfg, None, held)))


def forward_flops_per_token(cfg: Lfm2Moe, seq_len: int) -> float:
    """Forward FLOPs a token for MFU: 2 per multiply-accumulate of every
    matmul (the tied head once), the causal half of attention's two products,
    the router, and the routed rows this chip expects: ``top_k * held /
    num_experts`` a token. The conv's taps and gates are not matmuls and are
    not counted."""
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    parts = _layer_macs(cfg, seq_len, cfg.top_k * held / cfg.num_experts)
    return 2.0 * (_layers_sum(cfg, parts) + cfg.d_model * cfg.vocab_size)

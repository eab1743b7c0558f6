"""The ``smallthinker`` family (PowerInfer SmallThinker:
``PowerInfer/SmallThinker-21BA3B-Instruct``, 21.5B total / 3B active): every
layer a mixture of ReLU-gated experts, routed *ahead of attention* by a
softmax over the chosen logits, under plain GQA that alternates a
position-free full layer with window layers.

Per token, hidden ``d`` (published ``config.json`` keys in brackets; what the
config does not state is the published ``smallthinker`` modelling code's):

- ``h = E[id]``, no multiplier; ``logits = RMS(h; g_f) W_head``, untied.
- A layer has two norms: ``x = RMS(h; g1); route = Router(x); h += Attn(x);
  y = RMS(h; g2); h += Experts(route, y)``. The router reads the attention's
  input: its plan is made before attention runs and used after it.
- ``Attn``: ``q, k, v = x W_q, x W_k, x W_v`` (``num_attention_heads`` /
  ``num_key_value_heads`` heads of ``head_dim``), no biases, no head norms,
  no gate. In layers whose ``sliding_window_layout`` is 1 the mask ``0 <= i -
  j < sliding_window_size``, in layers whose ``rope_layout`` is 1 rotary
  positions (``rope_theta``, all of ``head_dim``, rotate-half); the published
  layouts agree, ``[0, 1, 1, 1]`` thirteen times: a position-free causal
  layer, then three rotary window layers. ``softmax(q k^T /
  sqrt(head_dim)) v W_o``.
- ``Router``: :class:`parallel.moe.TopKSoftmaxRouter`
  (``moe_num_primary_experts`` float32 logits, the
  ``moe_num_active_primary_experts`` largest chosen, the softmax over the
  chosen alone, ``norm_topk_prob``). ``Experts``:
  :class:`parallel.moe.HeldExperts` (``(relu(y W_gate) * y W_up) W_down`` of
  width ``moe_ffn_hidden_size``). No shared expert, no dense layer, no bias,
  no auxiliary loss term, no buffer: ``batch_stats`` is empty.

Same conventions as ``afmoe.py``: ``dtype`` / ``param_dtype``, a float32
residual stream (the router's near-ties need its input unrounded: PERF.md,
PR 33), the residual constrained through ``mesh_lib``, ``remat`` per block,
named regions ``embed``, ``attn`` (the module's), ``mlp`` (inside it ``moe``,
the experts' module, with ``moe_dispatch``, ``moe_experts``, ``moe_combine``),
``norm``, ``head_loss``; and ``moe_router`` (the router's module) where the
router runs: ahead of ``attn``, outside ``mlp`` and ``moe``.

Training only, ``dp`` / ``fsdp`` only, as ``afmoe.py`` and for its reasons.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.models import llama
from pytorch_distributed_training_example_tpu.models.llama import RMSNorm
from pytorch_distributed_training_example_tpu.ops import attention as attn_lib
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

#: One period of the published ``sliding_window_layout`` / ``rope_layout``.
PERIOD = (0, 1, 1, 1)


class Attention(nn.Module):
    """Causal GQA without biases, head norms or a gate; ``window`` None is a
    full layer, ``rope`` False a position-free one."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int | None
    rope: bool
    rope_theta: float
    dtype: Any
    param_dtype: Any
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, h):
        heads = lambda n, name: nn.DenseGeneral(
            (n, self.head_dim), axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)(h)
        q, k = heads(self.num_heads, "query"), heads(self.num_kv_heads, "key")
        v = heads(self.num_kv_heads, "value")
        if self.rope:
            positions = jnp.arange(h.shape[1])[None, :]
            q = llama.rope(q, positions, self.rope_theta)
            k = llama.rope(k, positions, self.rope_theta)
        q = mesh_lib.constrain(q, llama._seq_rule("qkv"))
        k = mesh_lib.constrain(k, llama._seq_rule("qkv"))
        v = mesh_lib.constrain(v, llama._seq_rule("qkv"))
        out = attn_lib.attention(q, k, v, causal=True, impl=self.attn_impl,
                                 window=self.window)
        return nn.DenseGeneral(h.shape[-1], axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, param_dtype=self.param_dtype,
                               name="out")(out)


class SmallThinkerBlock(nn.Module):
    """Two norms; the router on the first one's output ahead of attention,
    the experts on the second one's after it. Everything here is static, so
    each kind of layer is one compiled body."""
    attn: dict          # Attention's sizes, with this layer's window and rope
    router: dict        # TopKSoftmaxRouter's sizes
    experts: dict       # HeldExperts' sizes
    epsilon: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        def rn(name, h):
            # float32 out: the router reads it unrounded; attention and the
            # experts round it to the compute dtype themselves
            with jax.named_scope("norm"):
                return RMSNorm(self.epsilon, jnp.float32, self.param_dtype,
                               name=name)(h)
        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        h = rn("attn_norm", x)
        route = moe_lib.TopKSoftmaxRouter(**self.router, name="moe_router")(h)
        a = Attention(**self.attn, **kinds, name="attn")(h.astype(self.dtype))
        x = mesh_lib.constrain(x + a.astype(jnp.float32),
                               llama._seq_rule("residual"))
        with jax.named_scope("mlp"):
            m = moe_lib.HeldExperts(**self.experts, **kinds, name="moe")(
                rn("ffn_norm", x), route)
        return mesh_lib.constrain(x + m.astype(jnp.float32),
                                  llama._seq_rule("residual"))


class SmallThinker(nn.Module):
    # the published sizes of SmallThinker-21BA3B-Instruct are the defaults
    vocab_size: int = 151936
    window_layout: tuple = PERIOD * 13     # sliding_window_layout
    rope_layout: tuple = PERIOD * 13
    d_model: int = 2560
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_ffn_dim: int = 768
    num_experts: int = 64
    top_k: int = 6
    held_experts: tuple | None = None   # (how many, starting where); None: all
    window: int = 4096
    rope_theta: float = 1.5e6
    epsilon: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "nothing"  # key into llama.REMAT_POLICIES
    attn_impl: str = "auto"
    logits_dtype: Any = jnp.float32

    @property
    def num_layers(self):
        return len(self.window_layout)

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 decode_ctx: dict | None = None):
        if decode_ctx is not None:
            raise NotImplementedError(
                "the smallthinker family trains only: serving it needs window "
                "layers in the paged cache, which serve/kv_cache.py does "
                "not have")
        if len(self.rope_layout) != len(self.window_layout):
            raise ValueError("rope_layout and window_layout differ in length")
        with jax.named_scope("embed"):
            # float32 rows: the residual stream starts unrounded
            x = nn.Embed(self.vocab_size, self.d_model, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name="embed")(tokens)
        x = mesh_lib.constrain(x, llama._seq_rule("residual"))
        block_cls = SmallThinkerBlock
        if self.remat:
            if self.remat_policy not in llama.REMAT_POLICIES:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    f"have {sorted(llama.REMAT_POLICIES)}")
            block_cls = nn.remat(
                SmallThinkerBlock, prevent_cse=False,
                policy=llama.REMAT_POLICIES[self.remat_policy])
        for i, (windowed, rotary) in enumerate(zip(self.window_layout,
                                                   self.rope_layout)):
            x = block_cls(
                attn=dict(num_heads=self.num_heads,
                          num_kv_heads=self.num_kv_heads,
                          head_dim=self.head_dim, rope_theta=self.rope_theta,
                          window=self.window if windowed else None,
                          rope=bool(rotary), attn_impl=self.attn_impl),
                router=dict(num_experts=self.num_experts, top_k=self.top_k),
                experts=dict(ffn_dim=self.expert_ffn_dim,
                             held_experts=self.held_experts, act="relu"),
                epsilon=self.epsilon, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"block_{i}")(x)
        with jax.named_scope("norm"):
            x = RMSNorm(self.epsilon, self.dtype, self.param_dtype,
                        name="final_norm")(x)
        with jax.named_scope("head_loss"):
            logits = nn.Dense(self.vocab_size, use_bias=False,
                              dtype=self.dtype, param_dtype=self.param_dtype,
                              name="lm_head")(x)
            logits = mesh_lib.constrain(logits, llama._seq_rule("logits"))
            return logits.astype(self.logits_dtype)


def smallthinker_21b(**kw) -> SmallThinker:
    """The published model: 52 layers, every one an expert layer, every
    fourth from the first a position-free full layer."""
    return SmallThinker(**kw)


#: The deployment the benchmark states: four chips (one v5e host) share
#: every layer.
SHARE_CHIPS = 4


def chip_share(model: SmallThinker, chip: int = 0) -> SmallThinker:
    """One chip's share of ``model`` in the deployment the benchmark states:
    four chips share each layer by expert parallelism, so this one holds a
    quarter of the experts (``chip`` says which) and of the vocabulary's
    rows, and attention and the router whole; of the depth, the first whole
    period (the others lie on further pipeline stages). No width changes."""
    held = model.num_experts // SHARE_CHIPS
    return model.clone(
        window_layout=model.window_layout[:len(PERIOD)],
        rope_layout=model.rope_layout[:len(PERIOD)],
        held_experts=(held, chip * held),
        vocab_size=model.vocab_size // SHARE_CHIPS)


def smallthinker_tiny(**kw) -> SmallThinker:
    """Test scale: one published period at toy widths, two of eight experts
    held (a quarter, as in the share: ``chunks`` is 2 and the bounded
    layout's both ways are within reach), three query heads a KV head (not a
    power of two, as the published seven)."""
    kw.setdefault("vocab_size", 96)
    kw.setdefault("window_layout", PERIOD)
    kw.setdefault("rope_layout", PERIOD)
    kw.setdefault("d_model", 64)
    kw.setdefault("num_heads", 6)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("expert_ffn_dim", 32)
    kw.setdefault("num_experts", 8)
    kw.setdefault("top_k", 3)
    kw.setdefault("held_experts", (2, 0))
    kw.setdefault("window", 16)
    return SmallThinker(**kw)


def num_params(cfg: SmallThinker) -> int:
    """Parameters held (the experts this chip holds)."""
    d, hd = cfg.d_model, cfg.head_dim
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    layer = (2 * d * hd * (cfg.num_heads + cfg.num_kv_heads) + 2 * d
             + d * cfg.num_experts + held * 3 * d * cfg.expert_ffn_dim)
    return 2 * cfg.vocab_size * d + d + cfg.num_layers * layer


def forward_flops_per_token(cfg: SmallThinker, seq_len: int) -> float:
    """Forward FLOPs a token for MFU: 2 per multiply-accumulate of every
    matmul (the head once), the keys a row sees in attention's two products
    (the causal half, or the window), the router, and the routed rows this
    chip expects: ``top_k * held / num_experts`` a token."""
    d, S, W = cfg.d_model, seq_len, min(cfg.window, seq_len)
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    proj = 2 * d * cfg.head_dim * (cfg.num_heads + cfg.num_kv_heads)
    keys = {0: (S + 1) / 2, 1: W - W * (W - 1) / (2 * S)}
    moe = d * cfg.num_experts + 3 * d * cfg.expert_ffn_dim * (
        cfg.top_k * held / cfg.num_experts)
    macs = sum(proj + 2 * cfg.num_heads * cfg.head_dim * keys[int(bool(w))]
               + moe for w in cfg.window_layout)
    return 2.0 * (macs + d * cfg.vocab_size)

"""The ``afmoe`` family (Arcee Trinity: ``arcee-ai/Trinity-Mini``, 26B total /
3B active): gated attention that alternates a sliding window with full
layers, over a sigmoid-routed mixture of experts with a shared expert.

Per token, hidden ``d`` (published ``config.json`` keys in brackets; what the
config does not state is the published ``afmoe`` modelling code's and
torchtitan's MoE, whose argument names the routing keys are):

- ``h = E[id] * sqrt(d)`` (``mup_enabled``); ``logits = RMS(h; g_f) W_head``,
  untied.
- A layer has four norms: ``a = Attn(RMS(h; g1)); h += RMS(a; g2); m =
  FFN(RMS(h; g3)); h += RMS(m; g4)``.
- ``Attn``: ``q, k, v, gate = x W_q, x W_k, x W_v, x W_g`` (``q`` and ``gate``
  ``num_attention_heads x head_dim``, ``k`` and ``v`` ``num_key_value_heads x
  head_dim``); ``q`` and ``k`` RMS-normed per head over ``head_dim``. In
  ``sliding_attention`` layers (``layer_types``) rotary positions
  (``rope_theta``, all of ``head_dim``, rotate-half) and the mask ``0 <= i - j
  < sliding_window``; in ``full_attention`` layers no positional term and the
  causal mask alone. ``out = (softmax(q k^T / sqrt(head_dim)) v * sigmoid(
  gate)) W_o``. No biases.
- ``FFN`` of the first ``num_dense_layers`` layers: SwiGLU of width
  ``intermediate_size``. Of the others: :class:`parallel.moe.SharedExpertMoE`
  (``num_experts`` sigmoid-scored, ``num_experts_per_tok`` chosen with a bias
  that the step updates and no optimizer sees, weights normalised and scaled
  by ``route_scale``, one shared expert; all SwiGLU of width
  ``moe_intermediate_size``). No auxiliary loss term.

Same conventions as ``llama.py`` and ``granite_hybrid.py``: ``dtype`` /
``param_dtype``, the residual constrained through ``mesh_lib``, ``remat`` per
block (the kinds differ, so there is no stacked ``scan_layers`` form), named
regions ``embed``, ``attn`` (the module's), ``mlp`` (inside it ``moe``, the
module's, with ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``, ``moe_shared``), ``norm``, ``head_loss``.

Training only: serving needs window layers in ``serve/kv_cache.py``. ``dp`` /
``fsdp`` only: the expert layer runs without an exchange (``held_experts``
says which experts this chip holds), and the family has no tensor-parallel
rule table.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.models import llama
from pytorch_distributed_training_example_tpu.models.llama import RMSNorm
from pytorch_distributed_training_example_tpu.ops import attention as attn_lib
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

#: One period of the published ``layer_types``.
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


class GatedAttention(nn.Module):
    """Causal GQA with per-head q/k norms and a sigmoid output gate;
    ``window`` None is a ``full_attention`` layer (no positional term),
    otherwise rotary positions and the window mask. The ``lfm2_moe`` models
    run the same q/k norms and rotary positions with neither a gate nor a
    window: ``gated`` False drops ``W_g`` and the sigmoid, ``rotary`` says
    where the positions go (None: where there is a window). The ``qwen3_next``
    models rotate the first ``rotary_dim`` columns of a head alone
    (rotate-half within them; None: the whole head)."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int | None
    rope_theta: float
    epsilon: float
    dtype: Any
    param_dtype: Any
    attn_impl: str = "auto"
    gated: bool = True
    rotary: bool | None = None
    rotary_dim: int | None = None

    @nn.compact
    def __call__(self, h):
        heads = lambda n, name: nn.DenseGeneral(
            (n, self.head_dim), axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)(h)
        norm = lambda name: RMSNorm(self.epsilon, self.dtype,
                                    self.param_dtype, name=name)
        q = norm("q_norm")(heads(self.num_heads, "query"))
        k = norm("k_norm")(heads(self.num_kv_heads, "key"))
        v = heads(self.num_kv_heads, "value")
        gate = heads(self.num_heads, "gate") if self.gated else None
        rotary = self.rotary
        if rotary is None:
            rotary = self.window is not None
        if rotary:
            positions = jnp.arange(h.shape[1])[None, :]
            cut = self.rotary_dim

            def turn(x):
                if cut is None:
                    return llama.rope(x, positions, self.rope_theta)
                return jnp.concatenate(
                    [llama.rope(x[..., :cut], positions, self.rope_theta),
                     x[..., cut:]], axis=-1)

            q, k = turn(q), turn(k)
        q = mesh_lib.constrain(q, llama._seq_rule("qkv"))
        k = mesh_lib.constrain(k, llama._seq_rule("qkv"))
        v = mesh_lib.constrain(v, llama._seq_rule("qkv"))
        out = attn_lib.attention(q, k, v, causal=True, impl=self.attn_impl,
                                 window=self.window)
        if self.gated:
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(self.dtype)
        return nn.DenseGeneral(h.shape[-1], axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, param_dtype=self.param_dtype,
                               name="out")(out)


class AfmoeBlock(nn.Module):
    """Four norms around attention and an FFN; ``experts`` None is a leading
    dense layer, otherwise :class:`SharedExpertMoE`'s sizes. Everything here
    is static, so each combination of kinds is one compiled body."""
    attn: dict          # GatedAttention's sizes, with this layer's window
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
        # The residual stream is float32 whatever the compute dtype: the
        # norms that close a branch add to it unrounded, and the router reads
        # its norm unrounded too. A bf16 stream is rounded at every add, and
        # the router's choice follows the rounding: a token whose eighth and
        # ninth scores nearly tie then chooses another expert than the
        # float32 model does (PERF.md, PR 33, has the counts). The matmuls'
        # operands are the compute dtype's either way.
        f32 = jnp.float32
        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        a = GatedAttention(**self.attn, epsilon=self.epsilon, **kinds,
                           name="attn")(rn("attn_norm", x))
        x = mesh_lib.constrain(x + rn("post_attn_norm", a, f32),
                               llama._seq_rule("residual"))
        with jax.named_scope("mlp"):
            if self.experts is None:
                m = llama.swiglu_mlp(rn("ffn_norm", x), self.dense_ffn_dim,
                                     **kinds)
            else:
                m = moe_lib.SharedExpertMoE(**self.experts, **kinds,
                                            name="moe")(
                    rn("ffn_norm", x, f32), self.train)
        return mesh_lib.constrain(x + rn("post_ffn_norm", m, f32),
                                  llama._seq_rule("residual"))


class Afmoe(nn.Module):
    # the published sizes of Trinity-Mini are the defaults
    vocab_size: int = 200192
    layer_types: tuple = PERIOD * 8
    num_dense_layers: int = 2
    d_model: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    dense_ffn_dim: int = 6144
    expert_ffn_dim: int = 1024
    num_experts: int = 128
    top_k: int = 8
    held_experts: tuple | None = None   # (how many, starting where); None: all
    shared_experts: int = 1
    window: int = 2048
    route_scale: float = 2.826
    balance_coeff: float = 0.001
    rope_theta: float = 10000.0
    epsilon: float = 1e-5
    mup: bool = True
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
                "the afmoe family trains only: serving it needs window "
                "layers in the paged cache, which serve/kv_cache.py does "
                "not have")
        with jax.named_scope("embed"):
            # float32 rows: the residual stream starts unrounded (AfmoeBlock)
            x = nn.Embed(self.vocab_size, self.d_model, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name="embed")(tokens)
            if self.mup:
                x = x * math.sqrt(self.d_model)
        x = mesh_lib.constrain(x, llama._seq_rule("residual"))
        block_cls = AfmoeBlock
        if self.remat:
            if self.remat_policy not in llama.REMAT_POLICIES:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    f"have {sorted(llama.REMAT_POLICIES)}")
            block_cls = nn.remat(
                AfmoeBlock, prevent_cse=False,
                policy=llama.REMAT_POLICIES[self.remat_policy])
        experts = dict(
            num_experts=self.num_experts, ffn_dim=self.expert_ffn_dim,
            top_k=self.top_k, held_experts=self.held_experts,
            shared_ffn_dim=self.shared_experts * self.expert_ffn_dim,
            route_scale=self.route_scale, balance_coeff=self.balance_coeff)
        for i, kind in enumerate(self.layer_types):
            if kind not in PERIOD:
                raise ValueError(f"unknown layer type {kind!r}")
            x = block_cls(
                attn=dict(num_heads=self.num_heads,
                          num_kv_heads=self.num_kv_heads,
                          head_dim=self.head_dim, rope_theta=self.rope_theta,
                          window=(self.window if kind == "sliding_attention"
                                  else None), attn_impl=self.attn_impl),
                dense_ffn_dim=self.dense_ffn_dim,
                experts=None if i < self.num_dense_layers else experts,
                epsilon=self.epsilon, train=train, dtype=self.dtype,
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


def trinity_mini(**kw) -> Afmoe:
    """The published model: 32 layers, the first 2 dense, every fourth full."""
    return Afmoe(**kw)


#: The deployment the benchmark states: eight chips share every layer.
SHARE_CHIPS = 8


def chip_share(model: Afmoe, chip: int = 0) -> Afmoe:
    """One chip's share of ``model`` in the deployment the benchmark states:
    eight chips share each layer by expert parallelism, so this one holds an
    eighth of the routed experts (``chip`` says which) and of the vocabulary's
    rows, and the attention, the router and the shared expert whole; of the
    depth, one leading dense layer and the first whole period of expert
    layers (the others lie on further pipeline stages). No width changes."""
    dense = model.num_dense_layers
    first = -(-dense // len(PERIOD)) * len(PERIOD)   # the next period's start
    held = model.num_experts // SHARE_CHIPS
    return model.clone(
        layer_types=(model.layer_types[:1]
                     + model.layer_types[first:first + len(PERIOD)]),
        num_dense_layers=1, held_experts=(held, chip * held),
        vocab_size=model.vocab_size // SHARE_CHIPS)


def afmoe_tiny(**kw) -> Afmoe:
    """Test scale: a dense layer and one published period at toy widths, two
    of eight experts held (a quarter of the rows a balanced router sends: the
    bounded layout's both ways are within reach), and a bias step large enough
    to change the choice within three steps."""
    kw.setdefault("vocab_size", 96)
    kw.setdefault("layer_types", PERIOD[:1] + PERIOD)
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
    kw.setdefault("window", 16)
    return Afmoe(**kw)


def _layer_params(cfg: Afmoe, experts: int) -> tuple[int, int]:
    """(a dense layer's, an expert layer's) parameters with ``experts``
    routed experts counted."""
    d, hd = cfg.d_model, cfg.head_dim
    attn = (d * hd * (3 * cfg.num_heads + 2 * cfg.num_kv_heads) + 2 * hd
            + 4 * d)                                # + four norms
    swiglu = lambda width: 3 * d * width
    moe = (d * cfg.num_experts + swiglu(cfg.expert_ffn_dim)
           * (cfg.shared_experts + experts))
    return attn + swiglu(cfg.dense_ffn_dim), attn + moe


def num_params(cfg: Afmoe) -> int:
    """Parameters held (the routed experts this chip holds)."""
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    dense, expert = _layer_params(cfg, held)
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    return (2 * cfg.vocab_size * cfg.d_model + cfg.d_model
            + n_dense * dense + (cfg.num_layers - n_dense) * expert)


def forward_flops_per_token(cfg: Afmoe, seq_len: int) -> float:
    """Forward FLOPs a token for MFU: 2 per multiply-accumulate of every
    matmul (the head once), the keys a row sees in attention's two products
    (the causal half, or the window), the router, the shared expert, and the
    routed rows this chip expects: ``top_k * held / num_experts`` a token."""
    d, S, W = cfg.d_model, seq_len, min(cfg.window, seq_len)
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    proj = d * cfg.head_dim * (3 * cfg.num_heads + 2 * cfg.num_kv_heads)
    keys = {"full_attention": (S + 1) / 2,
            "sliding_attention": W - W * (W - 1) / (2 * S)}
    swiglu = lambda width: 3 * d * width
    moe = (d * cfg.num_experts + swiglu(cfg.expert_ffn_dim)
           * (cfg.shared_experts + cfg.top_k * held / cfg.num_experts))
    macs = sum(
        proj + 2 * cfg.num_heads * cfg.head_dim * keys[kind]
        + (swiglu(cfg.dense_ffn_dim) if i < cfg.num_dense_layers else moe)
        for i, kind in enumerate(cfg.layer_types))
    return 2.0 * (macs + d * cfg.vocab_size)

"""The ``qwen3_next`` family (Qwen3-Next: ``Qwen/Qwen3-Next-80B-A3B-Instruct``,
80B total / 3B active): a hybrid whose mixer in three layers of four is a
gated delta rule (Gated DeltaNet: a matrix state a head, corrected by the
key's own readout) and in the fourth gated GQA with a quarter of the head
rotary, every layer over a softmax-routed mixture of 512 experts with a
sigmoid-gated shared expert.

Per token, hidden ``d`` (published ``config.json`` keys in brackets; what the
config does not state is the Gated Delta Networks paper's and the released
``qwen3_next`` modelling code's):

- ``h_0 = E[id]``, no multiplier; a layer is ``h += Mixer(RMS(h; g1)); h +=
  MoE(RMS(h; g2))`` (``rms_norm_eps``), the mixer ``full_attention`` where
  ``(l + 1) % full_attention_interval == 0`` and ``linear_attention``
  elsewhere; ``logits = RMS(h_L; g_f) W_head``, untied. The released norms
  are ``x_hat * (1 + w)`` with ``w`` from zero: ``RMSNorm`` with its scale
  from one. No bias in any linear map.
- ``linear_attention``: :class:`GatedDeltaNet`. ``[q; k; v; z] = W_qkvz u``
  (``linear_num_key_heads`` key heads and ``linear_num_value_heads`` value
  heads of ``linear_key_head_dim`` / ``linear_value_head_dim``), ``[b; a] =
  W_ba u``; ``[q; k; v] <- silu(conv([q; k; v]))``, depthwise and causal over
  ``linear_conv_kernel_dim`` taps with zero history and no bias
  (``ops/ssd.conv_silu``); per value head ``beta = sigmoid(b)``, ``g =
  -exp(A_log) * softplus(a + dt_bias)``, ``q`` and ``k`` divided by their
  norms (``q`` by ``sqrt`` of its width too), a key head serving
  ``value heads / key heads`` value heads in a row; the gated delta rule
  (``ops/gated_delta.py``); ``y = RMS(o; w) * silu(z)`` per head, norm first
  and gate after (``ops/ssd.norm_gate``: the kernel pair ``norm_gate_fwd`` /
  ``norm_gate_bwd`` on the ``[b, S, Hv Dv]`` the rule's kernels write, ``z``
  read out of ``qkvz``; one ``w`` for all heads); ``W_out y``.
- ``full_attention``: :class:`models.afmoe.GatedAttention` with rotary
  positions over the first ``partial_rotary_factor * head_dim`` columns of a
  head (``rope_theta``): ``num_attention_heads`` query and
  ``num_key_value_heads`` key/value heads of ``head_dim``, ``q`` and ``k``
  RMS-normed per head, causal ``softmax(q k^T / sqrt(head_dim)) v``, the
  output times ``sigmoid(gate)``, ``W_o``. No window.
- ``MoE`` (every layer): :class:`parallel.moe.TopKSoftmaxRouter`
  (``num_experts`` float32 logits, the ``num_experts_per_tok`` largest
  chosen, the softmax over the chosen alone: the published softmax over all,
  top-k and ``norm_topk_prob`` to a rounding),
  :class:`parallel.moe.HeldExperts` with SwiGLU experts of
  ``moe_intermediate_size``, and a shared SwiGLU expert of
  ``shared_expert_intermediate_size`` whose result is scaled by ``sigmoid(u
  w_sg)``, one scalar a token. No bias that chooses, no auxiliary loss term,
  no buffer: ``batch_stats`` is empty.

The residual stream and the router's input are float32 whatever the compute
dtype (``models/afmoe.py`` says why); the matmuls' operands are the compute
dtype's.

Same conventions as ``smallthinker.py`` and ``lfm2_moe.py``: ``dtype`` /
``param_dtype``, the residual constrained through ``mesh_lib``, ``remat`` per
block (the kinds differ, so there is no stacked ``scan_layers`` form), named
regions ``embed``, ``norm``, ``gated_delta_net`` (inside it ``in_proj``,
``conv_silu``, ``delta_rule``, ``gate_norm``, ``out_proj``), ``attn``, ``mlp``
(inside it ``moe`` with ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``, ``moe_shared``), ``head_loss``. A delta-rule block sows
``gdn_decay.<block>`` into ``telemetry``: the mean of ``exp(g)`` over tokens
and heads (how long the state lives).

Training only: serving needs a cache for a matrix state a head and the
conv's rows of history beside the KV pages. ``dp`` / ``fsdp`` only: the expert
layer runs without an exchange (``held_experts`` says which experts this chip
holds), and the family has no tensor-parallel rule table. No prediction
module: the published ``config.json`` has no key for one.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.models import llama
from pytorch_distributed_training_example_tpu.models.afmoe import (
    GatedAttention)
from pytorch_distributed_training_example_tpu.models.llama import RMSNorm
from pytorch_distributed_training_example_tpu.ops import gated_delta
from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

#: One period of the published layout (``full_attention_interval`` 4).
KINDS = ("linear_attention", "full_attention")
PERIOD = ("linear_attention",) * 3 + ("full_attention",)
#: The released rule's constant under the square root of a key's and a
#: query's norm.
L2_EPS = 1e-6


class GatedDeltaNet(nn.Module):
    """``W_out (RMS(o) * silu(z))`` with ``o`` the gated delta rule over the
    conv'd ``q``, ``k``, ``v``; the gates ``beta`` and ``g``, the norms of
    ``q`` and ``k``, the rule's state and its output float32."""
    key_heads: int      # Hk
    value_heads: int    # Hv
    key_dim: int        # Dk
    value_dim: int      # Dv
    conv_taps: int
    chunk: int
    epsilon: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, u):
        b, S, d = u.shape
        Hk, Hv, Dk, Dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        K, V, f32 = Hk * Dk, Hv * Dv, jnp.float32
        dense = lambda feat, name: nn.Dense(
            feat, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        with jax.named_scope("in_proj"):
            qkvz = dense(2 * K + 2 * V, "in_proj_qkvz")(u)
            ba = dense(2 * Hv, "in_proj_ba")(u)
        with jax.named_scope("conv_silu"):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (self.conv_taps, 2 * K + V), self.param_dtype)
            qkv = ssd_lib.conv_silu(qkvz[..., :2 * K + V], kernel,
                                    jnp.zeros((2 * K + V,), f32), source=qkvz)
        # Small tensors that steer the decay stay float32 under bf16 compute.
        A_log = self.param("A_log", nn.initializers.zeros, (Hv,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.constant(-3.0), (Hv,),
                             f32)
        with jax.named_scope("delta_rule"):
            q, k, v = jnp.split(qkv, [K, 2 * K], axis=-1)
            unit = lambda x: x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)
            q = unit(q.astype(f32).reshape(b, S, Hk, Dk)) / math.sqrt(Dk)
            k = unit(k.astype(f32).reshape(b, S, Hk, Dk))
            beta = jax.nn.sigmoid(ba[..., :Hv].astype(f32))
            g = -jnp.exp(A_log) * jax.nn.softplus(
                ba[..., Hv:].astype(f32) + dt_bias)
            # one call, no flag: the rule picks its body from the shapes (the
            # kernel pair at the published widths, whose residuals beside the
            # block's remat are its inputs and the chunks' start states; its
            # own checkpoint only around the XLA body, whose chunk tensors
            # would else live beside the expert layer's residuals)
            o = gated_delta.gated_delta_rule(
                q.astype(self.dtype), k.astype(self.dtype),
                v.reshape(b, S, Hv, Dv), g, beta, chunk=self.chunk)
        moe_lib._sow_telemetry(self, gdn_decay=jnp.mean(jnp.exp(g)))
        with jax.named_scope("gate_norm"):
            scale = self.param("norm_scale", nn.initializers.ones, (Dv,),
                               self.param_dtype)
            # o and z where they lie: the rule's kernels write [b, S, Hv Dv]
            # (the two reshapes cancel), and z is qkvz's last V lanes
            y = ssd_lib.norm_gate(o.reshape(b, S, V), qkvz[..., 2 * K + V:],
                                  scale, groups=Hv, epsilon=self.epsilon,
                                  dtype=self.dtype, source=qkvz,
                                  offset=2 * K + V)
        return dense(d, "out_proj")(y)


class Qwen3NextBlock(nn.Module):
    """``x += Mixer(RMS(x)); x += MoE(RMS(x))`` on a float32 stream; ``kind``
    (static) picks the mixer, so the two kinds are two compiled bodies."""
    kind: str           # "linear_attention" | "full_attention"
    delta: dict         # GatedDeltaNet's sizes
    attn: dict          # GatedAttention's sizes
    router: dict        # TopKSoftmaxRouter's sizes
    experts: dict       # HeldExperts' sizes
    shared_ffn_dim: int
    epsilon: float
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
        h = rn("mixer_norm", x)
        if self.kind == "linear_attention":
            h = GatedDeltaNet(**self.delta, epsilon=self.epsilon, **kinds,
                              name="gated_delta_net")(h)
        elif self.kind == "full_attention":
            h = GatedAttention(**self.attn, window=None, rotary=True,
                               epsilon=self.epsilon, **kinds, name="attn")(h)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}; have "
                             f"{KINDS}")
        x = mesh_lib.constrain(x + h.astype(f32), llama._seq_rule("residual"))
        with jax.named_scope("mlp"), jax.named_scope("moe"):
            # the router reads the norm unrounded; the experts round it to the
            # compute dtype themselves
            u = rn("ffn_norm", x, f32)
            route = moe_lib.TopKSoftmaxRouter(**self.router,
                                              name="moe_router")(u)
            m = moe_lib.HeldExperts(**self.experts, act="silu", **kinds,
                                    name="moe")(u, route).astype(f32)
            with jax.named_scope("moe_shared"):
                tokens = u.astype(self.dtype)
                gate = nn.Dense(1, use_bias=False, name="shared_expert_gate",
                                **kinds)(tokens)
                m = m + jax.nn.sigmoid(gate.astype(f32)) * moe_lib.SwiGLU(
                    self.shared_ffn_dim, **kinds, name="shared_expert")(
                        tokens).astype(f32)
        return mesh_lib.constrain(x + m, llama._seq_rule("residual"))


class Qwen3Next(nn.Module):
    # the published sizes of Qwen3-Next-80B-A3B-Instruct are the defaults
    vocab_size: int = 151936
    layer_types: tuple = PERIOD * 12
    d_model: int = 2048
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64                # partial_rotary_factor 0.25 of 256
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_taps: int = 4
    chunk: int = 64
    expert_ffn_dim: int = 512
    shared_ffn_dim: int = 512
    num_experts: int = 512
    top_k: int = 10
    held_experts: tuple | None = None   # (how many, starting where); None: all
    rope_theta: float = 1e7
    epsilon: float = 1e-6
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
                "the qwen3_next family trains only: serving it needs a cache "
                "for the matrix state of every delta-rule head and the rows "
                "of history of its conv beside the KV pages, which "
                "serve/kv_cache.py does not have")
        with jax.named_scope("embed"):
            # float32 rows: the residual stream starts unrounded
            x = nn.Embed(self.vocab_size, self.d_model, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name="embed")(tokens)
        x = mesh_lib.constrain(x, llama._seq_rule("residual"))
        block_cls = Qwen3NextBlock
        if self.remat:
            if self.remat_policy not in llama.REMAT_POLICIES:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    f"have {sorted(llama.REMAT_POLICIES)}")
            block_cls = nn.remat(
                Qwen3NextBlock, prevent_cse=False,
                policy=llama.REMAT_POLICIES[self.remat_policy])
        for i, kind in enumerate(self.layer_types):
            x = block_cls(
                kind=kind,
                delta=dict(key_heads=self.linear_key_heads,
                           value_heads=self.linear_value_heads,
                           key_dim=self.linear_key_dim,
                           value_dim=self.linear_value_dim,
                           conv_taps=self.conv_taps, chunk=self.chunk),
                attn=dict(num_heads=self.num_heads,
                          num_kv_heads=self.num_kv_heads,
                          head_dim=self.head_dim, rope_theta=self.rope_theta,
                          rotary_dim=self.rotary_dim,
                          attn_impl=self.attn_impl),
                router=dict(num_experts=self.num_experts, top_k=self.top_k),
                experts=dict(ffn_dim=self.expert_ffn_dim,
                             held_experts=self.held_experts),
                shared_ffn_dim=self.shared_ffn_dim, epsilon=self.epsilon,
                dtype=self.dtype, param_dtype=self.param_dtype,
                name=f"block_{i}")(x)
        with jax.named_scope("norm"):
            x = RMSNorm(self.epsilon, self.dtype, self.param_dtype,
                        name="final_norm")(x)
        with jax.named_scope("head_loss"):
            logits = nn.Dense(self.vocab_size, use_bias=False,
                              dtype=self.dtype, param_dtype=self.param_dtype,
                              name="lm_head")(x)
            logits = mesh_lib.constrain(logits, llama._seq_rule("logits"))
            return logits.astype(self.logits_dtype)


def qwen3_next_80b(**kw) -> Qwen3Next:
    """The published model: 48 layers, every one an expert layer, every
    fourth gated attention and the others the gated delta rule."""
    return Qwen3Next(**kw)


#: The deployment the benchmark states: sixteen chips share every layer's
#: experts; the vocabulary's rows are cut in eight.
SHARE_CHIPS = 16
SHARE_VOCAB = 8


def chip_share(model: Qwen3Next, chip: int = 0) -> Qwen3Next:
    """One chip's share of ``model`` in the deployment the benchmark states:
    sixteen chips share each layer by expert parallelism, so this one holds a
    sixteenth of the routed experts (``chip`` says which), an eighth of the
    vocabulary's rows, and the delta-rule mixers, the attention, the routers,
    the shared expert and its gate whole; of the depth, the first whole period
    (the others lie on further pipeline stages). No width changes."""
    held = model.num_experts // SHARE_CHIPS
    return model.clone(layer_types=model.layer_types[:len(PERIOD)],
                       held_experts=(held, chip * held),
                       vocab_size=model.vocab_size // SHARE_VOCAB)


def qwen3_next_tiny(**kw) -> Qwen3Next:
    """Test scale: one published period at toy widths, two value heads a key
    head, a quarter of the head rotary, two of eight experts held (a quarter,
    so that the bounded layout's both ways are within reach), a chunk that a
    short sequence spans several times."""
    kw.setdefault("vocab_size", 96)
    kw.setdefault("layer_types", PERIOD)
    kw.setdefault("d_model", 64)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("rotary_dim", 4)
    kw.setdefault("linear_key_heads", 2)
    kw.setdefault("linear_value_heads", 4)
    kw.setdefault("linear_key_dim", 16)
    kw.setdefault("linear_value_dim", 16)
    kw.setdefault("chunk", 16)
    kw.setdefault("expert_ffn_dim", 32)
    kw.setdefault("shared_ffn_dim", 32)
    kw.setdefault("num_experts", 8)
    kw.setdefault("top_k", 3)
    kw.setdefault("held_experts", (2, 2))
    return Qwen3Next(**kw)


def _layer_macs(cfg: Qwen3Next, seq_len: int | None, experts: float) -> dict:
    """Multiply-accumulates a token of each mixer's and the expert FFN's
    matmuls, with ``experts`` routed experts a token; with ``seq_len`` None,
    the parameters instead (``experts`` then the experts held; the conv's
    taps, the decay's two vectors and the head norms added)."""
    d, hd = cfg.d_model, cfg.head_dim
    K = cfg.linear_key_heads * cfg.linear_key_dim
    V = cfg.linear_value_heads * cfg.linear_value_dim
    delta = d * (2 * K + 2 * V) + d * 2 * cfg.linear_value_heads + V * d
    attn = d * hd * (3 * cfg.num_heads + 2 * cfg.num_kv_heads)
    moe = (d * cfg.num_experts + 3 * d * cfg.shared_ffn_dim + d
           + experts * 3 * d * cfg.expert_ffn_dim)
    if seq_len is None:
        delta += (cfg.conv_taps * (2 * K + V) + 2 * cfg.linear_value_heads
                  + cfg.linear_value_dim)
        attn += 2 * hd
    else:
        # the rule's state a token: the write and the two reads of it
        delta += 3 * cfg.linear_value_heads * cfg.linear_key_dim \
            * cfg.linear_value_dim
        attn += 2 * cfg.num_heads * hd * (seq_len + 1) / 2
    return {"linear_attention": delta + moe, "full_attention": attn + moe}


def num_params(cfg: Qwen3Next) -> int:
    """Parameters held (the routed experts this chip holds; two norms a layer
    and the last; the embedding and the head)."""
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    parts = _layer_macs(cfg, None, held)
    d = cfg.d_model
    return (2 * cfg.vocab_size * d + d
            + sum(parts[kind] + 2 * d for kind in cfg.layer_types))


def forward_flops_per_token(cfg: Qwen3Next, seq_len: int) -> float:
    """Forward FLOPs a token for MFU: 2 per multiply-accumulate of every
    matmul (the head once), the causal half of attention's two products, the
    delta rule as its recurrence counts it (a token and value head, the
    state's decayed readout by the key, its write and its readout by the
    query: three products of ``key_dim x value_dim``; the chunked form's
    in-chunk products are an implementation's and are not counted), the
    router, the shared expert and its gate, and the routed rows this chip
    expects: ``top_k * held / num_experts`` a token. The conv's taps, the
    norms and the gates are not matmuls and are not counted."""
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    parts = _layer_macs(cfg, seq_len, cfg.top_k * held / cfg.num_experts)
    return 2.0 * (sum(parts[kind] for kind in cfg.layer_types)
                  + cfg.d_model * cfg.vocab_size)

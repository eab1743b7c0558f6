"""Granite 4.0-H family (``model_type: granitemoehybrid``, dense variant):
Mamba-2 state-space mixers among grouped-query attention, in the order the
published ``layer_types`` gives.

Architecture, from the published config of ``ibm-granite/granite-4.0-h-micro``:
a tied embedding scaled by ``embedding_multiplier``; pre-norm (RMSNorm)
residual blocks ``x += residual_multiplier * Mix(norm(x))`` then ``x +=
residual_multiplier * MLP(norm(x))`` where ``Mix`` is a Mamba-2 mixer
(:class:`MambaMixer`, ``ops/ssd.py``) or causal GQA attention with **no**
positional term (``position_embedding_type: nope``) and the scale
``attention_multiplier`` in place of ``1/sqrt(head_dim)``; the MLP is the
shared SwiGLU (``models.llama.swiglu_mlp``; ``num_local_experts`` 0, so no
routed part); logits are ``norm(x) @ E^T / logits_scaling``. No bias anywhere
but the conv's.

Same conventions as ``llama.py``: ``dtype``/``param_dtype``, the residual
constrained through ``mesh_lib``, ``remat`` per block (two block kinds, so
there is no stacked ``scan_layers`` form; ``HybridBlock.kind`` is static),
named regions for the profiler:
``mamba`` (the whole mixer; inside it ``conv1d``, ``ssd``, ``gated_norm``),
``attn``, ``mlp``, ``norm``, ``embed``, ``head_loss``.

Training only: serving needs a recurrent-state cache beside the KV pages
(``serve/kv_cache.py`` has none), and the mixer has no tensor-parallel rules.
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
from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib

#: One period of the published ``layer_types``: attention is layer 5 of ten.
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


class MambaMixer(nn.Module):
    """Mamba-2 mixer: ``[z, xBC, dt] = W_in h``; a causal
    depthwise conv and silu over ``xBC``; the SSD scan over ``[x, B, C] =
    split(xBC)`` with ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``;
    ``RMSNorm(y * silu(z))`` (gate first, then the norm); ``W_out``.
    ``groups`` B/C groups (``B``, ``C`` [S, groups, N], head ``h`` reading
    group ``h // (H / groups)``), and the gated norm over each of the
    ``groups`` runs of ``H P / groups`` inner channels by itself (one scale
    vector over them all); at 1, Granite's, one B and C for every head and
    the norm over all inner channels."""
    num_heads: int      # H
    head_dim: int       # P
    state_dim: int      # N
    conv_width: int
    chunk: int
    epsilon: float
    dtype: Any
    param_dtype: Any
    groups: int = 1

    @nn.compact
    def __call__(self, h):
        b, S, d = h.shape
        H, P, N, G = self.num_heads, self.head_dim, self.state_dim, self.groups
        inner, conv_dim = H * P, H * P + 2 * G * N
        dense = lambda feat, name: nn.Dense(
            feat, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        zxbcdt = dense(inner + conv_dim + H, "in_proj")(h)
        z, xBC, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
        with jax.named_scope("conv1d"):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (self.conv_width, conv_dim), self.param_dtype)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (conv_dim,), self.param_dtype)
            xBC = ssd_lib.conv_silu(xBC, kernel, bias, source=zxbcdt,
                                    offset=inner)
        x, B, C = jnp.split(xBC, [inner, inner + G * N], axis=-1)
        if G > 1:
            B, C = B.reshape(b, S, G, N), C.reshape(b, S, G, N)
        # Small tensors that steer the decay stay float32 under bf16 compute.
        dt_bias = self.param("dt_bias", nn.initializers.constant(-3.0), (H,),
                             jnp.float32)
        A_log = self.param("A_log", nn.initializers.normal(1.0), (H,),
                           jnp.float32)
        D = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        with jax.named_scope("ssd"):
            y = ssd_lib.ssd(
                x.reshape(b, S, H, P),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(A_log), B, C, D, chunk=self.chunk)
        with jax.named_scope("gated_norm"):
            # In float32 from the scan's accumulator through the gate into
            # the norm. ``y`` holds ``D * x`` and the norm that follows is
            # blind to its input's scale, so ``D``'s gradient is what is left
            # of two terms that all but cancel: one bf16 rounding of ``y`` (or
            # of its cotangent) in between puts it off by a percent.
            y = GroupRMSNorm(G, self.epsilon, self.dtype, self.param_dtype,
                             name="norm")(y.reshape(b, S, inner), gate=z)
        return dense(d, "out_proj")(y)


class GroupRMSNorm(nn.Module):
    """``RMSNorm`` over each of ``groups`` equal runs of the last axis by
    itself, with one scale vector over them all; with ``gate``, of ``x *
    silu(gate)`` (``x`` float32, and float32 throughout:
    ``ops/ssd.gate_norm``)."""
    groups: int
    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, gate=None):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        if gate is not None:
            return ssd_lib.gate_norm(x, gate, scale, groups=self.groups,
                                     epsilon=self.epsilon, dtype=self.dtype)
        return ssd_lib.group_rms_norm(x, scale, self.groups, self.epsilon,
                                      self.dtype)


class GraniteAttention(nn.Module):
    """Causal GQA with no positional term and the published scale."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    multiplier: float
    dtype: Any
    param_dtype: Any
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, h):
        dg = lambda heads, name: nn.DenseGeneral(
            (heads, self.head_dim), axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        q = dg(self.num_heads, "query")(h)
        k = dg(self.num_kv_heads, "key")(h)
        v = dg(self.num_kv_heads, "value")(h)
        # ``attn_lib.attention`` and the flash kernels under it take no scale
        # and apply 1/sqrt(head_dim); the published scale is folded into q.
        # For this family it is a power of two (0.015625 * sqrt(64) = 1/8),
        # so the fold is exact in bf16.
        q = q * jnp.asarray(self.multiplier * math.sqrt(self.head_dim),
                            q.dtype)
        q = mesh_lib.constrain(q, llama._seq_rule("qkv"))
        k = mesh_lib.constrain(k, llama._seq_rule("qkv"))
        v = mesh_lib.constrain(v, llama._seq_rule("qkv"))
        out = attn_lib.attention(q, k, v, causal=True, impl=self.attn_impl)
        return nn.DenseGeneral(h.shape[-1], axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, param_dtype=self.param_dtype,
                               name="out")(out)


class HybridBlock(nn.Module):
    """``x += r * Mix(norm(x))`` then ``x += r * MLP(norm(x))``; ``kind``
    (static) picks the mixer, so the two kinds are two compiled bodies."""
    kind: str           # "mamba" | "attention"
    ffn_dim: int
    residual_multiplier: float
    epsilon: float
    mamba: dict         # MambaMixer's sizes
    attn: dict          # GraniteAttention's sizes
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        rn = lambda name: RMSNorm(self.epsilon, self.dtype, self.param_dtype,
                                  name=name)
        r = jnp.asarray(self.residual_multiplier, self.dtype)
        with jax.named_scope("norm"):
            h = rn("mix_norm")(x)
        if self.kind == "mamba":
            h = MambaMixer(**self.mamba, epsilon=self.epsilon,
                           dtype=self.dtype, param_dtype=self.param_dtype,
                           name="mamba")(h)
        elif self.kind == "attention":
            h = GraniteAttention(**self.attn, dtype=self.dtype,
                                 param_dtype=self.param_dtype, name="attn")(h)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        x = mesh_lib.constrain(x + r * h, llama._seq_rule("residual"))
        with jax.named_scope("norm"):
            h = rn("mlp_norm")(x)
        with jax.named_scope("mlp"):
            h = llama.swiglu_mlp(h, self.ffn_dim, self.dtype, self.param_dtype)
        return mesh_lib.constrain(x + r * h, llama._seq_rule("residual"))


class GraniteHybrid(nn.Module):
    # the published sizes of granite-4.0-h-micro are the defaults
    vocab_size: int = 100352
    layer_types: tuple = PERIOD * 4
    d_model: int = 2048
    ffn_dim: int = 8192
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_conv: int = 4
    mamba_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
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
                "the Granite hybrid family trains only: serving it needs a "
                "recurrent-state cache (the conv's history and the SSD state "
                "of every Mamba layer) beside the KV pages, which "
                "serve/kv_cache.py does not have")
        emb = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="embed")
        with jax.named_scope("embed"):
            x = emb(tokens) * jnp.asarray(self.embedding_multiplier, self.dtype)
        x = mesh_lib.constrain(x, llama._seq_rule("residual"))
        block_cls = HybridBlock
        if self.remat:
            if self.remat_policy not in llama.REMAT_POLICIES:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    f"have {sorted(llama.REMAT_POLICIES)}")
            block_cls = nn.remat(
                HybridBlock, prevent_cse=False,
                policy=llama.REMAT_POLICIES[self.remat_policy])
        block_args = dict(
            ffn_dim=self.ffn_dim, epsilon=self.epsilon,
            residual_multiplier=self.residual_multiplier,
            mamba=dict(num_heads=self.mamba_heads,
                       head_dim=self.mamba_head_dim,
                       state_dim=self.mamba_state,
                       conv_width=self.mamba_conv, chunk=self.mamba_chunk),
            attn=dict(num_heads=self.num_heads,
                      num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                      multiplier=self.attention_multiplier,
                      attn_impl=self.attn_impl),
            dtype=self.dtype, param_dtype=self.param_dtype)
        for i, kind in enumerate(self.layer_types):
            x = block_cls(kind=kind, name=f"block_{i}", **block_args)(x)
        with jax.named_scope("norm"):
            x = RMSNorm(self.epsilon, self.dtype, self.param_dtype,
                        name="final_norm")(x)
        # Tied head, as gpt2.py: attend promotes both operands to the compute
        # dtype; dividing by logits_scaling (8) is exact there.
        with jax.named_scope("head_loss"):
            logits = emb.attend(x.astype(self.param_dtype))
            logits = logits / jnp.asarray(self.logits_scaling, logits.dtype)
            logits = mesh_lib.constrain(logits, llama._seq_rule("logits"))
            return logits.astype(self.logits_dtype)


def granite4_h_micro(**kw) -> GraniteHybrid:
    """The published model: 40 layers, attention at 5, 15, 25, 35."""
    return GraniteHybrid(**kw)


def chip_share(model: GraniteHybrid) -> GraniteHybrid:
    """One chip's share of ``model`` in the deployment the benchmark states:
    the first period of its layers (one pipeline stage) and the first eighth
    of the tied vocabulary's rows. Nothing else changes."""
    return model.clone(layer_types=model.layer_types[:len(PERIOD)],
                       vocab_size=model.vocab_size // 8)


def granite_hybrid_tiny(**kw) -> GraniteHybrid:
    """Test scale: one published period at toy widths."""
    kw.setdefault("vocab_size", 96)
    kw.setdefault("layer_types", PERIOD)
    kw.setdefault("d_model", 64)
    kw.setdefault("ffn_dim", 128)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("mamba_heads", 4)
    kw.setdefault("mamba_head_dim", 16)
    kw.setdefault("mamba_state", 16)
    kw.setdefault("mamba_chunk", 8)
    return GraniteHybrid(**kw)


def num_params(cfg: GraniteHybrid) -> int:
    d = cfg.d_model
    inner = cfg.mamba_heads * cfg.mamba_head_dim
    conv_dim = inner + 2 * cfg.mamba_state
    mamba = (d * (inner + conv_dim + cfg.mamba_heads)          # in_proj
             + conv_dim * (cfg.mamba_conv + 1)                  # conv + bias
             + 3 * cfg.mamba_heads + inner + inner * d)         # dt/A/D, norm, out
    attn = 2 * d * cfg.head_dim * (cfg.num_heads + cfg.num_kv_heads)
    mlp = 3 * d * cfg.ffn_dim + 2 * d                           # + two norms
    kinds = {"mamba": mamba + mlp, "attention": attn + mlp}
    return (cfg.vocab_size * d + d
            + sum(kinds[k] for k in cfg.layer_types))


def forward_flops_per_token(cfg: GraniteHybrid, seq_len: int) -> float:
    """Forward FLOPs a token for MFU: 2 per multiply-accumulate of every
    matmul (the tied head once), the causal half of attention's two products,
    and the SSD's in-chunk products and chunk states."""
    d, S = cfg.d_model, seq_len
    H, P, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state
    Q = min(cfg.mamba_chunk, S)
    inner = H * P
    mamba = d * (2 * inner + 2 * N + H) + inner * d \
        + (Q + 1) / 2 * (N + inner) + 2 * inner * N
    attn = 2 * d * cfg.head_dim * (cfg.num_heads + cfg.num_kv_heads) \
        + 2 * cfg.num_heads * cfg.head_dim * (S + 1) / 2
    mlp = 3 * d * cfg.ffn_dim
    kinds = {"mamba": mamba + mlp, "attention": attn + mlp}
    return 2.0 * (sum(kinds[k] for k in cfg.layer_types)
                  + d * cfg.vocab_size)

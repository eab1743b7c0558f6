"""GPT-2 decoder LM — the reference's "GPT-2 124M LM" config (BASELINE.json
configs[3]: FSDP -> GSPMD param-shard).

Architecture (standard GPT-2): learned token+position embeddings, pre-LN
blocks, GELU MLP at 4x width, biased projections, weight-tied LM head.

TPU-first details:
- QKV projection kernels are shaped [d_model, heads, head_dim] (DenseGeneral's
  parameters) so tensor-parallel rules shard the *head* dimension (Megatron
  column-split) purely via PartitionSpec — no parallel linear classes. They
  are applied as flat [d_model, heads*head_dim] matmuls (``_HeadsDense``).
- Activations carry sharding constraints (batch over data axes, sequence
  over 'context') so CP/ring-attention engages by mesh shape alone.
- ``remat`` wraps each block in ``jax.checkpoint`` (the reference matrix's
  gradient-checkpointing capability).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.ops import attention as attn_lib
from pytorch_distributed_training_example_tpu.parallel import sharding

BATCH = mesh_lib.BATCH_AXES


def _seq_rule(name: str, sp: bool = False):
    """Sequence/context activation spec from the shared rule table
    (parallel/sharding.seq_rules): with Megatron-style SP on, the residual
    stream's sequence dim also shards over the TP axis between matmul
    regions (GSPMD inserts the gather/scatter Megatron's SP does by hand)."""
    return sharding.seq_rules(sp)[name]


class _HeadsDense(nn.Module):
    """``nn.DenseGeneral`` between d_model and (heads, head_dim), computed as
    one flat matmul.

    The parameters are DenseGeneral's, name for name, shape for shape and
    initial value for initial value (``kernel`` [d, H, D] and ``bias`` [H, D]
    when splitting, [H, D, d] and [d] when merging, drawn flat and reshaped),
    so checkpoints, ``TP_RULES`` and the benchmark's weights see no change.
    The activation stays [..., H*D]: that row-major layout is what the flash
    kernels block, so XLA puts no transpose or copy between the two.
    """
    heads: int
    head_dim: int
    merge: bool
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        width = self.heads * self.head_dim
        d = width if self.merge else x.shape[-1]
        flat = (width, d) if self.merge else (d, width)
        shape = ((self.heads, self.head_dim, d) if self.merge
                 else (d, self.heads, self.head_dim))

        def kernel_init(rng, shape, dtype):
            return nn.linear.default_kernel_init(rng, flat, dtype).reshape(shape)

        kernel = self.param("kernel", kernel_init, shape, self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          shape[-1:] if self.merge else shape[1:],
                          self.param_dtype)
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        # The barrier keeps the relayout between [d, H, D] and [d, H*D] (the
        # 64-wide minor dimension pads to 128 lanes) on the weight and on its
        # gradient. Without it XLA moves it onto the [B, S, H*D] activations
        # of the backward matmuls: nine 38 MB copies a layer at GPT-2's size.
        kernel = jax.lax.optimization_barrier(kernel.reshape(flat))
        return x @ kernel + bias.reshape(flat[1])


class SelfAttention(nn.Module):
    num_heads: int
    dtype: Any
    param_dtype: Any
    dropout: float = 0.0
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, train: bool):
        d = x.shape[-1]
        head_dim = d // self.num_heads
        dense = lambda name, merge=False: _HeadsDense(
            self.num_heads, head_dim, merge, self.dtype, self.param_dtype,
            name=name)
        heads = lambda t: mesh_lib.constrain(
            t.reshape(*t.shape[:-1], self.num_heads, head_dim),
            _seq_rule("qkv"))
        q, k, v = (heads(dense(name)(x)) for name in ("query", "key", "value"))
        out = attn_lib.attention(q, k, v, causal=True, impl=self.attn_impl)
        out = dense("out", merge=True)(out.reshape(*x.shape))
        if self.dropout > 0:
            out = nn.Dropout(self.dropout, deterministic=not train)(out)
        return out


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int
    dtype: Any
    param_dtype: Any
    dropout: float = 0.0
    attn_impl: str = "auto"
    sp: bool = False

    @nn.compact
    def __call__(self, x, train: bool):
        ln = lambda name: nn.LayerNorm(epsilon=1e-5, dtype=self.dtype,
                                       param_dtype=self.param_dtype, name=name)
        # Region names for the profiler (jax.named_scope touches neither the
        # parameter paths nor the numerics): norm | attn (the module's own
        # name) | mlp; embed, head_loss and optimizer are named where they run.
        with jax.named_scope("norm"):
            h = ln("ln_1")(x)
        x = x + SelfAttention(self.num_heads, self.dtype, self.param_dtype,
                              self.dropout, self.attn_impl,
                              name="attn")(h, train)
        x = mesh_lib.constrain(x, _seq_rule("residual", self.sp))
        with jax.named_scope("norm"):
            h = ln("ln_2")(x)
        d = x.shape[-1]
        with jax.named_scope("mlp"):
            h = nn.Dense(self.mlp_ratio * d, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="mlp_up")(h)
            h = mesh_lib.constrain(h, _seq_rule("ffn_hidden"))
            h = nn.gelu(h, approximate=True)
            h = nn.Dense(d, dtype=self.dtype, param_dtype=self.param_dtype,
                         name="mlp_down")(h)
            if self.dropout > 0:
                h = nn.Dropout(self.dropout, deterministic=not train)(h)
        x = x + h
        return mesh_lib.constrain(x, _seq_rule("residual", self.sp))


class GPT2(nn.Module):
    vocab_size: int = 50257
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    max_seq_len: int = 1024
    mlp_ratio: int = 4
    dropout: float = 0.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "auto"
    sp: bool = False
    logits_dtype: Any = jnp.float32  # storage dtype; loss upcasts per-element

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        B, S = tokens.shape
        emb = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="wte")
        pos_emb = self.param("wpe", nn.initializers.normal(0.01),
                             (self.max_seq_len, self.d_model), self.param_dtype)
        with jax.named_scope("embed"):
            x = emb(tokens) + pos_emb[None, :S].astype(self.dtype)
        x = mesh_lib.constrain(x, _seq_rule("residual", self.sp))
        if self.dropout > 0:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)

        block_cls = Block
        if self.remat:
            block_cls = nn.remat(
                Block, prevent_cse=False,
                policy=jax.checkpoint_policies.nothing_saveable,
                static_argnums=(1,))
        for i in range(self.num_layers):
            x = block_cls(self.num_heads, self.mlp_ratio, self.dtype,
                          self.param_dtype, self.dropout, self.attn_impl,
                          self.sp, name=f"block_{i}")(x, train)
        with jax.named_scope("norm"):
            x = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype,
                             param_dtype=self.param_dtype, name="ln_f")(x)
        # Weight-tied LM head (GPT-2 convention). flax's attend promotes both
        # operands to the module dtype (bf16 under the bf16 policy), so the
        # matmul output is already bf16-rounded; logits_dtype only decides
        # what lands in HBM (metrics.cross_entropy upcasts fp32 per-element).
        with jax.named_scope("head_loss"):
            logits = emb.attend(x.astype(self.param_dtype))
            logits = mesh_lib.constrain(logits, _seq_rule("logits", self.sp))
            return logits.astype(self.logits_dtype)


#: Tensor-parallel rule table (path regex -> PartitionSpec). AUTO_FSDP
#: composition happens in parallel.sharding when the mesh has an fsdp axis.
TP_RULES = (
    (r"attn/(query|key|value)/kernel", P(None, "model", None)),
    # The one sequence-dim parameter in the repo: learned position embeddings
    # shard over 'context' so each seq shard holds only its own positions
    # (pruned to replicated when the mesh has no context axis).
    (r"wpe", P("context", None)),
    (r"attn/(query|key|value)/bias", P("model", None)),
    (r"attn/out/kernel", P("model", None, None)),
    (r"mlp_up/kernel", P(None, "model")),
    (r"mlp_up/bias", P("model")),
    (r"mlp_down/kernel", P("model", None)),
    (r"wte/embedding", P(None, "model")),
)


def gpt2_124m(**kw) -> GPT2:
    return GPT2(**kw)


def gpt2_tiny(**kw) -> GPT2:
    """4-layer toy for tests/dry-runs."""
    kw.setdefault("vocab_size", 512)
    kw.setdefault("num_layers", 4)
    kw.setdefault("num_heads", 4)
    kw.setdefault("d_model", 128)
    kw.setdefault("max_seq_len", 256)
    return GPT2(**kw)


def num_params(cfg: GPT2) -> int:
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    per_block = 4 * d * d + 4 * d + 2 * cfg.mlp_ratio * d * d \
        + (cfg.mlp_ratio + 1) * d + 4 * d
    return V * d + cfg.max_seq_len * d + L * per_block + 2 * d

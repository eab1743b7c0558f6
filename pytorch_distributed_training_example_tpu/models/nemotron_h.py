"""The ``nemotron_h`` family (NVIDIA Nemotron-3-Nano:
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, 31.6B total / about 3.2B
active): a hybrid whose every layer is one norm and one mixer alone, the mixer
a Mamba-2 state-space mixer, an expert layer or attention by the letter of a
published pattern string.

Per token, hidden ``d`` (published ``config.json`` keys in brackets):

- ``h_0 = E[id]``; for each layer ``h += Mixer_kind(RMS(h; g_l))``, kind by
  the layer's letter in ``hybrid_override_pattern``; ``logits = RMS(h_L; g_f)
  W_head``, untied. No bias in any linear map.
- ``M``: :class:`models.granite_hybrid.MambaMixer` with ``n_groups`` B/C
  groups (``mamba_num_heads`` heads of ``mamba_head_dim``, state
  ``ssm_state_size``, conv ``conv_kernel`` with bias, chunk ``chunk_size``):
  head ``h`` reads group ``h // (H / n_groups)``, and the gated norm norms
  each group's channels by itself.
- ``*``: :class:`models.granite_hybrid.GraniteAttention`: causal GQA
  (``num_attention_heads`` / ``num_key_value_heads`` of ``head_dim``) with
  **no** positional term, scale ``1/sqrt(head_dim)``.
- ``E``: :class:`parallel.moe.SharedExpertMoE`, ungated: ``s = sigmoid(x
  W_r)`` in float32 over ``n_routed_experts``; the ``num_experts_per_tok``
  largest of ``s + b`` are chosen; weights normalised and scaled by
  ``routed_scaling_factor``; experts ``relu(x W_up)^2 W_down`` of width
  ``moe_intermediate_size`` beside a shared one of
  ``moe_shared_expert_intermediate_size``. No auxiliary loss term.

The residual stream and the router's input are float32 whatever the compute
dtype (``models/afmoe.py`` says why); the matmuls' operands are the compute
dtype's.

Same conventions as ``granite_hybrid.py`` and ``afmoe.py``: ``dtype`` /
``param_dtype``, the residual constrained through ``mesh_lib``, ``remat`` per
block (three kinds, so there is no stacked ``scan_layers`` form; a block's
kind is static), named regions ``embed``, ``norm``, ``mamba`` (inside it
``conv1d``, ``ssd``, ``gated_norm``), ``attn``, ``mlp`` (inside it ``moe``
with ``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``,
``moe_shared``), ``head_loss``.

Training only: serving needs a recurrent-state cache beside the KV pages.
``dp`` / ``fsdp`` only: the expert layer runs without an exchange
(``held_experts`` says which experts this chip holds), and the family has no
tensor-parallel rule table.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.models import llama
from pytorch_distributed_training_example_tpu.models.granite_hybrid import (
    GraniteAttention, MambaMixer)
from pytorch_distributed_training_example_tpu.models.llama import RMSNorm
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib

#: The published ``hybrid_override_pattern``: 23 Mamba (M), 23 expert (E) and
#: 6 attention (*) layers.
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
KINDS = "ME*"


class NemotronBlock(nn.Module):
    """``x += Mixer(RMS(x))`` on a float32 stream; ``kind`` (static, a letter
    of ``KINDS``) picks the mixer, so each kind is one compiled body."""
    kind: str
    mamba: dict         # MambaMixer's sizes
    attn: dict          # GraniteAttention's sizes
    experts: dict       # SharedExpertMoE's sizes
    epsilon: float
    train: bool
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        # the router reads the norm unrounded; the other mixers' matmuls take
        # the compute dtype's operands either way
        routed = self.kind == "E"
        with jax.named_scope("norm"):
            h = RMSNorm(self.epsilon, jnp.float32 if routed else self.dtype,
                        self.param_dtype, name="norm")(x)
        if self.kind == "M":
            h = MambaMixer(**self.mamba, epsilon=self.epsilon, **kinds,
                           name="mamba")(h)
        elif self.kind == "*":
            h = GraniteAttention(**self.attn, **kinds, name="attn")(h)
        elif routed:
            with jax.named_scope("mlp"):
                h = moe_lib.SharedExpertMoE(**self.experts, gated=False,
                                            **kinds, name="moe")(
                    h, self.train)
        else:
            raise ValueError(f"unknown layer letter {self.kind!r}; the "
                             f"pattern's letters are {KINDS!r}")
        return mesh_lib.constrain(x + h.astype(jnp.float32),
                                  llama._seq_rule("residual"))


class NemotronH(nn.Module):
    # the published sizes of Nemotron-3-Nano-30B-A3B are the defaults
    vocab_size: int = 131072
    pattern: str = PATTERN
    d_model: int = 2688
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 8
    mamba_conv: int = 4
    mamba_chunk: int = 128
    expert_ffn_dim: int = 1856
    shared_ffn_dim: int = 3712
    num_experts: int = 128
    top_k: int = 6
    held_experts: tuple | None = None   # (how many, starting where); None: all
    route_scale: float = 2.5
    balance_coeff: float = 0.001
    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "nothing"  # key into llama.REMAT_POLICIES
    attn_impl: str = "auto"
    logits_dtype: Any = jnp.float32

    @property
    def num_layers(self):
        return len(self.pattern)

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 decode_ctx: dict | None = None):
        if decode_ctx is not None:
            raise NotImplementedError(
                "the nemotron_h family trains only: serving it needs a "
                "recurrent-state cache (the conv's history and the SSD state "
                "of every Mamba layer) beside the KV pages, which "
                "serve/kv_cache.py does not have")
        odd = sorted(set(self.pattern) - set(KINDS))
        if odd:
            raise ValueError(f"unknown layer letters {odd} in the pattern "
                             f"{self.pattern!r}; have {KINDS!r}")
        with jax.named_scope("embed"):
            # float32 rows: the residual stream starts unrounded
            x = nn.Embed(self.vocab_size, self.d_model, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name="embed")(tokens)
        x = mesh_lib.constrain(x, llama._seq_rule("residual"))
        block_cls = NemotronBlock
        if self.remat:
            if self.remat_policy not in llama.REMAT_POLICIES:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    f"have {sorted(llama.REMAT_POLICIES)}")
            block_cls = nn.remat(
                NemotronBlock, prevent_cse=False,
                policy=llama.REMAT_POLICIES[self.remat_policy])
        block_args = dict(
            mamba=dict(num_heads=self.mamba_heads,
                       head_dim=self.mamba_head_dim,
                       state_dim=self.mamba_state, groups=self.mamba_groups,
                       conv_width=self.mamba_conv, chunk=self.mamba_chunk),
            attn=dict(num_heads=self.num_heads,
                      num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                      multiplier=1.0 / math.sqrt(self.head_dim),
                      attn_impl=self.attn_impl),
            experts=dict(
                num_experts=self.num_experts, ffn_dim=self.expert_ffn_dim,
                top_k=self.top_k, held_experts=self.held_experts,
                shared_ffn_dim=self.shared_ffn_dim,
                route_scale=self.route_scale,
                balance_coeff=self.balance_coeff),
            epsilon=self.epsilon, train=train, dtype=self.dtype,
            param_dtype=self.param_dtype)
        for i, kind in enumerate(self.pattern):
            x = block_cls(kind=kind, name=f"block_{i}", **block_args)(x)
        with jax.named_scope("norm"):
            x = RMSNorm(self.epsilon, self.dtype, self.param_dtype,
                        name="final_norm")(x)
        with jax.named_scope("head_loss"):
            logits = nn.Dense(self.vocab_size, use_bias=False,
                              dtype=self.dtype, param_dtype=self.param_dtype,
                              name="lm_head")(x)
            logits = mesh_lib.constrain(logits, llama._seq_rule("logits"))
            return logits.astype(self.logits_dtype)


def nemotron3_nano(**kw) -> NemotronH:
    """The published model: 52 layers, 128 experts, a vocabulary of 131,072."""
    return NemotronH(**kw)


#: The deployment the benchmark states: sixteen chips share every layer's
#: experts; the vocabulary's rows are cut in eight.
SHARE_CHIPS = 16
SHARE_VOCAB = 8
SHARE_LAYERS = 9    # ``MEMEM*EME``: the pattern's first period, every kind


def chip_share(model: NemotronH, chip: int = 0) -> NemotronH:
    """One chip's share of ``model`` in the deployment the benchmark states:
    sixteen chips share each expert layer by expert parallelism, so this one
    holds a sixteenth of the routed experts (``chip`` says which), an eighth
    of the vocabulary's rows, and the mixers, attention, the router and the
    shared expert whole; of the depth, the published layers 0..8 (the others
    lie on further pipeline stages). No width changes."""
    held = model.num_experts // SHARE_CHIPS
    return model.clone(pattern=model.pattern[:SHARE_LAYERS],
                       held_experts=(held, chip * held),
                       vocab_size=model.vocab_size // SHARE_VOCAB)


def nemotron_h_tiny(**kw) -> NemotronH:
    """Test scale: every kind of layer at toy widths, two B/C groups, two of
    eight experts held, and an expert width (136) that is no whole number of
    lane tiles, so that the grouped matmuls' last column block is
    part-filled."""
    kw.setdefault("vocab_size", 96)
    kw.setdefault("pattern", "ME*EM")
    kw.setdefault("d_model", 64)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("mamba_heads", 4)
    kw.setdefault("mamba_head_dim", 16)
    kw.setdefault("mamba_state", 16)
    kw.setdefault("mamba_groups", 2)
    kw.setdefault("mamba_chunk", 8)
    kw.setdefault("expert_ffn_dim", 136)
    kw.setdefault("shared_ffn_dim", 272)
    kw.setdefault("num_experts", 8)
    kw.setdefault("top_k", 2)
    kw.setdefault("held_experts", (2, 2))
    kw.setdefault("balance_coeff", 0.05)
    return NemotronH(**kw)


def _layer_macs(cfg: NemotronH, seq_len: int, experts: float) -> dict:
    """Multiply-accumulates a token of each kind of layer's matmuls, with
    ``experts`` routed experts a token; with ``seq_len`` None, the layer's
    parameters instead (``experts`` then the experts held, the conv, the
    small vectors and the layer's norm added)."""
    d = cfg.d_model
    H, P, N, G = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
                  cfg.mamba_groups)
    inner = H * P
    conv_dim = inner + 2 * G * N
    mamba = d * (inner + conv_dim + H) + inner * d
    attn = 2 * d * cfg.head_dim * (cfg.num_heads + cfg.num_kv_heads)
    moe = (d * cfg.num_experts + 2 * d * cfg.shared_ffn_dim
           + experts * 2 * d * cfg.expert_ffn_dim)
    if seq_len is None:
        mamba += conv_dim * (cfg.mamba_conv + 1) + 3 * H + inner
        return {"M": mamba + d, "*": attn + d, "E": moe + d}
    Q = min(cfg.mamba_chunk, seq_len)
    mamba += (Q + 1) / 2 * (G * N + inner) + 2 * inner * N
    attn += 2 * cfg.num_heads * cfg.head_dim * (seq_len + 1) / 2
    return {"M": mamba, "*": attn, "E": moe}


def num_params(cfg: NemotronH) -> int:
    """Parameters held (the routed experts this chip holds)."""
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    kinds = _layer_macs(cfg, None, held)
    return (2 * cfg.vocab_size * cfg.d_model + cfg.d_model
            + sum(kinds[k] for k in cfg.pattern))


def forward_flops_per_token(cfg: NemotronH, seq_len: int) -> float:
    """Forward FLOPs a token for MFU: 2 per multiply-accumulate of every
    matmul (the head once), the causal half of attention's two products, the
    scan's in-chunk products (``C B^T`` a group) and chunk states, the router,
    the shared expert, and the routed rows this chip expects: ``top_k * held
    / num_experts`` a token."""
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    kinds = _layer_macs(cfg, seq_len, cfg.top_k * held / cfg.num_experts)
    return 2.0 * (sum(kinds[k] for k in cfg.pattern)
                  + cfg.d_model * cfg.vocab_size)

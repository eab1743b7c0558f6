"""The ``glm4_moe_lite`` family (Z.ai GLM-4.7-Flash: ``zai-org/GLM-4.7-Flash``,
30B total / about 3B active): multi-head latent attention (MLA, DeepSeek-V2)
over a sigmoid-routed mixture of experts with a shared expert, and one
multi-token-prediction module (MTP, DeepSeek-V3 section 2.2).

Per token, hidden ``d`` (published ``config.json`` keys in brackets; what the
config does not state is the DeepSeek-V3 modelling code's, which the family
took over):

- ``h = E[id]``; ``logits = RMS(h_L; g_f) W_head``, untied.
- A layer, pre-norm, two norms: ``h += MLA(RMS(h; g1)); h += FFN(RMS(h; g2))``.
- ``MLA`` on ``x`` with ``num_attention_heads`` heads: ``c_q = RMS(x W_qa;
  g_q)`` (``q_lora_rank``), ``q = c_q W_qb``, a head ``[q_nope
  (qk_nope_head_dim); q_rope (qk_rope_head_dim)]``; ``[c_kv (kv_lora_rank);
  k_r (qk_rope_head_dim)] = x W_kva``, ``c_kv = RMS(c_kv; g_kv)``, ``[k_nope;
  v (v_head_dim)]`` a head ``= c_kv W_kvb``; ``q_h = [q_nope_h;
  RoPE(q_rope_h)]``, ``k_h = [k_nope_h; RoPE(k_r)]`` with the one ``k_r``
  shared by all heads (``rope_theta`` over all of ``qk_rope_head_dim``,
  rotate-half: the checkpoint's interleaved pairing is a fixed permutation of
  ``W_qb``'s and ``W_kva``'s rope columns). ``softmax(q_h k_h^T / sqrt(
  qk_nope_head_dim + qk_rope_head_dim)) v_h``, causal, all heads through
  ``W_o``. No biases. This is the expanded form, the training form; the
  absorbed form that attends over ``(c_kv, k_r)`` alone is a serving matter
  and is not built here (``(c_kv, RoPE(k_r))`` is what a latent cache would
  hold: ``LatentAttention`` sows both forms into ``intermediates``).
- ``FFN`` of the first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``. Of the others: :class:`parallel.moe.SharedExpertMoE`
  (``n_routed_experts`` sigmoid-scored, ``num_experts_per_tok`` chosen with a
  bias that the step updates and no optimizer sees, ``noaux_tc`` with one
  group; weights normalised and scaled by ``routed_scaling_factor``; one
  shared expert; all SwiGLU of width ``moe_intermediate_size``). No
  auxiliary loss term.
- ``MTP``, one module (``num_nextn_predict_layers``): ``h'_i = [RMS(E[t_{i+1}];
  g_e); RMS(h_i; g_h)] W_eh`` (the released code's order, embedding first;
  the DeepSeek-V3 paper writes the hidden state first), ``h_i`` the main
  model's output *after* its last norm (as the released serving code hands it
  over), then one expert layer of its own, then ``RMS(.; g_s) W_head``
  through the **same** embedding and head, predicting ``t_{i+2}``. ``loss =
  L_main + mtp_coeff * L_mtp``. The model sees ``tokens`` alone, so ``t_{i+1}``
  is ``tokens`` rolled left by one with id 0 behind the last (Megatron-LM's
  ``roll_tensor``): the module runs at all ``S`` positions, and ``L_mtp`` is
  the mean over the ``S - 2`` positions ``0..S-3`` a sequence whose ``t_{i+1}``
  and ``t_{i+2}`` are both in ``tokens``. The term rides the step through the
  ``losses`` collection (``core/train_loop.make_train_step`` adds what is
  sown there), coefficient applied here.

Same conventions as ``afmoe.py``: ``dtype`` / ``param_dtype``, a float32
residual stream (the router's near-ties need its input unrounded: PERF.md,
PR 33), ``remat`` per block, named regions ``embed``, ``attn`` (the module's;
inside it ``mla`` with ``mla_q``, ``mla_kv``, ``mla_rope``, ``mla_out``),
``mlp`` (inside it ``moe``, the module's), ``norm``, ``head_loss``; and
``mtp`` (the module's) around the prediction module: ``mtp_merge``, its
block's regions, and its own ``head_loss``. Sows into ``telemetry``:
``loss_mtp`` (unweighted) and ``loss_main`` (over the ``S - 1`` positions
whose target is in ``tokens``), beside the expert layers' own.

Training only: serving needs a latent ``(c_kv, k_r)`` paged cache and the
absorbed decode path. ``dp`` / ``fsdp`` only, as ``afmoe.py``.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.models import llama
from pytorch_distributed_training_example_tpu.models.llama import RMSNorm
from pytorch_distributed_training_example_tpu.ops import attention as attn_lib
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib
from pytorch_distributed_training_example_tpu.utils import metrics as metrics_lib


class LatentAttention(nn.Module):
    """Causal multi-head latent attention in its expanded form. The value
    width may differ from the query/key width (``ops/attention.attention``
    sends such a call to the online flash kernels or the XLA path).
    ``rope_inv_freq``: the rotary columns' inverse frequencies where they are
    not ``rope_theta``'s own powers; ``softmax_scale``: the scores' factor
    where it is not ``1 / sqrt(nope_dim + rope_dim)`` (a YaRN-scaled model
    brings both)."""
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    epsilon: float
    dtype: Any
    param_dtype: Any
    attn_impl: str = "auto"
    rope_inv_freq: tuple | None = None
    softmax_scale: float | None = None

    @nn.compact
    def __call__(self, h):
        kinds = dict(use_bias=False, dtype=self.dtype,
                     param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(self.epsilon, self.dtype,
                                    self.param_dtype, name=name)
        H, nope, S = self.num_heads, self.nope_dim, h.shape[1]
        rope = functools.partial(llama.rope, theta=self.rope_theta,
                                 inv_freq=self.rope_inv_freq)
        with jax.named_scope("mla"):
            with jax.named_scope("mla_q"):
                c_q = norm("q_norm")(nn.Dense(self.q_rank, name="q_a",
                                              **kinds)(h))
                q = nn.DenseGeneral((H, nope + self.rope_dim), name="q_b",
                                    **kinds)(c_q)
            with jax.named_scope("mla_kv"):
                down = nn.Dense(self.kv_rank + self.rope_dim, name="kv_a",
                                **kinds)(h)
                c_kv = norm("kv_norm")(down[..., :self.kv_rank])
                kv = nn.DenseGeneral((H, nope + self.v_dim), name="kv_b",
                                     **kinds)(c_kv)
            with jax.named_scope("mla_rope"):
                positions = jnp.arange(S)[None, :]
                k_r = rope(down[..., None, self.kv_rank:],
                           positions)                      # [B, S, 1, rope]
                q = jnp.concatenate(
                    [q[..., :nope], rope(q[..., nope:], positions)], axis=-1)
                k = jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(
                        k_r, (*kv.shape[:-1], self.rope_dim))], axis=-1)
                v = kv[..., nope:]
            self.sow("intermediates", "latent", (c_kv, k_r[..., 0, :]))
            self.sow("intermediates", "expanded", (k, v))
            q = mesh_lib.constrain(q, llama._seq_rule("qkv"))
            k = mesh_lib.constrain(k, llama._seq_rule("qkv"))
            v = mesh_lib.constrain(v, llama._seq_rule("qkv"))
            out = attn_lib.attention(q, k, v, causal=True,
                                     impl=self.attn_impl,
                                     scale=self.softmax_scale)
            with jax.named_scope("mla_out"):
                return nn.DenseGeneral(h.shape[-1], axis=(-2, -1), name="out",
                                       **kinds)(out)


class GlmBlock(nn.Module):
    """Two norms around latent attention and an FFN; ``experts`` None is a
    leading dense layer, otherwise :class:`SharedExpertMoE`'s sizes.
    Everything here is static, so each kind is one compiled body."""
    attn: dict          # LatentAttention's sizes
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
        # a float32 stream, and the router reads its norm unrounded
        # (afmoe.AfmoeBlock says why)
        f32 = jnp.float32
        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        a = LatentAttention(**self.attn, epsilon=self.epsilon, **kinds,
                            name="attn")(rn("attn_norm", x))
        x = mesh_lib.constrain(x + a.astype(f32),
                               llama._seq_rule("residual"))
        with jax.named_scope("mlp"):
            if self.experts is None:
                m = llama.swiglu_mlp(rn("ffn_norm", x), self.dense_ffn_dim,
                                     **kinds)
            else:
                m = moe_lib.SharedExpertMoE(**self.experts, **kinds,
                                            name="moe")(
                    rn("ffn_norm", x, f32), self.train)
        return mesh_lib.constrain(x + m.astype(f32),
                                  llama._seq_rule("residual"))


class MTPModule(nn.Module):
    """One prediction depth: merges the main model's output with the next
    token's embedding, runs one expert layer of its own and its own last
    norm; the caller owns the shared embedding and head."""
    block: Any          # the (possibly remat) block class
    block_args: dict
    epsilon: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, h, next_emb):
        norm = lambda name: RMSNorm(self.epsilon, self.dtype,
                                    self.param_dtype, name=name)
        with jax.named_scope("mtp_merge"):
            with jax.named_scope("norm"):
                merged = jnp.concatenate(
                    [norm("enorm")(next_emb), norm("hnorm")(h)], axis=-1)
            with jax.named_scope("embed"):
                x = nn.Dense(h.shape[-1], use_bias=False, dtype=self.dtype,
                             param_dtype=self.param_dtype,
                             name="eh_proj")(merged).astype(jnp.float32)
        x = mesh_lib.constrain(x, llama._seq_rule("residual"))
        x = self.block(**self.block_args, name="mtp_block")(x)
        with jax.named_scope("norm"):
            return norm("head_norm")(x)


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _masked_head_loss(h, kernel, targets, weight, dtype):
    """``sum(weight * CE(h W_head, targets)) / sum(weight)``, the logits
    float32. Checkpointed: the backward makes the ``[B, S, vocab]`` logits
    again from ``h``, so a second depth's logits do not wait beside the main
    model's for their gradient."""
    logits = jnp.dot(h.astype(dtype), kernel.astype(dtype))
    logits = mesh_lib.constrain(logits, llama._seq_rule("logits"))
    ce = metrics_lib.per_example_cross_entropy(logits.astype(jnp.float32),
                                               targets)
    return jnp.sum(ce * weight) / jnp.sum(weight)


class GlmMoeLite(nn.Module):
    # the published sizes of GLM-4.7-Flash are the defaults
    vocab_size: int = 154880
    num_layers: int = 47
    num_dense_layers: int = 1           # first_k_dense_replace
    mtp_layers: int = 1                 # num_nextn_predict_layers (0 or 1)
    mtp_coeff: float = 0.3
    d_model: int = 2048
    num_heads: int = 20
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 192
    rope_dim: int = 64
    v_dim: int = 256
    dense_ffn_dim: int = 10240
    expert_ffn_dim: int = 1536
    num_experts: int = 64
    top_k: int = 4
    held_experts: tuple | None = None   # (how many, starting where); None: all
    shared_experts: int = 1
    route_scale: float = 1.8
    balance_coeff: float = 0.001
    rope_theta: float = 1e6
    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "nothing"  # key into llama.REMAT_POLICIES
    attn_impl: str = "auto"
    logits_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 decode_ctx: dict | None = None):
        if decode_ctx is not None:
            raise NotImplementedError(
                "the glm_moe_lite family trains only: serving it needs a "
                "latent (c_kv, k_r) cache in serve/kv_cache.py and the "
                "absorbed decode path, which the repo does not have")
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers={self.mtp_layers}: 0 or 1")
        # float32 rows: the residual stream starts unrounded
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name="embed")
        head = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="lm_head")
        with jax.named_scope("embed"):
            x = embed(tokens)
        x = mesh_lib.constrain(x, llama._seq_rule("residual"))
        block_cls = GlmBlock
        if self.remat:
            if self.remat_policy not in llama.REMAT_POLICIES:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    f"have {sorted(llama.REMAT_POLICIES)}")
            block_cls = nn.remat(
                GlmBlock, prevent_cse=False,
                policy=llama.REMAT_POLICIES[self.remat_policy])
        experts = dict(
            num_experts=self.num_experts, ffn_dim=self.expert_ffn_dim,
            top_k=self.top_k, held_experts=self.held_experts,
            shared_ffn_dim=self.shared_experts * self.expert_ffn_dim,
            route_scale=self.route_scale, balance_coeff=self.balance_coeff)
        block_args = dict(
            attn=dict(num_heads=self.num_heads, q_rank=self.q_rank,
                      kv_rank=self.kv_rank, nope_dim=self.nope_dim,
                      rope_dim=self.rope_dim, v_dim=self.v_dim,
                      rope_theta=self.rope_theta, attn_impl=self.attn_impl),
            dense_ffn_dim=self.dense_ffn_dim, epsilon=self.epsilon,
            train=train, dtype=self.dtype, param_dtype=self.param_dtype)
        for i in range(self.num_layers):
            x = block_cls(
                **block_args, name=f"block_{i}",
                experts=None if i < self.num_dense_layers else experts)(x)
        with jax.named_scope("norm"):
            # float32 out: the prediction module norms it again
            x = RMSNorm(self.epsilon, jnp.float32, self.param_dtype,
                        name="final_norm")(x)
        with jax.named_scope("head_loss"):
            logits = head(x)
            logits = mesh_lib.constrain(logits, llama._seq_rule("logits"))
            logits = logits.astype(self.logits_dtype)
        if self.mtp_layers and (train or self.is_initializing()):
            self._second_depth(tokens, x, logits, embed, head, block_cls,
                               dict(block_args, experts=experts))
        return logits

    def _second_depth(self, tokens, h, logits, embed, head, block_cls,
                      block_args):
        """Sows ``mtp_coeff * L_mtp`` into ``losses``, and both depths'
        losses into ``telemetry``."""
        S = tokens.shape[1]
        ahead = lambda n: jnp.concatenate(
            [tokens[:, n:], jnp.zeros_like(tokens[:, :n])], axis=1)
        scored = lambda n: jnp.broadcast_to(
            (jnp.arange(S) < S - n).astype(jnp.float32), tokens.shape)
        with jax.named_scope("mtp"):
            with jax.named_scope("embed"):
                next_emb = embed(ahead(1))
            x = MTPModule(block=block_cls, block_args=block_args,
                          epsilon=self.epsilon, dtype=self.dtype,
                          param_dtype=self.param_dtype, name="mtp")(
                h, next_emb)
            with jax.named_scope("head_loss"):
                loss_mtp = _masked_head_loss(
                    x, head.variables["params"]["kernel"], ahead(2),
                    scored(2), self.dtype)
        self.sow("losses", "mtp_loss", self.mtp_coeff * loss_mtp)
        if self.is_mutable_collection("telemetry"):
            with jax.named_scope("head_loss"):
                ce = metrics_lib.per_example_cross_entropy(
                    logits.astype(jnp.float32), ahead(1))
                loss_main = jnp.sum(ce * scored(1)) / jnp.sum(scored(1))
            self.sow("telemetry", "loss_main", loss_main)
            self.sow("telemetry", "loss_mtp", loss_mtp)


def glm47_flash(**kw) -> GlmMoeLite:
    """The published model: 47 layers, the first dense, and the MTP layer."""
    return GlmMoeLite(**kw)


#: The deployment the benchmark states: eight chips share every layer.
SHARE_CHIPS = 8
#: Expert layers kept after the leading dense one (the pattern's period is 1).
SHARE_EXPERT_LAYERS = 4


def chip_share(model: GlmMoeLite, chip: int = 0) -> GlmMoeLite:
    """One chip's share of ``model`` in the deployment the benchmark states:
    eight chips share each layer by expert parallelism, so this one holds an
    eighth of the routed experts (``chip`` says which) and of the vocabulary's
    rows, and attention, the router and the shared expert whole; of the
    depth, the leading dense layer, the four expert layers after it and the
    MTP layer, which reads the last of them (the others lie on further
    pipeline stages). No width changes."""
    held = model.num_experts // SHARE_CHIPS
    return model.clone(
        num_layers=model.num_dense_layers + SHARE_EXPERT_LAYERS,
        held_experts=(held, chip * held),
        vocab_size=model.vocab_size // SHARE_CHIPS)


def glm_moe_lite_tiny(**kw) -> GlmMoeLite:
    """Test scale: a dense layer, two expert layers and the MTP layer at toy
    widths (value and query/key widths equal, as published), two of eight
    experts held, and a bias step large enough to change the choice within
    three steps."""
    kw.setdefault("vocab_size", 96)
    kw.setdefault("num_layers", 3)
    kw.setdefault("d_model", 64)
    kw.setdefault("num_heads", 4)
    kw.setdefault("q_rank", 24)
    kw.setdefault("kv_rank", 16)
    kw.setdefault("nope_dim", 12)
    kw.setdefault("rope_dim", 4)
    kw.setdefault("v_dim", 16)
    kw.setdefault("dense_ffn_dim", 128)
    kw.setdefault("expert_ffn_dim", 32)
    kw.setdefault("num_experts", 8)
    kw.setdefault("top_k", 2)
    kw.setdefault("held_experts", (2, 2))
    kw.setdefault("balance_coeff", 0.05)
    return GlmMoeLite(**kw)


def _attn_params(cfg: GlmMoeLite) -> int:
    d, H = cfg.d_model, cfg.num_heads
    qk = cfg.nope_dim + cfg.rope_dim
    return (d * cfg.q_rank + cfg.q_rank * H * qk
            + d * (cfg.kv_rank + cfg.rope_dim)
            + cfg.kv_rank * H * (cfg.nope_dim + cfg.v_dim)
            + H * cfg.v_dim * d)


def _layer_params(cfg: GlmMoeLite, experts: int) -> tuple[int, int]:
    """(a dense layer's, an expert layer's) parameters with ``experts``
    routed experts counted."""
    d = cfg.d_model
    attn = (_attn_params(cfg) + cfg.q_rank + cfg.kv_rank   # latent norms
            + 2 * d)                                       # the block's two
    swiglu = lambda width: 3 * d * width
    moe = (d * cfg.num_experts + swiglu(cfg.expert_ffn_dim)
           * (cfg.shared_experts + experts))
    return attn + swiglu(cfg.dense_ffn_dim), attn + moe


def num_params(cfg: GlmMoeLite) -> int:
    """Parameters held (the routed experts this chip holds); the MTP module
    with its own expert layer, three norms and ``eh_proj``."""
    d = cfg.d_model
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    dense, expert = _layer_params(cfg, held)
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    return (2 * cfg.vocab_size * d + d
            + n_dense * dense + (cfg.num_layers - n_dense) * expert
            + cfg.mtp_layers * (expert + 3 * d + 2 * d * d))


def forward_flops_per_token(cfg: GlmMoeLite, seq_len: int) -> float:
    """Forward FLOPs a token for MFU: 2 per multiply-accumulate of every
    matmul: the low-rank projections, the keys a row sees in attention's two
    products (the causal half, at the query/key and the value widths), the
    router, the shared expert, the routed rows this chip expects (``top_k *
    held / num_experts`` a token); the MTP module's merge, layer and head
    beside the main head."""
    d, S = cfg.d_model, seq_len
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    keys = (S + 1) / 2
    attn = (_attn_params(cfg) + cfg.num_heads * keys
            * (cfg.nope_dim + cfg.rope_dim + cfg.v_dim))
    swiglu = lambda width: 3 * d * width
    moe = (d * cfg.num_experts + swiglu(cfg.expert_ffn_dim)
           * (cfg.shared_experts + cfg.top_k * held / cfg.num_experts))
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    macs = (cfg.num_layers * attn + n_dense * swiglu(cfg.dense_ffn_dim)
            + (cfg.num_layers - n_dense) * moe + d * cfg.vocab_size
            + cfg.mtp_layers * (2 * d * d + attn + moe + d * cfg.vocab_size))
    return 2.0 * macs

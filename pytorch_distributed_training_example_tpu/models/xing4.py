"""The ``xing4_0`` family (XingChen-AGI Xing4.0-29B-A4B, about 29B total / 4B
active): DeepSeek-V3's block (multi-head latent attention over a
sigmoid-routed mixture of experts with a shared expert) on a **residual path
four streams wide**, mixed by manifold-constrained hyper-connections (mHC: Xie
et al., arXiv 2512.24880, on Zhu et al.'s hyper-connections, arXiv
2409.19606), with a value width (128) under the query/key width (128 + 64)
and YaRN-scaled rotary positions.

Per token, hidden ``d``, ``n = hc_mult`` streams, ``X`` the token's ``[n, d]``
float32 stream (published ``config.json`` keys in brackets; what the config
does not state is the DeepSeek-V3 modelling code's and the two papers', as
``chipbench/configs/xing4_29b.json`` lists under ``assumed``):

- ``X_0 = [E[id]] * n`` (every stream starts as the embedding); after the
  last block ``h = sum_i X[i]`` and ``logits = RMS(h; g_f) W_head``, untied
  (the hyper-connections paper's expand and sum).
- A layer is two sub-layers, each with its own pre-norm branch ``F`` and its
  own hyper-connection: ``F = MLA(RMS(u; g1))``, then ``F = FFN(RMS(u; g2))``.
- A sub-layer (:class:`HyperConnection` and :func:`hc_write`): ``x' =
  RMS(vec(X))`` over all ``n d`` entries (``rms_norm_eps``, no learned
  scale); ``H^_pre = a_pre (x' phi_pre) + b_pre`` and ``H^_post = a_post (x'
  phi_post) + b_post`` in ``R^n``, ``H^_res = a_res mat(x' phi_res) + b_res`` in
  ``R^{n x n}`` (``phi``: ``[n d, n]``, ``[n d, n]``, ``[n d, n n]``; ``a``
  scalars); ``H_pre = sigmoid(H^_pre)``, ``H_post = 2 sigmoid(H^_post)``,
  ``H_res = SK(clip(H^_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))``:
  ``M = exp(.)``, then ``hc_sinkhorn_iters`` times every column divided by
  (its sum + ``hc_eps``) and then every row by (its sum + ``hc_eps``): rows
  sum to one, columns nearly. ``u = H_pre X`` (``[d]``: the read); ``y =
  F(u)``; ``X <- H_res X + H_post^T y`` (stream ``i`` gets ``H_post[i] y``:
  the write). All float32, the ``phi`` product at ``Precision.HIGHEST`` as
  the routers' is (its scores decide a mixture, as theirs a choice); the norm
  is taken as ``(vec(X) phi) / rms``: the same number, with no ``x'`` in
  memory. ``b_res`` is stored flat (``[n n]``): the optimizer decays
  matrices, and this is a bias.
- ``MLA``: :class:`glm_moe_lite.LatentAttention` (its docstring has the
  equations) with ``v_head_dim != qk_nope_head_dim + qk_rope_head_dim``, the
  rotary columns' inverse frequencies YaRN's (:func:`yarn_inv_freq`,
  ``rope_scaling``) and the scores' factor ``(qk_nope_head_dim +
  qk_rope_head_dim)^-1/2 mscale^2``, ``mscale = 0.1 mscale_all_dim ln(factor)
  + 1`` (:func:`yarn_mscale`; the cos/sin factor ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)`` is 1 where the two are equal, as
  published).
- ``FFN`` of the first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``; of the others :class:`parallel.moe.SharedExpertMoE`
  as the ``glm_moe_lite`` family configures it (``n_routed_experts``
  sigmoid-scored, ``num_experts_per_tok`` chosen with a bias that the step
  updates and no optimizer sees, weights normalised and scaled by
  ``routed_scaling_factor``, one shared expert, no auxiliary loss).
- ``num_nextn_predict_layers``: the published model has one prediction module
  (DeepSeek-V3 section 2.2). The row does not say how the module's merge
  ``[RMS(E[t+1]); RMS(h)] W`` joins a stream ``n`` wide (which of ``X``, ``h``
  or ``u`` it reads, and whether its block carries hyper-connections of its
  own), so ``mtp_layers`` other than 0 raises rather than guesses.

Same conventions as ``glm_moe_lite.py``: ``dtype`` / ``param_dtype``, a
float32 stream, ``remat`` per block (a block's boundary is the ``[B, S, n,
d]`` stream), named regions ``embed``, ``attn`` / ``mla`` and its parts,
``mlp`` / ``moe``, ``norm``, ``head_loss``, and around each hyper-connection
``hc`` with ``hc_maps`` (norm, product, sigmoids), ``hc_sinkhorn``,
``hc_read``, ``hc_write`` inside. Under ``--telemetry`` a block sows
``hc_res_row_err`` (max |row sum - 1| after the last iteration),
``hc_res_diag`` (the mean diagonal of ``H_res``), ``hc_pre_mean`` and
``hc_post_mean``, each the mean over the block's two hyper-connections, with
the block's name behind a dot, beside the expert layers' own.

Training only: serving needs a latent ``(c_kv, k_r)`` cache and a decode
step that carries ``n`` streams a token. ``dp`` / ``fsdp`` only, as
``afmoe.py``.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.models import llama
from pytorch_distributed_training_example_tpu.models.glm_moe_lite import (
    LatentAttention, _attn_params)
from pytorch_distributed_training_example_tpu.models.llama import RMSNorm
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib


def yarn_inv_freq(rope_dim: int, theta: float, factor: float,
                  original_positions: int, beta_fast: float,
                  beta_slow: float) -> tuple:
    """YaRN's ``rope_dim / 2`` inverse frequencies (DeepSeek-V3's modelling
    code): ``f_i = theta^(-2i / rope_dim)``; the pair whose wavelength turns
    ``beta`` times over the original positions is ``rope_dim ln(original /
    (2 pi beta)) / (2 ln theta)``, ``low`` its floor at ``beta_fast`` and
    ``high`` its ceiling at ``beta_slow``, both clipped to ``[0, rope_dim -
    1]``; ``m_i = 1 - clip((i - low) / (high - low), 0, 1)`` keeps the fast
    pairs as they are and divides the slow ones by ``factor``: ``(f_i /
    factor) (1 - m_i) + f_i m_i``. Python floats: a module's field."""
    half = rope_dim // 2
    turns = lambda beta: (rope_dim * math.log(
        original_positions / (2 * math.pi * beta)) / (2 * math.log(theta)))
    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), rope_dim - 1)
    span = max(high - low, 0.001)       # the released code's guard
    out = []
    for i in range(half):
        f = theta ** (-2.0 * i / rope_dim)
        keep = 1.0 - min(max((i - low) / span, 0.0), 1.0)
        out.append(f / factor * (1.0 - keep) + f * keep)
    return tuple(out)


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 mscale ln(factor) + 1`` (1 where nothing is scaled)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


#: Iterations of the projection a trip of its loop.
SINKHORN_UNROLL = 5


def sinkhorn(logits, iters: int, eps: float):
    """``[..., n, n]`` logits to the (nearly) doubly stochastic ``T_r(T_c(.
    ))^iters (exp(logits))``: every column divided by (its sum + ``eps``),
    then every row, ``iters`` times. Worked with the tokens along the lanes
    (``[n, n, T]``): a sum over four is then an add of whole vectors, and an
    iteration's ``[T, 4, 4]`` would fill a thirty-second of its tiles. The
    iterations are a ``scan`` unrolled by ``SINKHORN_UNROLL``: all unrolled,
    ten hyper-connections' chains were three thousand operations of a step's
    text (27 MB) and a minute of its compile; none unrolled, six hundred loop
    trips a step cost 11 ms of a 116 ms step (PERF.md section 6, PR 54)."""
    n = logits.shape[-1]
    m = jnp.exp(jnp.moveaxis(logits.reshape(-1, n, n), 0, -1))

    def iteration(m, _):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=1, keepdims=True) + eps), None

    m, _ = jax.lax.scan(iteration, m, None, length=iters,
                        unroll=SINKHORN_UNROLL)
    return jnp.moveaxis(m, -1, 0).reshape(logits.shape)


def _stream_rule():
    """The ``[B, S, n, d]`` stream's constraint: the residual's, the streams
    and the hidden size whole."""
    residual = llama._seq_rule("residual")
    return P(residual[0], residual[1], None, None)


class HyperConnection(nn.Module):
    """One sub-layer's maps and its read: ``X [B, S, n, d]`` float32 to ``(u
    [B, S, d], H_res [B, S, n, n], H_post [B, S, n])``; :func:`hc_write`
    puts the branch's output back. The equations are in the module's
    docstring. The program's own init is a plain residual path: ``H_pre =
    1 / n`` (``u`` the streams' mean), ``H_post = 1``, ``H_res`` near the
    identity, and small input-dependent parts."""
    sinkhorn_iters: int
    sinkhorn_eps: float
    clamp: tuple            # (min, max) of the residual map's logits
    epsilon: float          # the norm's
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, X):
        n, d = X.shape[-2:]
        f32 = jnp.float32
        normal = nn.initializers.normal(0.02)
        const = lambda value: nn.initializers.constant(value)
        phi = lambda name, width: self.param(
            name, normal, (n * d, width), self.param_dtype).astype(f32)
        alpha = lambda name: self.param(
            name, const(0.01), (), self.param_dtype).astype(f32)
        b_pre = self.param("b_pre", const(-math.log(n - 1.0)), (n,),
                           self.param_dtype).astype(f32)
        b_post = self.param("b_post", const(0.0), (n,),
                            self.param_dtype).astype(f32)
        b_res = self.param(
            "b_res", lambda *_: 4.0 * jnp.eye(n, dtype=self.param_dtype
                                              ).reshape(n * n),
            (n * n,), self.param_dtype).astype(f32)
        with jax.named_scope("hc"):
            with jax.named_scope("hc_maps"):
                flat = X.astype(f32).reshape(*X.shape[:-2], n * d)
                inv_rms = jax.lax.rsqrt(jnp.mean(
                    jnp.square(flat), axis=-1, keepdims=True) + self.epsilon)
                kernel = jnp.concatenate(
                    [phi("phi_pre", n), phi("phi_post", n),
                     phi("phi_res", n * n)], axis=1)
                raw = jnp.dot(flat, kernel,
                              precision=jax.lax.Precision.HIGHEST) * inv_rms
                h_pre = jax.nn.sigmoid(
                    alpha("alpha_pre") * raw[..., :n] + b_pre)
                h_post = 2.0 * jax.nn.sigmoid(
                    alpha("alpha_post") * raw[..., n:2 * n] + b_post)
                logits = jnp.clip(
                    alpha("alpha_res") * raw[..., 2 * n:] + b_res,
                    *self.clamp).reshape(*raw.shape[:-1], n, n)
            with jax.named_scope("hc_sinkhorn"):
                h_res = sinkhorn(logits, self.sinkhorn_iters,
                                 self.sinkhorn_eps)
            with jax.named_scope("hc_read"):
                u = jnp.sum(h_pre[..., None] * X, axis=-2)
        if self.is_mutable_collection("telemetry"):
            rows = jnp.sum(h_res, axis=-1)
            moe_lib._sow_telemetry(
                self, hc_res_row_err=jnp.max(jnp.abs(rows - 1.0)),
                hc_res_diag=jnp.mean(jnp.trace(h_res, axis1=-2, axis2=-1)) / n,
                hc_pre_mean=jnp.mean(h_pre), hc_post_mean=jnp.mean(h_post))
        return u, h_res, h_post


def hc_write(X, h_res, h_post, y):
    """``H_res X + H_post^T y``: the streams mixed, and the branch's output
    added to each by its own weight."""
    with jax.named_scope("hc"), jax.named_scope("hc_write"):
        mixed = jnp.sum(h_res[..., :, :, None] * X[..., None, :, :], axis=-2)
        X = mixed + h_post[..., None] * y.astype(jnp.float32)[..., None, :]
    return mesh_lib.constrain(X, _stream_rule())


class XingBlock(nn.Module):
    """Latent attention and an FFN, each read from and written to the ``n``
    streams by its own hyper-connection; ``experts`` None is a leading dense
    layer, otherwise :class:`SharedExpertMoE`'s sizes. Everything here is
    static, so each kind is one compiled body."""
    attn: dict          # LatentAttention's sizes
    hc: dict            # HyperConnection's
    dense_ffn_dim: int
    experts: dict | None
    epsilon: float
    train: bool
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, X):
        def rn(name, h, dtype=self.dtype):
            with jax.named_scope("norm"):
                return RMSNorm(self.epsilon, dtype, self.param_dtype,
                               name=name)(h)
        connection = lambda name: HyperConnection(
            **self.hc, epsilon=self.epsilon, param_dtype=self.param_dtype,
            name=name)
        kinds = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        u, h_res, h_post = connection("hc_attn")(X)
        a = LatentAttention(**self.attn, epsilon=self.epsilon, **kinds,
                            name="attn")(rn("attn_norm", u))
        X = hc_write(X, h_res, h_post, a)
        u, h_res, h_post = connection("hc_ffn")(X)
        with jax.named_scope("mlp"):
            if self.experts is None:
                m = llama.swiglu_mlp(rn("ffn_norm", u), self.dense_ffn_dim,
                                     **kinds)
            else:
                # the router reads its norm unrounded (afmoe.AfmoeBlock says
                # why)
                m = moe_lib.SharedExpertMoE(**self.experts, **kinds,
                                            name="moe")(
                    rn("ffn_norm", u, jnp.float32), self.train)
        return hc_write(X, h_res, h_post, m)


class Xing4(nn.Module):
    # the published sizes of Xing4.0-29B-A4B are the defaults
    vocab_size: int = 131072
    num_layers: int = 40
    num_dense_layers: int = 2           # first_k_dense_replace
    mtp_layers: int = 1                 # num_nextn_predict_layers
    d_model: int = 3584
    num_heads: int = 32
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_ffn_dim: int = 9216
    expert_ffn_dim: int = 1024
    num_experts: int = 64
    top_k: int = 4
    held_experts: tuple | None = None   # (how many, starting where); None: all
    shared_experts: int = 1
    route_scale: float = 2.0
    balance_coeff: float = 0.001
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    rope_theta: float = 1e4
    # rope_scaling (type yarn); factor 1 is a plain rope
    yarn_factor: float = 64.0
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    epsilon: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "nothing"  # key into llama.REMAT_POLICIES
    attn_impl: str = "auto"
    logits_dtype: Any = jnp.float32

    def attention_sizes(self) -> dict:
        """:class:`LatentAttention`'s fields, YaRN's two among them."""
        scaled = self.yarn_factor > 1
        if scaled and self.yarn_mscale != self.yarn_mscale_all_dim:
            raise NotImplementedError(
                "rope_scaling with mscale != mscale_all_dim multiplies cos "
                "and sin by their ratio; llama.rope has no such factor")
        return dict(
            num_heads=self.num_heads, q_rank=self.q_rank,
            kv_rank=self.kv_rank, nope_dim=self.nope_dim,
            rope_dim=self.rope_dim, v_dim=self.v_dim,
            rope_theta=self.rope_theta, attn_impl=self.attn_impl,
            rope_inv_freq=yarn_inv_freq(
                self.rope_dim, self.rope_theta, self.yarn_factor,
                self.yarn_original_positions, self.yarn_beta_fast,
                self.yarn_beta_slow) if scaled else None,
            softmax_scale=(self.nope_dim + self.rope_dim) ** -0.5 * yarn_mscale(
                self.yarn_factor, self.yarn_mscale_all_dim) ** 2
            if scaled else None)

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 decode_ctx: dict | None = None):
        if decode_ctx is not None:
            raise NotImplementedError(
                "the xing4 family trains only: serving it needs a latent "
                "(c_kv, k_r) cache in serve/kv_cache.py, the absorbed decode "
                "path, and a decode step that carries hc_mult streams a "
                "token, which the repo does not have")
        if self.mtp_layers:
            raise NotImplementedError(
                f"mtp_layers={self.mtp_layers}: the published config does "
                "not state how the prediction module joins a stream hc_mult "
                "wide (whether its merge reads the streams, their sum or a "
                "hyper-connection's read, and whether its block has "
                "hyper-connections of its own); build with mtp_layers=0")
        # float32 rows: the streams start unrounded
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name="embed")
        head = nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="lm_head")
        with jax.named_scope("embed"):
            x = embed(tokens)
            X = jnp.broadcast_to(x[..., None, :],
                                 (*x.shape[:-1], self.hc_mult, self.d_model))
        X = mesh_lib.constrain(X, _stream_rule())
        block_cls = XingBlock
        if self.remat:
            if self.remat_policy not in llama.REMAT_POLICIES:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    f"have {sorted(llama.REMAT_POLICIES)}")
            block_cls = nn.remat(
                XingBlock, prevent_cse=False,
                policy=llama.REMAT_POLICIES[self.remat_policy])
        experts = dict(
            num_experts=self.num_experts, ffn_dim=self.expert_ffn_dim,
            top_k=self.top_k, held_experts=self.held_experts,
            shared_ffn_dim=self.shared_experts * self.expert_ffn_dim,
            route_scale=self.route_scale, balance_coeff=self.balance_coeff)
        block_args = dict(
            attn=self.attention_sizes(),
            hc=dict(sinkhorn_iters=self.hc_sinkhorn_iters,
                    sinkhorn_eps=self.hc_eps, clamp=self.hc_res_clamp),
            dense_ffn_dim=self.dense_ffn_dim, epsilon=self.epsilon,
            train=train, dtype=self.dtype, param_dtype=self.param_dtype)
        for i in range(self.num_layers):
            X = block_cls(
                **block_args, name=f"block_{i}",
                experts=None if i < self.num_dense_layers else experts)(X)
        with jax.named_scope("norm"):
            x = RMSNorm(self.epsilon, self.dtype, self.param_dtype,
                        name="final_norm")(jnp.sum(X, axis=-2))
        x = mesh_lib.constrain(x, llama._seq_rule("residual"))
        with jax.named_scope("head_loss"):
            logits = head(x)
            logits = mesh_lib.constrain(logits, llama._seq_rule("logits"))
            return logits.astype(self.logits_dtype)


def xing4_29b(**kw) -> Xing4:
    """The published model: 40 layers, the first two dense, and the
    prediction layer (which this family does not build: ``mtp_layers=0``
    trains the 40)."""
    return Xing4(**kw)


#: The deployment the benchmark states: eight chips share every layer.
SHARE_CHIPS = 8
#: Expert layers kept after the leading dense one (the pattern's period is 1).
SHARE_EXPERT_LAYERS = 4


def chip_share(model: Xing4, chip: int = 0) -> Xing4:
    """One chip's share of ``model`` in the deployment the benchmark states:
    eight chips share each layer by expert parallelism, so this one holds an
    eighth of the routed experts (``chip`` says which) and of the vocabulary's
    rows, and attention, the hyper-connections, the router and the shared
    expert whole; of the depth, one leading dense layer (the two are alike:
    they count once) and the four expert layers after the dense ones; the
    others, and the prediction layer behind the last, lie on further pipeline
    stages. No width changes."""
    held = model.num_experts // SHARE_CHIPS
    return model.clone(
        num_layers=1 + SHARE_EXPERT_LAYERS, num_dense_layers=1, mtp_layers=0,
        held_experts=(held, chip * held),
        vocab_size=model.vocab_size // SHARE_CHIPS)


def xing4_tiny(**kw) -> Xing4:
    """Test scale: a dense layer and two expert layers at toy widths (the
    value width under the query/key width, as published), three streams, two
    of eight experts held, YaRN over 16 original positions so that a
    48-token sequence crosses them, and a bias step large enough to change
    the choice within three steps."""
    kw.setdefault("vocab_size", 96)
    kw.setdefault("num_layers", 3)
    kw.setdefault("num_dense_layers", 1)
    kw.setdefault("mtp_layers", 0)
    kw.setdefault("d_model", 64)
    kw.setdefault("num_heads", 4)
    kw.setdefault("q_rank", 24)
    kw.setdefault("kv_rank", 16)
    kw.setdefault("nope_dim", 8)
    kw.setdefault("rope_dim", 8)
    kw.setdefault("v_dim", 8)
    kw.setdefault("dense_ffn_dim", 128)
    kw.setdefault("expert_ffn_dim", 32)
    kw.setdefault("num_experts", 8)
    kw.setdefault("top_k", 2)
    kw.setdefault("held_experts", (2, 2))
    kw.setdefault("balance_coeff", 0.05)
    kw.setdefault("hc_mult", 3)
    kw.setdefault("yarn_factor", 4.0)
    kw.setdefault("yarn_original_positions", 16)
    return Xing4(**kw)


def _hc_params(cfg: Xing4) -> int:
    """One hyper-connection's: the three ``phi``, the biases, the scalars."""
    n = cfg.hc_mult
    maps = 2 * n + n * n
    return n * cfg.d_model * maps + maps + 3


def _layer_params(cfg: Xing4, experts: int) -> tuple[int, int]:
    """(a dense layer's, an expert layer's) parameters with ``experts``
    routed experts counted."""
    d = cfg.d_model
    attn = (_attn_params(cfg) + cfg.q_rank + cfg.kv_rank   # latent norms
            + 2 * d + 2 * _hc_params(cfg))      # the block's two norms, two hc
    swiglu = lambda width: 3 * d * width
    moe = (d * cfg.num_experts + swiglu(cfg.expert_ffn_dim)
           * (cfg.shared_experts + experts))
    return attn + swiglu(cfg.dense_ffn_dim), attn + moe


def num_params(cfg: Xing4) -> int:
    """Parameters held (the routed experts this chip holds), a prediction
    module counted as DeepSeek-V3's: one expert layer, three norms and the
    ``2d x d`` merge."""
    d = cfg.d_model
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    dense, expert = _layer_params(cfg, held)
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    return (2 * cfg.vocab_size * d + d
            + n_dense * dense + (cfg.num_layers - n_dense) * expert
            + cfg.mtp_layers * (expert + 3 * d + 2 * d * d))


def forward_flops_per_token(cfg: Xing4, seq_len: int) -> float:
    """Forward FLOPs a token for MFU: 2 per multiply-accumulate of every
    matmul: the low-rank projections, the keys a row sees in attention's two
    products (the causal half, at the query/key and the value widths), the
    hyper-connections' ``phi`` products (two a layer), the router, the shared
    expert, the routed rows this chip expects (``top_k * held / num_experts``
    a token), the head. The streams' reads, mixes and writes are no matmul
    and are not counted."""
    d, S, n = cfg.d_model, seq_len, cfg.hc_mult
    held = (cfg.held_experts or (cfg.num_experts, 0))[0]
    keys = (S + 1) / 2
    attn = (_attn_params(cfg) + cfg.num_heads * keys
            * (cfg.nope_dim + cfg.rope_dim + cfg.v_dim)
            + 2 * n * d * (2 * n + n * n))
    swiglu = lambda width: 3 * d * width
    moe = (d * cfg.num_experts + swiglu(cfg.expert_ffn_dim)
           * (cfg.shared_experts + cfg.top_k * held / cfg.num_experts))
    n_dense = min(cfg.num_dense_layers, cfg.num_layers)
    macs = (cfg.num_layers * attn + n_dense * swiglu(cfg.dense_ffn_dim)
            + (cfg.num_layers - n_dense) * moe + d * cfg.vocab_size)
    return 2.0 * macs

"""Llama-3 family — the reference's large-model config (BASELINE.json
configs[4]: Llama-3 8B, FSDP + gradient checkpointing on v5p-32).

Standard Llama-3 architecture: RMSNorm (pre-norm), rotary position
embeddings (theta 500k), grouped-query attention (8 KV heads), SwiGLU MLP,
no biases, untied output head.

TPU-first: same sharding-by-annotation scheme as gpt2.py (heads sharded on
'model', sequence on 'context', GQA KV heads replicated across TP when
num_kv_heads < tp); ``remat`` per block for the grad-checkpoint config;
``scan_layers`` trades python-loop unrolling for an ``nn.scan`` over a
stacked block (constant compile time at depth 32+, params gain a leading
layer dim handled by the partition rules).
"""

from __future__ import annotations

import contextlib
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import ad_checkpoint
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.ops import attention as attn_lib
from pytorch_distributed_training_example_tpu.parallel import sharding

BATCH = mesh_lib.BATCH_AXES


def _seq_rule(name: str, sp: bool = False):
    """Sequence/context activation spec from the shared rule table
    (parallel/sharding.seq_rules): Megatron SP additionally shards the
    residual stream's sequence dim over the TP axis between matmul regions
    (GSPMD reshards)."""
    return sharding.seq_rules(sp)[name]


class RMSNorm(nn.Module):
    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        return (norm * scale.astype(jnp.float32)).astype(self.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq=None) -> jax.Array:
    """Rotary embeddings on [B, S, H, D] (rotate half, fp32 trig).
    ``inv_freq``: the ``D / 2`` inverse frequencies where they are not
    ``theta``'s own powers (a scaled rope's, e.g. YaRN's blend)."""
    d_half = x.shape[-1] // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B?,S,d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    cos = cos[:, :, None, :]  # broadcast over heads
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class LlamaAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    dtype: Any
    param_dtype: Any
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, train: bool, decode_ctx: dict | None = None):
        B, S, d = x.shape
        dg = lambda heads, name: nn.DenseGeneral(
            (heads, self.head_dim), axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        q = dg(self.num_heads, "query")(x)
        k = dg(self.num_kv_heads, "key")(x)
        v = dg(self.num_kv_heads, "value")(x)
        if decode_ctx is not None:
            return self._decode(q, k, v, d, decode_ctx)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        q = rope(q, positions, self.rope_theta)
        k = rope(k, positions, self.rope_theta)
        q = mesh_lib.constrain(q, _seq_rule("qkv"))
        k = mesh_lib.constrain(k, _seq_rule("qkv"))
        v = mesh_lib.constrain(v, _seq_rule("qkv"))
        out = attn_lib.attention(q, k, v, causal=True, impl=self.attn_impl)
        # Named for the "attn_out" remat policy (save attention outputs,
        # recompute everything else): a no-op unless that policy is active.
        out = ad_checkpoint.checkpoint_name(out, "attn_out")
        return nn.DenseGeneral(d, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, param_dtype=self.param_dtype,
                               name="out")(out)

    def _decode(self, q, k, v, d, decode_ctx):
        """Serving path (serve/): RoPE at explicit per-request positions,
        K/V appended through the page table into this layer's pools (the
        flax ``cache`` collection — the engine threads it through each step
        via ``mutable=["cache"]`` and donates the buffers), then attention
        reads the cache. S == 1 is a decode step (paged flash-decode
        kernel); S > 1 is prefill. A fresh prefill starts at position 0,
        where causal self-attention over the chunk IS the full answer, so
        it reuses the training dispatcher for exact parity. A window with
        HISTORY (suffix prefill after a prefix-cache splice, or a later
        chunk of a chunked prefill — ``decode_ctx["history"]``, static so
        each flavor is its own compiled program) must also attend to the
        cached positions before it, so it reads back through the page
        table instead."""
        from pytorch_distributed_training_example_tpu.ops import (
            flash_attention as flash_lib)
        from pytorch_distributed_training_example_tpu.serve import kv_cache

        B, S = q.shape[0], q.shape[1]
        positions = decode_ctx["positions"]             # [B, S] int32
        page_table = decode_ctx["page_table"]           # [B, max_pages]
        num_pages, page_size = decode_ctx["cache_spec"]
        q = rope(q, positions, self.rope_theta)
        k = rope(k, positions, self.rope_theta)
        init = lambda: jnp.zeros(
            (num_pages, page_size, self.num_kv_heads, self.head_dim),
            self.dtype)
        k_pages = self.variable("cache", "k_pages", init)
        v_pages = self.variable("cache", "v_pages", init)
        with jax.named_scope("serve_cache"):
            k_pages.value = kv_cache.append_pages(k_pages.value, k,
                                                  page_table, positions)
            v_pages.value = kv_cache.append_pages(v_pages.value, v,
                                                  page_table, positions)
        with jax.named_scope("serve_attn"):
            if S == 1:
                out = flash_lib.paged_decode_attention(
                    q[:, 0], k_pages.value, v_pages.value, page_table,
                    positions[:, 0],
                    impl=decode_ctx.get("attn_impl", "auto"))[:, None]
            elif decode_ctx.get("history"):
                out = flash_lib.paged_prefill_attention(
                    q, k_pages.value, v_pages.value, page_table, positions)
            else:
                out = attn_lib.attention(q, k, v, causal=True,
                                         impl=self.attn_impl)
        return nn.DenseGeneral(d, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, param_dtype=self.param_dtype,
                               name="out")(out)


def swiglu_mlp(h, ffn_dim: int, dtype, param_dtype):
    """SwiGLU MLP, ``down(silu(gate(h)) * up(h))``, without biases. Called
    inside a block's ``nn.compact`` method: the three ``Dense`` layers become
    that block's own ``gate``/``up``/``down`` (shared by the Llama and the
    Granite hybrid blocks)."""
    dense = lambda feat, name: nn.Dense(
        feat, use_bias=False, dtype=dtype, param_dtype=param_dtype, name=name)
    gate = dense(ffn_dim, "gate")(h)
    up = dense(ffn_dim, "up")(h)
    gate = mesh_lib.constrain(gate, _seq_rule("ffn_hidden"))
    up = mesh_lib.constrain(up, _seq_rule("ffn_hidden"))
    return dense(h.shape[-1], "down")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    ffn_dim: int
    rope_theta: float
    dtype: Any
    param_dtype: Any
    attn_impl: str = "auto"
    sp: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True, decode_ctx: dict | None = None):
        rn = lambda name: RMSNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                                  name=name)
        x = x + LlamaAttention(self.num_heads, self.num_kv_heads, self.head_dim,
                               self.rope_theta, self.dtype, self.param_dtype,
                               self.attn_impl, name="attn")(rn("attn_norm")(x), train,
                                                            decode_ctx)
        x = mesh_lib.constrain(x, _seq_rule("residual", self.sp))
        h = rn("mlp_norm")(x)
        scope = (jax.named_scope("serve_mlp") if decode_ctx is not None
                 else contextlib.nullcontext())
        with scope:
            h = swiglu_mlp(h, self.ffn_dim, self.dtype, self.param_dtype)
        x = x + h
        return mesh_lib.constrain(x, _seq_rule("residual", self.sp))


#: Remat policies for the grad-checkpoint config (selected by name so the
#: flag threads through Config/argparse). "nothing" is the default (r4, on
#: a machine that is gone: rate-neutral at S=8192 b=1 vs no-remat, and the
#: only policy that admitted b=2). The alternatives trade activation memory
#: for recompute FLOPs; none has a reading on this machine
#: (``main.py --remat-policy``):
#:   nothing       recompute the whole block (minimum memory)
#:   dots          save every matmul output (maximum saveable under remat)
#:   dots_no_batch save matmul outputs with no batch dims (XLA's classic
#:                 "save weights-only matmuls" heuristic)
#:   attn_out      save only the attention outputs (tagged below): skips
#:                 recomputing the S^2 attention in the backward at the cost
#:                 of one [B,S,H,D] residual per layer
REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_saveable,
    "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "attn_out": jax.checkpoint_policies.save_only_these_names("attn_out"),
}


class Llama(nn.Module):
    vocab_size: int = 128256
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    d_model: int = 4096
    ffn_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "nothing"  # key into REMAT_POLICIES
    scan_layers: bool = False
    attn_impl: str = "auto"
    sp: bool = False
    logits_dtype: Any = jnp.float32  # storage dtype; loss upcasts per-element

    @property
    def head_dim(self):
        return self.d_model // self.num_heads

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 decode_ctx: dict | None = None):
        """``decode_ctx`` switches to the serving forward (serve/engine.py):
        a dict with ``positions`` [B,S], ``page_table`` [B,max_pages],
        ``cache_spec`` (num_pages, page_size), ``last_index`` [B] and
        optionally ``attn_impl`` / ``history`` / ``all_logits``. K/V live
        in the flax ``cache`` collection (paged pools); the return value is
        next-token logits [B, vocab] taken at ``last_index`` — or the full
        [B, S, vocab] when ``all_logits`` is set (the speculative-decode
        verify step scores every draft position in one forward)."""
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                     param_dtype=self.param_dtype, name="embed")(tokens)
        x = mesh_lib.constrain(x, _seq_rule("residual", self.sp))

        block_cls = LlamaBlock
        if self.remat:
            if self.remat_policy not in REMAT_POLICIES:
                raise ValueError(
                    f"unknown remat_policy {self.remat_policy!r}; "
                    f"have {sorted(REMAT_POLICIES)}")
            block_cls = nn.remat(
                LlamaBlock, prevent_cse=False,
                policy=REMAT_POLICIES[self.remat_policy])
        block_args = dict(
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim, ffn_dim=self.ffn_dim,
            rope_theta=self.rope_theta, dtype=self.dtype,
            param_dtype=self.param_dtype, attn_impl=self.attn_impl,
            sp=self.sp)
        if self.scan_layers:
            # One stacked block scanned over a leading 'layers' dim: constant
            # trace/compile cost regardless of depth. The body wrapper adapts
            # LlamaBlock's single-array return to scan's (carry, ys) contract.
            # Under ``decode_ctx`` the per-block paged K/V pools become a
            # STACKED carry too: scanning the ``cache`` collection on axis 0
            # gives [L, P, page_size, Hkv, D] pools, so scanned checkpoints
            # serve without a retrain (serve/kv_cache.py rank-dispatches its
            # page ops on the extra leading dim).
            inner = block_cls

            class _ScanBody(nn.Module):
                @nn.compact
                def __call__(self, carry, _):
                    return inner(name="block", **block_args)(
                        carry, train, decode_ctx), None

            variable_axes = {"params": 0}
            if decode_ctx is not None:
                variable_axes["cache"] = 0
            ScanBlocks = nn.scan(
                _ScanBody, variable_axes=variable_axes,
                split_rngs={"params": True, "dropout": True},
                length=self.num_layers)
            x, _ = ScanBlocks(name="blocks")(x, None)
        else:
            for i in range(self.num_layers):
                x = block_cls(name=f"block_{i}", **block_args)(x, train,
                                                               decode_ctx)
        if decode_ctx is not None:
            # Serving: only the last real position's logits matter (the
            # next-token distribution). Gather the hidden row BEFORE the
            # [d, vocab] head matmul — at decode S == 1 this is free, at
            # prefill it turns a [B,S,V] matmul into [B,V]. The speculative
            # verify step instead needs EVERY position's next-token
            # distribution (one score per draft token plus the bonus), so
            # ``decode_ctx["all_logits"]`` (static — its own compiled
            # program) skips the gather and returns [B, S, vocab].
            with jax.named_scope("serve_head"):
                norm = RMSNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                               name="final_norm")
                head = nn.Dense(self.vocab_size, use_bias=False,
                                dtype=self.dtype,
                                param_dtype=self.param_dtype, name="lm_head")
                if decode_ctx.get("all_logits"):
                    # Score every draft position through the SAME [B, d]
                    # head matmul shape the decode program uses (unrolled
                    # over the small verify width) rather than one
                    # [B, S, vocab] matmul: XLA lowers the rank-3 head
                    # differently (bf16 materialization vs fused fp32
                    # accumulation), and that sub-bf16 numerical skew can
                    # flip near-tie argmaxes — which would break the
                    # bit-identity contract between speculative verify and
                    # plain decode.
                    # The fp32 cast must land INSIDE the stack: XLA fuses
                    # convert(dot) into an fp32-accumulated matmul, and the
                    # decode program gets that fusion — a stack between dot
                    # and convert would materialize bf16 logits instead and
                    # reintroduce grid ties.
                    logits = jnp.stack(
                        [head(norm(x[:, m])).astype(self.logits_dtype)
                         for m in range(x.shape[1])], axis=1)
                else:
                    idx = decode_ctx["last_index"].astype(jnp.int32)  # [B]
                    x = jnp.take_along_axis(
                        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
                    logits = head(norm(x))
            return logits.astype(self.logits_dtype)
        x = RMSNorm(dtype=self.dtype, param_dtype=self.param_dtype,
                    name="final_norm")(x)
        x = mesh_lib.constrain(x, _seq_rule("residual", self.sp))
        with jax.named_scope("head_loss"):
            logits = nn.Dense(self.vocab_size, use_bias=False,
                              dtype=self.dtype, param_dtype=self.param_dtype,
                              name="lm_head")(x)
            logits = mesh_lib.constrain(logits, _seq_rule("logits", self.sp))
            return logits.astype(self.logits_dtype)


TP_RULES = (
    (r"attn/(query|key|value)/kernel", P(None, "model", None)),
    (r"attn/out/kernel", P("model", None, None)),
    (r"(gate|up)/kernel", P(None, "model")),
    (r"down/kernel", P("model", None)),
    (r"embed/embedding", P(None, "model")),
    (r"lm_head/kernel", P(None, "model")),
)


def llama3_8b(**kw) -> Llama:
    return Llama(**kw)


def llama_400m(**kw) -> Llama:
    """One-chip bench scale: full Llama architecture (GQA 4:1, RoPE,
    SwiGLU, RMSNorm) at ~400M params, sized for a single v5e. Llama-2-sized
    vocab keeps embeddings from dominating. A test and dry-run preset: the
    family is not a benchmark configuration (ROADMAP queue B)."""
    kw.setdefault("vocab_size", 32000)
    kw.setdefault("num_layers", 16)
    kw.setdefault("num_heads", 16)
    kw.setdefault("num_kv_heads", 4)
    kw.setdefault("d_model", 1024)
    kw.setdefault("ffn_dim", 4096)
    kw.setdefault("max_seq_len", 2048)
    return Llama(**kw)


def llama_tiny(**kw) -> Llama:
    """Test-scale Llama (same architecture, toy dims)."""
    kw.setdefault("vocab_size", 512)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("d_model", 128)
    kw.setdefault("ffn_dim", 256)
    kw.setdefault("max_seq_len", 256)
    return Llama(**kw)


def num_params(cfg: Llama) -> int:
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    hd = cfg.head_dim
    attn = d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd \
        + cfg.num_heads * hd * d
    mlp = 3 * d * cfg.ffn_dim
    return V * d + L * (attn + mlp + 2 * d) + d + d * V

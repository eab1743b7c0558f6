"""Attention family: XLA reference, ring (context-parallel) and Ulysses.

The reference has no attention of its own (its models come from torchvision /
minimal GPT-2; long-context parallelism is absent, SURVEY.md §5) — but the
framework treats sequence/context parallelism as first-class (§2c):

- :func:`dot_product_attention` — the single-device oracle. Plain XLA ops:
  on TPU, XLA fuses QK^T -> softmax -> PV into an MXU-friendly pipeline; the
  Pallas flash kernel (ops/flash_attention.py) replaces it when profitable.
- :func:`ring_attention` — context-parallel attention: Q stays put, K/V
  blocks rotate around the ``context`` mesh axis via ``ppermute`` (ICI
  neighbors on the torus), with blockwise online-softmax accumulation, so
  sequence length scales with the number of chips while memory per chip
  stays O(S/c * S/c).
- :func:`ulysses_attention` — all-to-all alternative: swap sequence-sharding
  for head-sharding around the attention core (preferable when
  heads >= context shards and full-sequence attention per head is cheap).

Shapes follow the TPU-native convention ``[batch, seq, heads, head_dim]``
(BSHD; heads before head_dim keeps the trailing 128-lane dim dense for the
MXU). GQA is supported by passing fewer K/V heads than Q heads.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_training_example_tpu.ops import backend

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """Broadcast GQA KV heads up to the Q head count."""
    num_kv = k.shape[2]
    if num_kv == num_q_heads:
        return k
    assert num_q_heads % num_kv == 0, (num_q_heads, num_kv)
    return jnp.repeat(k, num_q_heads // num_kv, axis=2)


def _causal_masked(logits, q_offset, window=None):
    q_pos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) + q_offset
    k_pos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 3)
    seen = q_pos >= k_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return jnp.where(seen, logits, NEG_INF)


def dot_product_attention(
    q: jax.Array,           # [B, Sq, H, D]
    k: jax.Array,           # [B, Skv, Hkv, D]
    v: jax.Array,           # [B, Skv, Hkv, D]
    *,
    causal: bool = False,
    bias: jax.Array | None = None,
    q_offset: int | jax.Array = 0,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Reference attention in pure XLA; fp32 softmax, inputs' dtype out.
    ``v`` may be of another width than ``q`` and ``k``; ``scale`` is the
    scores' factor, ``1 / sqrt(D)`` of the query/key width where None.

    ``q_offset`` positions the query block within the global sequence for
    causal masking (used by the ring schedule where K/V blocks come from
    other context shards). ``window`` (causal only): row i sees the keys
    [i - window + 1, i].
    """
    if window is not None and not causal:
        raise ValueError("window attention is causal")
    orig_dtype = q.dtype
    depth = q.shape[-1]
    k = _repeat_kv(k, q.shape[2])
    v = _repeat_kv(v, q.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (scale or 1.0 / math.sqrt(depth))
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        logits = _causal_masked(logits, q_offset, window)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.astype(orig_dtype)


# ---------------------------------------------------------------------------
# Ring attention (context parallelism) — SURVEY.md §2c "Ring attention"
# ---------------------------------------------------------------------------


def _online_block(q, k, v, *, causal, q_offset, k_offset, m, l, acc,
                  kv_len=None):
    """One ring step: attend q against a K/V block, updating the online
    softmax state (m: running max, l: running denom, acc: unnormalized out).

    ``kv_len`` bounds the VALID global key positions: keys at
    ``k_offset + j >= kv_len`` are padding (the torn-last-block case, where
    the sequence was padded up to a ring-degree multiple) and are masked
    out exactly like causally-future keys.
    """
    depth = q.shape[-1]
    k = _repeat_kv(k, q.shape[2])
    v = _repeat_kv(v, q.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (1.0 / math.sqrt(depth))
    if causal or kv_len is not None:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) + q_offset
        k_pos = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 3) + k_offset
        valid = jnp.ones(logits.shape, bool)
        if causal:
            valid &= q_pos >= k_pos
        if kv_len is not None:
            valid &= k_pos < kv_len
        logits = jnp.where(valid, logits, NEG_INF)
    block_max = jnp.max(logits, axis=-1)               # [B,H,Q]
    new_m = jnp.maximum(m, block_max)
    correction = jnp.exp(m - new_m)
    p = jnp.exp(logits - new_m[..., None])             # [B,H,Q,K]
    new_l = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(jnp.float32),
                    v.astype(jnp.float32), preferred_element_type=jnp.float32)
    new_acc = acc * correction[..., None] + pv
    return new_m, new_l, new_acc


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "context",
    causal: bool = False,
    batch_axes=("data", "fsdp"),
    head_axis: str = "model",
    ring_impl: str = "ppermute",
) -> jax.Array:
    """Context-parallel attention over the ``axis`` mesh dimension.

    Inputs are globally-shaped ``[B, S, H, D]`` arrays whose sequence dim is
    sharded over ``axis``; inside ``shard_map`` each device holds its local
    ``S/c`` block, and K/V blocks rotate around the ring with ``ppermute``
    (one ICI hop per step — neighbor exchange rides the torus,
    ``ops.collectives.ring_shift``). The online softmax keeps the result
    exactly equal to full attention (tested against
    :func:`dot_product_attention` on a fake 8-device mesh).

    ``S`` need not divide the ring degree: a torn last block is handled by
    padding the sequence up to the next multiple of ``c`` — padded keys are
    masked out of every block's softmax (``kv_len``) and the padded query
    rows are sliced off (their cotangents are zero, so gradients are exact).

    The head dim stays sharded on ``head_axis`` (tensor parallelism composes
    with the ring: each TP shard rings its own head slice). With
    ``causal=True``, blocks that are entirely in a query shard's future are
    skipped with ``lax.cond`` (they still circulate — the ring must stay in
    lockstep — but their QK/PV FLOPs are elided; their contribution is
    identically zero either way).

    ``ring_impl``:

    - ``"ppermute"`` — the rotating-block schedule above (default; K/V
      memory stays O(S/c) per device and each hop overlaps with compute).
    - ``"allgather"`` — gather the full K/V along the ring axis once and
      run one masked local attention. Keeps activation memory for Q/out at
      O(S/c) but materializes full K/V per device; the fallback for
      backends where ppermute-in-a-loop doesn't lower or overlap (and a
      directly testable oracle for the rotating schedule).
    """
    c = mesh.shape[axis]
    if c == 1:
        return dot_product_attention(q, k, v, causal=causal)
    if ring_impl not in ("ppermute", "allgather"):
        raise ValueError(
            f"unknown ring_impl {ring_impl!r}; have ['ppermute', 'allgather']")
    if k.shape[1] != q.shape[1]:
        raise ValueError(
            f"ring attention is self-attention over one sharded sequence; "
            f"got Sq={q.shape[1]}, Skv={k.shape[1]}")
    from pytorch_distributed_training_example_tpu.ops import collectives

    # Torn last block: pad S up to a ring-degree multiple; padded keys are
    # masked via kv_len, padded query rows are sliced off below.
    S = q.shape[1]
    kv_len = None
    if S % c:
        Sp = -(-S // c) * c
        pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
        kv_len = S
    # Keep heads TP-sharded only when BOTH q and kv head counts divide by the
    # TP degree — otherwise local GQA head-group pairing would be wrong, so
    # fall back to replicated heads inside the ring.
    tp = mesh.shape.get(head_axis, 1)
    h_ax = head_axis if (tp > 1 and q.shape[2] % tp == 0
                         and k.shape[2] % tp == 0) else None

    def local_fn(q, k, v):
        idx = jax.lax.axis_index(axis)
        s_local = q.shape[1]
        q_offset = idx * s_local

        if ring_impl == "allgather":
            # One gather, one masked block. The named scope is load-bearing:
            # graftlint GL105 sanctions attention-issued collectives in the
            # lowered step by scope tag (attn_ring_allgather).
            with jax.named_scope("attn_ring_allgather"):
                kg = collectives.all_gather(k, axis, axis_index=1)
                vg = collectives.all_gather(v, axis, axis_index=1)
            bias = None
            if kv_len is not None:
                k_pos = jnp.arange(kg.shape[1])
                bias = jnp.where(k_pos < kv_len, 0.0, NEG_INF)[
                    None, None, None, :]
            return dot_product_attention(q, kg, vg, causal=causal, bias=bias,
                                         q_offset=q_offset)

        B, _, H, D = q.shape
        m = jnp.full((B, H, s_local), NEG_INF, jnp.float32)
        l = jnp.zeros((B, H, s_local), jnp.float32)
        acc = jnp.zeros((B, H, s_local, D), jnp.float32)

        def compute(step, m, l, acc, kb, vb):
            # K/V block currently held came from shard (idx - step) mod c.
            src = (idx - step) % c

            def do(ops):
                m, l, acc, kb, vb = ops
                return _online_block(q, kb, vb, causal=causal,
                                     q_offset=q_offset,
                                     k_offset=src * s_local,
                                     m=m, l=l, acc=acc, kv_len=kv_len)

            if not causal:
                return do((m, l, acc, kb, vb))
            # Causal: a block from a strictly-later shard is entirely in
            # this shard's future — skip its QK/PV work (contribution is
            # identically zero; the block still circulates in lockstep).
            return jax.lax.cond(src <= idx, do,
                                lambda ops: (ops[0], ops[1], ops[2]),
                                (m, l, acc, kb, vb))

        def body(step, carry):
            m, l, acc, kb, vb = carry
            m, l, acc = compute(step, m, l, acc, kb, vb)
            # Rotate: send our block to the next shard, receive previous.
            # Scope sanctions the collective-permute for graftlint GL105.
            with jax.named_scope("attn_ring_ppermute"):
                kb = collectives.ring_shift(kb, axis)
                vb = collectives.ring_shift(vb, axis)
            return m, l, acc, kb, vb

        # Final step outside the loop: its rotation would be discarded, and
        # 1/c of the schedule's ICI traffic with it.
        m, l, acc, kb, vb = jax.lax.fori_loop(0, c - 1, body, (m, l, acc, k, v))
        m, l, acc = compute(c - 1, m, l, acc, kb, vb)
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # [B,H,Q,D]
        return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)

    spec = P(batch_axes, axis, h_ax, None)
    out = jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(q, k, v)
    return out[:, :S] if kv_len is not None else out


def zigzag_ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "context",
    causal: bool = True,
    batch_axes=("data", "fsdp"),
    head_axis: str = "model",
) -> jax.Array:
    """Load-balanced causal ring attention (zigzag chunk placement).

    A contiguous ring under a causal mask is imbalanced: shard 0's queries
    only ever attend to 1/c of the KV while shard c-1 attends to all of it,
    and because the ring rotates in lockstep every tick runs at the slowest
    shard's pace. Zigzag placement splits the sequence into ``2c`` chunks
    and gives shard ``i`` the pair ``(i, 2c-1-i)`` — one early + one late
    chunk — so every shard does ~the same causal work on every tick
    (the Llama-3 context-parallel schedule).

    Chunks are re-laid out with two static ``ppermute``s (one per local
    half), rung for ``c`` steps over the paired KV halves with 4 sub-block
    online-softmax updates per tick (fully-masked sub-blocks are skipped
    with ``lax.cond``), then outputs are permuted back to the contiguous
    layout. Exactly equals full attention (oracle-tested, incl. grads).
    """
    c = mesh.shape[axis]
    if c == 1:
        return dot_product_attention(q, k, v, causal=causal)
    if not causal or q.shape[1] % (2 * c) != 0:
        # Balance only matters under a causal mask; odd half-chunks fall
        # back to the contiguous schedule.
        return ring_attention(q, k, v, mesh=mesh, axis=axis, causal=causal,
                              batch_axes=batch_axes, head_axis=head_axis)
    tp = mesh.shape.get(head_axis, 1)
    h_ax = head_axis if (tp > 1 and q.shape[2] % tp == 0
                         and k.shape[2] % tp == 0) else None

    # Static chunk routing. Contiguous shard i holds chunks (2i, 2i+1);
    # zigzag shard j holds {j, 2c-1-j}: slot A gets chunk j for even j else
    # 2c-1-j, slot B the other one (parity falls out of the permutation).
    def dest_first(i):
        return 2 * i if 2 * i < c else 2 * c - 1 - 2 * i

    def dest_second(i):
        return 2 * i + 1 if 2 * i + 1 < c else 2 * c - 2 - 2 * i

    perm_a = [(i, dest_first(i)) for i in range(c)]
    perm_b = [(i, dest_second(i)) for i in range(c)]
    inv_a = [(d, s) for s, d in perm_a]
    inv_b = [(d, s) for s, d in perm_b]

    from pytorch_distributed_training_example_tpu.ops import collectives

    def local_fn(q, k, v):
        idx = jax.lax.axis_index(axis)
        L = q.shape[1]
        h = L // 2
        B, _, H, D = q.shape

        def scatter(x):
            # Scoped for graftlint GL105 (sanctioned attention collectives).
            with jax.named_scope("attn_ring_ppermute"):
                xa = jax.lax.ppermute(x[:, :h], axis, perm_a)
                xb = jax.lax.ppermute(x[:, h:], axis, perm_b)
            return xa, xb

        (qa, qb), (ka, kb), (va, vb) = scatter(q), scatter(k), scatter(v)

        def chunk_ids(j):
            a = jnp.where(j % 2 == 0, j, 2 * c - 1 - j)
            return a, (2 * c - 1 - j) - a + j  # the partner chunk

        my_a, my_b = chunk_ids(idx)
        Hq = q.shape[2]
        state = [
            (jnp.full((B, Hq, h), NEG_INF, jnp.float32),
             jnp.zeros((B, Hq, h), jnp.float32),
             jnp.zeros((B, Hq, h, D), jnp.float32))
            for _ in range(2)
        ]

        def compute(step, sa, sb, ka, kb, va, vb):
            src = (idx - step) % c
            src_a, src_b = chunk_ids(src)

            def update(s, q_half, q_chunk, k_half, v_half, k_chunk):
                m, l, acc = s
                active = k_chunk <= q_chunk  # causal: skip all-future chunks

                def do(ops):
                    m, l, acc, kh, vh = ops
                    return _online_block(
                        q_half, kh, vh, causal=True,
                        q_offset=q_chunk * h, k_offset=k_chunk * h,
                        m=m, l=l, acc=acc)

                return jax.lax.cond(active, do,
                                    lambda ops: (ops[0], ops[1], ops[2]),
                                    (m, l, acc, k_half, v_half))

            for k_half, v_half, k_chunk in ((ka, va, src_a), (kb, vb, src_b)):
                sa = update(sa, qa, my_a, k_half, v_half, k_chunk)
                sb = update(sb, qb, my_b, k_half, v_half, k_chunk)
            return sa, sb

        def body(step, carry):
            sa, sb, ka, kb, va, vb = carry
            sa, sb = compute(step, sa, sb, ka, kb, va, vb)
            with jax.named_scope("attn_ring_ppermute"):
                ka = collectives.ring_shift(ka, axis)
                kb = collectives.ring_shift(kb, axis)
                va = collectives.ring_shift(va, axis)
                vb = collectives.ring_shift(vb, axis)
            return sa, sb, ka, kb, va, vb

        # Last step hoisted out of the loop (its rotation would be waste).
        sa, sb, ka, kb, va, vb = jax.lax.fori_loop(
            0, c - 1, body, (state[0], state[1], ka, kb, va, vb))
        sa, sb = compute(c - 1, sa, sb, ka, kb, va, vb)

        def finish(s):
            m, l, acc = s
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)

        # Send each output half back to its contiguous home.
        with jax.named_scope("attn_ring_ppermute"):
            oa = jax.lax.ppermute(finish(sa), axis, inv_a)
            ob = jax.lax.ppermute(finish(sb), axis, inv_b)
        return jnp.concatenate([oa, ob], axis=1)

    spec = P(batch_axes, axis, h_ax, None)
    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all sequence<->head) — SURVEY.md §2c "Ulysses"
# ---------------------------------------------------------------------------


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "context",
    causal: bool = False,
    batch_axes=("data", "fsdp"),
    head_axis: str = "model",
) -> jax.Array:
    """All-to-all context parallelism: trade sequence-sharding for
    head-sharding, run full-sequence attention per (local) head, trade back.

    Requires the per-TP-shard head count to divide by the context shards
    (GQA KV heads are broadcast up first when smaller than the shard count).
    """
    c = mesh.shape[axis]
    if c == 1:
        return dot_product_attention(q, k, v, causal=causal)
    tp = mesh.shape.get(head_axis, 1)
    h_ax = head_axis if (tp > 1 and q.shape[2] % tp == 0
                         and k.shape[2] % tp == 0) else None
    local_heads = q.shape[2] // (tp if h_ax else 1)
    if local_heads % c:
        # Head-pad so each TP shard's heads divide the context shards
        # (r3 hard-errored here; README "Known limits"). Zero heads attend
        # uniformly, their outputs are sliced off, and the slice's vjp
        # drops their gradient contributions — exactness is tested. Cost:
        # the padded heads do full attention compute (pad/H overhead).
        # The pad target is a multiple of tp*c regardless of whether H
        # divided tp before: this both keeps heads TP-sharded after the
        # pad (h_ax=None would replicate all heads across the model axis)
        # and guarantees the recursive call pads no further.
        H = q.shape[2]
        group = tp * c
        h_pad = -(-H // group) * group
        import logging

        logging.getLogger(__name__).warning(
            "ulysses_attention: %d heads not divisible by %s=%d%s; "
            "zero-padding to %d heads (+%.0f%% attention compute). Ring "
            "attention has no head constraint if this overhead matters.",
            H, axis, c, f" x {head_axis}={tp}" if tp > 1 else "", h_pad,
            100.0 * (h_pad - H) / H)
        k = _repeat_kv(k, H)
        v = _repeat_kv(v, H)
        pad = ((0, 0), (0, 0), (0, h_pad - H), (0, 0))
        out = ulysses_attention(
            jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad), mesh=mesh,
            axis=axis, causal=causal, batch_axes=batch_axes,
            head_axis=head_axis)
        return out[:, :, :H]

    def local_fn(q, k, v):
        # The named scope is load-bearing: graftlint GL105 sanctions
        # all-to-all ops in the lowered step by scope tag (moe_* or
        # attn_ulysses_a2a) — an untagged a2a is flagged as unattributable.
        # [B, S/c, H', D] -> all_to_all -> [B, S, H'/c, D]
        def seq_to_heads(x):
            if x.shape[2] % c:   # GQA KV with fewer heads than shards
                x = _repeat_kv(x, c)
            with jax.named_scope("attn_ulysses_a2a"):
                return jax.lax.all_to_all(x, axis, split_axis=2,
                                          concat_axis=1, tiled=True)

        qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
        out = dot_product_attention(qh, kh, vh, causal=causal)
        # [B, S, H'/c, D] -> back to [B, S/c, H', D]
        with jax.named_scope("attn_ulysses_a2a"):
            return jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

    spec = P(batch_axes, axis, h_ax, None)
    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attention(
    q, k, v, *, causal=False, impl: str = "auto",
    mesh: Mesh | None = None, context_axis: str = "context",
    batch_axes=("data", "fsdp"), window: int | None = None,
    scale: float | None = None,
):
    """Dispatcher used by the models.

    impl: 'auto' | 'xla' | 'flash' | 'ring' | 'ring_zigzag' |
    'ring_allgather' | 'ulysses'. 'auto' picks ring when the ambient mesh
    has a context axis > 1, the Pallas flash kernel on TPU for long
    sequences, else plain XLA. Causal rings use the load-balanced zigzag
    schedule when the sequence divides into 2*ctx chunks (see
    :func:`zigzag_ring_attention`). 'ring_allgather' is the all-gather-KV
    fallback for backends where the ppermute ring doesn't lower or overlap
    (see :func:`ring_attention` ``ring_impl``).

    ``window`` (causal only): row i sees the keys [i - window + 1, i]. The
    flash path's online kernels take it as their schedule's second edge
    and the XLA path as a mask; the context-
    parallel schedules and the padded one-shot path have neither.

    ``v`` of another width than ``q`` and ``k``, or a ``scale`` other than ``1
    / sqrt(D)``: the flash path's online kernels or the XLA path, as under
    a window; the others hold one width and one factor.
    """
    from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib

    mesh = mesh or mesh_lib.current_mesh()
    ctx = mesh.shape.get(context_axis, 1) if mesh is not None else 1
    if (window is not None or scale is not None
            or v.shape[-1] != q.shape[-1]):
        return _online_attention(q, k, v, causal=causal, impl=impl, ctx=ctx,
                                 mesh=mesh, batch_axes=batch_axes,
                                 window=window, scale=scale)
    if impl == "auto":
        if ctx > 1:
            impl = "ring_zigzag" if causal else "ring"
        elif _flash_eligible(q, k):
            impl = "flash"
        elif _padded_flash_eligible(q, k, explicit=False):
            return _per_device_flash(padded_flash_attention, q, k, v,
                                     causal=causal, mesh=mesh,
                                     batch_axes=batch_axes)
        else:
            impl = "xla"
    elif impl in ("ring", "ring_zigzag", "ring_allgather",
                  "ulysses") and ctx == 1:
        # No context axis to parallelize over (includes init-time tracing
        # outside use_mesh): all collapse to plain attention.
        impl = "xla"
    if impl == "ring_zigzag":
        # Self-falls-back to contiguous when non-causal or indivisible.
        return zigzag_ring_attention(q, k, v, mesh=mesh, axis=context_axis,
                                     causal=causal, batch_axes=batch_axes)
    if impl == "ring":
        # Explicit 'ring' = the contiguous schedule (so the two can be
        # benchmarked against each other); only 'auto' upgrades causal runs.
        return ring_attention(q, k, v, mesh=mesh, axis=context_axis,
                              causal=causal, batch_axes=batch_axes)
    if impl == "ring_allgather":
        return ring_attention(q, k, v, mesh=mesh, axis=context_axis,
                              causal=causal, batch_axes=batch_axes,
                              ring_impl="allgather")
    if impl == "ulysses":
        return ulysses_attention(q, k, v, mesh=mesh, axis=context_axis,
                                 causal=causal, batch_axes=batch_axes)
    if impl == "flash":
        if not _flash_eligible(q, k, explicit=True):
            if _padded_flash_eligible(q, k):
                return _per_device_flash(padded_flash_attention, q, k, v,
                                         causal=causal, mesh=mesh,
                                         batch_axes=batch_axes)
            msg = (f"attn_impl='flash' not eligible for shape q={q.shape} "
                   f"k={k.shape} (needs seq % 512 == 0 or a VMEM-fitting "
                   "padded one-shot plan, head_dim in {64,128,256}, TPU)")
            if backend.on_tpu():
                # On the chip an explicit 'flash' that silently ran XLA
                # attention would be measured under the kernel's name.
                raise ValueError(msg)
            import logging

            logging.getLogger(__name__).warning(
                "%s; running XLA attention on cpu", msg)
            return dot_product_attention(q, k, v, causal=causal)
        from pytorch_distributed_training_example_tpu.ops import flash_attention

        return _per_device_flash(flash_attention.flash_attention, q, k, v,
                                 causal=causal, mesh=mesh,
                                 batch_axes=batch_axes)
    return dot_product_attention(q, k, v, causal=causal)


def _online_attention(q, k, v, *, causal, impl, ctx, mesh, batch_axes,
                      window, scale):
    """``attention`` with a window, a value width that differs from the
    query/key width, or the caller's scale: what the flash kernels' online
    family alone takes (the window as its schedule's second edge, each
    operand blocked at its own width, the scale as its factor), where the
    flash path is eligible (or asked for), else the masked XLA reference."""
    if impl not in ("auto", "flash", "xla") or ctx > 1:
        raise ValueError(
            f"window={window}, widths {q.shape[-1]} / {v.shape[-1]}, scale="
            f"{scale}: no context-parallel schedule takes a window, two "
            f"widths or a scale (impl={impl!r}, context axis {ctx}); use "
            f"impl auto, flash or xla")
    if impl != "xla" and _flash_eligible(q, k, explicit=impl == "flash", v=v):
        from pytorch_distributed_training_example_tpu.ops import flash_attention

        return _per_device_flash(
            functools.partial(flash_attention.flash_attention, window=window,
                              scale=scale),
            q, k, v, causal=causal, mesh=mesh, batch_axes=batch_axes)
    if impl == "flash" and backend.on_tpu():
        raise ValueError(f"attn_impl='flash' not eligible for shape "
                         f"q={q.shape} k={k.shape} v={v.shape} (window="
                         f"{window})")
    return dot_product_attention(q, k, v, causal=causal, window=window,
                                 scale=scale)


def _per_device_flash(fn, q, k, v, *, causal, mesh, batch_axes):
    """Call a Pallas flash entry point per device of ``mesh``.

    GSPMD cannot partition a Mosaic kernel (``mesh_lib.manual_call``), so
    under a multi-device mesh the call is ``shard_map``-ped the way the
    ring/Ulysses paths already are: batch over the data-parallel axes,
    heads over ``model``. An axis whose size does not divide its dimension
    (the batch-2 init template under fsdp=4, GQA KV heads under TP)
    replicates that dimension instead; the sequence is always whole.
    """
    from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib

    b_ax = h_ax = None
    if mesh is not None:
        axes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
        dp = math.prod(mesh.shape[a] for a in axes)
        if axes and q.shape[0] % dp == 0:
            b_ax = axes
        tp = mesh.shape.get("model", 1)
        if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0:
            h_ax = "model"
    spec = P(b_ax, None, h_ax, None)
    return mesh_lib.manual_call(
        functools.partial(fn, causal=causal), q, k, v, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec)


PAD_MULTIPLE = 64  # tile granularity shared by pad + eligibility below


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def padded_flash_attention(q, k, v, *, causal=False,
                           multiple: int = PAD_MULTIPLE):
    """Flash attention for non-tile-aligned S via padding + key masking.

    ViT-B/16's 197 tokens (and any sequence the block kernels can't tile)
    are zero-padded up to the next ``multiple``; the one-shot kernel masks
    padded keys with ``kv_len`` so softmax never attends to them, and the
    padded query rows are sliced away (their cotangents are zero, so the
    extra rows contribute nothing to gradients). Pays (Sp/S)^2 extra
    attention FLOPs — at ViT's 197->256 that is +69% on a term that is
    ~4% of model FLOPs, far cheaper than XLA attention's unfused softmax
    passes at these shapes (BENCH_FLASH_MICRO.json: one-shot 2.8x XLA).
    """
    from pytorch_distributed_training_example_tpu.ops import flash_attention

    S = q.shape[1]
    if k.shape[1] != S:
        raise ValueError(
            f"padded_flash_attention needs Sq == Skv (kv_len masking is "
            f"derived from q's length); got Sq={S}, Skv={k.shape[1]}")
    Sp = _round_up(S, multiple)
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
    out = flash_attention.flash_attention(
        q, k, v, causal, flash_attention.DEFAULT_BLOCK_Q,
        flash_attention.DEFAULT_BLOCK_KV, "auto", S if Sp != S else None)
    return out[:, :S] if Sp != S else out


def _padded_flash_eligible(q, k, multiple: int = PAD_MULTIPLE,
                           explicit: bool = True) -> bool:
    from pytorch_distributed_training_example_tpu.ops import flash_attention

    if not backend.on_tpu() or q.shape[-1] not in (64, 128, 256):
        return False
    if q.shape[1] != k.shape[1]:  # cross-shard ring chunks: keep simple
        return False
    Sp = _round_up(q.shape[1], multiple)
    if not explicit and Sp < 1024:
        # Same threshold as _flash_eligible's auto mode, re-validated for
        # the padded path: ViT-B/16 (197->256) measured 690 img/s padded
        # one-shot vs 730 img/s XLA — below ~1024 tokens XLA's fused
        # attention wins and padding FLOPs only add to that.
        return False
    H, D = q.shape[2], q.shape[3]
    return (flash_attention._oneshot_plan(H, Sp, Sp, D) is not None
            and flash_attention._oneshot_plan(H, Sp, Sp, D, bwd=True)
            is not None)


def _flash_eligible(q, k, explicit: bool = False, v=None) -> bool:
    """Whether the Pallas kernel can (explicit) / should (auto) run.

    ``auto`` additionally requires seq >= 1024 — below that the XLA fusion
    is already fast and kernel launch overhead dominates; an explicit
    ``impl='flash'`` only needs the kernel's hard shape constraints. ``v``
    where its width may differ (the online kernels' call): 192, latent
    attention's 128 + 64, is a query/key width there.
    """
    on_tpu = backend.on_tpu()
    seq_ok = q.shape[1] % 512 == 0 and k.shape[1] % 512 == 0
    if not explicit:
        seq_ok = seq_ok and q.shape[1] >= 1024
    if v is not None and v.shape[-1] != q.shape[-1]:
        return (on_tpu and seq_ok and q.shape[-1] in (64, 128, 192, 256)
                and v.shape[-1] in (64, 128, 256))
    return on_tpu and seq_ok and q.shape[-1] in (64, 128, 256)

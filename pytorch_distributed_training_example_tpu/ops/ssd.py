"""State-space duality (SSD) scan of Mamba-2, chunked, with the mixer's two
elementwise stages around it: the causal depthwise convolution with its silu
that feeds it (:func:`conv_silu`) and the gate with its group norm that
follows it (:func:`gate_norm`) (Dao & Gu 2024, arXiv:2405.21060).

Per head (``x_t`` in R^P, one scalar decay ``A < 0``, ``B_t``/``C_t`` in R^N
shared by the heads of a group: head ``h`` of ``H`` reads group ``h // (H /
groups)``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t,    S_{-1} = 0
    y_t = S_t C_t + D * x_t

:func:`ssd` computes it in chunks of ``chunk`` tokens. Inside a chunk the
masked quadratic form ``(L o C B^T)(dt * x)`` with ``L_ts = exp(sum_{r=s+1..t}
dt_r A)``; between chunks the state each chunk leaves, carried forward by the
chunks' total decays. Decays, ``dt``, ``A``, ``D``, the cumulative sums and
the state carry are float32 whatever the compute dtype; every matmul takes
operands in ``x.dtype`` and accumulates in float32; ``y`` comes back float32.

Two bodies, one algorithm, chosen by :func:`_kernel_plan` from what the call
shows (dtype, chunk, head and state widths, groups):

- a Pallas kernel pair under a ``custom_vjp`` (``ssd_fwd`` / ``ssd_bwd``): a
  program holds one chunk of a run of heads (within one B/C group, or whole
  groups), everything [chunk, chunk] per head lives and dies in VMEM, and the grid walks a sequence's chunks in order
  with the state in float32 scratch. The backward recomputes the tiles; its
  residuals are the inputs and the state each chunk started from. Compiled on
  ``tpu``, interpreted on ``cpu`` (the tests' route); under a mesh through
  ``core/mesh.manual_call`` with the batch sharded.
- :func:`_scan_xla`, plain ``jax.numpy`` that XLA differentiates (the carry
  as a [chunks x chunks] lower-triangular product per head, no sequential
  loop): every shape the plan refuses, and what the kernels are tested
  against. Its [chunks, H, chunk, chunk] tiles pass through HBM.

The two stages are chosen the same way (:func:`_stage_plan`): a row-tiled
kernel pair under a ``custom_vjp`` each (``conv_silu_fwd`` / ``conv_silu_bwd``,
``gate_norm_fwd`` / ``gate_norm_bwd``) that reads its operands from HBM once
and writes its results once, or the ``jax.numpy`` bodies
(:func:`causal_conv1d`, :func:`group_rms_norm`).

:func:`gated_conv` is another family's mixer on the same conv: the doubly
gated short convolution of the ``lfm2_moe`` models, ``C * conv(B * x)`` with
no activation, as ``jax.numpy`` alone.

:func:`norm_gate` is the ``qwen3_next`` models' order of the output stage,
``group_rms_norm(y) * silu(z)`` (norm first, gate after): the same row-tiled
stage, kernels, specs and ``custom_vjp`` as :func:`gate_norm` with the order a
static argument that the public function binds (its calls are named
``norm_gate_fwd`` / ``norm_gate_bwd``), on the ``[b, S, Hv Dv]`` layout that
the delta rule's kernels (``ops/gated_delta.py``) write, a head a run of
lanes.

No packed documents (no state or mask resets) and no recurrent-state cache
for serving: one document a sequence.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from pytorch_distributed_training_example_tpu.ops import backend
from pytorch_distributed_training_example_tpu.ops.flash_attention import (
    _nt_dot, _tn_dot)

F32 = jnp.float32


def causal_conv1d(x: jax.Array, kernel: jax.Array,
                  bias: jax.Array | None = None) -> jax.Array:
    """Causal depthwise convolution over the sequence with zero history.

    ``x`` [B, S, C]; ``kernel`` [K, C]; ``y_t = sum_k kernel[k] *
    x_{t-(K-1)+k} (+ bias)``. Float32 accumulation, ``x.dtype`` out. K shifted
    reads of one padded array: an elementwise fusion, no conv op.
    """
    K, S = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    w = kernel.astype(F32)
    y = sum(xp[:, k:k + S] * w[k] for k in range(K))
    if bias is not None:
        y = y + bias.astype(F32)
    return y.astype(x.dtype)


def gated_conv(bcx: jax.Array, kernel: jax.Array) -> jax.Array:
    """The doubly gated short convolution of the ``lfm2_moe`` models: ``y = C
    * conv(B * x)``, the conv :func:`causal_conv1d`'s (depthwise, causal, zero
    history, no bias), with no activation anywhere.

    ``bcx`` [b, S, 3 C]: the chunks ``B``, ``C``, ``x`` in that order, as the
    one input projection leaves them; ``kernel`` [K, C]. Float32 inside,
    ``bcx.dtype`` out [b, S, C]. Elementwise ``jax.numpy`` that XLA fuses and
    differentiates: no kernel of its own yet.
    """
    B, C, x = jnp.split(bcx.astype(F32), 3, axis=-1)
    return (C * causal_conv1d(B * x, kernel)).astype(bcx.dtype)


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array | None = None, *,
        chunk: int = 256) -> jax.Array:
    """Chunked SSD scan.

    ``x`` [b, S, H, P] (compute dtype); ``dt`` [b, S, H] float32, already
    through its softplus; ``A`` [H] float32, negative; ``B``, ``C`` [b, S, N]
    (one group) or [b, S, groups, N]; ``D`` [H] or None. Returns ``y`` [b, S, H, P] in float32, as
    accumulated (see :class:`models.granite_hybrid.MambaMixer` for why).
    A sequence that is no multiple of ``chunk`` is padded here: a padded step
    has ``dt = 0``, so it neither decays nor feeds the state.

    Shapes :func:`_kernel_plan` admits take the Pallas kernels; every other
    one takes :func:`_scan_xla`, which is also what the kernels are tested
    against.
    """
    b, S, H, P = x.shape
    cd = x.dtype
    if B.ndim == 3:
        B, C = B[:, :, None], C[:, :, None]
    groups, N = B.shape[2:]
    if H % groups:
        raise ValueError(f"{groups} B/C groups do not divide {H} heads")
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
    dt = dt.astype(F32)
    A = A.astype(F32)
    plan = _kernel_plan(H, P, N, Q, cd, groups)
    _say_plan(H, P, N, groups, Q, plan)
    if plan is None:
        y = _scan_xla(x, dt, A, B, C, D, Q)
    else:
        # log-decay of each step and its running sum inside the chunk: small
        # [b, S, H] float32 work that stays in XLA, which differentiates it.
        # As a product with a triangle of ones at full float32 precision: on
        # the chip ``cumsum`` is a reduce-window of 13 us, and its transpose
        # one of 52 (benchmarks/ssd_micro.py), a layer.
        cum = jnp.einsum("ts,bcsh->bcth", jnp.tril(jnp.ones((Q, Q), F32)),
                         (dt * A).reshape(b, -1, Q, H),
                         precision=jax.lax.Precision.HIGHEST)
        y = _scan_kernels(
            x.reshape(b, S + pad, H * P), dt, cum.reshape(dt.shape),
            B.astype(cd).reshape(b, S + pad, groups * N),
            C.astype(cd).reshape(b, S + pad, groups * N),
            jnp.zeros((H,), F32) if D is None else D.astype(F32),
            (Q, P, plan, groups)).reshape(x.shape)
    return y[:, :S] if pad else y


def _say(name, value):
    """One record of no duration under the span that caused the trace (a name
    of ``telemetry.COMPILE_RECORDS``): a program is static, so the counter of
    whether its kernels engaged is a record a traced call."""
    from pytorch_distributed_training_example_tpu.utils import telemetry
    telemetry.recorder().compile_event(name, 0.0, value)


def _say_plan(H, P, N, groups, Q, plan):
    """One ``ssd_plan`` record a traced call of :func:`ssd`: whether the
    kernels engaged and at how many heads a program."""
    _say("ssd_plan", {
        "H": H, "P": P, "N": N, "groups": groups, "chunk": Q,
        "heads_per_program": "xla" if plan is None else plan})


def _scan_xla(x, dt, A, B, C, D, Q):
    """The scan in plain ``jax.numpy`` on a sequence of whole chunks of ``Q``
    (``B``, ``C`` [b, S, groups, N]; ``k`` below is a head of its group): XLA
    differentiates it, and the [chunks, H, Q, Q] tiles pass through HBM."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    K = H // G
    cd = x.dtype
    nc = S // Q
    by_group = lambda a: a.reshape(*a.shape[:-1], G, K)     # [.., H] -> [.., G, K]
    xc = x.reshape(b, nc, Q, G, K, P)
    Bc = B.reshape(b, nc, Q, G, N).astype(cd)
    Cc = C.reshape(b, nc, Q, G, N).astype(cd)
    dtc = dt.reshape(b, nc, Q, H)
    # log-decay of each step and its running sum inside the chunk
    cum = jnp.cumsum(dtc * A, axis=2)                        # [b,c,Q,H]
    xdt = (xc.astype(F32) * by_group(dtc)[..., None]).astype(cd)   # dt * x

    # 1. inside a chunk: (L o C B^T) (dt x)
    scores = jnp.einsum("bctgn,bcsgn->bcgts", Cc, Bc,
                        preferred_element_type=F32)          # [b,c,G,Q,Q]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    cumh = cum.transpose(0, 1, 3, 2)                         # [b,c,H,Q]
    seg = cumh[..., :, None] - cumh[..., None, :]            # [b,c,H,t,s]
    # masked before the exp: above the diagonal the difference is positive
    # and may overflow, and 0 * inf in the backward would be NaN
    L = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    M = (L.reshape(b, nc, G, K, Q, Q)
         * scores[:, :, :, None]).astype(cd)                 # [b,c,G,K,t,s]
    y = jnp.einsum("bcgkts,bcsgkp->bctgkp", M, xdt,
                   preferred_element_type=F32)

    # 2. the state each chunk leaves: sum_s exp(cum_end - cum_s) dt x (x) B
    to_end = by_group(jnp.exp(cum[:, :, -1:, :] - cum))      # [b,c,Q,G,K]
    left = jnp.einsum("bcsgkp,bcsgn->bcgkpn",
                      (xdt.astype(F32) * to_end[..., None]).astype(cd), Bc,
                      preferred_element_type=F32)            # [b,c,G,K,P,N]

    # 3. the state each chunk starts from: the earlier chunks' states, decayed
    #    by every chunk in between (strictly lower triangular over chunks)
    if nc > 1:
        total = cum[:, :, -1, :]                             # [b,c,H]
        run = jnp.cumsum(total, axis=1)
        # chunk z starts from sum_{c<z} exp(run[z-1] - run[c]) left[c]
        between = (run - total)[:, :, None, :] - run[:, None, :, :]  # [b,z,c,H]
        before = jnp.tril(jnp.ones((nc, nc), bool), -1)
        carry = jnp.exp(jnp.where(before[:, :, None], between, -jnp.inf))
        start = jnp.einsum("bzcgk,bcgkpn->bzgkpn", by_group(carry),
                           left)                             # float32
        # 4. what the carried state adds: exp(cum_t) C_t S_start
        y = y + jnp.einsum("bctgn,bcgkpn->bctgkp", Cc, start.astype(cd),
                           preferred_element_type=F32) \
            * by_group(jnp.exp(cum))[..., None]
    if D is not None:
        y = y + xc.astype(F32) * D.astype(F32).reshape(G, K)[..., None]
    return y.reshape(b, S, H, P)


# ---------------------------------------------------------------------------
# The Pallas kernel pair. A program is one (sequence, chunk, run of G heads:
# a part of one B/C group, or whole groups); the grid walks a sequence's chunks in order (the backward in
# reverse) with the state of every head, [H*P, N] float32, in VMEM scratch.
# ``x``, ``y`` and their cotangents stay lane-dense [b, S, H*P]: a head is a
# lane slice, and where P < 128 the 128 // P heads of one 128-lane block are
# worked together (each head's matmul runs over the block's lanes and keeps
# its own), so nothing is shifted across lanes. Everything [Q, Q] per head
# (``seg``, ``L``, ``M`` and their cotangents) lives and dies in a program.
# ---------------------------------------------------------------------------

LANES = 128
# Bytes :func:`_kernel_plan` lets the backward program hold, under the 16 MB of
# scoped VMEM the v5e compiler gives a kernel.
KERNEL_VMEM_BUDGET = 13 * 2 ** 20


def _kernel_plan(H, P, N, Q, dtype, groups=1):
    """G, the heads a program of the kernels holds, or None where the
    kernels do not serve the shape and :func:`_scan_xla` runs instead.

    Admitted: bf16 or float32 operands (Mosaic refuses fp16 loads); a chunk
    and a state width on the 128-lane tiling (``L`` is [Q, Q], the state
    [*, N]); a head width that divides or is a multiple of 128 lanes, and a
    head count made of whole lane blocks. G is the largest run of heads (a
    multiple of 8 heads, the row blocks' sublane tiling, or all of them; a
    part of one of the ``groups`` B/C groups or whole groups, so that a
    program's B/C block is its own groups' and no other's) whose
    backward program fits the budget: its double-buffered blocks (x, dx, dy
    in float32, the chunk's start state, its groups' B, C and their
    cotangents, the columns padded to 128 lanes), every head's state
    cotangent in scratch, the [Q, G*P] and [Q, Q] temporaries. At Granite's widths (H 64, P 64,
    N 128, Q 256) that is 16 heads in bf16 and 8 in float32: the groups
    ``benchmarks/ssd_micro.py`` timed (8 was 8% slower than 16) and
    ``tests/test_chip_compile.py`` compiles. The model is on the safe side:
    the compiler also admits 32 and 64, which were not timed unrolled. At
    Nemotron-3-Nano's (the same widths, 8 groups, Q 128) it is 32 heads, four
    groups, in bf16 and 16, two groups, in float32.
    """
    if dtype not in (jnp.bfloat16, jnp.float32):
        return None
    if Q % LANES or N % LANES or (LANES % P and P % LANES):
        return None
    per_block = max(1, LANES // P)
    if H % per_block:
        return None
    if H % groups:
        return None
    item = jnp.dtype(dtype).itemsize
    for G in range(H, 0, -1):
        if H % G or G % per_block or (G % 8 and G != H):
            continue
        held, shared_by = _group_span(H, G, groups)
        if G * shared_by != held * (H // groups):
            continue
        GP = G * P
        blocks = 2 * (2 * Q * GP * item + Q * GP * 4 + GP * N * 4
                      + 2 * Q * held * N * (item + 4) + 4 * Q * LANES * 4)
        temps = 2 * Q * GP * 4 + 8 * Q * Q * 4
        if blocks + H * P * N * 4 + temps <= KERNEL_VMEM_BUDGET:
            return G
    return None


def _group_span(H, G, groups):
    """``(held, shared_by)`` for programs of ``G`` heads: the B/C groups a
    program holds, and the programs that share one group (one of the two is
    1 in a plan that :func:`_kernel_plan` admits)."""
    per_group = H // groups
    return max(1, G // per_group), max(1, per_group // G)


def _group_cols(ref, q, N):
    """Group ``q``'s [Q, N] of a program's [1, Q, held * N] block of B or C
    (or of their cotangents): the lanes to index ``ref[0]`` with. ``q`` is
    traced where a program holds several groups."""
    if ref.shape[2] == N:
        return slice(None)
    return pl.ds(pl.multiple_of(q * N, N), N)


def _by_head(parts, width, axis):
    """One array whose ``axis`` is cut into ``len(parts)`` runs of ``width``,
    run i taken from ``parts[i]`` (each broadcastable to the result)."""
    out = parts[-1]
    if len(parts) > 1:
        shape = jnp.broadcast_shapes(*(p.shape for p in parts))
        shape = shape[:axis] + (width * len(parts),) + shape[axis + 1:]
        at = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        for i in range(len(parts) - 2, -1, -1):
            out = jnp.where(at < (i + 1) * width, parts[i], out)
    return out


def _split(v, dtype):
    """``v`` rounded to ``dtype`` for the MXU, and what the rounding lost,
    rounded in turn (None where nothing is lost: float32 operands). The
    backward puts the second back, as a second pass of the matmul, wherever a
    float32 value meets the MXU on the way to A's gradient: that gradient is
    the difference of sums that all but cancel, and the chip benchmark's
    ``grad_leaf`` reads it on 64-element leaves."""
    high = v.astype(dtype)
    if dtype == F32:
        return high, None
    return high, (v - high.astype(F32)).astype(dtype)


def _decay_tile(a_col, a_row, diagonal):
    """``L[t, s] = exp(a_t - a_s)`` for a [T, T] tile of the chunk. On a
    diagonal tile the difference is masked above the diagonal before the exp
    (it is positive there and may overflow); under the diagonal tiles
    a_t <= a_s throughout."""
    seg = a_col - a_row
    if diagonal:
        T = seg.shape[0]
        seg = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
                        >= jax.lax.broadcasted_iota(jnp.int32, (T, T), 1),
                        seg, -jnp.inf)
    return jnp.exp(seg)


def _column(block, h):
    """Column ``h`` (a traced index) of ``block`` [Q, G], as [Q, 1]."""
    at = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(at == h, block, 0.0), axis=1, keepdims=True)


def _row(block, h):
    """Row ``h`` (a traced index) of ``block`` [G, T], as [1, T]. (Mosaic
    loads no single row at a traced, unaligned sublane.)"""
    at = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(at == h, block, 0.0), axis=0, keepdims=True)


def _set_column(ref, rows, h, col):
    """``ref[0, 0, rows, h] = col`` for a traced ``h``: the other columns of
    the [Q, G] block keep what they hold."""
    old = ref[0, 0, rows, :]
    at = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
    ref[0, 0, rows, :] = jnp.where(at == h, col, old)


def _ssd_fwd_kernel(x_ref, dt_ref, ac_ref, ar_ref, b_ref, c_ref, d_ref,
                    y_ref, s0_ref, state, *, P):
    """One chunk of G heads. ``x_ref`` [1, Q, G*P]; ``dt_ref``, ``ac_ref``
    [1, 1, Q, G] (dt and the running log-decay, a head a lane: columns);
    ``ar_ref`` [1, 1, G, Q] (the same log-decay, a head a row); ``b_ref``,
    ``c_ref`` [1, Q, held * N], the B/C groups of the program's heads side by
    side; ``d_ref`` [1, G*P]. Out: ``y_ref`` [1, Q, G*P]
    float32 and ``s0_ref`` [1, 1, G*P, N], the state the chunk started from
    (the backward's residual). ``state`` [H/G, G*P, N] carries every head's
    state from chunk to chunk. One 128-lane block of heads at a time, in a
    ``fori_loop`` that Pallas unrolls: a Python loop over the 8 blocks traced
    the body 8 times, 7 s of every start-up on the chip's host, and a loop
    left rolled was 0.32 ms a layer against 0.26 (the backward 0.95 against
    0.67). Where the program holds several B/C groups, a loop of the same
    kind over them around it: ``C B^T`` is a group's. The [Q, Q] tile is walked in [128, 128] tiles, at and under the
    diagonal ones only (in one piece the backward took 10% longer)."""
    c, g = pl.program_id(1), pl.program_id(2)
    Q, GP = x_ref.shape[1], x_ref.shape[2]
    W = max(P, LANES)
    per_block = W // P
    T = LANES
    cd = x_ref.dtype

    @pl.when(c == 0)
    def _():
        state[g] = jnp.zeros(state.shape[1:], F32)

    s0_ref[0, 0] = state[g]
    a_all, dt_all = ac_ref[0, 0], dt_ref[0, 0]                # [Q, G]
    N = state.shape[2]
    held = b_ref.shape[2] // N
    per_group = GP // W // held       # lane blocks of a group's heads

    def group(q, _):
        at = _group_cols(b_ref, q, N)
        Bm, Cm = b_ref[0, :, at], c_ref[0, :, at]
        scores = _nt_dot(Cm, Bm)                              # [Q, Q]
        return jax.lax.fori_loop(
            0, per_group,
            lambda i, _: block(Bm, Cm, scores, q * per_group + i, _),
            0, unroll=True)

    def block(Bm, Cm, scores, j, _):
        lanes = pl.ds(pl.multiple_of(j * W, W), W)
        heads = [j * per_block + i for i in range(per_block)]
        xf = x_ref[0, :, lanes].astype(F32)                   # [Q, W]
        a_cols = [_column(a_all, h) for h in heads]           # [Q, 1] each
        a = _by_head(a_cols, P, 1)                            # [Q, W]
        dt = _by_head([_column(dt_all, h) for h in heads], P, 1)
        xdt = (xf * dt).astype(cd)
        s0 = state[g, lanes, :]                               # [W, N]
        rest = (_nt_dot(Cm, s0.astype(cd)) * jnp.exp(a)       # the carried
                + xf * d_ref[:, lanes])
        for lo in range(0, Q, T):
            rows = slice(lo, lo + T)
            ys = []
            for i, h in enumerate(heads):
                y = jnp.zeros((T, W), F32)
                for co in range(0, lo + T, T):
                    cols = slice(co, co + T)
                    L = _decay_tile(a_cols[i][rows],
                                    _row(ar_ref[0, 0, :, cols], h), co == lo)
                    M = (L * scores[rows, cols]).astype(cd)
                    y += jnp.dot(M, xdt[cols], preferred_element_type=F32)
                ys.append(y)
            y_ref[0, rows, lanes] = _by_head(ys, P, 1) + rest[rows]
        # the state this chunk leaves: exp(a_end) S + sum_s exp(a_end - a_s)
        # dt x (x) B
        a_end = a[Q - 1:Q, :]                                 # [1, W]
        xe = (xdt.astype(F32) * jnp.exp(a_end - a)).astype(cd)
        keep = _by_head([jnp.exp(col[Q - 1:Q, :]) for col in a_cols], P, 0)
        state[g, lanes, :] = s0 * keep + _tn_dot(xe, Bm)
        return 0

    if held > 1:
        jax.lax.fori_loop(0, held, group, 0, unroll=True)
    else:
        group(0, 0)


def _ssd_bwd_kernel(x_ref, dt_ref, ac_ref, ar_ref, b_ref, c_ref, d_ref,
                    s0_ref, dy_ref,
                    dx_ref, ddt_ref, da_ref, dar_ref, db_ref, dc_ref, dd_ref,
                    dstate, dscores, dxdt_t, *, P, shared_by):
    """The forward's program, backwards: chunks arrive last first and
    ``dstate`` [H/G, G*P, N] carries the cotangent of the state a chunk
    leaves. Recomputes ``L`` and ``M`` from the log-decays and ``C B^T``, in
    the forward's loop and tiles. Out: ``dx_ref`` [1, Q, G*P]; ``ddt_ref``
    [1, 1, Q, G] (of dt as it scales x); of the running log-decay,
    ``da_ref`` [1, 1, Q, G] less ``dar_ref`` [1, 1, G, Q] (what a token
    gathers as t, a column, and what it loses as s, a row); ``db_ref``,
    ``dc_ref`` [1, Q, held * N] float32, a group's summed over its heads in the
    program and over the ``shared_by`` programs of a chunk that share the
    group (they follow one another); ``dd_ref`` [1, 1, 1, G*P], the chunk's sum
    over tokens of dy * x. Scratch: ``dscores`` [Q, Q], the cotangent of
    ``C B^T`` summed over the heads of one group; ``dxdt_t`` [W, Q], a lane
    block's ``(M^T dy)^T`` (transposing dy for the MXU is a quarter of
    transposing M)."""
    c, g = pl.program_id(1), pl.program_id(2)
    Q, GP = x_ref.shape[1], x_ref.shape[2]
    W = max(P, LANES)
    per_block = W // P
    T = LANES
    cd = x_ref.dtype

    @pl.when(c == 0)
    def _():
        dstate[g] = jnp.zeros(dstate.shape[1:], F32)

    last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    a_all, dt_all = ac_ref[0, 0], dt_ref[0, 0]                # [Q, G]
    at = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    own = [(at >= i * P) & (at < (i + 1) * P) for i in range(per_block)]
    mine = lambda i, v: jnp.where(own[i], v, 0) if per_block > 1 else v
    N = dstate.shape[2]
    held = b_ref.shape[2] // N
    per_group = GP // W // held       # lane blocks of a group's heads

    def group(q, _):
        at = _group_cols(b_ref, q, N)
        Bm, Cm = b_ref[0, :, at], c_ref[0, :, at]
        scores = _nt_dot(Cm, Bm)                              # [Q, Q]
        dscores[:] = jnp.zeros((Q, Q), F32)
        zero = jnp.zeros(Bm.shape, F32)
        db, dc = jax.lax.fori_loop(
            0, per_group,
            lambda i, sums: block(Bm, Cm, scores, q * per_group + i, sums),
            (zero, zero), unroll=True)
        # 6. C B^T is every head's of the group: its cotangent was summed
        #    over them above
        dsc = dscores[:].astype(cd)
        dc += jnp.dot(dsc, Bm, preferred_element_type=F32)
        db += _tn_dot(dsc, Cm)
        if shared_by == 1:      # the group's heads are all this program's
            db_ref[0, :, at] = db
            dc_ref[0, :, at] = dc
            return 0
        # the first of the programs that share the group, and the others
        nth = g % shared_by

        @pl.when(nth == 0)
        def _():
            db_ref[0] = db
            dc_ref[0] = dc

        @pl.when(nth > 0)
        def _():
            db_ref[0] += db
            dc_ref[0] += dc
        return 0

    def block(Bm, Cm, scores, j, sums):
        db, dc = sums
        lanes = pl.ds(pl.multiple_of(j * W, W), W)
        heads = [j * per_block + i for i in range(per_block)]
        xf = x_ref[0, :, lanes].astype(F32)                   # [Q, W]
        dy = dy_ref[0, :, lanes]
        dyc, dy_low = _split(dy, cd)
        a_cols = [_column(a_all, h) for h in heads]
        a = _by_head(a_cols, P, 1)
        dt = _by_head([_column(dt_all, h) for h in heads], P, 1)
        d = d_ref[:, lanes]
        xdt, xdt_low = _split(xf * dt, cd)
        s0 = s0_ref[0, 0, lanes, :]                           # [W, N]
        s0c, s0_low = _split(s0, cd)
        ds1 = dstate[g, lanes, :]                             # [W, N]
        ds1c, ds1_low = _split(ds1, cd)
        # 1. through the carried state: y += (C S0^T) exp(a)
        decay = jnp.exp(a)
        dcarried, dcarried_low = _split(dy * decay, cd)       # [Q, W]
        dc += jnp.dot(dcarried, s0c, preferred_element_type=F32)
        ds0 = _tn_dot(dcarried, Cm)                           # [W, N]
        carried = _nt_dot(Cm, s0c)
        # 2. through the state the chunk leaves: S1 = exp(a_end) S0 +
        #    (dt x exp(a_end - a))^T B
        to_end = jnp.exp(a[Q - 1:Q, :] - a)
        xe = xdt.astype(F32) * to_end
        dxe = _nt_dot(Bm, ds1c)                               # [Q, W]
        if dy_low is not None:  # second passes: see _split
            ds0 += _tn_dot(dcarried_low, Cm)
            carried += _nt_dot(Cm, s0_low)
            dxe += _nt_dot(Bm, ds1_low)
        db += jnp.dot(xe.astype(cd), ds1c, preferred_element_type=F32)
        keep = _by_head([jnp.exp(col[Q - 1:Q, :]) for col in a_cols], P, 0)
        dstate[g, lanes, :] = ds1 * keep + ds0
        # 3. the log-decays' part of 1 and 2: a_t gathers dy_t . carried_t
        #    and loses what it gave the left state; a_end gathers what the
        #    others lost there, and S0's part of S1
        to_state = dxe * xe
        of_a = dy * carried * decay - to_state
        lost = jnp.sum(to_state, axis=0, keepdims=True)       # [1, W]
        # 4. through y = M (dt x), a head and a tile at a time: dM = dy
        #    (dt x)^T (the other heads' lanes zeroed, so the contraction keeps
        #    to its own). With W[t, s] = dM M in float32, a_t gathers its row
        #    of W and a_s loses its column: the two all but cancel in A's
        #    gradient, so both are sums over the one tile.
        dxdt_t[:] = jnp.zeros((W, Q), F32)
        for i, h in enumerate(heads):
            state_rows = slice(i * P, (i + 1) * P)
            end = (jnp.sum(mine(i, lost), axis=1, keepdims=True)
                   + jnp.exp(a_cols[i][Q - 1:Q, :])
                   * jnp.sum(ds1[state_rows, :] * s0[state_rows, :],
                             keepdims=True))
            for lo in range(0, Q, T):
                rows = slice(lo, lo + T)
                dy_h = mine(i, dyc[rows])
                dy_h_low = None if dy_low is None else mine(i, dy_low[rows])
                gathered = mine(i, of_a[rows])                # [T, W]
                for co in range(0, lo + T, T):
                    cols = slice(co, co + T)
                    L = _decay_tile(a_cols[i][rows],
                                    _row(ar_ref[0, 0, :, cols], h), co == lo)
                    M = L * scores[rows, cols]
                    dM = _nt_dot(dy_h, xdt[cols])             # [T, T]
                    if dy_low is not None:
                        # what the two operands lost to rounding, put back
                        dM += (_nt_dot(dy_h_low, xdt[cols])
                               + _nt_dot(dy_h, xdt_low[cols]))
                    dscores[rows, cols] += dM * L
                    dxdt_t[:, cols] += _tn_dot(dy_h, M.astype(cd))
                    dM = dM * M
                    lost_s = jnp.sum(dM, axis=0, keepdims=True)
                    old = dar_ref[0, 0, :, cols]              # [G, T]
                    if lo != co:  # a column's first tile is its diagonal one
                        lost_s = lost_s + old
                    dar_ref[0, 0, :, cols] = jnp.where(
                        jax.lax.broadcasted_iota(jnp.int32, old.shape, 0) == h,
                        lost_s, old)
                    gathered = jnp.concatenate([gathered, dM], axis=1)
                _set_column(da_ref, rows, h, jnp.sum(
                    gathered, axis=1, keepdims=True) + jnp.where(
                        last[rows], end, 0.0))
        # 5. x, dt, D
        dxdt = dxdt_t[:].T + dxe * to_end
        dx_ref[0, :, lanes] = (dy * d + dxdt * dt).astype(dx_ref.dtype)
        dd_ref[0, 0, :, lanes] = jnp.sum(dy * xf, axis=0, keepdims=True)
        of_dt = dxdt * xf
        for i, h in enumerate(heads):
            _set_column(ddt_ref, slice(None), h,
                        jnp.sum(mine(i, of_dt), axis=1, keepdims=True))
        return db, dc

    if held > 1:
        jax.lax.fori_loop(0, held, group, 0, unroll=True)
    else:
        group(0, 0)


def _columns(a, G):
    """[b, S, H] -> [b, H/G, S, G]: a program's heads side by side on lanes."""
    b, S, H = a.shape
    return a.reshape(b, S, H // G, G).transpose(0, 2, 1, 3)


def _rows(a, Q):
    """[b, S, H] -> [b, S/Q, H, Q]: a head a row, a chunk's tokens on lanes."""
    b, S, H = a.shape
    return a.reshape(b, S // Q, Q, H).transpose(0, 1, 3, 2)


def _specs(Q, N, G, P, order, held, shared_by):
    """Block specs for the grid (sequence, chunk, run of heads), by what a
    block holds; ``order`` maps the grid's chunk index to the chunk. A
    program's B/C block is its ``held`` groups', or the one that it shares
    with the ``shared_by - 1`` programs beside it (of one group, every
    program shares the one block)."""
    GP = G * P
    return dict(
        tokens=pl.BlockSpec((1, Q, GP), lambda i, c, g: (i, order(c), g)),
        cols=pl.BlockSpec((1, 1, Q, G), lambda i, c, g: (i, g, order(c), 0)),
        rows=pl.BlockSpec((1, 1, G, Q), lambda i, c, g: (i, order(c), g, 0)),
        shared=pl.BlockSpec((1, Q, held * N), lambda i, c, g: (
            i, order(c), g // shared_by)),
        state=pl.BlockSpec((1, 1, GP, N), lambda i, c, g: (i, order(c), g, 0)),
        per_lane=pl.BlockSpec((1, GP), lambda i, c, g: (0, g)),
        chunk_sum=pl.BlockSpec((1, 1, 1, GP),
                               lambda i, c, g: (i, order(c), 0, g)))


def _operands(x, dt, cum, B, C, D, plan):
    """Both kernels' first seven operands and their specs' names: dt and the
    log-decay as columns, the log-decay as rows, D a lane a channel."""
    Q, P, G, _ = plan
    return ((x, _columns(dt, G), _columns(cum, G), _rows(cum, Q), B, C,
             jnp.repeat(D, P)[None]),
            ("tokens", "cols", "cols", "rows", "shared", "shared", "per_lane"))


#: Both kernels' grid: (sequence, chunk, head group), the chunks in order.
_GRID_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


@functools.partial(jax.jit, static_argnames="plan")
def _fwd_call(x, dt, cum, B, C, D, *, plan):
    """``y`` [b, S, H*P] float32 and the states the chunks started from,
    [b, S/Q, H*P, N] float32. Under ``jit`` so that a model's layers, and the
    recomputation in its backward, share one trace and one lowering."""
    Q, P, G, groups = plan
    b, S, HP = x.shape
    H, N, nc = HP // P, B.shape[-1] // groups, S // Q
    operands, names = _operands(x, dt, cum, B, C, D, plan)
    spec = _specs(Q, N, G, P, lambda c: c, *_group_span(H, G, groups))
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, P=P),
        name="ssd_fwd",
        grid=(b, nc, H // G),
        in_specs=[spec[n] for n in names],
        out_specs=(spec["tokens"], spec["state"]),
        out_shape=(jax.ShapeDtypeStruct((b, S, HP), F32),
                   jax.ShapeDtypeStruct((b, nc, HP, N), F32)),
        scratch_shapes=[pltpu.VMEM((H // G, G * P, N), F32)],
        compiler_params=_GRID_ORDER,
        interpret=not backend.on_tpu(),
    )(*operands)


@functools.partial(jax.jit, static_argnames="plan")
def _bwd_call(x, dt, cum, B, C, D, states, dy, *, plan):
    """Cotangents of x, dt, cum, B, C (the last two float32) and, per
    sequence and chunk, the sum over tokens of dy * x [b, S/Q, 1, H*P]."""
    Q, P, G, groups = plan
    b, S, HP = x.shape
    H, N, nc = HP // P, B.shape[-1] // groups, S // Q
    operands, names = _operands(x, dt, cum, B, C, D, plan)
    held, shared_by = _group_span(H, G, groups)
    spec = _specs(Q, N, G, P, lambda c: nc - 1 - c, held, shared_by)
    columns = jax.ShapeDtypeStruct((b, H // G, S, G), F32)
    shared = jax.ShapeDtypeStruct(B.shape, F32)
    dx, ddt, da, dar, db, dc, dd = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, P=P, shared_by=shared_by),
        name="ssd_bwd",
        grid=(b, nc, H // G),
        in_specs=[spec[n] for n in names + ("state", "tokens")],
        out_specs=tuple(spec[n] for n in (
            "tokens", "cols", "cols", "rows", "shared", "shared",
            "chunk_sum")),
        out_shape=(jax.ShapeDtypeStruct((b, S, HP), x.dtype), columns, columns,
                   jax.ShapeDtypeStruct((b, nc, H, Q), F32), shared, shared,
                   jax.ShapeDtypeStruct((b, nc, 1, HP), F32)),
        scratch_shapes=[pltpu.VMEM((H // G, G * P, N), F32),
                        pltpu.VMEM((Q, Q), F32),
                        pltpu.VMEM((max(P, LANES), Q), F32)],
        compiler_params=_GRID_ORDER,
        interpret=not backend.on_tpu(),
    )(*operands, states, dy)
    heads = lambda a: a.transpose(0, 2, 1, 3).reshape(b, S, H)
    da = heads(da) - dar.transpose(0, 1, 3, 2).reshape(b, S, H)
    return dx, heads(ddt), da, db, dc, dd


def _per_device(fn, *args, n_out):
    """``fn`` over [b, S, ..] arrays whose first axis is the batch (a
    parameter, of one or two axes, is every device's whole), per device of
    the ambient mesh with the batch sharded: GSPMD cannot partition a Mosaic
    call (``core/mesh.manual_call``). A batch the data-parallel axes do not
    divide is replicated. ``n_out`` results, or with None the one array."""
    from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib

    mesh = mesh_lib.current_mesh()
    axes = None
    if mesh is not None:
        axes = tuple(a for a in mesh_lib.BATCH_AXES
                     if mesh.shape.get(a, 1) > 1)
        ways = math.prod(mesh.shape[a] for a in axes)
        if not axes or args[0].shape[0] % ways:
            axes = None
    spec = lambda a: (PartitionSpec(axes, *([None] * (a.ndim - 1)))
                      if a.ndim > 2 else PartitionSpec())
    out = PartitionSpec(axes)
    return mesh_lib.manual_call(
        fn, *args, mesh=mesh, in_specs=tuple(spec(a) for a in args),
        out_specs=out if n_out is None else (out,) * n_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_kernels(x, dt, cum, B, C, D, plan):
    """The scan through the kernels. ``x`` [b, S, H*P]; ``dt``, ``cum``
    [b, S, H] float32 (``cum`` the log-decay's running sum inside each
    chunk); ``B``, ``C`` [b, S, groups * N] in ``x.dtype``; ``D`` [H] float32;
    ``plan`` (Q, P, G, groups). Returns ``y`` [b, S, H*P] float32."""
    return _scan_fwd(x, dt, cum, B, C, D, plan)[0]


def _scan_fwd(x, dt, cum, B, C, D, plan):
    y, states = _per_device(functools.partial(_fwd_call, plan=plan),
                            x, dt, cum, B, C, D, n_out=2)
    return y, (x, dt, cum, B, C, D, states)


def _scan_bwd(plan, res, dy):
    B, D = res[3], res[5]
    dx, ddt, dcum, dB, dC, dD = _per_device(
        functools.partial(_bwd_call, plan=plan), *res, dy, n_out=6)
    dD = dD.reshape(-1, D.shape[0], plan[1]).sum((0, 2))
    return dx, ddt, dcum, dB.astype(B.dtype), dC.astype(B.dtype), dD


_scan_kernels.defvjp(_scan_fwd, _scan_bwd)


# ---------------------------------------------------------------------------
# The mixer's two elementwise stages, each one pass over HBM a direction:
# :func:`conv_silu` (the causal conv with its bias and silu) and
# :func:`gate_norm` (the gate with its group norm; :func:`norm_gate` is the
# same stage with the norm first). A program is one
# (sequence, column block, row tile); the grid walks a column block's row
# tiles in order (the conv's backward in reverse), so that the conv's history
# and every parameter's gradient are carried in VMEM from tile to tile. Both
# are chosen by :func:`_stage_plan` the way the scan's pair is, from dtype and
# widths, and fall back to the ``jax.numpy`` bodies (:func:`causal_conv1d`
# with ``silu``; :func:`group_rms_norm` of the gated value).
# ---------------------------------------------------------------------------

#: Rows of history a conv tile carries in VMEM: one float32 sublane tile,
#: which is also what holds the taps with the bias, and their gradients.
HALO = 8
#: Rows the conv's kernels work at a time: a strip of a tile whose float32
#: values stay in registers from the load to the store (a sublane tile of
#: bf16). Whole tiles at a time, every elementwise step went through VMEM.
STRIP = 16
#: Elements of a program's [rows, cols] tile, and the widest column block of
#: the conv, and of the norm where a group is narrower (a block of the norm is
#: whole groups). ``benchmarks/ssd_micro.py --stages --tiles`` timed others
#: of as many elements: the norm's within 2% of these, the conv's up to 5%
#: slower ([128, 1024], [512, 256]), and at half of them 28% (forward).
STAGE_TILE = 128 * 1024
STAGE_COLS = 512


def _stage_plan(stage, S, C, groups, dtype, K=1):
    """``(rows, cols)`` of a program's tile of ``stage`` (``conv_silu``, or
    ``gate_norm`` / ``norm_gate``: one tile for both orders) over ``[S, C]``,
    or None where the ``jax.numpy`` body runs.

    Admitted: bf16 or float32 (Mosaic refuses fp16 loads); channels on the
    128-lane tiling, for the norm every group's run of them; a conv of at most
    7 taps (its history is one 8-row tile, and its taps' and bias's gradients
    one); a sequence of at least one tile's rows. The conv's columns are the
    most lane tiles up to 512 lanes that divide C; the norm's are whole
    groups, as many as 512 lanes hold or one where a group is wider. The rows
    follow from :data:`STAGE_TILE` elements a tile, in whole strips (which are
    bf16's sublane tiles): [256, 512] for both stages at
    Nemotron-3-Nano's widths (6144 channels; eight groups of 512), [512, 256]
    and [32, 4096] at Granite's (4352; one group), [256, 512] at Qwen3-Next's
    delta rule (8192 conv channels; 32 heads of 128, four a block)."""
    if dtype not in (jnp.bfloat16, jnp.float32):
        return None
    if C % groups or (C // groups) % LANES:
        return None
    if stage == "conv_silu":
        if not 1 <= K <= HALO - 1:
            return None
        cols = next(c for c in range(STAGE_COLS, 0, -LANES) if C % c == 0)
    else:
        width = C // groups
        held = max((n for n in range(1, groups + 1)
                    if groups % n == 0 and n * width <= STAGE_COLS), default=1)
        cols = held * width
    rows = max(STRIP, STAGE_TILE // cols // STRIP * STRIP)
    return (rows, cols) if S >= rows else None


def _say_stage_plan(stage, S, C, groups, plan):
    """One ``mixer_plan`` record a traced call of a stage: the tile, or
    ``"xla"``."""
    _say("mixer_plan", {
        "stage": stage, "rows": S, "channels": C, "groups": groups,
        "tile": "xla" if plan is None else list(plan)})


def _silu_and_slope(v, rounded=False):
    """``silu(v)`` and its derivative, from one sigmoid. ``rounded``: the
    operands and the results are bf16, and the sigmoid is taken as a ``tanh``,
    one transcendental and no division: 1e-5 off on the chip where the
    exponential's is 1e-7, under bf16's 4e-3 (a parameter's gradient, summed
    in float32, comes out 6e-6 off where the ``jax.numpy`` body's is 4.5e-6),
    for an eighth of the conv's kernels' time (``benchmarks/ssd_micro.py
    --stages --check 1``)."""
    s = 0.5 * jnp.tanh(0.5 * v) + 0.5 if rounded else jax.nn.sigmoid(v)
    return v * s, s * (1.0 + v * (1.0 - s))


def _valid_rows(first, rows, S):
    """[rows, 1] mask of the rows from ``first`` on that the sequence has."""
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return first + at < S


def _strip(at):
    """The STRIP rows from ``at``, a traced multiple of STRIP."""
    return pl.ds(pl.multiple_of(at, STRIP), STRIP)


def _over_strips(rows, body, carry, reverse=False):
    """``body(at, carry)`` for each strip of a tile in turn, in a loop left
    rolled: a strip is hundreds of vector operations, and unrolled the
    sixteen or thirty-two copies of it cost every start-up seconds of tracing
    and lowering (5 to 10 s of ``setup_s`` on the chip's host)."""
    last = rows // STRIP - 1
    return jax.lax.fori_loop(
        0, last + 1,
        lambda i, carry: body((last - i if reverse else i) * STRIP, carry),
        carry)


def _rows_back(strip, before, shift):
    """``strip`` [STRIP, cols] ``shift`` rows back, its first rows from the
    end of ``before`` [HALO, cols]: a sublane rotation of the two stacked
    (Mosaic loads no strip at a traced offset off the sublane tiling)."""
    if not shift:
        return strip
    return pltpu.roll(jnp.concatenate([before, strip], axis=0), shift,
                      0)[HALO:]


def _rows_on(strip, after, shift):
    """``strip`` ``shift`` rows on, its last rows from the start of
    ``after`` [HALO, cols]."""
    if not shift:
        return strip
    return pltpu.roll(jnp.concatenate([strip, after], axis=0),
                      STRIP + HALO - shift, 0)[:STRIP]


def _pre_activation(x, before, taps):
    """``bias + sum_k taps[k] x_{t-(K-1)+k}`` for a strip ``x`` with the HALO
    rows ``before`` it; ``taps``: the K taps and the bias, [1, cols] each.
    Returns it and the K shifted strips, tap by tap."""
    K = len(taps) - 1
    reads = [_rows_back(x, before, K - 1 - k) for k in range(K)]
    pre = taps[K]
    for read, tap in zip(reads, taps):
        pre = pre + read * tap
    return pre, reads


def _conv_silu_fwd_kernel(x_ref, taps_ref, o_ref, history, *, K):
    """``x_ref``, ``o_ref`` [1, rows, cols]; ``taps_ref`` [8, cols] float32,
    rows 0..K-1 the taps and row K the bias; ``history`` [HALO, cols] float32
    scratch: the last rows of the tile before, zero at a sequence's start. A
    strip hands its own last rows to the next as the loop's carry."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        history[:] = jnp.zeros(history.shape, F32)

    taps = [taps_ref[k:k + 1, :] for k in range(K + 1)]

    def strip(at, before):
        x = x_ref[0, _strip(at), :].astype(F32)
        pre, _ = _pre_activation(x, before, taps)
        o_ref[0, _strip(at), :] = _silu_and_slope(
            pre, o_ref.dtype == jnp.bfloat16)[0].astype(o_ref.dtype)
        return x[STRIP - HALO:]

    history[:] = _over_strips(x_ref.shape[1], strip, history[:])


def _conv_silu_bwd_kernel(x_ref, before_ref, dy_ref, taps_ref,
                          dx_ref, dtaps_ref, after, *, K, S):
    """The row tiles last first, and a tile's strips last first.
    ``before_ref`` [1, h, cols]: the sublane tile of ``x`` that ends where the
    tile starts (the pre-activation is recomputed from ``x``; inside a tile
    the rows before a strip are read from ``x_ref`` again). ``after`` [HALO,
    cols] float32 scratch: the first rows of the pre-activation's cotangent
    of the tile that follows, zero at a sequence's end; a strip hands its own
    first rows to the one before it as the loop's carry. ``dtaps_ref`` [1, 8,
    cols] float32 gathers the taps' (rows 0..K-1) and the bias's (row K)
    gradients over a column block's tiles."""
    r = pl.program_id(2)
    tile = pl.num_programs(2) - 1 - r
    rows, cols = x_ref.shape[1:]

    @pl.when(r == 0)
    def _():
        after[:] = jnp.zeros(after.shape, F32)
        dtaps_ref[0] = jnp.zeros(dtaps_ref.shape[1:], F32)

    ahead = before_ref[0].astype(F32)
    ahead = jnp.where(tile == 0, 0.0, ahead[ahead.shape[0] - HALO:])
    taps = [taps_ref[k:k + 1, :] for k in range(K + 1)]
    fold = lambda v: sum(v[i:i + HALO] for i in range(0, STRIP, HALO))

    def load(ref, at):
        v = ref[0, _strip(at), :].astype(F32)
        if S % rows:   # the last tile's rows past the sequence hold anything
            v = jnp.where(_valid_rows(tile * rows + at, STRIP, S), v, 0.0)
        return v

    def strip(at, carry):
        following, sums = carry
        x, dy = load(x_ref, at), load(dy_ref, at)
        before = jnp.where(at == 0, ahead, load(
            x_ref, jnp.maximum(at - STRIP, 0))[STRIP - HALO:])
        pre, reads = _pre_activation(x, before, taps)
        dpre = dy * _silu_and_slope(pre, dx_ref.dtype == jnp.bfloat16)[1]
        # dx_t = sum_k taps[k] dpre_{t+(K-1)-k}
        dx_ref[0, _strip(at), :] = sum(
            _rows_on(dpre, following, K - 1 - k) * taps[k]
            for k in range(K)).astype(dx_ref.dtype)
        return dpre[:HALO], [
            acc + fold(dpre * read) for acc, read in zip(sums, reads)] + [
            sums[K] + fold(dpre)]

    # a parameter's sum over the tile, a sublane a partial sum until the end
    following, sums = _over_strips(
        rows, strip, (after[:], [jnp.zeros((HALO, cols), F32)] * (K + 1)),
        reverse=True)
    after[:] = following
    for k, acc in enumerate(sums):
        dtaps_ref[0, k:k + 1, :] += jnp.sum(acc, axis=0, keepdims=True)


def _tiles_in_order(*sliced):
    """The stages' grid: (sequence, column block, row tile), the tiles in
    order. ``sliced``, an operand each where any: whether XLA may fuse what
    makes it into the call's reads. The norm is handed ``z``, a lane slice of
    ``zxbcdt``: fused, the kernel reads it where it lies and no copy of the
    slice is made (``norm_gate`` is handed ``qkvz`` itself and the lane where
    ``z`` starts, and reads it through its index map as the conv does). (Not
    the conv's operands: with a loop in the kernel the chip's compiler fails
    on a fused operand's staging buffer, so the conv reads its slice of
    ``zxbcdt`` through its own index maps.)"""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        allow_input_fusion=list(sliced) or None)


def _taps_block(kernel, bias):
    """[8, C] float32: the taps, then the bias."""
    K, C = kernel.shape
    return jnp.concatenate([kernel.astype(F32), bias.astype(F32)[None],
                            jnp.zeros((HALO - K - 1, C), F32)])


def _conv_tiles(plan, offset, order):
    """Block specs of the conv's kernels over the grid (sequence, column
    block, row tile): a [rows, cols] tile of the source, whose lanes from
    ``offset`` on are the conv's channels, and of an array of the channels
    alone; ``order`` maps the grid's row index to the tile."""
    rows, cols = plan
    skip = offset // cols
    return (pl.BlockSpec((1, rows, cols),
                         lambda i, j, r: (i, order(r), skip + j)),
            pl.BlockSpec((1, rows, cols), lambda i, j, r: (i, order(r), j)))


@functools.partial(jax.jit, static_argnames=("plan", "offset"))
def _conv_silu_fwd_call(source, kernel, bias, *, plan, offset):
    rows, cols = plan
    b, S, _ = source.shape
    K, C = kernel.shape
    read, written = _conv_tiles(plan, offset, lambda r: r)
    return pl.pallas_call(
        functools.partial(_conv_silu_fwd_kernel, K=K),
        name="conv_silu_fwd",
        grid=(b, C // cols, pl.cdiv(S, rows)),
        in_specs=[read, pl.BlockSpec((HALO, cols), lambda i, j, r: (0, j))],
        out_specs=written,
        out_shape=jax.ShapeDtypeStruct((b, S, C), source.dtype),
        scratch_shapes=[pltpu.VMEM((HALO, cols), F32)],
        compiler_params=_tiles_in_order(),
        interpret=not backend.on_tpu(),
    )(source, _taps_block(kernel, bias))


@functools.partial(jax.jit, static_argnames=("plan", "offset"))
def _conv_silu_bwd_call(source, kernel, bias, dy, *, plan, offset):
    """``dx`` and, a sequence, [8, C] float32: the taps' and the bias's
    gradients."""
    rows, cols = plan
    b, S, _ = source.shape
    K, C = kernel.shape
    nr = pl.cdiv(S, rows)
    h = HALO * 4 // source.dtype.itemsize     # the source's sublane tile
    read, written = _conv_tiles(plan, offset, lambda r: nr - 1 - r)
    before = pl.BlockSpec((1, h, cols), lambda i, j, r: (
        i, jnp.maximum((nr - 1 - r) * (rows // h) - 1, 0),
        offset // cols + j))
    return pl.pallas_call(
        functools.partial(_conv_silu_bwd_kernel, K=K, S=S),
        name="conv_silu_bwd",
        grid=(b, C // cols, nr),
        in_specs=[read, before, written,
                  pl.BlockSpec((HALO, cols), lambda i, j, r: (0, j))],
        out_specs=(written, pl.BlockSpec((1, HALO, cols),
                                         lambda i, j, r: (i, 0, j))),
        out_shape=(jax.ShapeDtypeStruct((b, S, C), source.dtype),
                   jax.ShapeDtypeStruct((b, HALO, C), F32)),
        scratch_shapes=[pltpu.VMEM((HALO, cols), F32)],
        compiler_params=_tiles_in_order(),
        interpret=not backend.on_tpu(),
    )(source, source, dy, _taps_block(kernel, bias))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _conv_silu_kernels(x, source, kernel, bias, how):
    """``how``: (plan, offset), a program's tile and the lane of ``source``
    where ``x`` starts. ``x`` [b, S, C] is never read: it is there for its
    cotangent, and the kernels read its values out of ``source`` where they
    lie, so that a slice of a wider array is not made for their sake."""
    return _conv_silu_fwd(x, source, kernel, bias, how)[0]


def _conv_silu_fwd(x, source, kernel, bias, how):
    plan, offset = how
    out = _per_device(
        functools.partial(_conv_silu_fwd_call, plan=plan, offset=offset),
        source, kernel, bias, n_out=None)
    return out, (source, kernel, bias)


def _conv_silu_bwd(how, res, dy):
    plan, offset = how
    kernel, bias = res[1:]
    K = kernel.shape[0]
    dx, dtaps = _per_device(
        functools.partial(_conv_silu_bwd_call, plan=plan, offset=offset),
        *res, dy, n_out=2)
    dtaps = dtaps.sum(0)
    return (dx, None, dtaps[:K].astype(kernel.dtype),
            dtaps[K].astype(bias.dtype))


_conv_silu_kernels.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(x: jax.Array, kernel: jax.Array, bias: jax.Array, *,
              source: jax.Array | None = None, offset: int = 0) -> jax.Array:
    """``silu(causal_conv1d(x, kernel, bias))``: ``x`` [b, S, C] read once and
    the result written once, each way, where :func:`_stage_plan` admits the
    shape; the ``jax.numpy`` body where not. Float32 inside, ``x.dtype`` out;
    the backward recomputes the pre-activation (its residual is what it
    reads) and sums the taps' and the bias's gradients in float32.

    ``source``, where given, is an array [b, S, W] whose lanes ``offset :
    offset + C`` are ``x`` (the mixer's ``zxbcdt``): where ``offset`` falls on
    a column block's edge the kernels read ``x`` out of it, and the slice
    that ``x`` is is never made."""
    b, S, C = x.shape
    plan = _stage_plan("conv_silu", S, C, 1, x.dtype, kernel.shape[0])
    _say_stage_plan("conv_silu", S, C, 1, plan)
    if plan is None:
        return jax.nn.silu(causal_conv1d(x, kernel, bias))
    if (source is None or source.dtype != x.dtype or offset % plan[1]
            or source.shape[:2] != x.shape[:2]):
        source, offset = x, 0
    return _conv_silu_kernels(x, source, kernel, bias, (plan, offset))


def group_rms_norm(x: jax.Array, scale: jax.Array, groups: int,
                   epsilon: float, dtype) -> jax.Array:
    """``RMSNorm`` in float32 over each of ``groups`` equal runs of the last
    axis by itself, times ``scale`` (one vector over them all), as ``dtype``."""
    x32 = x.astype(F32).reshape(*x.shape[:-1], groups, -1)
    norm = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + epsilon)
    return (norm.reshape(x.shape) * scale.astype(F32)).astype(dtype)


def _gated_groups(y_ref, z_ref, width):
    """``(lanes, y, z)`` in float32 for each group, a run of ``width`` lanes,
    of a program's tile."""
    for lo in range(0, y_ref.shape[2], width):
        lanes = slice(lo, lo + width)
        yield lanes, y_ref[0, :, lanes], z_ref[0, :, lanes].astype(F32)


def _gate_norm_fwd_kernel(y_ref, z_ref, scale_ref, o_ref, *, width, epsilon,
                          norm_first):
    """``y_ref`` [1, rows, cols] float32, ``z_ref`` the same in the compute
    dtype, ``scale_ref`` [1, cols] float32; a group is ``width`` lanes.
    ``norm_first``: the statistics are ``y``'s and the gate comes after
    (:func:`norm_gate`), else the gated value's (:func:`gate_norm`)."""
    for lanes, y, z in _gated_groups(y_ref, z_ref, width):
        gate = _silu_and_slope(z)[0]
        g = y if norm_first else y * gate
        r = jax.lax.rsqrt(jnp.mean(g * g, axis=1, keepdims=True) + epsilon)
        n = g * r * scale_ref[:, lanes]
        o_ref[0, :, lanes] = (n * gate if norm_first else n).astype(
            o_ref.dtype)


def _gate_norm_bwd_kernel(y_ref, z_ref, scale_ref, do_ref,
                          dy_ref, dz_ref, dscale_ref, *, width, epsilon, S,
                          norm_first):
    """Recomputes the gate and the statistics. With ``n`` the normed value
    and ``dn = do * scale``: ``dg = r (dn - n mean(dn n))``, ``dy = dg
    silu(z)`` in float32, ``dz = dg y silu'(z)``; ``dscale_ref`` [1, 1, cols]
    float32 gathers ``do * n`` over a column block's tiles. ``norm_first``:
    ``n = y r`` and the gate multiplies what leaves the norm, so ``do`` meets
    the norm as ``do silu(z)``: ``dy = dg`` and ``dz = do n scale
    silu'(z)``."""
    tile = pl.program_id(2)
    rows = y_ref.shape[1]

    @pl.when(tile == 0)
    def _():
        dscale_ref[0] = jnp.zeros(dscale_ref.shape[1:], F32)

    valid = _valid_rows(tile * rows, rows, S) if S % rows else None
    for lanes, y, z in _gated_groups(y_ref, z_ref, width):
        do = do_ref[0, :, lanes].astype(F32)
        if valid is not None:   # rows past the sequence hold anything
            y, z, do = (jnp.where(valid, v, 0.0) for v in (y, z, do))
        gate, slope = _silu_and_slope(z)
        g = y if norm_first else y * gate
        r = jax.lax.rsqrt(jnp.mean(g * g, axis=1, keepdims=True) + epsilon)
        n = g * r
        met = do * gate if norm_first else do   # the cotangent of n * scale
        dscale_ref[0, :, lanes] += jnp.sum(met * n, axis=0, keepdims=True)
        dn = met * scale_ref[:, lanes]
        dg = r * (dn - n * jnp.mean(dn * n, axis=1, keepdims=True))
        if norm_first:
            dy_ref[0, :, lanes] = dg
            dz_ref[0, :, lanes] = (do * n * scale_ref[:, lanes]
                                   * slope).astype(dz_ref.dtype)
        else:
            dy_ref[0, :, lanes] = dg * gate
            dz_ref[0, :, lanes] = (dg * y * slope).astype(dz_ref.dtype)


def _gate_norm_specs(plan, offset):
    """A [rows, cols] tile of an array of the channels alone, the scale's
    lanes of a column block, and the tile of ``z`` in an array whose lanes
    from ``offset`` on are ``z`` (the conv's way of reading its source)."""
    gate, tile = _conv_tiles(plan, offset, lambda r: r)
    return (tile, pl.BlockSpec((1, plan[1]), lambda i, j, r: (0, j)),
            gate if offset else tile)


@functools.partial(jax.jit, static_argnames=("stage", "plan", "offset",
                                             "groups", "epsilon", "dtype"))
def _gate_norm_fwd_call(y, z, scale, *, stage, plan, offset, groups, epsilon,
                        dtype):
    """``z``: the gate's values [b, S, C], or a wider array that holds them
    from lane ``offset`` on (read there by block: nothing to fuse)."""
    rows, cols = plan
    b, S, C = y.shape
    tile, lane, gate = _gate_norm_specs(plan, offset)
    return pl.pallas_call(
        functools.partial(_gate_norm_fwd_kernel, width=C // groups,
                          epsilon=epsilon, norm_first=stage == "norm_gate"),
        name=stage + "_fwd",
        grid=(b, C // cols, pl.cdiv(S, rows)),
        in_specs=[tile, gate, lane],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(y.shape, dtype),
        compiler_params=_tiles_in_order(False, z.shape == y.shape, False),
        interpret=not backend.on_tpu(),
    )(y, z, scale.astype(F32)[None])


@functools.partial(jax.jit, static_argnames=("stage", "plan", "offset",
                                             "groups", "epsilon"))
def _gate_norm_bwd_call(y, z, scale, do, *, stage, plan, offset, groups,
                        epsilon):
    """``dy`` float32, ``dz`` [b, S, C] in ``z.dtype`` and, a sequence, the
    scale's gradient [1, C] float32."""
    rows, cols = plan
    b, S, C = y.shape
    tile, lane, gate = _gate_norm_specs(plan, offset)
    return pl.pallas_call(
        functools.partial(_gate_norm_bwd_kernel, width=C // groups,
                          epsilon=epsilon, S=S,
                          norm_first=stage == "norm_gate"),
        name=stage + "_bwd",
        grid=(b, C // cols, pl.cdiv(S, rows)),
        in_specs=[tile, gate, lane, tile],
        out_specs=(tile, tile, pl.BlockSpec((1, 1, cols),
                                            lambda i, j, r: (i, 0, j))),
        out_shape=(jax.ShapeDtypeStruct(y.shape, F32),
                   jax.ShapeDtypeStruct(y.shape, z.dtype),
                   jax.ShapeDtypeStruct((b, 1, C), F32)),
        compiler_params=_tiles_in_order(False, z.shape == y.shape, False,
                                        False),
        interpret=not backend.on_tpu(),
    )(y, z, scale.astype(F32)[None], do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gate_norm_kernels(y, z, source, scale, how):
    """``how``: (stage, plan, offset, groups, epsilon, dtype); the stage's
    name is the order. ``source`` is None, or an array that holds ``z`` from
    lane ``offset`` on: the kernels then read ``z`` out of it where it lies,
    and ``z`` itself is there for its cotangent alone, as the conv's ``x``
    is."""
    return _gate_norm_fwd(y, z, source, scale, how)[0]


def _gate_norm_fwd(y, z, source, scale, how):
    stage, plan, offset, groups, epsilon, dtype = how
    res = (y, z if source is None else source, scale)
    out = _per_device(
        functools.partial(_gate_norm_fwd_call, stage=stage, plan=plan,
                          offset=offset, groups=groups, epsilon=epsilon,
                          dtype=dtype), *res, n_out=None)
    return out, res


def _gate_norm_bwd(how, res, do):
    stage, plan, offset, groups, epsilon, _ = how
    dy, dz, dscale = _per_device(
        functools.partial(_gate_norm_bwd_call, stage=stage, plan=plan,
                          offset=offset, groups=groups, epsilon=epsilon),
        *res, do, n_out=3)
    return dy, dz, None, dscale.sum((0, 1)).astype(res[2].dtype)


_gate_norm_kernels.defvjp(_gate_norm_fwd, _gate_norm_bwd)


def _gated_norm(stage, y, z, scale, groups, epsilon, dtype, source=None,
                offset=0):
    """The gate and the group norm in the order that ``stage`` names
    (``gate_norm`` or ``norm_gate``) through the kernel pair, or None where
    :func:`_stage_plan` refuses the shape and the caller's ``jax.numpy`` body
    runs. ``scale`` [C]."""
    b, S, C = y.shape
    plan = _stage_plan(stage, S, C, groups, z.dtype)
    if y.dtype != F32 or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        plan = None
    _say_stage_plan(stage, S, C, groups, plan)
    if plan is None:
        return None
    if source is not None and (source.dtype != z.dtype or offset % plan[1]
                               or source.shape[:2] != z.shape[:2]):
        source = None
    return _gate_norm_kernels(
        y, z, source, scale,
        (stage, plan, 0 if source is None else offset, groups,
         float(epsilon), jnp.dtype(dtype)))


def gate_norm(y: jax.Array, z: jax.Array, scale: jax.Array, *, groups: int,
              epsilon: float, dtype) -> jax.Array:
    """``group_rms_norm(y * silu(z))``: ``y`` [b, S, C] float32 (the scan's,
    as accumulated), ``z`` [b, S, C] in the compute dtype, ``scale`` [C];
    ``dtype`` out. Float32 from ``y`` through the gate into the norm and back
    (``dy`` comes back float32, the scale's gradient is summed in float32);
    one pass each way where :func:`_stage_plan` admits the shape, the
    ``jax.numpy`` body where not."""
    out = _gated_norm("gate_norm", y, z, scale, groups, epsilon, dtype)
    if out is None:
        out = group_rms_norm(y * jax.nn.silu(z.astype(F32)), scale, groups,
                             epsilon, dtype)
    return out


def norm_gate(y: jax.Array, z: jax.Array, scale: jax.Array, *, groups: int,
              epsilon: float, dtype, source: jax.Array | None = None,
              offset: int = 0) -> jax.Array:
    """``group_rms_norm(y) * silu(z)``: the other order of the two steps that
    :func:`gate_norm` takes (Mamba-2 gates and then norms; the ``qwen3_next``
    models' delta-rule mixer norms a head and gates after). ``y`` [b, S, C]
    float32 as accumulated, ``z`` [b, S, C] in the compute dtype, ``scale``
    one vector of ``C / groups`` for every group; float32 throughout with the
    exact sigmoid, ``dtype`` out. :func:`gate_norm`'s kernel pair in this
    order (``norm_gate_fwd`` / ``norm_gate_bwd``: ``dy`` float32, ``dz`` in
    ``z``'s dtype, the scale's gradient summed in float32 over rows and
    groups) where :func:`_stage_plan` admits the shape, the ``jax.numpy`` body
    where not.

    ``source``, where given, is an array [b, S, W] whose lanes ``offset :
    offset + C`` are ``z`` (the mixer's ``qkvz``): where ``offset`` falls on a
    column block's edge the kernels read ``z`` out of it, and the slice that
    ``z`` is is never made, as for :func:`conv_silu`."""
    wide = jnp.tile(scale, groups)
    out = _gated_norm("norm_gate", y, z, wide, groups, epsilon, dtype, source,
                      offset)
    if out is None:
        normed = group_rms_norm(y, wide, groups, epsilon, F32)
        out = (normed * jax.nn.silu(z.astype(F32))).astype(dtype)
    return out

"""State-space duality (SSD) scan of Mamba-2, chunked, and the causal
depthwise convolution that feeds it (Dao & Gu 2024, arXiv:2405.21060).

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


def _say_plan(H, P, N, groups, Q, plan):
    """One ``ssd_plan`` record a traced call of :func:`ssd`, under the span
    that caused the trace: whether the kernels engaged and at how many heads
    a program is static, so its counter is a record."""
    from pytorch_distributed_training_example_tpu.utils import telemetry
    telemetry.recorder().compile_event("ssd_plan", 0.0, {
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
    """``fn`` over arrays whose first axis is the batch (``D`` [H] apart),
    per device of the ambient mesh with the batch sharded: GSPMD cannot
    partition a Mosaic call (``core/mesh.manual_call``). A batch the
    data-parallel axes do not divide is replicated."""
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
                      if a.ndim > 1 else PartitionSpec())
    return mesh_lib.manual_call(
        fn, *args, mesh=mesh, in_specs=tuple(spec(a) for a in args),
        out_specs=tuple(PartitionSpec(axes) for _ in range(n_out)))


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

"""Pallas TPU flash attention (blockwise online softmax in VMEM).

The hot attention kernel for long sequences: never materializes the
[Sq, Skv] score matrix in HBM. Grid is (batch, heads, q-blocks, kv-blocks)
with the kv dimension innermost — TPU grids execute sequentially over the
trailing dimension, so the online-softmax state (running max ``m``, denom
``l``, unnormalized accumulator) lives in VMEM scratch across kv steps and
the output block is written once on the last step.

Under the causal mask the grid holds only the block pairs the mask leaves
(a walked table of steps: a block above the diagonal is neither a step nor
a copy), a block wholly below the diagonal takes a body without a mask, and
a block the diagonal crosses is computed in sub-tiles (``online_schedule``).
A sliding window (row i sees the keys [i - window + 1, i]) is that mask's
second edge and nothing else: the same three kernels under a schedule that
also leaves out the blocks wholly behind the window and computes the blocks
its far edge crosses in sub-tiles; their calls carry the names
``flash_fwd_window``, ``flash_bwd_window_dq``, ``flash_bwd_window_dkv``.

Backward is a Pallas dq/dkv kernel pair under ``custom_vjp`` (see
``_dq_kernel``/``_dkv_kernel`` below): recompute-based, using the
saved forward LSE, under the same schedule. Layout: [B, S, H, D] in;
the online, one-shot and streaming kernels transpose to [B, H, S, D]
internally, the causal pair blocks the [B, S, H*D] rows as they lie (see
"Causal kernels" below).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_example_tpu.ops import attention as attn_lib
from pytorch_distributed_training_example_tpu.ops import backend

NEG_INF = -1e30
# Online-kernel defaults (the one-shot kernels self-plan their tiling):
# 1024x1024 measured best e2e of the {256,512,1024}^2 grid — GPT-2 S=1024
# forced-online MFU 0.5475 vs 0.4888 at the old 512x512 (LM_SWEEP.json).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_KV = 1024
LSE_LANES = 8  # lse stored [B,H,S,8]: minor dims satisfy Mosaic tiling

# Measured per-shape block overrides for the ONLINE kernels, keyed
# (bwd, S, D) -> (block_q, block_kv), or (bwd, S, D, window) for a call under
# a sliding window; D is the head's width, or (query/key width, value width)
# where the two differ. Consulted only when the caller left
# block_q/block_kv at the module defaults (an explicit caller choice always
# wins), so it is a tuning table, not an API change. Entries are added ONLY
# from on-chip sweeps (``benchmarks/flash_micro.py --block-sweep`` emits the
# grid); the r3 LM sweep that picked the 1024x1024 default ran at D=64 —
# D=128 long-S shapes get their own rows here as they are measured.
ONLINE_BLOCK_TABLE: dict[tuple[int, ...], tuple[int, int]] = {
    # D=128, S=4096 fwd: default 1024x1024 measured 1.371 ms = 0.509 of MXU
    # peak (r4, a machine that is gone; records in git at 6739a2e) — the
    # default IS the tuned choice.
    (False, 4096, 128): (1024, 1024),
    # D=256, S=8192 (GLM-4.7-Flash's expanded latent attention, B1 H20, bf16),
    # read again under the causal schedule (PERF.md section 6, PR 42, the
    # sweep's rows; PR 41's, of the whole rectangle, in brackets): forward
    # 4.70 ms [5.98] at 1024x1024 against 5.56 [6.67] at (1024, 512), 5.02
    # [7.04] at (512, 1024), 6.49 [8.32] at 512x512: the default stays.
    # Backward (the row's fwd+bwd less its fwd) 14.97 ms [18.98] at
    # (512, 1024) against 15.24 [19.96] at (1024, 512), the rule's choice, and
    # 15.83 [20.79] at 512x512; (1024, 1024) does not compile. With the empty
    # steps gone the two are 1.8% apart where they were 5%; the row stays.
    (True, 8192, 256): (512, 1024),
}


def _widths(d):
    """``(query/key width, value width)`` of ``d``: one width for both, or
    the pair."""
    return (d, d) if isinstance(d, int) else tuple(d)


def _online_held(bwd: bool, block_q: int, block_kv: int, d, itemsize: int):
    """Bytes of ``[block, D]`` rows an online kernel holds in VMEM at a grid
    step: its operand and output blocks, which the pipeline double-buffers,
    and its float32 accumulators. ``flash_fwd_online`` reads q, k, v, writes
    o and accumulates o; ``flash_bwd_dq`` reads q, dO, k, v, writes dq and
    accumulates dq; ``flash_bwd_dkv`` reads the same, writes dk and dv and
    accumulates both (the backward's count is the fuller of its two). q, k,
    dq and dk are of the query/key width, v, o, dO and dv of the value width
    (``d``: one width, or the pair). The ``[block_q, block_kv]`` score tiles
    beside them do not grow with D or the dtype and are not counted."""
    qk, v = _widths(d)
    if not bwd:
        return (itemsize * 2 * (block_q + block_kv) * (qk + v)
                + 4 * block_q * v)
    dq = (itemsize * 2 * (block_q * (2 * qk + v) + block_kv * (qk + v))
          + 4 * block_q * qk)
    dkv = (itemsize * 2 * (block_q + 2 * block_kv) * (qk + v)
           + 4 * block_kv * (qk + v))
    return max(dq, dkv)


#: The most the v5e compiler has taken of such rows beside a default score
#: tile under its 16 MiB of scoped VMEM: the backward's at the defaults and
#: D = 128 in float32 (7 MiB; tests/test_chip_compile.py compiles it). At
#: D = 256 the defaults hold 8 MiB in the bf16 backward, which the compiler
#: counts 36 KB over at B1 H20 S8192, and 9 MiB in the float32 forward, which
#: it refuses too (PERF.md section 6, PR 41).
ONLINE_HELD_MAX = _online_held(True, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_KV, 128, 4)


def _online_blocks(bwd: bool, s: int, d, block_q: int, block_kv: int,
                   itemsize: int = 2, window: int | None = None):
    """The online kernels' block sizes: the caller's where it chose them, a
    row of ONLINE_BLOCK_TABLE where the shape (with its window, where the
    call has one) was measured, else the defaults, halved (the larger of the
    two, the kv block first) until the rows the kernel holds fit
    ``ONLINE_HELD_MAX``. At D <= 128 that halves nothing."""
    if (block_q, block_kv) != (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_KV):
        return block_q, block_kv
    shape = (bwd, s, d) if window is None else (bwd, s, d, window)
    if shape in ONLINE_BLOCK_TABLE:
        return ONLINE_BLOCK_TABLE[shape]
    while min(block_q, block_kv) > 128 and _online_held(
            bwd, block_q, block_kv, d, itemsize) > ONLINE_HELD_MAX:
        if block_kv >= block_q:
            block_kv //= 2
        else:
            block_q //= 2
    return block_q, block_kv


def _fit_block(s: int, requested: int) -> int:
    """Largest divisor of ``s`` that is <= ``requested``.

    DEFAULT_BLOCK_Q/KV are preferences, not contracts: ``_flash_eligible``
    admits any S % 512 == 0, so S=2560 under a 1024 default must tile at
    640 — flooring the grid instead (Sq // block) would silently drop the
    trailing rows (dq unwritten, dk/dv missing contributions). Every
    eligible S (% 512 == 0) lands on a block >= 512 (640, 704, 768...).
    No alignment guarantee beyond divisibility is claimed — block_q/kv sit
    on the second-minor (sublane) dim, where Mosaic handles any size and
    512-divisible S gives at least 8-alignment in the worst case; odd
    explicit S still gets an exact tiling (worst case 1).
    """
    b = min(requested, s)
    while s % b:
        b -= 1
    return b


def _mxu(x):
    """MXU operand dtype: bf16/fp32 as stored; fp16 upcast to fp32.

    fp16's 5-bit exponent overflows on scale-multiplied gradients (the
    GradScaler path multiplies do by up to 2^15+), and softmax probabilities
    below 2^-24 flush to zero — so the fp16 AMP policy keeps kernel math in
    fp32 while bf16 training uses native-dtype operands for MXU rate.
    """
    return x.astype(jnp.float32) if x.dtype == jnp.float16 else x


# ---------------------------------------------------------------------------
# The online kernels' schedule: which (q block, kv block) pairs a call visits,
# fetches and masks. One pure function of (causal, Sq, Skv, block_q,
# block_kv, window), read by the three kernels, by the recorder and by the
# tests.
#
# Row r sees column c iff 0 <= r - c (< window, where the call has one), so a
# block pair at offset d = qi * block_q - kvi * block_kv is one of three
# things:
#   outside: above the diagonal (d <= -block_q) or wholly ``window`` or more
#     behind it (d >= window + block_kv - 1): nothing of it is visible. The
#     grid does not hold it: its steps are the visited pairs alone, and
#     (qi, kvi) come from a scalar-prefetched table, so it costs no step and
#     no DMA.
#   inside (block_kv - 1 <= d <= window - block_q): every pair is visible;
#     the body has no mask.
#   crossed by the diagonal or by the window's far edge (by both, where the
#     window is narrower than a block): computed in stripes of ``sub`` rows
#     (columns, in the dk/dv pass) against the keys (rows) the stripe can
#     see: static slices, one unrolled body for each offset d a crossed block
#     can have. Only the stripe's ``sub`` x ``sub`` squares an edge passes
#     through are masked (the diagonal's keeps row >= column, the far edge's,
#     its mirror, row < column), and a square wholly outside is not computed.
# What is left out adds exact zeros in the whole-block form: a masked
# probability is exp(NEG_INF - m) = 0.0, and the stripes split the axis the
# kernel writes along and shorten the one it contracts over, so no float32
# sum changes its order: on the chip all five results are the rectangle's bit
# for bit (benchmarks/flash_micro.py --schedule-parts compares them;
# tests/test_attention.py holds o, lse and dq so in interpret mode, and dk, dv
# to a rounding where XLA's CPU dot adds a shorter contraction in another order).
# ---------------------------------------------------------------------------

ONLINE_KERNELS = ("flash_fwd_online", "flash_bwd_dq", "flash_bwd_dkv")
#: The names the same three kernels' calls carry under a window.
WINDOW_KERNELS = ("flash_fwd_window", "flash_bwd_window_dq",
                  "flash_bwd_window_dkv")
#: Offsets a crossed block may have for it to be computed in sub-tiles (one
#: at equal blocks, two at 512 x 1024 or at equal blocks under a window that
#: is whole blocks), and stripes a block may be cut into: each offset is an
#: unrolled body to compile, of a tile a stripe.
SUB_OFFSETS_MAX = 2
SUB_STRIPES_MAX = 4


@dataclasses.dataclass(frozen=True)
class OnlineSchedule:
    """One online kernel's schedule. ``steps``: the (qi, kvi) pairs of the
    innermost grid dimension(s) in order. ``walk``: they are a table the grid
    walks (else the whole rectangle). ``split``: blocks inside take the body
    without a mask. ``sub``: the sub-tiles' side in crossed blocks (0: such a
    block is computed whole under an elementwise mask). ``window``: row r
    sees the ``window`` keys up to its own (None: all of them)."""

    kernel: str
    causal: bool
    sq: int
    skv: int
    block_q: int
    block_kv: int
    walk: bool
    split: bool
    sub: int
    steps: tuple
    window: int | None = None

    @property
    def by_kv(self):
        """The dk/dv pass: kv blocks outer, a row of steps walks q blocks."""
        return self.kernel == "flash_bwd_dkv"

    @property
    def name(self):
        """The name the kernel's ``pallas_call`` carries."""
        if self.window is None:
            return self.kernel
        return WINDOW_KERNELS[ONLINE_KERNELS.index(self.kernel)]

    def offset(self, qi, kvi):
        return qi * self.block_q - kvi * self.block_kv

    def kind(self, d):
        """``(inside, crossed)`` of a block at offset ``d``, static or traced:
        every pair of it is visible; an edge of the mask passes through it."""
        inside = d >= self.block_kv - 1
        if self.window is not None:
            inside &= d <= self.window - self.block_q
        crossed = (d > -self.block_q) & (
            jnp.logical_not(inside) if isinstance(inside, jax.Array)
            else not inside)
        if self.window is not None:
            crossed &= d < self.window + self.block_kv - 1
        return inside, crossed

    @property
    def offsets(self):
        """The offsets of the blocks an edge crosses, ascending."""
        return tuple(sorted({self.offset(*s) for s in self.steps
                             if self.kind(self.offset(*s))[1]}))

    def tiles(self, d):
        """``(tiles, skipped)`` of a crossed block at offset ``d``: the
        computed ``(rows, cols, squares)`` in the block's own coordinates,
        ``squares`` the corners in the tile of its ``sub``-squares on the
        diagonal and on the window's far edge, ``(near, far)``, either None
        (None for both: every pair is visible; "whole": the tile is the
        block, masked by position), and the count of sub-tiles left out."""
        bq, bkv, sub = self.block_q, self.block_kv, self.sub
        if not sub:
            return [((0, bq), (0, bkv), "whole")], 0
        vary = int(self.by_kv)      # the stripes are rows (0) or columns (1)
        size = (bq, bkv)[1 - vary]  # of the block along the other axis
        # how far behind its own diagonal a stripe sees (without a window:
        # farther than a block reaches)
        reach = bq + bkv if self.window is None else self.window
        tiles, skipped = [], 0
        for lo in range(0, (bq, bkv)[vary], sub):
            # where, along the other axis, the stripe's squares on the two
            # edges begin: it sees from the one to the other
            near = lo - d if vary else lo + d
            far = near + reach if vary else near - reach
            first = min(max(min(near, far), 0), size)
            end = min(max(max(near, far) + sub, first), size)
            skipped += (size - (end - first)) // sub
            if end == first:
                continue
            squares = tuple(
                ((at - first, 0) if vary else (0, at - first))
                if first <= at < end else None for at in (near, far))
            tile = (((first, end), (lo, lo + sub)) if vary
                    else ((lo, lo + sub), (first, end)))
            last = tiles[-1] if tiles else None
            if (last and not any(squares) and last[2] is None
                    and last[1 - vary] == tile[1 - vary]):
                # stripes that see the same keys (rows) whole are one tile
                span = (last[vary][0], tile[vary][1])
                tile = (tile[0], span) if vary else (span, tile[1])
                tiles.pop()
            tiles.append((*tile, squares if any(squares) else None))
        return tiles, skipped

    def counts(self):
        """What the schedule does, a head: the blocks of the whole rectangle,
        grid steps, blocks computed, blocks under a mask, sub-tiles left out
        of crossed blocks, copies of the streamed operand (a step that names
        the previous step's block copies nothing), and the (row, column)
        pairs computed beside those the mask leaves."""
        bq, bkv = self.block_q, self.block_kv
        out = dict(rectangle=(self.sq // bq) * (self.skv // bkv),
                   steps=len(self.steps), computed=0, masked=0,
                   skipped_subtiles=0, fetched=0, pairs_computed=0)
        before = None
        for qi, kvi in self.steps:
            streamed = qi if self.by_kv else kvi
            out["fetched"] += streamed != before
            before = streamed
            d = self.offset(qi, kvi)
            inside, crossed = self.kind(d)
            if not self.causal or inside:
                out["computed"] += 1
                out["masked"] += self.causal and not self.split
                out["pairs_computed"] += bq * bkv
            elif crossed:
                tiles, skipped = self.tiles(d)
                out["computed"] += 1
                out["masked"] += 1
                out["skipped_subtiles"] += skipped
                out["pairs_computed"] += sum(
                    (r[1] - r[0]) * (c[1] - c[0]) for r, c, _ in tiles)
        tri = min(self.sq, self.skv)    # rows that see fewer keys than all
        needed = (tri * (tri + 1) // 2 + (self.sq - tri) * self.skv
                  if self.causal else self.sq * self.skv)
        if self.window is not None:     # sq == skv: a row sees min(r + 1, W)
            w = self.window
            needed = w * self.sq - w * (w - 1) // 2
        out["pairs_needed"] = needed
        return {k: int(v) for k, v in out.items()}

    def record(self, d):
        """The ``flash_schedule`` record's value at head width ``d`` (where
        the value width differs, beside it as ``Dv``)."""
        qk, v = _widths(d)
        return dict(kernel=self.name, Sq=self.sq, Skv=self.skv, D=qk,
                    **({"Dv": v} if v != qk else {}),
                    block_q=self.block_q, block_kv=self.block_kv,
                    causal=self.causal, window=self.window, sub=self.sub,
                    **self.counts())


def online_schedule(kernel, causal, sq, skv, block_q, block_kv, *, window=None,
                    walk=True, split=True, sub=None):
    """The schedule of ``kernel`` (one of ONLINE_KERNELS) over ``sq`` x ``skv``
    in blocks that tile them. Not causal: the whole rectangle, nothing masked.
    ``window`` (causal self-attention, shorter than the sequence): the mask's
    second edge. ``walk``, ``split`` and ``sub`` switch the schedule's three
    parts off one at a time (benchmarks/flash_micro.py --schedule-parts times
    them so; the program leaves them alone). Sub-tiles engage where ``sub``
    leaves a square to skip, both edges pass through corners of ``sub``-squares
    and a crossed block has at most SUB_OFFSETS_MAX offsets."""
    assert kernel in ONLINE_KERNELS, kernel
    assert window is None or (causal and sq == skv and 0 < window < skv), window
    n_q, n_kv = sq // block_q, skv // block_kv
    by_kv = kernel == "flash_bwd_dkv"
    if not causal:
        walk, split, sub = False, False, 0
    steps = ([(qi, kvi) for kvi in range(n_kv) for qi in range(n_q)] if by_kv
             else [(qi, kvi) for qi in range(n_q) for kvi in range(n_kv)])
    if walk:
        def seen(qi, kvi):      # not above the diagonal, not behind the window
            d = qi * block_q - kvi * block_kv
            return d > -block_q and (window is None
                                     or d < window + block_kv - 1)

        rows = [[s for s in steps if s[by_kv] == row]
                for row in range(n_kv if by_kv else n_q)]
        # a row with nothing to see (keys past the last query row) keeps its
        # last step: the init and finish there write the zeros
        steps = [s for row in rows
                 for s in [s for s in row if seen(*s)] or row[-1:]]
    if sub is None:
        # half the larger block in whole 128-lane tiles, or the widest such
        # that divides both blocks, if SUB_STRIPES_MAX of them span a block
        # (640 = 5 x 128, S = 2560's fitted block, has none). Narrower
        # sub-tiles skip more and read no faster (PERF.md section 6, PR 42):
        # each is one more unrolled tile to trace, lower and compile.
        cap = max(block_q, block_kv) // 2
        sub = next((s for s in range(cap - cap % 128, 0, -128)
                    if block_q % s == 0 == block_kv % s
                    and max(block_q, block_kv) <= SUB_STRIPES_MAX * s), 0)
    plan = OnlineSchedule(kernel, causal, sq, skv, block_q, block_kv, walk,
                          split, 0, tuple(steps), window)
    if (sub and sub < max(block_q, block_kv)
            and block_q % sub == 0 == block_kv % sub
            and len(plan.offsets) <= SUB_OFFSETS_MAX
            and all(d % sub == 0 for d in plan.offsets)
            and (window is None or window % sub == 0)):
        plan = dataclasses.replace(plan, sub=sub)
    return plan


def _say_schedules(kernels, causal, sq, skv, d, block_q, block_kv,
                   window=None):
    """One ``flash_schedule`` record a kernel of a traced call, under the span
    that caused the trace: the schedule is static, so its counter is a
    record."""
    from pytorch_distributed_training_example_tpu.utils import telemetry
    for kernel in kernels:
        plan = online_schedule(kernel, causal, sq, skv,
                               _fit_block(sq, block_q), _fit_block(skv, block_kv),
                               window=window)
        telemetry.recorder().compile_event("flash_schedule", 0.0,
                                           plan.record(d))


def _online_where(refs, plan):
    """``(qi, kvi, first, last, refs)`` of a grid step: the block pair, two
    thunks that say whether the step opens or closes a row of its output
    block, and the kernel's own refs (less the walked table's)."""
    if plan.walk:
        qi_ref, kvi_ref, *refs = refs
        t, n = pl.program_id(2), pl.num_programs(2)
        row_ref = kvi_ref if plan.by_kv else qi_ref
        first = lambda: (t == 0) | (row_ref[jnp.maximum(t - 1, 0)]
                                    != row_ref[t])
        last = lambda: (t == n - 1) | (row_ref[jnp.minimum(t + 1, n - 1)]
                                       != row_ref[t])
        return qi_ref[t], kvi_ref[t], first, last, refs
    row, inner = pl.program_id(2), pl.program_id(3)
    n_inner = pl.num_programs(3)
    qi, kvi = (inner, row) if plan.by_kv else (row, inner)
    return qi, kvi, lambda: inner == 0, lambda: inner == n_inner - 1, refs


def _online_bodies(plan, qi, kvi, tile):
    """Run ``tile(rows, cols, squares)`` for what the schedule computes of the
    block (qi, kvi): under ``pl.when`` by the block's kind, a crossed block's
    sub-tiles unrolled in the body of its offset."""
    whole = lambda squares: lambda: tile(None, None, squares)
    if not plan.causal:
        return whole(None)()
    d = plan.offset(qi, kvi)
    inside, crossed = plan.kind(d)
    pl.when(inside)(whole(None if plan.split else "whole"))
    if not plan.sub:
        return pl.when(crossed)(whole("whole"))
    for at in plan.offsets:
        @pl.when(d == at)
        def _stripes(at=at):
            for rows, cols, squares in plan.tiles(at)[0]:
                tile(rows, cols, squares)


def _rows(ref, span):
    """The block a ref holds, or the rows ``span`` of it."""
    return ref[0, 0] if span is None else ref[0, 0, span[0]:span[1], :]


def _at(span):
    return slice(None) if span is None else slice(*span)


def _mask_tile(s, squares, plan, qi, kvi):
    """The mask of a score tile: nothing, the whole block by position, or the
    ``sub``-squares of the tile that lie on the diagonal and on the window's
    far edge."""
    if squares is None:
        return s
    if squares == "whole":
        q_pos = qi * plan.block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = kvi * plan.block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        visible = q_pos >= k_pos
        if plan.window is not None:
            visible &= q_pos - k_pos < plan.window
        return jnp.where(visible, s, NEG_INF)
    sub = plan.sub
    row = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    # the tile is a stripe along ``axis``: its masked squares in their order
    # (the far edge's keeps the diagonal's mirror), and between them the
    # tile as it is
    axis = int(s.shape[0] == sub)
    masked = sorted(
        ((corner[axis], jnp.where(
            row >= col if edge == 0 else row < col,
            s[corner[0]:corner[0] + sub, corner[1]:corner[1] + sub], NEG_INF))
         for edge, corner in enumerate(squares) if corner),
        key=lambda square: square[0])
    cut = lambda lo, hi: s[:, lo:hi] if axis else s[lo:hi]
    parts, at = [], 0
    for lo, square in masked:
        if lo > at:
            parts.append(cut(at, lo))
        parts.append(square)
        at = lo + sub
    if at < s.shape[axis]:
        parts.append(cut(at, s.shape[axis]))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _fwd_kernel(*refs, sm_scale: float, plan: OnlineSchedule):
    qi, kvi, first, last, refs = _online_where(refs, plan)
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs

    @pl.when(first())
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile(rows, cols, squares):
        # MXU-native operands: dots take q/k/v in their stored dtype (bf16 in
        # training) with fp32 accumulation via preferred_element_type — the
        # FlashAttention-2 scheme. Upcasting operands to fp32 here measured
        # ~20 TF/s on v5e (fp32 MXU rate); bf16 operands run ~2-3x faster.
        # All softmax state (m, l, acc) stays fp32.
        q = _mxu(_rows(q_ref, rows))                  # [bq, D]
        k = _mxu(_rows(k_ref, cols))                  # [bkv, D]
        v = _mxu(_rows(v_ref, cols))                  # [bkv, D]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bkv]
        logits = _mask_tile(logits, squares, plan, qi, kvi)

        at = _at(rows)
        m_prev = m_ref[at, :1]                        # [bq, 1] (lane-bcast)
        block_max = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        p = jnp.exp(logits - m_new)                   # [bq, bkv]
        correction = jnp.exp(m_prev - m_new)          # [bq, 1]
        l_new = l_ref[at, :1] * correction + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[at] = acc_ref[at] * correction + pv
        m_ref[at] = jnp.broadcast_to(m_new, (q.shape[0], m_ref.shape[1]))
        l_ref[at] = jnp.broadcast_to(l_new, (q.shape[0], l_ref.shape[1]))

    _online_bodies(plan, qi, kvi, tile)

    @pl.when(last())
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        # lse rows broadcast over LSE_LANES (Mosaic tiling needs >= 2D tiles).
        lse_ref[0, 0] = (m_ref[:, :LSE_LANES]
                         + jnp.log(jnp.maximum(l_ref[:, :LSE_LANES], 1e-30)))


def _online_grid(plan, B, H, *, in_blocks, out_blocks, scratch_shapes):
    """``(keywords, table)`` of an online kernel's ``pallas_call`` under
    ``plan``: the grid, the block specs and the compiler's parameters, and
    the walked table's two operands to put first (none over the rectangle).
    ``in_blocks`` and ``out_blocks``: a ``(rows, width, "q" | "kv")`` an
    operand, blocked along the q or the kv blocks of a step."""
    if plan.walk:
        pick = {"q": lambda t, qi, kvi: qi[t], "kv": lambda t, qi, kvi: kvi[t]}
        grid = (B, H, len(plan.steps))
    elif plan.by_kv:
        pick = {"q": lambda j, i: i, "kv": lambda j, i: j}
        grid = (B, H, plan.skv // plan.block_kv, plan.sq // plan.block_q)
    else:
        pick = {"q": lambda i, j: i, "kv": lambda i, j: j}
        grid = (B, H, plan.sq // plan.block_q, plan.skv // plan.block_kv)

    def spec(block):
        rows, width, axis = block
        return pl.BlockSpec((1, 1, rows, width),
                            lambda b, h, *g: (b, h, pick[axis](*g), 0))

    specs = dict(
        in_specs=[spec(b) for b in in_blocks],
        out_specs=(spec(out_blocks) if isinstance(out_blocks[0], int)
                   else tuple(spec(b) for b in out_blocks)),
        scratch_shapes=scratch_shapes)
    semantics = pltpu.CompilerParams(dimension_semantics=(
        "parallel",) * (len(grid) - 1) + ("arbitrary",))
    if not plan.walk:
        return dict(grid=grid, compiler_params=semantics, **specs), ()
    table = tuple(jnp.asarray([s[axis] for s in plan.steps], jnp.int32)
                  for axis in (0, 1))
    return dict(grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid, **specs),
        compiler_params=semantics), table


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_kv", "window", "walk", "split", "sub",
    "scale"))
def _flash_fwd(q, k, v, *, causal: bool, block_q: int, block_kv: int,
               window=None, walk=True, split=True, sub=None, scale=None):
    """Returns (out [B,S,H,Dv], lse [B,H,S,LSE_LANES]) with K/V already
    GQA-expanded; q and k ``D`` wide, v and the result ``Dv`` (each operand's
    blocks are of its own width: nothing is padded to the other's).
    ``window``: the mask's second edge (``_window_of``'s). ``scale``: the
    scores' factor, ``1 / sqrt(D)`` where None. ``walk``, ``split``, ``sub``:
    ``online_schedule``'s switches (the micro-benchmark's).

    Under ``jit``, as the causal pair is: a model's layers, and its later
    traces, share one trace and one lowering of the unrolled bodies (traced
    a layer, GLM-4.7-Flash's six attentions added 5 s to its first step)."""
    B, Sq, H, D = q.shape
    Skv, Dv = k.shape[1], v.shape[3]
    # head-major layout for the kernel
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    block_q = _fit_block(Sq, block_q)
    block_kv = _fit_block(Skv, block_kv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, Skv, block_q, block_kv)
    plan = online_schedule("flash_fwd_online", causal, Sq, Skv, block_q,
                           block_kv, window=window, walk=walk, split=split,
                           sub=sub)

    call, table = _online_grid(
        plan, B, H,
        in_blocks=[(block_q, D, "q"), (block_kv, D, "kv"), (block_kv, Dv, "kv")],
        out_blocks=[(block_q, Dv, "q"), (block_q, LSE_LANES, "q")],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # m
            pltpu.VMEM((block_q, 128), jnp.float32),   # l
            pltpu.VMEM((block_q, Dv), jnp.float32),    # acc
        ])
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=scale or 1.0 / math.sqrt(D),
                          plan=plan),
        name=plan.name,
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, LSE_LANES), jnp.float32),
        ),
        **call,
    )(*table, qt, kt, vt)
    return jnp.transpose(out, (0, 2, 1, 3)), lse


# ---------------------------------------------------------------------------
# Backward kernels (FlashAttention-2 style): dq pass over kv blocks; dk/dv
# pass over q blocks. Residuals: q,k,v,o + the forward logsumexp rows. Both
# walk the schedule above, the dk/dv pass with its stripes along the keys.
# ---------------------------------------------------------------------------


def _online_probs(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, rows, cols,
                  squares, *, sm_scale, plan, qi, kvi):
    """``(p, ds, q, k, do)`` of one tile: the probabilities from the saved
    lse, and a thunk for dS in the operands' dtype (the dk/dv pass adds
    P^T dO to its accumulator before it forms dS)."""
    # Native-dtype matmul operands, fp32 accumulation (see _fwd_kernel).
    q = _mxu(_rows(q_ref, rows))
    k = _mxu(_rows(k_ref, cols))
    v = _mxu(_rows(v_ref, cols))
    do = _mxu(_rows(do_ref, rows))
    lse = lse_ref[0, 0, _at(rows), :1]           # [bq, 1]
    delta = delta_ref[0, 0, _at(rows), :1]       # [bq, 1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = _mask_tile(s, squares, plan, qi, kvi)
    p = jnp.exp(s - lse)                         # [bq, bkv]

    def ds():
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return (p * (dp - delta) * sm_scale).astype(k.dtype)

    return p, ds, q, k, do


def _dq_kernel(*refs, sm_scale, plan):
    qi, kvi, first, last, refs = _online_where(refs, plan)
    *operands, dq_ref, acc_ref = refs

    @pl.when(first())
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile(rows, cols, squares):
        _, ds, _, k, _ = _online_probs(*operands, rows, cols, squares,
                                       sm_scale=sm_scale, plan=plan, qi=qi,
                                       kvi=kvi)
        ds = ds()
        acc_ref[_at(rows)] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _online_bodies(plan, qi, kvi, tile)

    @pl.when(last())
    def _finish():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, sm_scale, plan):
    qi, kvi, first, last, refs = _online_where(refs, plan)
    *operands, dk_ref, dv_ref, dk_acc, dv_acc = refs

    @pl.when(first())
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile(rows, cols, squares):
        p, ds, q, _, do = _online_probs(*operands, rows, cols, squares,
                                        sm_scale=sm_scale, plan=plan, qi=qi,
                                        kvi=kvi)
        # dV += P^T dO
        dv_acc[_at(cols)] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = ds()
        # dK += dS^T Q
        dk_acc[_at(cols)] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _online_bodies(plan, qi, kvi, tile)

    @pl.when(last())
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _delta_rows(g, o):
    """delta_i = rowsum(dO * O) as [B,H,S,LSE_LANES] float32: a cheap
    elementwise+reduce that XLA fuses, broadcast over LSE_LANES to match the
    kernels' tile layout."""
    delta = jnp.einsum("bshd,bshd->bhs", g.astype(jnp.float32),
                       o.astype(jnp.float32))
    return jnp.broadcast_to(delta[..., None], (*delta.shape, LSE_LANES))


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_kv", "window", "walk", "split", "sub",
    "scale"))
def _flash_bwd(q, k, v, o, lse, g, *, causal, block_q, block_kv, window=None,
               walk=True, split=True, sub=None, scale=None):
    """q,k: [B,S,H,D], v,o,g: [B,S,H,Dv] (kv already GQA-expanded); lse:
    [B,H,Sq,LSE_LANES]. Under ``jit``, with the window, the scale and
    ``online_schedule``'s switches, as ``_flash_fwd``."""
    B, Sq, H, D = q.shape
    Skv, Dv = k.shape[1], v.shape[3]
    block_q = _fit_block(Sq, block_q)
    block_kv = _fit_block(Skv, block_kv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, Skv, block_q, block_kv)
    sm_scale = scale or 1.0 / math.sqrt(D)
    delta = _delta_rows(g, o)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    dot = jnp.transpose(g, (0, 2, 1, 3))
    qrows, krows = (block_q, D, "q"), (block_kv, D, "kv")
    vrows, orows = (block_kv, Dv, "kv"), (block_q, Dv, "q")
    lrows = (block_q, LSE_LANES, "q")
    in_blocks = [qrows, krows, vrows, orows, lrows, lrows]
    plans = [online_schedule(name, causal, Sq, Skv, block_q, block_kv,
                             window=window, walk=walk, split=split, sub=sub)
             for name in ONLINE_KERNELS[1:]]

    operands = (qt, kt, vt, dot, lse, delta)

    call, table = _online_grid(
        plans[0], B, H, in_blocks=in_blocks, out_blocks=qrows,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)])
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, plan=plans[0]),
        name=plans[0].name,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        **call,
    )(*table, *operands)

    # dk/dv pass: kv blocks outer (parallel), q blocks inner (accumulated).
    call, table = _online_grid(
        plans[1], B, H, in_blocks=in_blocks, out_blocks=(krows, vrows),
        scratch_shapes=[pltpu.VMEM((block_kv, D), jnp.float32),
                        pltpu.VMEM((block_kv, Dv), jnp.float32)])
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, plan=plans[1]),
        name=plans[1].name,
        out_shape=(jax.ShapeDtypeStruct((B, H, Skv, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Skv, Dv), v.dtype)),
        **call,
    )(*table, *operands)

    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return tr(dq), tr(dk), tr(dv)


# ---------------------------------------------------------------------------
# One-shot kernels: short/medium sequences (the LM bench shapes).
#
# The online-softmax kernels above are grid-step bound at small head_dim:
# measured on v5e at B=16,H=12,S=1024,D=64, the (B,H,q,kv) grid runs ~8 us
# per step regardless of causality or FLOPs (6.2 ms fwd ~ 2% of MXU peak;
# XLA's attention and jax.experimental's reference Pallas kernel land in the
# same 6-9 ms band — see BENCH_FLASH_MICRO.json). When the whole KV fits in
# VMEM there is no reason to stream it: these kernels give each program a
# full [block_q, Skv] score tile and do plain fp32 softmax in registers —
# no scratch state, no revisiting, no per-kv-step DMA boundaries — and
# optionally batch G heads per program to amortize DMA latency. Backward
# computes dq/dk/dv in ONE pass (dk/dv accumulated across q blocks in VMEM).
#
# Skipping the masked causal work by two kernel VARIANTS (low-kv half +
# full-kv half) lost to its dk/dv stitch in r3 (PERF.md section 6, PR 27);
# skipping it INSIDE one program is what the chunked backward below and the
# causal kernels further down do.
# ---------------------------------------------------------------------------

# Live-bytes budgets for one-shot plans. r3 ran 10 MB ("16 MB VMEM minus
# operand buffers"); r4's plan sweep (see PROFILE_GPT2.md r4 addendum)
# measured that the 16.8 MB-modeled (G=2, bq=512) backward compiles and is
# the fastest fwd+bwd combo at GPT-2 shapes — the cost model overstates
# live bytes (softmax tiles reuse the score tile's registers). r4 raised
# the single budget to 17 MB, but that sits ABOVE the ~16 MB physical
# VMEM: any not-measured shape whose true live bytes exceed VMEM would
# hard-fail the Mosaic compile instead of falling back to online
# (ADVICE r4). r5 split the policy:
#   - general admission (impl="auto"): 13 MB modeled — margin under
#     physical VMEM (the model is known to over-count), chosen as the
#     smallest cap that preserves every plan choice the r4 benches
#     measured on-chip (Llama-400M bwd (G=1, bq=256) at S=2048/D=128 =
#     11.3 MB; S=4096/D=128 non-causal fwd (G=1, bq=256) = 12.5 MB; both
#     r4 readings of a machine that is gone, records in git at 6739a2e);
#   - plans above 13 MB are admitted under auto only via the explicit
#     measured allowlist below;
#   - forced impl="oneshot" keeps the 17 MB cap (an opt-in: the caller
#     asked for this kernel and gets the compile error if it won't fit).
ONESHOT_BUDGET = 13 * 1024 * 1024
ONESHOT_FORCED_BUDGET = 17 * 1024 * 1024
# (bwd, g, bq, Skv, D) plans above ONESHOT_BUDGET measured to compile and
# win on v5e in bf16 (PROFILE_GPT2.md r4 plan sweep: fastest GPT-2
# backward, 16.8 MB modeled; in float32 the compiler counts 17.5 MB
# against its 16).
ONESHOT_MEASURED_PLANS = {
    (True, 2, 512, 1024, 64),
}


def _oneshot_plan(H, Sq, Skv, D, *, bwd=False, forced=False,
                  dtype=jnp.bfloat16):
    """Pick (heads_per_program G, q_rows_per_program bq), or None.

    Cost model (bytes live per program): fwd keeps s/p f32 + p bf16 tiles
    (~10 B per (g, q, kv) cell) + k/v blocks; bwd adds dp/ds tiles and the
    f32 dk/dv accumulators. None -> KV too long for a dense score tile;
    caller falls back to the online-softmax kernels.
    """
    cell = 14 if bwd else 10
    kvbytes = (16 if bwd else 4) * Skv * D
    # Under "auto", plans whose q tile is thinner than 256 rows are
    # rejected — they lose to the online kernels: measured at S=4096/D=128
    # the degenerate bq=16/128 one-shot plans run 2x slower than
    # online@1024-blocks (BENCH_FLASH_MICRO.json), while every bq>=256 plan
    # measured wins. Tiny sequences (Sq<256) are exempt — there the whole
    # problem fits one program. impl="oneshot" (forced) skips the
    # threshold so the kernel stays measurable at any feasible shape.
    min_bq = 1 if forced else min(256, Sq)
    budget = ONESHOT_FORCED_BUDGET if forced else ONESHOT_BUDGET
    best = None
    for g in range(min(H, 8), 0, -1):
        if H % g:
            continue
        for bq in (1024, 512, 256, 128, 64, 32, 16):
            if bq > Sq or Sq % bq or bq < min_bq:
                continue
            if (cell * g * bq * Skv + g * kvbytes <= budget
                    or (dtype == jnp.bfloat16
                        and (bwd, g, bq, Skv, D) in ONESHOT_MEASURED_PLANS)):
                # Maximize work per program; on ties prefer MORE HEADS over
                # fatter q tiles — measured at B16·H12·S1024·D64 (r4 plan
                # sweep): (2,512) runs fwd+bwd 1.87 ms vs 2.49 ms for
                # (1,1024) at the identical program count, the extra heads
                # amortizing per-program DMA better than extra q rows.
                key = (g * bq, g)
                if best is None or key > best[0]:
                    best = (key, (g, bq))
                break  # smaller bq only shrinks work per program
    return best[1] if best else None


def _causal_mask(s, qi, block_q):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _kv_len_mask(s, kv_len):
    """Mask keys at positions >= kv_len (padded keys; see ``kv_len`` docs)."""
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    return jnp.where(k_pos < kv_len, s, NEG_INF)


def _causal_mask_chunk(s, qi, block_q, k_base):
    """Causal mask for a kv chunk whose global key offset is ``k_base``."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    k_pos = k_base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _oneshot_num_chunks(causal, kv_len, Skv) -> int:
    """kv chunks per program of the causal one-shot backward (1 = dense).

    Causal one-shot programs waste ~(nq-1)/(2nq) of their dot/exp work on
    fully-masked keys. The backward splits the keys WITHIN the program: a
    python-unrolled chunk loop whose invisible chunks are skipped via
    pl.when on the q-block index (exact: probabilities come from the saved
    lse). Chunks of 512 keys keep the per-chunk dots MXU-sized; shapes that
    don't tile fall back to dense. The same scheme with an online softmax
    lost as a forward in r4 (PERF.md section 6, PR 27).
    """
    if not causal or kv_len is not None:
        return 1
    for ck in (512, 256):
        if Skv % ck == 0 and Skv // ck > 1:
            return Skv // ck
    return 1


def _oneshot_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                        sm_scale, causal, block_q, kv_len):
    qi = pl.program_id(2)
    q = _mxu(q_ref[0])                            # [G, bq, D]
    k = _mxu(k_ref[0])                            # [G, Skv, D]
    v = _mxu(v_ref[0])
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
    if causal:
        s = _causal_mask(s, qi, block_q)
    if kv_len is not None:
        s = _kv_len_mask(s, kv_len)
    m = jnp.max(s, axis=2, keepdims=True)         # [G, bq, 1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=2, keepdims=True)
    o = jax.lax.dot_general(p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    lse_ref[0] = jnp.broadcast_to(lse, (*lse.shape[:2], LSE_LANES))


def _oneshot_fwd(q, k, v, *, causal, plan, kv_len=None):
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    G, bq = plan
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out, lse = pl.pallas_call(
        functools.partial(_oneshot_fwd_kernel, sm_scale=1.0 / math.sqrt(D),
                          causal=causal, block_q=bq, kv_len=kv_len),
        name="flash_fwd_oneshot",
        grid=(B, H // G, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, G, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, G, Skv, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, G, Skv, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, G, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, G, bq, LSE_LANES), lambda b, h, i: (b, h, i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, LSE_LANES), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(qt, kt, vt)
    return jnp.transpose(out, (0, 2, 1, 3)), lse


def _oneshot_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                        sm_scale, causal, block_q, kv_len):
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = _mxu(q_ref[0])                            # [G, bq, D]
    k = _mxu(k_ref[0])                            # [G, Skv, D]
    v = _mxu(v_ref[0])
    do = _mxu(do_ref[0])
    lse = lse_ref[0][..., :1]                     # [G, bq, 1]
    delta = delta_ref[0][..., :1]
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
    if causal:
        s = _causal_mask(s, qi, block_q)
    if kv_len is not None:
        s = _kv_len_mask(s, kv_len)
    p = jnp.exp(s - lse)                          # [G, bq, Skv]
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
    dq = jax.lax.dot_general(ds, k, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                     (((1,), (1,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)
    dk_acc[:] += jax.lax.dot_general(ds, q, (((1,), (1,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _oneshot_bwd_kernel_chunked(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, dq_ref, dk_ref, dv_ref,
                                dk_acc, dv_acc, dq_acc, *,
                                sm_scale, block_q, num_chunks):
    """Causal one-shot backward with in-program kv-chunk skipping. Exact
    (probabilities recomputed from the saved forward lse, so no online
    state): invisible chunks contribute nothing to dq and nothing from
    these queries to dk/dv."""
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)
    G, Skv, D = k_ref.shape[1], k_ref.shape[2], k_ref.shape[3]
    ck = Skv // num_chunks

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    dq_acc[:] = jnp.zeros_like(dq_acc)
    q = _mxu(q_ref[0])                            # [G, bq, D]
    do = _mxu(do_ref[0])
    lse = lse_ref[0][..., :1]                     # [G, bq, 1]
    delta = delta_ref[0][..., :1]

    for c in range(num_chunks):
        @pl.when(c * ck < (qi + 1) * block_q)
        def _chunk(c=c):
            k_c = _mxu(k_ref[0, :, c * ck:(c + 1) * ck, :])
            v_c = _mxu(v_ref[0, :, c * ck:(c + 1) * ck, :])
            s = jax.lax.dot_general(q, k_c, (((2,), (2,)), ((0,), (0,))),
                                    preferred_element_type=jnp.float32)
            s = _causal_mask_chunk(s * sm_scale, qi, block_q, c * ck)
            p = jnp.exp(s - lse)                  # [G, bq, ck]
            dv_acc[:, c * ck:(c + 1) * ck, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v_c, (((2,), (2,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * sm_scale).astype(k_c.dtype)
            dq_acc[:] += jax.lax.dot_general(
                ds, k_c, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            dk_acc[:, c * ck:(c + 1) * ck, :] += jax.lax.dot_general(
                ds, q, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)

    dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _oneshot_bwd(q, k, v, o, lse, g, *, causal, plan, kv_len=None):
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    G, bq = plan
    delta = _delta_rows(g, o)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    dot = jnp.transpose(g, (0, 2, 1, 3))
    qspec = pl.BlockSpec((1, G, bq, D), lambda b, h, i: (b, h, i, 0))
    kspec = pl.BlockSpec((1, G, Skv, D), lambda b, h, i: (b, h, 0, 0))
    lspec = pl.BlockSpec((1, G, bq, LSE_LANES), lambda b, h, i: (b, h, i, 0))
    nc = _oneshot_num_chunks(causal, kv_len, Skv)
    if nc > 1:
        kernel = functools.partial(
            _oneshot_bwd_kernel_chunked, sm_scale=1.0 / math.sqrt(D),
            block_q=bq, num_chunks=nc)
        scratch = [pltpu.VMEM((G, Skv, D), jnp.float32),   # dk
                   pltpu.VMEM((G, Skv, D), jnp.float32),   # dv
                   pltpu.VMEM((G, bq, D), jnp.float32)]    # dq
    else:
        kernel = functools.partial(
            _oneshot_bwd_kernel, sm_scale=1.0 / math.sqrt(D),
            causal=causal, block_q=bq, kv_len=kv_len)
        scratch = [pltpu.VMEM((G, Skv, D), jnp.float32),
                   pltpu.VMEM((G, Skv, D), jnp.float32)]
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_oneshot",
        grid=(B, H // G, Sq // bq),
        in_specs=[qspec, kspec, kspec, qspec, lspec, lspec],
        out_specs=(qspec, kspec, kspec),
        out_shape=(jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Skv, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Skv, D), v.dtype)),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(qt, kt, vt, dot, lse, delta)
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return tr(dq), tr(dk), tr(dv)


# ---------------------------------------------------------------------------
# Causal kernels: causal self-attention whose whole head fits VMEM (GPT-2's
# S=1024, D=64). A program holds G heads' whole q, k, v and walks q sub-tiles
# of T rows in a Python loop, so sub-tile i's visible keys are the STATIC
# prefix k[:(i+1)*T]: fully visible keys left of the diagonal tile are never
# masked, keys right of it are never computed. Plain one-shot softmax per
# sub-tile (no running max, no scratch); the backward walks the sub-tiles
# from the last (whole prefix) to the first, so the first visit assigns the
# float32 dk/dv accumulators and the later ones add to their static prefixes.
# Work done at nt = S/T sub-tiles: (nt+1)/(2*nt) of the dense S^2 tile.
#
# Layout: operands and results are the projections' own [B, S, H*D], blocked
# (1, S, G*D), so no transpose sits on either side and every HBM row is whole
# 128-lane tiles. Inside a block the kernels work on chunks of
# ``_causal_lanes`` lanes: one head at D=128, two at D=64. Heads that share a
# chunk are told apart without lane shuffles: each head's copy of the q (and
# do) sub-tile, the other heads' lanes zeroed, is stacked along the rows, so
# one K=128 contraction against the chunk's k or v gives every head's exact
# scores, and the rows' sums over the stack give dk and dv (a head's rows are
# zero outside its lanes); each head's lanes of the N=128 results (o, dq) are
# taken from its rows with a select. On a 128 x 128 MXU that is as many passes
# as K=64 / N=64, with each k and v weight tile loaded once for the stack. The
# backward computes delta = rowsum(dO*O) from the sub-tiles it holds. lse
# leaves the forward as [B, H*D/lanes, S, lanes] float32, each head's value
# across its own D lanes.
# ---------------------------------------------------------------------------


def _nt_dot(a, b):
    """a [M, K] x b [N, K]^T -> [M, N] float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn_dot(a, b):
    """a [K, M]^T x b [K, N] -> [M, N] float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# (S, D) at which benchmarks/flash_micro.py --kernels on a v5e read both
# causal kernels ahead of auto's earlier choice (online forward, chunked
# one-shot backward); PERF.md section 6 has the tables (PR 27, PR 31). Every
# reading is of bf16. Other shapes, and other dtypes at any shape, keep the
# earlier kernels until they are measured. (2048, 64) was one of them while
# a program could hold one 64-wide head; ``_causal_plan`` says why not now.
CAUSAL_MEASURED = {(1024, 64), (1024, 128), (2048, 128)}


def _causal_lanes(H, D):
    """Lanes of one chunk of the causal kernels' blocks: a 128-lane tile row
    of D-wide heads, one wider head, or all of a narrower H*D."""
    return max(D, min(H * D, 128))


def _causal_plan(H, S, D, *, bwd=False):
    """Pick (heads per program G, q sub-tile rows T) for the causal kernels,
    or None.

    T is the measured choice (benchmarks/flash_micro.py, ms a layer; PERF.md
    section 6, PR 31). The forward stacks 256 rows, a chunk's heads by T:
    at GPT-2's shape (B24 H12 S1024 D64, two heads a chunk) 0.71 at 128
    against 0.72 at 256 and 1.07 at 64; at B8 H16 S1024 D128 0.35 at 256
    against 0.39 at 128 (fewer rows stream too little past each MXU weight
    tile, more skip too little). The backward takes 128 at either width:
    1.20 against 1.34 at 64, 0.56 against 0.60 at 256 (its five matmuls gain
    more from the skipped work).

    G heads are G*D lanes of the [B, S, H*D] operands: whole chunks of
    ``_causal_lanes``, or None (a device's H/tp heads of 64 that do not
    pair up; the earlier kernels serve those). Bytes live per program, held
    to the one-shot planner's budget: the double-buffered [S, G*D] blocks of
    bf16 operands and of float32 lse; forward, a head's score tiles, which
    the compiler keeps for every sub-tile at once (S*S/2 floats: this
    counts 10 bytes for 256 rows of S, right at S=1024 and 4 MB short at
    2048); backward, the s/p/dp/ds tiles of one sub-tile and a chunk's dk/dv
    accumulators. The v5e compiler's own counts of what this admits, MB
    (this model's in brackets): S1024/D64 G=2 forward 8.2 [8.4], G=4
    backward 13.2 [13.4]; S1024/D128 G=2 11.6 [11.5] and 12.0 [13.4];
    S2048/D128 G=1 forward 14.8 [11.5] of its 16, the backward (14.3 [16.3])
    stays with the chunked one-shot kernel. S2048/D64 fits neither way: two
    heads' forward needs 22.8 MB, one head's 64 lanes are no block.
    """
    lanes = _causal_lanes(H, D)
    if S % 256 or S == 256 or (H * D) % lanes:
        return None
    tile = 128 if bwd else 256 * D // lanes
    blocks = 2 * ((8 if bwd else 4) * S * D * 2 + S * D * 4)
    for g in range(min(H, 8), 0, -1):
        if H % g or (g * D) % lanes:
            continue
        live = (g * blocks + 14 * tile * S + 2 * S * lanes * 4 if bwd
                else g * (blocks + 10 * 256 * S))
        if live <= ONESHOT_BUDGET:
            return g, tile
    return None


def _auto_causal_plan(impl, causal, kv_len, Sq, Skv, H, D, dtype, *,
                      bwd=False):
    """The causal kernels' plan where auto dispatch takes them, else None:
    causal self-attention over the whole sequence at a measured shape, in
    the dtype that was measured and whose bytes ``_causal_plan`` counts
    (float32 blocks are twice the bytes, as are fp16's operands once
    ``_mxu`` has widened them: the v5e compiler refuses float32 at S=2048,
    and at GPT-2's shape in the backward)."""
    if (impl != "auto" or not causal or kv_len is not None or Sq != Skv
            or (Sq, D) not in CAUSAL_MEASURED or dtype != jnp.bfloat16):
        return None
    return _causal_plan(H, Sq, D, bwd=bwd)


def _mask_diagonal(s, lo, tile):
    """Causal mask for stacked q rows [lo, lo+T) against the keys [0, lo+T):
    only the last T columns (the diagonal tile) hold anything to mask."""
    rows = s.shape[0]
    visible = (jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0) % tile
               >= jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1))
    diag = jnp.where(visible, s[:, lo:], NEG_INF)
    return jnp.concatenate([s[:, :lo], diag], axis=1) if lo else diag


def _head_lanes(tile, lanes, head_dim):
    """One [tile, lanes] mask per head of a chunk: that head's own lanes."""
    head = jax.lax.broadcasted_iota(jnp.int32, (tile, lanes), 1) // head_dim
    return [head == h for h in range(lanes // head_dim)]


def _stack_heads(x, own):
    """[T, lanes] -> [heads*T, lanes]: a copy of x per head of the chunk,
    zero outside that head's lanes."""
    if len(own) == 1:
        return x
    return jnp.concatenate([jnp.where(mine, x, 0) for mine in own], axis=0)


def _own_lanes(stacked, own):
    """[heads*T, lanes] -> [T, lanes]: each head's lanes from its own rows."""
    tile = stacked.shape[0] // len(own)
    out = stacked[:tile]
    for h, mine in enumerate(own[1:], 1):
        out = jnp.where(mine, stacked[h * tile:(h + 1) * tile], out)
    return out


def _causal_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, tile,
                       head_dim):
    S, lanes = q_ref.shape[1], lse_ref.shape[3]
    own = _head_lanes(tile, lanes, head_dim)
    for c in range(lse_ref.shape[1]):
        at = slice(c * lanes, (c + 1) * lanes)
        for lo in range(0, S, tile):
            n = lo + tile
            q = _stack_heads(_mxu(q_ref[0, lo:n, at]), own)   # [hT, lanes]
            k = _mxu(k_ref[0, :n, at])                        # [n, lanes]
            v = _mxu(v_ref[0, :n, at])
            s = _mask_diagonal(_nt_dot(q, k) * sm_scale, lo, tile)  # [hT, n]
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            o = jnp.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32) / l
            lse = jnp.broadcast_to(m + jnp.log(l), o.shape)
            o_ref[0, lo:n, at] = _own_lanes(o, own).astype(o_ref.dtype)
            lse_ref[0, c, lo:n, :] = _own_lanes(lse, own)


def _causal_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                       dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                       sm_scale, tile, head_dim):
    S, lanes = q_ref.shape[1], lse_ref.shape[3]
    own = _head_lanes(tile, lanes, head_dim)
    for c in range(lse_ref.shape[1]):
        at = slice(c * lanes, (c + 1) * lanes)
        for lo in reversed(range(0, S, tile)):
            n = lo + tile
            q = _stack_heads(_mxu(q_ref[0, lo:n, at]), own)   # [hT, lanes]
            do = _stack_heads(_mxu(do_ref[0, lo:n, at]), own)
            k = _mxu(k_ref[0, :n, at])                        # [n, lanes]
            v = _mxu(v_ref[0, :n, at])
            # delta = rowsum(dO * O): a head's rows of do hold its lanes only
            o = o_ref[0, lo:n, at].astype(jnp.float32)
            delta = jnp.sum(do.astype(jnp.float32)
                            * jnp.concatenate([o] * len(own), axis=0),
                            axis=1, keepdims=True)
            lse = jnp.concatenate(
                [lse_ref[0, c, lo:n, h * head_dim:h * head_dim + 1]
                 for h in range(len(own))], axis=0)           # [hT, 1]
            s = _mask_diagonal(_nt_dot(q, k) * sm_scale, lo, tile)  # [hT, n]
            p = jnp.exp(s - lse)
            dp = _nt_dot(do, v)
            ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
            dq = jnp.dot(ds, k, preferred_element_type=jnp.float32)
            dq_ref[0, lo:n, at] = _own_lanes(dq, own).astype(dq_ref.dtype)
            dv = _tn_dot(p.astype(do.dtype), do)              # [n, lanes]
            dk = _tn_dot(ds, q)
            if n == S:  # the whole prefix comes first: assigns every key row
                dv_acc[:] = dv
                dk_acc[:] = dk
            else:
                dv_acc[:n, :] += dv
                dk_acc[:n, :] += dk
        dk_ref[0, :, at] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, :, at] = dv_acc[:].astype(dv_ref.dtype)


def _causal_specs(B, S, H, D, G):
    """Block specs of the [B, S, H*D] operands and of lse, and lse's shape."""
    lanes = _causal_lanes(H, D)
    spec = pl.BlockSpec((1, S, G * D), lambda b, h: (b, 0, h))
    lspec = pl.BlockSpec((1, G * D // lanes, S, lanes),
                         lambda b, h: (b, h, 0, 0))
    return spec, lspec, (B, H * D // lanes, S, lanes)


@functools.partial(jax.jit, static_argnames="plan")
def _causal_fwd(q, k, v, *, plan):
    """Returns (out [B,S,H,D], lse [B, H*D/lanes, S, lanes], each head's value
    across its own D lanes); K/V GQA-expanded.

    Under ``jit`` so that a model's layers, and its later traces, share one
    trace and one lowering of the unrolled kernel (it costs a few hundred
    jnp calls; retraced per layer it added 7 s to the GPT-2 cell's set-up).
    """
    B, S, H, D = q.shape
    G, tile = plan
    spec, lspec, lse_shape = _causal_specs(B, S, H, D, G)
    out, lse = pl.pallas_call(
        functools.partial(_causal_fwd_kernel, sm_scale=1.0 / math.sqrt(D),
                          tile=tile, head_dim=D),
        name="flash_fwd_causal",
        grid=(B, H // G),
        in_specs=[spec, spec, spec],
        out_specs=(spec, lspec),
        out_shape=(jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
                   jax.ShapeDtypeStruct(lse_shape, jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*(x.reshape(B, S, H * D) for x in (q, k, v)))
    return out.reshape(B, S, H, D), lse


def _causal_lse_rows(lse, H):
    """The causal forward's lse as the [B,H,S,LSE_LANES] rows that every
    other backward reads."""
    B, chunks, S, lanes = lse.shape
    D = chunks * lanes // H
    rows = lse.reshape(B, chunks, S, lanes // D, D)[..., 0]
    rows = jnp.moveaxis(rows, 3, 2).reshape(B, H, S, 1)
    return jnp.broadcast_to(rows, (B, H, S, LSE_LANES))


@functools.partial(jax.jit, static_argnames="plan")
def _causal_bwd(q, k, v, o, lse, g, *, plan):
    """q,k,v,o,g: [B,S,H,D] (kv GQA-expanded); lse: as ``_causal_fwd`` gives."""
    B, S, H, D = q.shape
    G, tile = plan
    spec, lspec, _ = _causal_specs(B, S, H, D, G)
    lanes = _causal_lanes(H, D)
    shape = jax.ShapeDtypeStruct((B, S, H * D), q.dtype)
    grads = pl.pallas_call(
        functools.partial(_causal_bwd_kernel, sm_scale=1.0 / math.sqrt(D),
                          tile=tile, head_dim=D),
        name="flash_bwd_causal",
        grid=(B, H // G),
        in_specs=[spec, spec, spec, spec, spec, lspec],
        out_specs=(spec, spec, spec),
        out_shape=(shape, shape, shape),
        scratch_shapes=[pltpu.VMEM((S, lanes), jnp.float32),   # dk, a chunk
                        pltpu.VMEM((S, lanes), jnp.float32)],  # dv
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*(x.reshape(B, S, H * D) for x in (q, k, v, o, g)), lse)
    return tuple(x.reshape(B, S, H, D) for x in grads)


# ---------------------------------------------------------------------------
# Streaming one-shot backward: the D=128 long-context path (ISSUE r6).
#
# At S >= 4096 with D=128 the dense one-shot backward no longer fits VMEM
# (``_oneshot_plan(..., bwd=True)`` returns None) and dispatch fell back to
# the two-kernel online backward. That path recomputes the score matrix
# TWICE (dq pass + dkv pass): 7 S^2-scale matmuls and 2 full exp sweeps per
# backward. This kernel does the whole backward in ONE pass — 5 matmuls,
# 1 exp — by inverting the residency: each program pins one (batch,
# head-group)'s full-Sq q/do/lse/delta plus an fp32 dq accumulator in VMEM
# and STREAMS the kv axis on the innermost grid dimension. The kv dimension
# is "arbitrary", so the Pallas pipeline double-buffers the k/v chunk
# fetches against the compute of the previous chunk — the HBM->VMEM KV DMA
# overlap the online kernels get per kv block, kept, while the score tile
# is computed once. dk/dv for a chunk complete within its grid step (every
# q subtile contributes in the unrolled loop); dq accumulates across chunks
# and flushes on the last one. Causal chunk skipping is per q-subtile via
# pl.when, same scheme as the chunked one-shot kernels.
#
# Auto-dispatch is gated to D=128 (this round's target; the D=64 dispatch
# map is measured and unchanged) and can be widened or killed via
# PDTX_STREAM_BWD ("all" = any head dim, "0" = off) until the on-chip A/B
# lands.
# ---------------------------------------------------------------------------

STREAM_BWD = os.environ.get("PDTX_STREAM_BWD", "1")
STREAM_BWD_BUDGET = 13 * 1024 * 1024  # same general-admission cap as one-shot
# The byte model below undercounts what Mosaic keeps live across the
# unrolled q-subtile loop, and the gap grows with the rows a program pins.
# v5e compiler (16 MB scoped VMEM), kernel compiled alone at D=128:
# g*Sq = 4096 fits (Sq=4096 g=1, Sq=2048 g=2, either bsub); g*Sq = 6144 is
# counted at 16.58 MB (bsub=512) / 21.29 MB (256); g*Sq = 8192 at 20.79-
# 28.79 MB (16.75 MB inside the fwd+bwd program) — all refused. So admission
# is also bounded by resident rows (of 128 lanes: a narrower head pads to
# them, a wider one counts double); refused shapes take the online
# two-kernel backward. Outside D=128 (PDTX_STREAM_BWD=all) every plan the
# compiler refused had 256-row subtiles, so only 512-row ones are admitted.
STREAM_BWD_MAX_ROWS = 4096


def _stream_bwd_plan(H, Sq, Skv, D, *, mode=None):
    """Pick (heads_per_program G, q_subtile_rows bsub, kv_chunk ck), or None.

    Cost model (bytes live per program): resident q/do (bf16) + fp32 dq
    accumulator + lse/delta rows, plus the double-buffered k/v chunk pair,
    per-chunk dk/dv output blocks and fp32 accumulators, plus the transient
    s/p/dp/ds tiles (14 B per (g, bsub, ck) cell, as in the one-shot bwd
    model), bounded by ``STREAM_BWD_MAX_ROWS`` resident rows — the bound the
    v5e compiler actually enforces. None -> caller falls back to the online
    two-kernel backward.
    """
    mode = STREAM_BWD if mode is None else mode
    if mode in ("0", "off"):
        return None
    if D != 128 and mode != "all":
        return None
    best = None
    D = max(D, 128)  # VMEM rows are lane-padded
    for g in range(min(H, 8), 0, -1):
        if H % g or g * Sq * D > STREAM_BWD_MAX_ROWS * 128:
            continue
        for bsub in (512, 256) if D == 128 else (512,):
            if bsub > Sq or Sq % bsub:
                continue
            ck = 512  # keeps per-chunk dots MXU-sized (see _oneshot_num_chunks)
            if Skv % ck or Skv // ck < 2:
                continue
            resident = g * (2 * Sq * D * 2          # q + do (bf16)
                            + Sq * D * 4            # dq accumulator (f32)
                            + 2 * Sq * LSE_LANES * 4)  # lse + delta rows
            chunk = g * ck * D * (2 * 2 * 2         # k/v, double-buffered
                                  + 2 * 2           # dk/dv output blocks
                                  + 2 * 4)          # dk/dv accumulators (f32)
            tiles = 14 * g * bsub * ck              # s/p/dp f32 + ds bf16
            if resident + chunk + tiles <= STREAM_BWD_BUDGET:
                key = (g, bsub)
                if best is None or key > best[0]:
                    best = (key, (g, bsub, ck))
    return best[1] if best else None


def _stream_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                       sm_scale, causal, bsub, num_sub):
    c = pl.program_id(2)
    n_c = pl.num_programs(2)
    ck = k_ref.shape[2]

    @pl.when(c == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    # dk/dv complete within this grid step — reset every chunk.
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    k_c = _mxu(k_ref[0])                          # [G, ck, D]
    v_c = _mxu(v_ref[0])
    for qs in range(num_sub):
        visible = True
        if causal:
            # Subtile qs sees chunk c iff any of its rows reach the chunk's
            # first key; fully-above-diagonal (subtile, chunk) pairs skip
            # the dots AND the exp entirely.
            visible = c * ck < (qs + 1) * bsub

        @pl.when(visible)
        def _sub(qs=qs):
            lo = qs * bsub
            q_s = _mxu(q_ref[0, :, lo:lo + bsub, :])      # [G, bsub, D]
            do_s = _mxu(do_ref[0, :, lo:lo + bsub, :])
            lse_s = lse_ref[0, :, lo:lo + bsub, :1]       # [G, bsub, 1]
            delta_s = delta_ref[0, :, lo:lo + bsub, :1]
            s = jax.lax.dot_general(q_s, k_c, (((2,), (2,)), ((0,), (0,))),
                                    preferred_element_type=jnp.float32)
            s = s * sm_scale
            if causal:
                s = _causal_mask_chunk(s, qs, bsub, c * ck)
            p = jnp.exp(s - lse_s)                        # [G, bsub, ck]
            dv_acc[:] += jax.lax.dot_general(
                p.astype(do_s.dtype), do_s, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do_s, v_c, (((2,), (2,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_s) * sm_scale).astype(k_c.dtype)
            dq_acc[:, lo:lo + bsub, :] += jax.lax.dot_general(
                ds, k_c, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            dk_acc[:] += jax.lax.dot_general(
                ds, q_s, (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)

    dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(c == n_c - 1)
    def _flush():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _stream_bwd(q, k, v, o, lse, g, *, causal, plan):
    """q,k,v,o,g: [B,S,H,D] (kv already GQA-expanded); lse: [B,H,Sq,LANES]."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    G, bsub, ck = plan
    sm_scale = 1.0 / math.sqrt(D)
    delta = _delta_rows(g, o)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    dot = jnp.transpose(g, (0, 2, 1, 3))
    qspec = pl.BlockSpec((1, G, Sq, D), lambda b, h, c: (b, h, 0, 0))
    cspec = pl.BlockSpec((1, G, ck, D), lambda b, h, c: (b, h, c, 0))
    lspec = pl.BlockSpec((1, G, Sq, LSE_LANES), lambda b, h, c: (b, h, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_stream_bwd_kernel, sm_scale=sm_scale,
                          causal=causal, bsub=bsub, num_sub=Sq // bsub),
        name="flash_bwd_stream",
        grid=(B, H // G, Skv // ck),
        in_specs=[qspec, cspec, cspec, qspec, lspec, lspec],
        out_specs=(qspec, cspec, cspec),
        out_shape=(jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Skv, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Skv, D), v.dtype)),
        scratch_shapes=[pltpu.VMEM((G, Sq, D), jnp.float32),   # dq
                        pltpu.VMEM((G, ck, D), jnp.float32),   # dk
                        pltpu.VMEM((G, ck, D), jnp.float32)],  # dv
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(qt, kt, vt, dot, lse, delta)
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return tr(dq), tr(dk), tr(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal: bool = False,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_kv: int = DEFAULT_BLOCK_KV,
                    impl: str = "auto",
                    kv_len: int | None = None,
                    window: int | None = None,
                    scale: float | None = None):
    """Flash attention with the XLA oracle's exact semantics.

    [B, S, H, D] layout; fp32 softmax; GQA via fewer KV heads. Forward and
    backward are both Pallas kernels. ``impl``: "auto" picks the one-shot
    dense-score kernels when KV fits VMEM (short/medium S — see
    ``_oneshot_plan``) and the online-softmax streaming kernels otherwise
    (FlashAttention-2 recomputation scheme: residuals are q/k/v/o + per-row
    logsumexp, never the S x S matrix in HBM); "oneshot"/"online" force.

    ``kv_len`` (static): mask keys at positions >= kv_len. Used by the
    tile-padding path in :func:`attention.padded_flash_attention` that
    serves non-tile-aligned sequences (e.g. ViT's 197 tokens padded to
    256); one-shot kernels only.

    ``window`` (static): row i sees the keys [i - window + 1, i] only
    (causal self-attention; the online kernels under the window's schedule,
    whatever ``impl``). A window that covers the sequence is plain causal
    attention and dispatches as such.

    ``v`` may be of another width than ``q`` and ``k`` (latent attention's
    192 / 128), and ``scale`` (static) another factor on the scores than ``1 /
    sqrt(D)``: either makes the call the online kernels', which block each
    operand at its own width; the one-shot, causal and streaming kernels
    hold one ``D`` and one factor and refuse such a call.
    """
    k = attn_lib._repeat_kv(k, q.shape[2])
    v = attn_lib._repeat_kv(v, q.shape[2])
    out, _ = _fwd_dispatch(q, k, v, causal, block_q, block_kv, impl, kv_len,
                           window, scale)
    return out


def _window_of(window, causal, kv_len, Sq, Skv):
    """The window the kernels mask by, or None where it masks nothing."""
    if window is None:
        return None
    if not causal or kv_len is not None or Sq != Skv or window < 1:
        raise ValueError(
            f"window={window} needs causal self-attention without kv_len "
            f"(causal={causal}, kv_len={kv_len}, Sq={Sq}, Skv={Skv})")
    return window if window < Skv else None


def _online_only(q, v, scale, impl, kv_len):
    """Whether the call is one only the online kernels compute: a value width
    that differs from the query/key width, or the caller's own scale. Raises
    where the caller pinned it to kernels that cannot."""
    if v.shape[-1] == q.shape[-1] and scale is None:
        return False
    if impl == "oneshot" or kv_len is not None:
        raise ValueError(
            f"impl={impl!r}, kv_len={kv_len}: the one-shot, causal and "
            f"streaming kernels take one head width and 1 / sqrt(D) (query/"
            f"key width {q.shape[-1]}, value width {v.shape[-1]}, scale "
            f"{scale}); the online kernels serve such a call")
    return True


def _fwd_dispatch(q, k, v, causal, block_q, block_kv, impl, kv_len,
                  window=None, scale=None):
    """Auto dispatch is per direction, each from measurements on the chip:

    - Unequal widths or a scale of the caller's: the online kernels, both
      directions (``_online_only``).
    - A window shorter than the sequence: the online kernels, both
      directions, under a schedule with the window as its second edge (1024
      x 1024 blocks with 512 sub-tiles on both edges at the published S8192
      D128 windows of 2048 and 4096; PERF.md section 6, PR 45).
    - Causal self-attention (Sq == Skv, no kv_len) at a shape in
      ``CAUSAL_MEASURED``: the causal kernels, both directions where
      ``_causal_plan`` has one (PERF.md section 6, PR 31: 0.71 vs 1.05 ms
      forward and 1.20 vs 1.94 backward a layer at B24·H12·S1024·D64).
    - Other causal forwards: the streaming online kernel (r4, on a machine
      that is gone: 0.72 vs 0.86 ms one-shot at S2048; 1.37 vs 1.99 at
      S4096/D128). Its grid walks only the blocks the mask leaves, and a
      block the diagonal crosses is computed in sub-tiles, a single
      1024-block too (``online_schedule``; PERF.md section 6, PR 42).
    - Other backwards: the one-shot chunked kernel whenever its plan fits
      VMEM; otherwise streaming (D=128) or online.
    - Non-causal forward: one-shot when a plan exists (no masked blocks
      for the online grid to skip, so fewer/fatter programs win).

    The residuals are q,k,v,o + lse. Every kernel but the causal pair
    reads and writes lse as [B,H,S,LSE_LANES], so mixing those directions
    is free; the causal forward's denser lse (``_causal_fwd``) goes to the
    causal backward as it is, and ``_vjp_fwd`` spreads it into
    [B,H,S,LSE_LANES] rows where another backward will read it. Forced
    impl="oneshot"/"online" still pin both sides.
    """
    B, Sq, H, D = q.shape
    window = _window_of(window, causal, kv_len, Sq, k.shape[1])
    if window is not None:
        return _online_fwd(q, k, v, True, block_q, block_kv, window, scale)
    if _online_only(q, v, scale, impl, kv_len):
        return _online_fwd(q, k, v, causal, block_q, block_kv, scale=scale)
    if kv_len is not None and impl == "online":
        raise ValueError("kv_len masking requires the one-shot kernels; "
                         "impl='online' cannot serve it")
    cplan = _auto_causal_plan(impl, causal, kv_len, Sq, k.shape[1], H, D,
                              q.dtype)
    if cplan is not None:
        return _causal_fwd(q, k, v, plan=cplan)
    plan = None
    if impl == "oneshot" or kv_len is not None:
        plan = _oneshot_plan(H, Sq, k.shape[1], D, forced=impl == "oneshot",
                             dtype=q.dtype)
    elif impl == "auto" and not causal:
        plan = _oneshot_plan(H, Sq, k.shape[1], D, dtype=q.dtype)
    if plan is None and (impl == "oneshot" or kv_len is not None):
        raise ValueError(f"oneshot flash attention cannot tile "
                         f"Sq={Sq}, Skv={k.shape[1]}, D={D} within VMEM"
                         + (" (kv_len masking requires the one-shot kernels)"
                            if kv_len is not None else ""))
    if plan is not None:
        return _oneshot_fwd(q, k, v, causal=causal, plan=plan, kv_len=kv_len)
    return _online_fwd(q, k, v, causal, block_q, block_kv)


def _width_of(q, v):
    """The call's head width as the block plan keys it: one number, or the
    pair where the value width differs."""
    return q.shape[3] if v.shape[3] == q.shape[3] else (q.shape[3], v.shape[3])


def _online_fwd(q, k, v, causal, block_q, block_kv, window=None, scale=None):
    """The online forward at the blocks ``_online_blocks`` gives the call,
    its schedule said to the recorder."""
    Sq, D = q.shape[1], _width_of(q, v)
    block_q, block_kv = _online_blocks(False, Sq, D, block_q, block_kv,
                                       q.dtype.itemsize, window)
    _say_schedules(ONLINE_KERNELS[:1], causal, Sq, k.shape[1], D, block_q,
                   block_kv, window)
    return _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                      block_kv=block_kv, window=window, scale=scale)


def _online_bwd(q, k, v, o, lse, g, causal, block_q, block_kv, window=None,
                scale=None):
    """The online backward, as ``_online_fwd``."""
    Sq, D = q.shape[1], _width_of(q, v)
    block_q, block_kv = _online_blocks(True, Sq, D, block_q, block_kv,
                                       q.dtype.itemsize, window)
    _say_schedules(ONLINE_KERNELS[1:], causal, Sq, k.shape[1], D, block_q,
                   block_kv, window)
    return _flash_bwd(q, k, v, o, lse, g, causal=causal, block_q=block_q,
                      block_kv=block_kv, window=window, scale=scale)


def _vjp_fwd(q, k, v, causal, block_q, block_kv, impl, kv_len, window,
             scale=None):
    ke = attn_lib._repeat_kv(k, q.shape[2])
    ve = attn_lib._repeat_kv(v, q.shape[2])
    out, lse = _fwd_dispatch(q, ke, ve, causal, block_q, block_kv, impl,
                             kv_len, window, scale)
    if (_window_of(window, causal, kv_len, q.shape[1], k.shape[1]) is not None
            or _online_only(q, v, scale, impl, kv_len)):
        return out, (q, k, v, out, lse)
    fwd_plan, bwd_plan = (
        _auto_causal_plan(impl, causal, kv_len, q.shape[1], k.shape[1],
                          q.shape[2], q.shape[3], q.dtype, bwd=bwd)
        for bwd in (False, True))
    if fwd_plan is not None and bwd_plan is None:
        # causal forward, one-shot backward (S=2048/D=128): its format
        lse = _causal_lse_rows(lse, q.shape[2])
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, block_q, block_kv, impl, kv_len, window, res, g,
             scale=None):
    q, k, v, o, lse = res
    H, Hkv = q.shape[2], k.shape[2]
    ke = attn_lib._repeat_kv(k, H)
    ve = attn_lib._repeat_kv(v, H)
    window = _window_of(window, causal, kv_len, q.shape[1], k.shape[1])
    if window is not None or _online_only(q, v, scale, impl, kv_len):
        dq, dk, dv = _online_bwd(q, ke, ve, o, lse, g, causal, block_q,
                                 block_kv, window, scale)
        return (dq,) + _fold_kv_heads(dk, dv, H, Hkv)
    if kv_len is not None and impl == "online":
        raise ValueError("kv_len masking requires the one-shot kernels; "
                         "impl='online' cannot serve it")
    plan = None
    cplan = _auto_causal_plan(impl, causal, kv_len, q.shape[1], ke.shape[1],
                              H, q.shape[3], q.dtype, bwd=True)
    if cplan is None and (impl in ("oneshot", "auto") or kv_len is not None):
        # auto: one-shot backward whenever its plan fits (see
        # _fwd_dispatch's dispatch-map docstring).
        plan = _oneshot_plan(H, q.shape[1], ke.shape[1], q.shape[3], bwd=True,
                             forced=impl == "oneshot",
                             dtype=q.dtype)
    if plan is None and (impl == "oneshot" or kv_len is not None):
        raise ValueError(
            f"oneshot flash attention backward cannot tile Sq={q.shape[1]}, "
            f"Skv={ke.shape[1]}, D={q.shape[3]} within VMEM (the backward "
            f"needs ~40% more live bytes than the forward"
            + ("; kv_len masking requires the one-shot kernels)"
               if kv_len is not None else "); use impl='auto' to fall back "
               "to the online kernels for such shapes"))
    if cplan is not None:
        dq, dk, dv = _causal_bwd(q, ke, ve, o, lse, g, plan=cplan)
    elif plan is not None:
        dq, dk, dv = _oneshot_bwd(q, ke, ve, o, lse, g, causal=causal,
                                  plan=plan, kv_len=kv_len)
    else:
        # Long-S fallback order: the streaming one-pass backward where its
        # plan fits (D=128 gate — see _stream_bwd_plan), else the online
        # two-kernel backward.
        splan = None
        if impl == "auto" and kv_len is None:
            splan = _stream_bwd_plan(H, q.shape[1], ke.shape[1], q.shape[3])
        if splan is not None:
            dq, dk, dv = _stream_bwd(q, ke, ve, o, lse, g, causal=causal,
                                     plan=splan)
        else:
            dq, dk, dv = _online_bwd(q, ke, ve, o, lse, g, causal, block_q,
                                     block_kv)
    return (dq,) + _fold_kv_heads(dk, dv, H, Hkv)


def _fold_kv_heads(dk, dv, H, Hkv):
    """GQA: fold the repeated-head grads back onto the shared KV heads."""
    if Hkv != H:
        fold = lambda d: d.reshape(*d.shape[:2], Hkv, H // Hkv,
                                   d.shape[3]).sum(3)
        dk, dv = fold(dk), fold(dv)
    return dk, dv


def _vjp_bwd_scaled(causal, block_q, block_kv, impl, kv_len, window, scale,
                    res, g):
    """``_vjp_bwd`` as ``custom_vjp`` calls it: the static arguments in
    ``flash_attention``'s order, the scale the last of them."""
    return _vjp_bwd(causal, block_q, block_kv, impl, kv_len, window, res, g,
                    scale=scale)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd_scaled)


# ---------------------------------------------------------------------------
# Paged decode attention (serving): one query token per request, K/V read
# through a per-request page table into the preallocated page pool
# (serve/kv_cache.py). Forward-only — no vjp; decode never differentiates.
# ---------------------------------------------------------------------------


def _paged_decode_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, sm_scale: float,
                         page_size: int, num_kv_heads: int):
    """Grid (B, max_pages), pages innermost ("arbitrary": online-softmax
    state persists in VMEM scratch across page steps, exactly the online
    kernels' scheme with the page table standing in for ONLINE_BLOCK_TABLE
    block indexing). ``pt_ref``/``pos_ref`` are the scalar-prefetched page
    table and query positions — the same values the in_specs' index_maps
    used to pick which physical page this step streams."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    pos = pos_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # A page whose first slot is past the query position is fully masked.
    @pl.when(p * page_size <= pos)
    def _compute():
        q = _mxu(q_ref[0])                       # [H, D]
        k = _mxu(k_ref[0])                       # [page_size, Hkv, D]
        v = _mxu(v_ref[0])
        H = q.shape[0]
        G = H // num_kv_heads
        # GQA without materializing repeated KV heads: per KV head, the G
        # grouped query heads share one [page_size, D] key tile.
        logits = jnp.concatenate([
            jax.lax.dot_general(
                q[h * G:(h + 1) * G], k[:, h, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            for h in range(num_kv_heads)], axis=0) * sm_scale  # [H, ps]
        k_pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(k_pos <= pos, logits, NEG_INF)

        m_prev = m_ref[:, :1]                    # [H, 1] (lane-bcast)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        prob = jnp.exp(logits - m_new)           # [H, ps]
        correction = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * correction + jnp.sum(prob, axis=1, keepdims=True),
            l_ref.shape)
        pv = jnp.concatenate([
            jax.lax.dot_general(
                prob[h * G:(h + 1) * G].astype(v.dtype), v[:, h, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for h in range(num_kv_heads)], axis=0)  # [H, D]
        acc_ref[:] = acc_ref[:] * correction + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(p == n_pages - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pages, v_pages, page_table, positions,
                         sm_scale):
    B, H, D = q.shape
    _, page_size, num_kv_heads, _ = k_pages.shape
    max_pages = page_table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, p, pt, pos: (b, 0, 0)),
            pl.BlockSpec((1, page_size, num_kv_heads, D),
                         lambda b, p, pt, pos: (pt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page_size, num_kv_heads, D),
                         lambda b, p, pt, pos: (pt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, p, pt, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),   # m
            pltpu.VMEM((H, 128), jnp.float32),   # l
            pltpu.VMEM((H, D), jnp.float32),     # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, sm_scale=sm_scale,
                          page_size=page_size, num_kv_heads=num_kv_heads),
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        # On cpu the identical kernel body runs interpreted — the parity
        # tests exercise this exact code path.
        interpret=not backend.on_tpu(),
    )(page_table, positions, q, k_pages, v_pages)


def _paged_decode_xla(q, k_pages, v_pages, page_table, positions, sm_scale):
    """Gather-based reference/CPU path: materialize each request's logical
    KV view from the pool, then masked softmax in fp32 (same math as the
    ``attention.dot_product_attention`` oracle the training forward uses —
    the prefill/decode parity tests lean on that)."""
    B, H, D = q.shape
    _, page_size, num_kv_heads, _ = k_pages.shape
    S = page_table.shape[1] * page_size
    flat = page_table.reshape(-1)
    k = jnp.take(k_pages, flat, axis=0).reshape(B, S, num_kv_heads, D)
    v = jnp.take(v_pages, flat, axis=0).reshape(B, S, num_kv_heads, D)
    G = H // num_kv_heads
    qg = q.reshape(B, num_kv_heads, G, D)
    logits = jnp.einsum("bhgd,bshd->bhgs", _mxu(qg), _mxu(k),
                        preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.arange(S)[None, :] <= positions[:, None]          # [B, S]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    prob = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", prob, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, positions,
                           impl: str = "auto"):
    """Decode-mode attention through a paged KV cache.

    q:          [B, H, D] — ONE query token per request (the decode step)
    k_pages:    [num_pages, page_size, Hkv, D] pool (one layer's K)
    v_pages:    same shape, the layer's V
    page_table: [B, max_pages] int32 physical page ids; entries past a
                request's length may be garbage (they are masked)
    positions:  [B] int32 position of the query token; keys at positions
                <= positions[b] are attended (the query's own K/V must
                already be appended — the model appends before attending)

    GQA is served natively: KV heads stay folded (H % Hkv == 0), queries
    are grouped per KV head. ``impl``: "auto" picks the Pallas page-table
    kernel on TPU and the gather-based XLA path elsewhere; "pallas"/"xla"
    force (the Pallas kernel runs interpreted on cpu — that is the
    parity-test configuration).
    """
    B, H, D = q.shape
    num_kv_heads = k_pages.shape[2]
    if H % num_kv_heads:
        raise ValueError(f"H={H} not a multiple of Hkv={num_kv_heads}")
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown paged decode impl {impl!r}")
    sm_scale = 1.0 / math.sqrt(D)
    page_table = page_table.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    if impl == "pallas" or (impl == "auto" and backend.on_tpu()):
        return _paged_decode_pallas(q, k_pages, v_pages, page_table,
                                    positions, sm_scale)
    return _paged_decode_xla(q, k_pages, v_pages, page_table, positions,
                             sm_scale)


def paged_prefill_attention(q, k_pages, v_pages, page_table, positions):
    """Prefill-window attention against a paged KV cache with history.

    q:          [B, S, H, D] — a window of query tokens starting mid-
                sequence (suffix prefill after a prefix-cache splice, or
                a later chunk of a chunked prefill)
    k_pages:    [num_pages, page_size, Hkv, D] pool (one layer's K)
    v_pages:    same shape, the layer's V
    page_table: [B, max_pages] int32 physical page ids
    positions:  [B, S] int32 logical position of each query token; keys
                at pool positions <= positions[b, s] are attended, which
                is causal masking that also covers the history before
                the window (those keys came from cached/earlier pages —
                the window's own K/V are appended before this runs).

    Plain-causal attention is wrong here: it would start every window at
    position 0. This is the gather-based XLA path (fp32 softmax, GQA
    grouped like ``_paged_decode_xla``); decode-bound serving keeps the
    Pallas budget on the decode kernel.
    """
    B, S, H, D = q.shape
    _, page_size, num_kv_heads, _ = k_pages.shape
    if H % num_kv_heads:
        raise ValueError(f"H={H} not a multiple of Hkv={num_kv_heads}")
    sm_scale = 1.0 / math.sqrt(D)
    page_table = page_table.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    T = page_table.shape[1] * page_size
    flat = page_table.reshape(-1)
    k = jnp.take(k_pages, flat, axis=0).reshape(B, T, num_kv_heads, D)
    v = jnp.take(v_pages, flat, axis=0).reshape(B, T, num_kv_heads, D)
    G = H // num_kv_heads
    qg = q.reshape(B, S, num_kv_heads, G, D)
    logits = jnp.einsum("bshgd,bthd->bhgst", _mxu(qg), _mxu(k),
                        preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.arange(T)[None, None, :] <= positions[:, :, None]  # [B, S, T]
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    prob = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", prob, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)

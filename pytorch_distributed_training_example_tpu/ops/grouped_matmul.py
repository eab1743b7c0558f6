"""Ragged grouped expert matmul (Pallas TPU): the dropless-MoE kernel.

``gmm(x [Tk, d], w [E, d, f], group_starts [E], group_counts [E]) -> [Tk, f]``
computes ``out[r] = x[r] @ w[e]`` for every row ``r`` of expert ``e``'s
contiguous segment ``[starts[e], starts[e] + counts[e])`` of the sorted
token layout that ``routing_stats()``'s stable argsort already produces
(parallel/moe.py). This is the MegaBlocks reformulation of the expert
FFN: no ``[E, C, d]`` capacity buffer is ever materialized and no token
is dropped — the kernel tiles the token dimension and a scalar-prefetched
per-tile expert index steers each tile's ``[d, bf]`` weight block straight
out of the stacked ``[E, d, f]`` weights (the BlockSpec index_map reads
the prefetched tile->expert table, so weight traffic is one block per
tile, reused across a segment's consecutive tiles).

Raggedness is handled by a tile-aligned relayout with STATIC shapes:
each expert's segment is padded up to a whole number of ``bt``-row tiles
(empty experts keep one all-padding tile so every expert's backward
weight block is visited and zero-initialized). The padded row count is
bounded by ``ceil(Tk/bt)*bt + E*bt`` independent of any capacity factor,
so the relayout is two O(Tk·d) gathers (in, out) against int32 index
vectors built from the segment offsets — the same compact-index
machinery the sort dispatch uses, never an ``[E, C]`` slot table.

Backward is a ``custom_vjp``:

- ``dx = gmm(dout, w^T)`` over the identical padded layout (the ISSUE's
  "gmm against transposed weights" — the swap of the weight's last two
  axes is left to XLA),
- ``dw[e] = sum over expert e's segment of x_r^T dout_r`` via a second
  kernel whose ``[1, d, bf]`` output block is a revisited accumulator:
  the grid walks token tiles innermost in segment order (sequential
  ``"arbitrary"`` dimension semantics), a prefetched first-tile flag
  zero-initializes each expert's block, and every tile of that expert
  accumulates into it before the block index moves on — segment-wise
  accumulation with no atomics and no ``[E, Tk]`` masks.

On the ``cpu`` platform both kernels run in interpret mode (numerically the
same program), so CPU tests and dryruns validate the real kernel bodies
(``ops/backend.py``). fp32 accumulation everywhere (``preferred_element_type``); outputs are
cast to the input dtype, gradients to the primal dtypes. Tile sizes are
powers of two down to 8 rows — Mosaic-friendly at bench shapes; lane-dim
(128) padding of small test shapes is interpret-mode territory and part
of the chip A/B, not correctness (PROFILE_MOE.md r14 hooks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_example_tpu.ops import backend


def _block_rows(n_rows: int, num_experts: int) -> int:
    """Power-of-two token-tile height balancing grid length against the
    worst-case padding ``E * bt`` (every expert rounds up at most one
    partial tile): the tile is capped so padding stays within ~1/8 of the
    real rows. Tiny test shapes bottom out at 8-row tiles (mostly-padding
    layouts are interpret-mode territory); the llama_moe bench shape
    (kT=16384, E=8) gets 256-row tiles — 12.5% worst-case padding instead
    of the 25% a 512-row tile costs, at twice the grid length. 512 stays
    the hard ceiling (MXU-friendly multiples of 128 beyond that buy no
    reuse: the weight block is already resident across a segment's tiles).
    """
    E = max(num_experts, 1)
    target = max(n_rows // (8 * E), 8)
    bt = 8
    while bt * 2 <= min(target, 512):
        bt *= 2
    return bt


def _block_cols(n: int) -> int:
    """Largest nice power-of-two column block; odd widths get one block."""
    for bc in (512, 256, 128, 64, 32, 16, 8):
        if n % bc == 0:
            return bc
    return n


def _padded_layout(group_starts, group_counts, n_rows: int,
                   num_experts: int, bt: int):
    """Tile-aligned relayout of the ragged segments, static shapes.

    Returns ``(tile_expert [G], tile_first [G], src [G*bt], dst [n_rows])``
    (all int32): padded row ``r`` reads input row ``src[r]`` (``n_rows`` =
    the appended zero row), tile ``g`` multiplies expert ``tile_expert[g]``'s
    weights (``tile_first[g]`` marks the expert's first tile — the backward
    accumulator init), and logical output row ``j`` reads padded row
    ``dst[j]``. ``G = ceil(n_rows/bt) + num_experts`` is a static bound on
    ``sum(max(ceil(counts/bt), 1))`` — every expert rounds up at most one
    partial tile and empty experts keep one tile each.
    """
    E = num_experts
    G = -(-n_rows // bt) + E
    tiles_per_e = jnp.maximum(-(-group_counts // bt), 1)          # [E]
    tile_starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(tiles_per_e)[:-1].astype(jnp.int32)])         # [E]
    tile_ids = jnp.arange(G, dtype=jnp.int32)
    tile_expert = (jnp.searchsorted(tile_starts, tile_ids, side="right")
                   .astype(jnp.int32) - 1)                        # [G]
    tile_first = (tile_ids == tile_starts[tile_expert]).astype(jnp.int32)

    padded_starts = tile_starts * bt                              # [E]
    r = jnp.arange(G * bt, dtype=jnp.int32)
    e_r = tile_expert[r // bt]
    off = r - padded_starts[e_r]
    src = jnp.where(off < group_counts[e_r], group_starts[e_r] + off,
                    n_rows).astype(jnp.int32)

    j = jnp.arange(n_rows, dtype=jnp.int32)
    # Owner of logical row j: highest expert with start <= j. Duplicate
    # starts (empty experts) resolve to the non-empty owner because empty
    # segments have zero width.
    e_j = (jnp.searchsorted(group_starts, j, side="right")
           .astype(jnp.int32) - 1)
    dst = (padded_starts[e_j] + (j - group_starts[e_j])).astype(jnp.int32)
    return tile_expert, tile_first, src, dst


def _gmm_kernel(te_ref, x_ref, w_ref, out_ref):
    del te_ref  # consumed by the index_maps
    out_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _gmm_call(x_pad, w, tile_expert, bt: int, out_dtype):
    Tp, d = x_pad.shape
    E, _, f = w.shape
    bf = _block_cols(f)
    return pl.pallas_call(
        _gmm_kernel,
        name="grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(f // bf, Tp // bt),
            in_specs=[
                pl.BlockSpec((bt, d), lambda jc, g, te: (g, 0)),
                pl.BlockSpec((1, d, bf), lambda jc, g, te: (te[g], 0, jc)),
            ],
            out_specs=pl.BlockSpec((bt, bf), lambda jc, g, te: (g, jc)),
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, f), out_dtype),
        # Sequential grid: consecutive same-expert tiles keep the weight
        # block resident instead of re-fetching it.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=not backend.on_tpu(),
    )(tile_expert, x_pad, w)


def _gmm_dw_kernel(te_ref, tf_ref, x_ref, g_ref, dw_ref):
    del te_ref
    g_idx = pl.program_id(1)

    # First tile of this expert's segment (per column block): the [1, d, bf]
    # output block is revisited by every later tile of the segment, so
    # zero it exactly once before accumulating.
    @pl.when(tf_ref[g_idx] == 1)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += jax.lax.dot_general(
        x_ref[...], g_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[None].astype(dw_ref.dtype)


def _gmm_dw_call(x_pad, g_pad, tile_expert, tile_first, num_experts: int,
                 bt: int):
    Tp, d = x_pad.shape
    f = g_pad.shape[1]
    bf = _block_cols(f)
    return pl.pallas_call(
        _gmm_dw_kernel,
        name="grouped_matmul_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # Token tiles are the INNER grid dim: for each column block the
            # tiles of one expert are visited consecutively (the padded
            # layout is segment-sorted), which is what makes the revisited
            # dw block a valid accumulator under sequential semantics.
            grid=(f // bf, Tp // bt),
            in_specs=[
                pl.BlockSpec((bt, d), lambda jc, g, te, tf: (g, 0)),
                pl.BlockSpec((bt, bf), lambda jc, g, te, tf: (g, jc)),
            ],
            out_specs=pl.BlockSpec(
                (1, d, bf), lambda jc, g, te, tf: (te[g], 0, jc)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_experts, d, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=not backend.on_tpu(),
    )(tile_expert, tile_first, x_pad, g_pad)


def _pad_rows(x, src):
    """Gather rows into the tile-aligned layout; index n_rows reads zeros."""
    return jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])[src]


@jax.custom_vjp
def _gmm_padded(x_pad, w, tile_expert, tile_first):
    """Kernel entry over the PADDED layout: [Tp, d] -> [Tp, f] (no relayout).

    The tile height is implied by the shapes (``bt = Tp // G``). Padded rows
    are zero on the way in and garbage-free on the way out (zero rows times
    weights are zero), so callers can chain padded-space ops — the grouped
    FFN runs up-proj -> gelu -> down-proj entirely in this layout and pays
    for ONE relayout round trip instead of one per matmul.
    """
    bt = x_pad.shape[0] // tile_expert.shape[0]
    return _gmm_call(x_pad, w, tile_expert, bt, x_pad.dtype)


def _gmm_padded_fwd(x_pad, w, tile_expert, tile_first):
    return _gmm_padded(x_pad, w, tile_expert, tile_first), (
        x_pad, w, tile_expert, tile_first)


def _gmm_padded_bwd(res, dout_pad):
    x_pad, w, tile_expert, tile_first = res
    bt = x_pad.shape[0] // tile_expert.shape[0]
    dx_pad = _gmm_call(dout_pad, jnp.swapaxes(w, 1, 2), tile_expert, bt,
                       x_pad.dtype)
    dw = _gmm_dw_call(x_pad, dout_pad, tile_expert, tile_first,
                      w.shape[0], bt).astype(w.dtype)
    zeros = functools.partial(np.zeros, dtype=jax.dtypes.float0)
    return dx_pad, dw, zeros(tile_expert.shape), zeros(tile_first.shape)


_gmm_padded.defvjp(_gmm_padded_fwd, _gmm_padded_bwd)


def grouped_ffn(x, w_up, w_down, group_starts, group_counts):
    """Full grouped expert MLP: gelu(x @ w_up[e]) @ w_down[e] per segment.

    Composition of two ``gmm``s that stays in the tile-padded layout across
    the activation, so the mid-FFN unpad/re-pad gathers (and their
    transposes in the backward) vanish — the relayout is paid once per FFN
    instead of once per matmul. Same math as ``ExpertFFN``'s einsums: fp32
    accumulation, gelu in the compute dtype (gelu keeps the padding rows at
    exactly zero). The boundary gathers differentiate through standard AD;
    the kernels through ``_gmm_padded``'s custom_vjp.
    """
    Tk = x.shape[0]
    E = w_up.shape[0]
    bt = _block_rows(Tk, E)
    tile_expert, tile_first, src, dst = _padded_layout(
        group_starts, group_counts, Tk, E, bt)
    x_pad = _pad_rows(x, src)
    h_pad = _gmm_padded(x_pad, w_up, tile_expert, tile_first)
    h_pad = jax.nn.gelu(h_pad)
    out_pad = _gmm_padded(h_pad, w_down, tile_expert, tile_first)
    return out_pad[dst]


def _gmm_impl(x, w, group_starts, group_counts):
    Tk, d = x.shape
    E = w.shape[0]
    bt = _block_rows(Tk, E)  # static (shape-derived) — recomputed in bwd
    tile_expert, tile_first, src, dst = _padded_layout(
        group_starts, group_counts, Tk, E, bt)
    out_pad = _gmm_call(_pad_rows(x, src), w, tile_expert, bt, x.dtype)
    return out_pad[dst], (tile_expert, tile_first, src, dst)


@jax.custom_vjp
def gmm(x, w, group_starts, group_counts):
    """Grouped/ragged expert matmul over contiguous per-expert segments.

    ``out[r] = x[r] @ w[e]`` for rows ``r`` in segment
    ``[group_starts[e], group_starts[e] + group_counts[e])``; segments must
    tile ``[0, Tk)`` in expert order (``group_starts`` = exclusive cumsum of
    ``group_counts``, ``sum == Tk``) — exactly what ``routing_stats()``
    hands out. fp32 accumulation, output in ``x.dtype``. Differentiable in
    ``x`` and ``w``; the integer segment offsets get float0 cotangents.
    """
    out, _ = _gmm_impl(x, w, group_starts, group_counts)
    return out


def _gmm_fwd(x, w, group_starts, group_counts):
    out, layout = _gmm_impl(x, w, group_starts, group_counts)
    return out, (x, w, group_starts, group_counts, layout)


def _gmm_bwd(res, dout):
    x, w, group_starts, group_counts, layout = res
    tile_expert, tile_first, src, dst = layout
    bt = _block_rows(x.shape[0], w.shape[0])
    dout_pad = _pad_rows(dout, src)
    # dx: the same grouped matmul against the transposed weight blocks,
    # reusing the tile layout (dout rows live in the same segments as x).
    dx_pad = _gmm_call(dout_pad, jnp.swapaxes(w, 1, 2), tile_expert, bt,
                       x.dtype)
    dx = dx_pad[dst]
    # dw: segment-wise accumulation — padded rows are zero on both sides,
    # so they contribute nothing; empty experts' single all-padding tile
    # zero-initializes their block.
    dw = _gmm_dw_call(_pad_rows(x, src), dout_pad, tile_expert, tile_first,
                      w.shape[0], bt).astype(w.dtype)
    zeros = functools.partial(np.zeros, dtype=jax.dtypes.float0)
    return dx, dw, zeros(group_starts.shape), zeros(group_counts.shape)


gmm.defvjp(_gmm_fwd, _gmm_bwd)

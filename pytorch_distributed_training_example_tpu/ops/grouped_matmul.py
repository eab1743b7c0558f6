"""Ragged grouped expert matmul (Pallas TPU): the dropless-MoE kernel.

``gmm(x [Tk, d], w [E, d, f], group_starts [E], group_counts [E]) -> [Tk, f]``
computes ``out[r] = x[r] @ w[e]`` for every row ``r`` of expert ``e``'s
contiguous segment ``[starts[e], starts[e] + counts[e])`` of a token
layout sorted by expert (``parallel/moe._plan`` makes the padded form of it
directly and calls ``_gmm_padded`` and the FFN forms). This is the
MegaBlocks reformulation of the expert FFN: no ``[E, C, d]`` capacity
buffer is ever materialized and no token is dropped — the kernel tiles
the token dimension and a scalar-prefetched per-tile expert index steers
each tile's ``[d, bf]`` weight block straight out of the stacked
``[E, d, f]`` weights (the BlockSpec index_map reads the prefetched
tile->expert table, so weight traffic is one block per tile, reused across
a segment's consecutive tiles).

Raggedness is handled by a tile-aligned relayout with STATIC shapes:
each expert's segment is padded up to a whole number of ``bt``-row tiles
(empty experts keep one all-padding tile so every expert's backward
weight block is visited and zero-initialized). The padded row count is
bounded by ``ceil(Tk/bt)*bt + E*bt`` independent of any capacity factor,
so the relayout is two O(Tk·d) gathers (in, out) against int32 index
vectors built from the segment offsets, never an ``[E, C]`` slot table.

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
powers of two down to 8 rows — Mosaic-friendly at the cells' shapes (on the
chip the kernels are read by name under the ``per_layer`` metric
``moe_experts_ms``); lane-dim (128) padding of small test shapes is
interpret-mode territory, not correctness.
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
    layouts are interpret-mode territory); kT=16384 rows over E=8
    get 256-row tiles — 12.5% worst-case padding instead
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


#: Largest weight (or weight-gradient) block a kernel keeps in VMEM; Pallas
#: double-buffers it, and the row tiles ride beside it under the 16 MB scope.
_BLOCK_BYTES = 4 * 1024 * 1024


def _block_cols(n: int, depth: int = 1, itemsize: int = 4) -> int:
    """Largest nice power-of-two column block whose ``[depth, block]`` tile
    of ``itemsize`` bytes stays within ``_BLOCK_BYTES`` (a wider block reads
    each row tile fewer times); odd widths get one block.

    A width over one lane tile that is no multiple of 256 (1856 = 14.5 tiles,
    2688 = 21) has no such divisor that Mosaic takes (a block's last dimension
    is whole 128-lane tiles or the whole array's): it gets blocks of whole
    lane tiles, the fewest that fit the bytes and then the narrowest of those,
    and where the width is no whole number of tiles the last block is
    part-filled (the callers' grids are ``pl.cdiv``: Pallas reads what lies
    past the array as padding and drops what is written there, and no
    contraction here runs over a blocked dimension)."""
    if n > 128 and n % 256:
        tiles = -(-n // 128)
        fit = [k for k in range(1, n // 128 + 1)
               if depth * 128 * k * itemsize <= _BLOCK_BYTES] or [1]
        return 128 * min(fit, key=lambda k: (-(-tiles // k), k))
    blocks = [bc for bc in (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
              if n % bc == 0]
    for bc in blocks:
        if depth * bc * itemsize <= _BLOCK_BYTES:
            return bc
    return blocks[-1] if blocks else n


def num_tiles(group_counts, bt: int):
    """Tiles the padded layout uses: whole ones for every segment, and one
    for an empty segment."""
    return jnp.sum(jnp.maximum(-(-group_counts // bt), 1)).astype(jnp.int32)


def _padded_layout(group_starts, group_counts, n_rows: int,
                   num_experts: int, bt: int, max_tiles: int | None = None):
    """Tile-aligned relayout of the ragged segments, static shapes.

    Returns ``(tiles, src [G*bt], dst [n_rows])`` (all int32) with ``tiles
    = (tile_expert [G], tile_first [G], num_tiles [1])``: padded row ``r``
    reads input row ``src[r]`` (``n_rows`` = no row: zeros), tile ``g``
    multiplies expert ``tile_expert[g]``'s weights (``tile_first[g]`` marks
    the expert's first tile — the backward accumulator init), and logical
    output row ``j`` reads padded row ``dst[j]``. ``G = ceil(n_rows/bt) +
    num_experts`` is a static bound on ``num_tiles = sum(max(ceil(counts/bt),
    1))`` — every expert rounds up at most one partial tile and empty experts
    keep one tile each; the kernels run the first ``num_tiles`` tiles and
    pass over the rest, whose output rows nobody reads. A caller that knows
    a tighter bound on ``num_tiles`` gives it as ``max_tiles``.

    The segments may end before ``n_rows`` (an expert layer that holds a part
    of the experts sorts the rows of the others behind its own): such rows
    belong to no tile and read ``dst = G*bt``, past the padded array.
    """
    E = num_experts
    G = -(-n_rows // bt) + E
    if max_tiles is not None:
        G = min(G, max_tiles)
    tiles_per_e = jnp.maximum(-(-group_counts // bt), 1)          # [E]
    tile_starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(tiles_per_e)[:-1].astype(jnp.int32)])         # [E]
    tile_ids = jnp.arange(G, dtype=jnp.int32)
    tile_expert = (jnp.searchsorted(tile_starts, tile_ids, side="right")
                   .astype(jnp.int32) - 1)                        # [G]
    tile_first = (tile_ids == tile_starts[tile_expert]).astype(jnp.int32)
    used = num_tiles(group_counts, bt).reshape(1)

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
    dst = jnp.where(j < group_starts[-1] + group_counts[-1], dst, G * bt)
    return (tile_expert, tile_first, used), src, dst


def _last_tile(g, nt):
    """Block index of tile ``g``: a tile past the last one in use repeats
    that one's, so the pipeline moves nothing for it."""
    return jnp.minimum(g, nt[0] - 1)


def _gmm_kernel(te_ref, tf_ref, nt_ref, x_ref, w_ref, out_ref, *,
                transposed: bool):
    del te_ref, tf_ref  # consumed by the index_maps / the dw kernel

    # A tile past the last one in use repeats that one's block indices (no
    # DMA) and leaves the output block as the last tile wrote it.
    @pl.when(pl.program_id(1) < nt_ref[0])
    def _tile():
        out_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _gmm_call(x_pad, w, tiles, bt: int, out_dtype, transposed: bool = False):
    """``x_pad [Tp, d] @ w[e] [d, f]`` a tile, or with ``transposed`` ``x_pad
    [Tp, f] @ w[e]^T``: the backward's dx reads the weight as it lies and
    contracts its last dimension, so no transposed copy is made."""
    Tp, d = x_pad.shape
    f = w.shape[1] if transposed else w.shape[2]
    bf = _block_cols(f, d, w.dtype.itemsize)
    if transposed:
        w_spec = pl.BlockSpec((1, bf, d), lambda jc, g, te, tf, nt: (
            te[_last_tile(g, nt)], jc, 0))
    else:
        w_spec = pl.BlockSpec((1, d, bf), lambda jc, g, te, tf, nt: (
            te[_last_tile(g, nt)], 0, jc))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        name="grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(f, bf), Tp // bt),
            in_specs=[
                pl.BlockSpec((bt, d),
                             lambda jc, g, te, tf, nt: (_last_tile(g, nt), 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec(
                (bt, bf), lambda jc, g, te, tf, nt: (_last_tile(g, nt), jc)),
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, f), out_dtype),
        # Sequential grid: consecutive same-expert tiles keep the weight
        # block resident instead of re-fetching it.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=not backend.on_tpu(),
    )(*tiles, x_pad, w)


def _gmm_dw_kernel(te_ref, tf_ref, nt_ref, x_ref, g_ref, dw_ref):
    del te_ref
    g_idx = pl.program_id(1)
    live = g_idx < nt_ref[0]

    # First tile of this expert's segment (per column block): the [1, d, bf]
    # output block is revisited by every later tile of the segment, so
    # zero it exactly once before accumulating.
    @pl.when(live & (tf_ref[g_idx] == 1))
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(live)
    def _tile():
        dw_ref[...] += jax.lax.dot_general(
            x_ref[...], g_ref[...],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None].astype(dw_ref.dtype)


def _gmm_dw_call(x_pad, g_pad, tiles, num_experts: int, bt: int):
    Tp, d = x_pad.shape
    f = g_pad.shape[1]
    bf = _block_cols(f, d, 4)
    return pl.pallas_call(
        _gmm_dw_kernel,
        name="grouped_matmul_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # Token tiles are the INNER grid dim: for each column block the
            # tiles of one expert are visited consecutively (the padded
            # layout is segment-sorted), which is what makes the revisited
            # dw block a valid accumulator under sequential semantics.
            grid=(pl.cdiv(f, bf), Tp // bt),
            in_specs=[
                pl.BlockSpec((bt, d),
                             lambda jc, g, te, tf, nt: (_last_tile(g, nt), 0)),
                pl.BlockSpec((bt, bf), lambda jc, g, te, tf, nt: (
                    _last_tile(g, nt), jc)),
            ],
            out_specs=pl.BlockSpec(
                (1, d, bf),
                lambda jc, g, te, tf, nt: (te[_last_tile(g, nt)], 0, jc)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_experts, d, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=not backend.on_tpu(),
    )(*tiles, x_pad, g_pad)


def _pad_rows(x, src):
    """Gather rows into the tile-aligned layout; index n_rows reads zeros."""
    return jnp.take(x, src, axis=0, mode="fill", fill_value=0)


def _float0(tiles):
    return tuple(np.zeros(t.shape, jax.dtypes.float0) for t in tiles)


@jax.custom_vjp
def _gmm_padded(x_pad, w, tiles):
    """Kernel entry over the PADDED layout: [Tp, d] -> [Tp, f] (no relayout).

    ``tiles`` is ``_padded_layout``'s triple; the tile height is implied by
    the shapes (``bt = Tp // G``). Padded rows of the tiles in use are zero
    on the way in and on the way out (zero rows times weights are zero); the
    rows of the tiles past ``num_tiles`` are never written, here or in the
    backward, and nothing may read them. Callers chain padded-space ops —
    the grouped FFNs run up-proj -> activation -> down-proj entirely in this
    layout and pay for ONE relayout round trip instead of one per matmul.
    """
    bt = x_pad.shape[0] // tiles[0].shape[0]
    return _gmm_call(x_pad, w, tiles, bt, x_pad.dtype)


def _gmm_padded_fwd(x_pad, w, tiles):
    return _gmm_padded(x_pad, w, tiles), (x_pad, w, tiles)


def _gmm_padded_bwd(res, dout_pad):
    x_pad, w, tiles = res
    bt = x_pad.shape[0] // tiles[0].shape[0]
    dx_pad = _gmm_call(dout_pad, w, tiles, bt, x_pad.dtype, transposed=True)
    dw = _gmm_dw_call(x_pad, dout_pad, tiles, w.shape[0], bt).astype(w.dtype)
    return dx_pad, dw, _float0(tiles)


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
    tiles, src, dst = _padded_layout(group_starts, group_counts, Tk, E, bt)
    x_pad = _pad_rows(x, src)
    h_pad = _gmm_padded(x_pad, w_up, tiles)
    h_pad = jax.nn.gelu(h_pad)
    out_pad = _gmm_padded(h_pad, w_down, tiles)
    return out_pad[dst]


#: The gate's function, by the name a caller gives as ``act``: what the two
#: expert families state (SwiGLU and ReGLU experts), nothing more.
GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _gated(gate, up, act="silu"):
    """``act(gate) * up`` in float32, rounded once."""
    return (GATES[act](gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def gated_down_padded(gate, up, w_down, tiles, act="silu"):
    """The gated product of the two projections through the down
    projection: ``gated_ffn_padded``'s result from its own ``gate`` and
    ``up``."""
    return _gmm_padded(_gated(gate, up, act), w_down, tiles)


def gated_ffn_padded_kept(x_pad, w_gate, w_up, w_down, tiles, act="silu"):
    """``gated_ffn_padded`` with the two projections it gated beside it:
    ``(y_pad, gate, up)``, all in the compute dtype. With ``x_pad`` they are
    all that ``gated_ffn_padded_bwd`` reads of the forward."""
    gate = _gmm_padded(x_pad, w_gate, tiles)
    up = _gmm_padded(x_pad, w_up, tiles)
    return gated_down_padded(gate, up, w_down, tiles, act), gate, up


def gated_ffn_padded(x_pad, w_gate, w_up, w_down, tiles, act="silu"):
    """Gated grouped expert MLP over rows ALREADY in the padded layout:
    ``(act(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]`` a tile, ``act`` a
    key of ``GATES`` and static (``"silu"``: SwiGLU experts; ``"relu"``:
    ReGLU experts). The caller owns the relayout (``_padded_layout``'s
    ``src`` and ``dst``): an expert layer that holds a part of the experts
    gathers token rows straight into this layout and combines straight out
    of it. The gate is multiplied in float32 and rounded once."""
    return gated_ffn_padded_kept(x_pad, w_gate, w_up, w_down, tiles, act)[0]


def gated_ffn_padded_bwd(x_pad, gate, up, w_gate, w_up, w_down, tiles, dy_pad,
                         act="silu"):
    """What differentiating ``gated_ffn_padded`` gives for ``dy_pad``, from
    the forward's own ``gate`` and ``up``: ``(dx_pad, dw_gate, dw_up,
    dw_down)``, each product as ``_gmm_padded``'s rule makes it and the
    gate's derivative as AD makes it for ``act`` (ReLU's is 0 at 0)."""
    h_pad, gated_vjp = jax.vjp(functools.partial(_gated, act=act), gate, up)
    dh_pad, dw_down, _ = _gmm_padded_bwd((h_pad, w_down, tiles), dy_pad)
    dgate, dup = gated_vjp(dh_pad)
    dx_gate, dw_gate, _ = _gmm_padded_bwd((x_pad, w_gate, tiles), dgate)
    dx_up, dw_up, _ = _gmm_padded_bwd((x_pad, w_up, tiles), dup)
    return dx_gate + dx_up, dw_gate, dw_up, dw_down


#: An ungated expert's activation, by the name a caller gives as ``act``:
#: the squared ReLU of the ``nemotron_h`` experts.
ACTS = {"relu2": lambda v: jnp.square(jax.nn.relu(v))}


def _activated(up, act="relu2"):
    """``act(up)`` in float32, rounded once."""
    return ACTS[act](up.astype(jnp.float32)).astype(up.dtype)


def ungated_down_padded(up, w_down, tiles, act="relu2"):
    """The activated up projection through the down projection:
    ``ungated_ffn_padded_kept``'s result from its own ``up``."""
    return _gmm_padded(_activated(up, act), w_down, tiles)


def ungated_ffn_padded_kept(x_pad, w_up, w_down, tiles, act="relu2"):
    """Ungated (two-matrix) grouped expert MLP over rows already in the
    padded layout, ``act(x @ w_up[e]) @ w_down[e]`` a tile (``act`` a key of
    ``ACTS``, static), with the projection it activated beside it: ``(y_pad,
    up)`` in the compute dtype. With ``x_pad``, ``up`` is all that
    ``ungated_ffn_padded_bwd`` reads of the forward. A padding row stays zero
    through the activation (``act(0) = 0``)."""
    up = _gmm_padded(x_pad, w_up, tiles)
    return ungated_down_padded(up, w_down, tiles, act), up


def ungated_ffn_padded_bwd(x_pad, up, w_up, w_down, tiles, dy_pad,
                           act="relu2"):
    """What differentiating ``ungated_ffn_padded_kept``'s ``y_pad`` gives for
    ``dy_pad``, from the forward's own ``up``: ``(dx_pad, dw_up, dw_down)``,
    as ``gated_ffn_padded_bwd`` for the gated form."""
    h_pad, act_vjp = jax.vjp(functools.partial(_activated, act=act), up)
    dh_pad, dw_down, _ = _gmm_padded_bwd((h_pad, w_down, tiles), dy_pad)
    dx_pad, dw_up, _ = _gmm_padded_bwd((x_pad, w_up, tiles),
                                       act_vjp(dh_pad)[0])
    return dx_pad, dw_up, dw_down


#: The expert forms by how many matrices an expert has: ``(kept, down, bwd)``.
#: ``kept(x_pad, *experts, tiles, act=)`` gives ``(y_pad, *pre)``, ``pre`` the
#: pre-activations a backward reads; ``down(*pre, w_down, tiles, act=)`` gives
#: ``y_pad`` again from them; ``bwd(x_pad, *pre, *experts, tiles, dy_pad,
#: act=)`` gives ``(dx_pad, *d_experts)``.
FFN_FORMS = {
    3: (gated_ffn_padded_kept, gated_down_padded, gated_ffn_padded_bwd),
    2: (ungated_ffn_padded_kept, ungated_down_padded, ungated_ffn_padded_bwd),
}


def _gmm_impl(x, w, group_starts, group_counts):
    Tk, d = x.shape
    E = w.shape[0]
    bt = _block_rows(Tk, E)  # static (shape-derived) — recomputed in bwd
    tiles, src, dst = _padded_layout(group_starts, group_counts, Tk, E, bt)
    out_pad = _gmm_call(_pad_rows(x, src), w, tiles, bt, x.dtype)
    return out_pad[dst], (tiles, src, dst)


@jax.custom_vjp
def gmm(x, w, group_starts, group_counts):
    """Grouped/ragged expert matmul over contiguous per-expert segments.

    ``out[r] = x[r] @ w[e]`` for rows ``r`` in segment
    ``[group_starts[e], group_starts[e] + group_counts[e])``; segments must
    tile ``[0, Tk)`` in expert order (``group_starts`` = exclusive cumsum of
    ``group_counts``, ``sum == Tk``). fp32 accumulation, output in
    ``x.dtype``. Differentiable in ``x`` and ``w``; the integer segment
    offsets get float0 cotangents.
    """
    out, _ = _gmm_impl(x, w, group_starts, group_counts)
    return out


def _gmm_fwd(x, w, group_starts, group_counts):
    out, layout = _gmm_impl(x, w, group_starts, group_counts)
    return out, (x, w, group_starts, group_counts, layout)


def _gmm_bwd(res, dout):
    x, w, group_starts, group_counts, layout = res
    tiles, src, dst = layout
    bt = _block_rows(x.shape[0], w.shape[0])
    dout_pad = _pad_rows(dout, src)
    # dx: the same grouped matmul against the weight blocks read transposed,
    # reusing the tile layout (dout rows live in the same segments as x).
    dx_pad = _gmm_call(dout_pad, w, tiles, bt, x.dtype, transposed=True)
    dx = dx_pad[dst]
    # dw: segment-wise accumulation — padded rows are zero on both sides,
    # so they contribute nothing; empty experts' single all-padding tile
    # zero-initializes their block.
    dw = _gmm_dw_call(_pad_rows(x, src), dout_pad, tiles, w.shape[0],
                      bt).astype(w.dtype)
    return (dx, dw) + _float0((group_starts, group_counts))


gmm.defvjp(_gmm_fwd, _gmm_bwd)

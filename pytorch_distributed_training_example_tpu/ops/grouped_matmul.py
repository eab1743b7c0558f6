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

The gated expert FFN (``gated_ffn_padded``: SwiGLU and ReGLU experts) has six
kernels of its own over the same layout, grid and prefetched tables, so that
a row tile meets each expert matrix once and the gate's elementwise work runs
on the tiles in use only: ``gated_ffn_up`` (gate and up in one pass
over the row tile, both weight blocks side by side in VMEM),
``gated_ffn_down`` (the down projection from ``gate`` and ``up``, ``h`` made in
VMEM), and in the backward ``gated_ffn_dh`` (``dy w_down^T`` leaves as ``dgate``
and ``dup``), ``gated_ffn_dx`` (both halves of ``dx`` in one float32 sum),
``gated_ffn_dw_up`` (``dw_gate`` and ``dw_up`` from one read of the ``x`` tile)
and ``gated_ffn_dw_down``; the weight gradients leave their kernels in the
weights' dtype. The ungated form (``ungated_ffn_padded_kept``) is two
``grouped_matmul`` calls with XLA's activation between them. Every traced
call of a kernel says what it was given as a ``gmm_plan`` record
(``_say_plan``).

On the ``cpu`` platform the kernels run in interpret mode (numerically the
same program), so CPU tests and dryruns validate the real kernel bodies
(``ops/backend.py``). fp32 accumulation everywhere (``preferred_element_type``); outputs are
cast to the input dtype, gradients to the primal dtypes. Tile sizes are
powers of two down to 8 rows — Mosaic-friendly at the cells' shapes (on the
chip the kernels are read by their scope, ``moe_experts``, under the
``per_layer`` metrics of the expert cells); lane-dim (128) padding of small
test shapes is interpret-mode territory, not correctness.
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
#: double-buffers it, and the row tiles ride beside it. A kernel that keeps
#: two side by side (the gated FFN's gate and up) gives each this much and
#: asks for the scoped VMEM that takes (``_vmem``).
_BLOCK_BYTES = 4 * 1024 * 1024

#: The scoped VMEM a kernel gets without asking (a v5e's default), and what a
#: kernel's float32 tiles between its blocks are given beside the blocks.
_SCOPED_VMEM = 16 * 1024 * 1024
_TILE_TEMPS = 6


def _block_cols(n: int, depth: int = 1, itemsize: int = 4) -> int:
    """The column block of a width ``n`` beside ``depth`` rows of ``itemsize``
    bytes: the fewest blocks of whole 128-lane tiles whose ``[depth, block]``
    tile stays within ``_BLOCK_BYTES`` (a wider block reads each row tile
    fewer times), and the narrowest of those. A width of whole lane tiles
    that fits is one block; a width within one lane tile is one block.

    Where the width is no whole number of lane tiles (1856 = 14.5 tiles) a
    block is never the whole width, and the last block is part-filled (the
    callers' grids are ``pl.cdiv``: Pallas reads what lies past the array as
    padding and drops what is written there, and no contraction here runs
    over a blocked dimension)."""
    if n <= 128:
        return n
    tiles = -(-n // 128)
    most = max(1, min(n // 128, _BLOCK_BYTES // (depth * 128 * itemsize)))
    blocks = -(-tiles // most)
    return 128 * -(-tiles // blocks)


def _say_plan(kernel, form, rows, bt, d, f, n, block, vmem):
    """One ``gmm_plan`` record a traced call of a kernel, under the span that
    caused the trace (a name of ``telemetry.COMPILE_RECORDS``): the blocks
    are static, so what was chosen is a record. ``n`` is the width that is
    cut in ``block``s, ``vmem`` the bytes of the blocks the kernel holds."""
    from pytorch_distributed_training_example_tpu.utils import telemetry
    telemetry.recorder().compile_event("gmm_plan", 0.0, {
        "kernel": kernel, "form": form, "rows": rows, "d": d, "f": f,
        "bt": bt, "block": block, "blocks": -(-n // block), "vmem": vmem})


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
    _say_plan("grouped_matmul", "plain", Tp, bt, d, f, f, bf, _vmem([
        ("rows", x_pad.shape, x_pad.dtype), ("cols", (Tp, f), out_dtype),
        ("w_rows" if transposed else "w_cols", w.shape, w.dtype)], (), bt,
        bf)[0])
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
    _say_plan("grouped_matmul_dw", "plain", Tp, bt, d, f, f, bf, _vmem([
        ("rows", x_pad.shape, x_pad.dtype), ("cols", g_pad.shape, g_pad.dtype),
        ("w_cols", (num_experts, d, f), jnp.float32)], (), bt, bf)[0])
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


# The gated FFN's own kernels. A row tile meets each expert matrix once: the
# gate's elementwise work rides in the kernels on either side of it, on the
# tiles in use only, and no ``[P, f]`` or ``[P, d]`` array is written that
# only the next kernel reads. Every kernel has the plain kernels' grid,
# ``(blocks of the cut width, row tiles)`` with the tiles inside, and the
# four kinds of block below.


def _dot(a, b, contract):
    """``a . b`` over ``a``'s and ``b``'s dimensions ``contract``, float32."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _live(nt_ref):
    """Is this grid step's tile one in use (``_gmm_kernel``'s rule)?"""
    return pl.program_id(1) < nt_ref[0]


def _up_kernel(te_ref, tf_ref, nt_ref, x_ref, wg_ref, wu_ref, gate_ref,
               up_ref):
    """``gate = x w_gate`` and ``up = x w_up`` of a row tile and a column
    block of both matrices, each rounded to the compute dtype as a
    ``grouped_matmul`` call rounds it."""
    @pl.when(_live(nt_ref))
    def _tile():
        x = x_ref[...]
        gate_ref[...] = _dot(x, wg_ref[0], (1, 0)).astype(gate_ref.dtype)
        up_ref[...] = _dot(x, wu_ref[0], (1, 0)).astype(up_ref.dtype)


def _down_kernel(te_ref, tf_ref, nt_ref, gate_ref, up_ref, w_ref, y_ref, *,
                 act):
    """``y = (act(gate) * up) w_down``: ``h`` is made in VMEM."""
    @pl.when(_live(nt_ref))
    def _tile():
        h = _gated(gate_ref[...], up_ref[...], act)
        y_ref[...] = _dot(h, w_ref[0], (1, 0)).astype(y_ref.dtype)


def _dh_kernel(te_ref, tf_ref, nt_ref, dy_ref, w_ref, gate_ref, up_ref,
               dgate_ref, dup_ref, *, act):
    """``dh = dy w_down^T`` (the weight read as it lies) leaves as ``dgate``
    and ``dup``: the gate's transpose as AD makes it for ``act``, on the
    float32 tile."""
    @pl.when(_live(nt_ref))
    def _tile():
        dh = _dot(dy_ref[...], w_ref[0], (1, 1))
        _, transpose = jax.vjp(
            lambda gate, up: GATES[act](gate) * up,
            gate_ref[...].astype(jnp.float32),
            up_ref[...].astype(jnp.float32))
        dgate, dup = transpose(dh)
        dgate_ref[...] = dgate.astype(dgate_ref.dtype)
        dup_ref[...] = dup.astype(dup_ref.dtype)


def _dx_kernel(te_ref, tf_ref, nt_ref, dgate_ref, dup_ref, wg_ref, wu_ref,
               dx_ref):
    """``dx = dgate w_gate^T + dup w_up^T``: one float32 sum, rounded once."""
    @pl.when(_live(nt_ref))
    def _tile():
        dx_ref[...] = (_dot(dgate_ref[...], wg_ref[0], (1, 1))
                       + _dot(dup_ref[...], wu_ref[0], (1, 1))
                       ).astype(dx_ref.dtype)


def _accumulate(tf_ref, nt_ref, products, acc_refs, dw_refs):
    """``acc += lhs^T rhs`` for each ``(lhs, rhs)`` that ``products()`` reads
    of a row tile, segment-wise as ``_gmm_dw_kernel``: an accumulator is
    zeroed on an expert's first tile, and on its last the weight gradient's
    block leaves in the weights' dtype (the float32 sum rounded once, in
    VMEM)."""
    g = pl.program_id(1)
    live = _live(nt_ref)

    @pl.when(live & (tf_ref[g] == 1))
    def _init():
        for acc_ref in acc_refs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _tile():
        for (lhs, rhs), acc_ref in zip(products(), acc_refs):
            acc_ref[...] += _dot(lhs, rhs, (0, 0))

    following = jnp.minimum(g + 1, pl.num_programs(1) - 1)

    @pl.when(live & ((g + 1 == nt_ref[0]) | (tf_ref[following] == 1)))
    def _leave():
        for acc_ref, dw_ref in zip(acc_refs, dw_refs):
            dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)


def _dw_up_kernel(te_ref, tf_ref, nt_ref, x_ref, dgate_ref, dup_ref,
                  dwg_ref, dwu_ref, accg_ref, accu_ref):
    """``dw_gate`` and ``dw_up`` from one read of the ``x`` tile."""
    def products():
        x = x_ref[...]
        return (x, dgate_ref[...]), (x, dup_ref[...])

    _accumulate(tf_ref, nt_ref, products, (accg_ref, accu_ref),
                (dwg_ref, dwu_ref))


def _dw_down_kernel(te_ref, tf_ref, nt_ref, gate_ref, up_ref, dy_ref,
                    dw_ref, acc_ref, *, act):
    """``dw_down = h^T dy`` with ``h`` made in VMEM, a block of its columns
    (``dw_down``'s rows) a grid step: each ``h`` is made once."""
    _accumulate(
        tf_ref, nt_ref,
        lambda: [(_gated(gate_ref[...], up_ref[...], act), dy_ref[...])],
        (acc_ref,), (dw_ref,))


#: The gated FFN's kernels by the names their calls carry.
GATED_KERNELS = {
    "gated_ffn_up": _up_kernel, "gated_ffn_down": _down_kernel,
    "gated_ffn_dh": _dh_kernel, "gated_ffn_dx": _dx_kernel,
    "gated_ffn_dw_up": _dw_up_kernel, "gated_ffn_dw_down": _dw_down_kernel}


def _block(kind, shape, bt, bc):
    """The block of an operand of ``shape`` and where a grid step ``(jc, g)``
    finds it: ``rows`` a row tile whole, ``cols`` a row tile's column block
    ``jc``, ``w_cols`` the tile's expert's ``[depth, bc]`` columns ``jc``,
    ``w_rows`` its ``[bc, depth]`` rows ``jc`` (a weight read transposed, as
    it lies)."""
    if kind == "rows":
        return (bt, shape[1]), lambda jc, g, te, tf, nt: (_last_tile(g, nt), 0)
    if kind == "cols":
        return (bt, bc), lambda jc, g, te, tf, nt: (_last_tile(g, nt), jc)
    if kind == "w_cols":
        return (1, shape[1], bc), lambda jc, g, te, tf, nt: (
            te[_last_tile(g, nt)], 0, jc)
    return (1, bc, shape[2]), lambda jc, g, te, tf, nt: (
        te[_last_tile(g, nt)], jc, 0)


def _vmem(blocks, scratch, bt, bc):
    """``(bytes the kernel's blocks take, the scoped VMEM to ask for)``:
    ``blocks`` are ``(kind, shape, dtype)`` of the operands and results
    (double-buffered), ``scratch`` the accumulators' ``(shape, dtype)``; the
    ask is None where the default scope holds them with ``_TILE_TEMPS``
    float32 tiles beside them."""
    size = lambda shape, dtype: int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    held = sum(2 * size(_block(kind, shape, bt, bc)[0], dtype)
               for kind, shape, dtype in blocks)
    held += sum(size(shape, dtype) for shape, dtype in scratch)
    ask = held + _TILE_TEMPS * bt * max(bc, 128) * 4
    return held, None if ask <= _SCOPED_VMEM else ask


@functools.partial(jax.jit, static_argnames=(
    "name", "act", "kinds", "outs", "n", "bc", "scratch", "limit",
    "interpret"))
def _run(tiles, *operands, name, act, kinds, outs, n, bc, scratch, limit,
         interpret):
    """The ``pallas_call`` of the gated kernel ``name`` (``act``: the gate's
    function, for the kernels that apply it). Under ``jit`` so that a model's
    layers, and the recomputation in its backward, share one trace."""
    bt = operands[0].shape[0] // tiles[0].shape[0]
    spec = lambda kind, shape: pl.BlockSpec(*_block(kind, shape, bt, bc))
    kernel = GATED_KERNELS[name]
    return pl.pallas_call(
        kernel if act is None else functools.partial(kernel, act=act),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # as the plain kernels': the row tiles inside, in segment order
            grid=(pl.cdiv(n, bc), operands[0].shape[0] // bt),
            in_specs=[spec(kind, a.shape) for kind, a in zip(kinds, operands)],
            out_specs=tuple(spec(kind, shape) for kind, shape, _ in outs),
            scratch_shapes=[pltpu.VMEM(shape, dtype)
                            for shape, dtype in scratch]),
        out_shape=tuple(jax.ShapeDtypeStruct(shape, dtype)
                        for _, shape, dtype in outs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret,
    )(*tiles, *operands)


def _gated_call(name, tiles, operands, kinds, outs, *, d, f, cut, depth,
                itemsize, act=None, accumulators=()):
    """The kernel ``name`` of the gated FFN over the padded layout:
    ``operands`` of ``kinds`` (``_block``'s) to ``outs`` (``(kind, shape,
    dtype)``), the width ``cut`` in column blocks beside ``depth`` rows of
    ``itemsize`` bytes; ``accumulators`` are the float32 ``[rows, cols]``
    scratch of a weight gradient, ``None`` for the cut width. Says its
    ``gmm_plan``."""
    rows = operands[0].shape[0]
    bt = rows // tiles[0].shape[0]
    bc = _block_cols(cut, depth, itemsize)
    scratch = tuple((tuple(bc if s is None else s for s in shape),
                     jnp.dtype(jnp.float32)) for shape in accumulators)
    outs = tuple((kind, shape, jnp.dtype(dtype)) for kind, shape, dtype in outs)
    held, limit = _vmem(
        [(kind, a.shape, a.dtype) for kind, a in zip(kinds, operands)]
        + list(outs), scratch, bt, bc)
    _say_plan(name, "gated", rows, bt, d, f, cut, bc, held)
    return _run(tiles, *operands, name=name, act=act,
                kinds=tuple(kinds), outs=outs, n=cut, bc=bc, scratch=scratch,
                limit=limit, interpret=not backend.on_tpu())


def _gated_up(x_pad, w_gate, w_up, tiles):
    """``(gate, up)`` of the padded rows: one kernel, each row tile read once
    a column block of the two matrices."""
    (rows, d), f = x_pad.shape, w_gate.shape[2]
    return _gated_call(
        "gated_ffn_up", tiles, (x_pad, w_gate, w_up),
        ("rows", "w_cols", "w_cols"),
        (("cols", (rows, f), x_pad.dtype),) * 2, d=d, f=f, cut=f, depth=d,
        itemsize=w_gate.dtype.itemsize)


def gated_down_padded(gate, up, w_down, tiles, act="silu"):
    """The gated product of the two projections through the down
    projection: ``gated_ffn_padded``'s result from its own ``gate`` and
    ``up``, in one kernel."""
    (rows, f), d = gate.shape, w_down.shape[2]
    return _gated_call(
        "gated_ffn_down", tiles, (gate, up, w_down),
        ("rows", "rows", "w_cols"), (("cols", (rows, d), gate.dtype),), d=d,
        f=f, cut=d, depth=f, itemsize=w_down.dtype.itemsize, act=act)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gated_ffn_padded_kept(x_pad, w_gate, w_up, w_down, tiles, act="silu"):
    """``gated_ffn_padded`` with the two projections it gated beside it:
    ``(y_pad, gate, up)``, all in the compute dtype. With ``x_pad`` they are
    all that ``gated_ffn_padded_bwd`` reads of the forward; no ``h`` is
    written, and a caller that reads no ``y_pad`` runs no down projection.
    Its derivative is ``gated_ffn_padded_bwd`` on its own ``gate`` and ``up``,
    under plain AD as in the expert layer's own rule."""
    gate, up = _gated_up(x_pad, w_gate, w_up, tiles)
    return gated_down_padded(gate, up, w_down, tiles, act), gate, up


def _gated_ffn_fwd(x_pad, w_gate, w_up, w_down, tiles, act):
    y_pad, gate, up = gated_ffn_padded_kept(x_pad, w_gate, w_up, w_down,
                                            tiles, act)
    return (y_pad, gate, up), (x_pad, gate, up, w_gate, w_up, w_down, tiles)


def _gated_ffn_bwd(act, res, cotangents):
    *res, tiles = res
    dy_pad, d_gate, d_up = cotangents
    return (*gated_ffn_padded_bwd(*res, tiles, dy_pad, act, d_gate, d_up),
            _float0(tiles))


gated_ffn_padded_kept.defvjp(_gated_ffn_fwd, _gated_ffn_bwd)


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
                         act="silu", d_gate=None, d_up=None):
    """What differentiating ``gated_ffn_padded`` gives for ``dy_pad``, from
    the forward's own ``gate`` and ``up``: ``(dx_pad, dw_gate, dw_up,
    dw_down)`` in four kernels. The gate's derivative is as AD makes it for
    ``act`` (ReLU's is 0 at 0), applied to ``dh`` before it is rounded; the
    two halves of ``dx`` are summed in float32; each weight gradient is the
    float32 sum over its expert's rows, rounded once. ``d_gate`` and ``d_up``
    are what a caller that differentiates ``gated_ffn_padded_kept``'s
    ``gate`` and ``up`` themselves adds to theirs (AD's zeros where nobody
    does, which XLA folds away)."""
    (rows, d), f = x_pad.shape, gate.shape[1]
    dtype, wide = x_pad.dtype, w_gate.dtype
    sizes = dict(d=d, f=f)
    dgate, dup = _gated_call(
        "gated_ffn_dh", tiles, (dy_pad, w_down, gate, up),
        ("rows", "w_rows", "cols", "cols"),
        (("cols", (rows, f), dtype),) * 2, cut=f, depth=d,
        itemsize=w_down.dtype.itemsize, act=act, **sizes)
    if d_gate is not None:
        dgate, dup = dgate + d_gate, dup + d_up
    dx_pad, = _gated_call(
        "gated_ffn_dx", tiles, (dgate, dup, w_gate, w_up),
        ("rows", "rows", "w_rows", "w_rows"), (("cols", (rows, d), dtype),),
        cut=d, depth=f, itemsize=wide.itemsize, **sizes)
    dw_gate, dw_up = _gated_call(
        "gated_ffn_dw_up", tiles, (x_pad, dgate, dup),
        ("rows", "cols", "cols"), (("w_cols", w_gate.shape, wide),) * 2,
        cut=f, depth=d, itemsize=4, accumulators=((d, None),) * 2, **sizes)
    dw_down, = _gated_call(
        "gated_ffn_dw_down", tiles, (gate, up, dy_pad),
        ("cols", "cols", "rows"), (("w_rows", w_down.shape, w_down.dtype),),
        cut=f, depth=d, itemsize=4, accumulators=((None, d),), act=act,
        **sizes)
    return dx_pad, dw_gate, dw_up, dw_down


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

"""The expert layer of models that are told which experts this chip holds.

Two halves that a block calls where its model says: *a router* (scores to the
chosen experts, their weights and every expert's count: ``route_sigmoid_bias``
for the ``afmoe``, ``glm_moe_lite``, ``nemotron_h`` and ``lfm2_moe`` families,
:class:`TopKSoftmaxRouter` for ``smallthinker`` and ``qwen3_next``) and *the
held experts' routine* (``_held_sum`` over ``(tokens, chosen, weights, counts)``:
``_routed`` / ``_routed_bounded`` through ``ops/grouped_matmul.py``'s FFN,
with the telemetry). :class:`SharedExpertMoE` is both in one module with a
shared expert beside them; :class:`HeldExperts` is the routine alone, for a
block that routes on another tensor than the experts read (``smallthinker``)
or gates its shared expert (``qwen3_next``: ``sigmoid(x w_sg)``, one scalar a
token, in the block).

The layer routes over all the experts and computes the part of the sum that
the held ones give: one chip's share. Dropless, whatever the imbalance; what
the other experts would add is the business of the chips that hold them, and
no exchange across chips is written yet (ROADMAP B4 (1)). The step's regions
carry ``jax.named_scope`` tags (``moe_router`` / ``moe_dispatch`` /
``moe_experts`` / ``moe_combine`` / ``moe_shared``) that a trace's device time
is attributed by.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib

#: Row tile of the held experts' grouped matmuls: an expert sees T*k/E rows
#: on average (512 at 8,192 tokens and 8 of 128, 768 at 6 of 64), and a
#: taller tile pads more of them.
EXPERT_TILE_ROWS = 128


def _rows(x, index):
    """``x[index]`` with zeros where ``index`` is past the last row."""
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


#: The most bytes of a row-gather's source that XLA copies into VMEM and
#: gathers from there: a v5e's 128 MiB less the 16 MiB the compiler keeps for
#: scoped use. Measured (``benchmarks/moe_rows_micro.py --sweep``; PERF.md
#: section 6, PR 40): the combine over ``bf16[P, 2560]`` reads 0.89 ms at
#: 111.9 MiB and, at 112.5 MiB, 2.70 ms whole (from HBM) and 1.20 in two parts;
#: compiled for a described v5e, 22,936 rows are placed and 22,944 are not.
GATHER_SOURCE_BYTES = 112 * 2**20


def _source_parts(rows, d, itemsize):
    """In how many column parts a ``[rows, d]`` source of row-gathers is asked
    for: the fewest whose part is whole 128-lane columns of at most
    ``GATHER_SOURCE_BYTES``; 1 where the whole source is, or no part would
    be."""
    for n in range(1, d // 128 + 1):
        if d % (128 * n) == 0 and rows * (d // n) * itemsize \
                <= GATHER_SOURCE_BYTES:
            return n
    return 1


def _choice_sum(x_pad, pair_row, weights=None):
    """``sum_c weights[t, c] * x_pad[pair_row[c, t]]`` as ``[T, d]`` float32 (a
    choice with no padded row adds nothing; no ``weights``: ones), in as many
    column parts as ``_source_parts`` says of the source."""
    return _choice_sum_in(x_pad, pair_row, weights, _source_parts(
        *x_pad.shape, x_pad.dtype.itemsize))


def _choice_sum_in(x_pad, pair_row, weights, n):
    """``_choice_sum`` over ``n`` column parts of ``x_pad``, their ``[T, d /
    n]`` sums joined along the columns: the same sum of the same terms in the
    same order in every element, whatever ``n``. Asked for choice-major: a
    ``[T, d / n]`` slab of rows a choice, summed in the order c = 0..k-1. With
    the choice axis between rows and lanes XLA relays the gathered rows out
    into ``[T, k, d]`` tiles first, wherever k is no multiple of 8. A part is
    cut only once the part before it is summed (the barrier): XLA else makes
    all the parts in one fusion, side by side in HBM."""
    width, sums = x_pad.shape[1] // n, []
    for lo in range(0, n * width, width):
        if sums:
            x_pad, sums[-1] = jax.lax.optimization_barrier((x_pad, sums[-1]))
        slabs = (_rows(x_pad[:, lo:lo + width], index).astype(jnp.float32)
                 for index in pair_row)
        if weights is not None:
            slabs = (slab * w[:, None] for slab, w in zip(slabs, weights.T))
        sums.append(functools.reduce(jnp.add, slabs))
    # (one part: a slice of every column and a join of one array trace to
    # nothing, and the lowered text is what it was)
    return jnp.concatenate(sums, axis=1)


@jax.custom_vjp
def _dispatch_rows(tokens, row_token, pair_row):
    """``tokens [T, d]`` into the experts' padded layout ``[P, d]``: padded
    row p holds token ``row_token[p]`` (T = none: zeros). ``pair_row [k, T]``
    is the inverse (the padded row of a token's c-th choice, P = none), so
    the transpose is a gather too and no scatter-add is ever lowered."""
    return _rows(tokens, row_token)


def _dispatch_fwd(tokens, row_token, pair_row):
    return _rows(tokens, row_token), (row_token, pair_row)


def _dispatch_bwd(res, d_pad):
    row_token, pair_row = res
    return (_choice_sum(d_pad, pair_row).astype(d_pad.dtype),
            _int_zeros(row_token), _int_zeros(pair_row))


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine_rows(y_pad, weights, pair_row, row_pair):
    """``out[t] = sum_c weights[t, c] * y_pad[pair_row[c, t]]`` in float32;
    ``row_pair [P]`` is the inverse (the flat (token, choice) of a padded
    row, T*k = none)."""
    return _choice_sum(y_pad, pair_row, weights)


def _combine_fwd(y_pad, weights, pair_row, row_pair):
    return (_combine_rows(y_pad, weights, pair_row, row_pair),
            (y_pad, weights, pair_row, row_pair))


def _combine_bwd(res, d_out):
    """Both from one gather of ``d_out``'s float32 rows into the padded layout:
    a pair's weight gets its padded row's ``<y_pad[p], d_out[t]>``, a scalar.
    """
    y_pad, weights, pair_row, row_pair = res
    k = weights.shape[1]
    d_rows = _rows(d_out, row_pair // k)                            # [P, d]
    dw_pad = jnp.sum(y_pad.astype(jnp.float32) * d_rows, axis=-1)
    d_pad = (d_rows.astype(y_pad.dtype)
             * _rows(weights.reshape(-1, 1), row_pair))
    d_weights = _rows(dw_pad, pair_row).T                           # [T, k]
    return (d_pad.astype(y_pad.dtype), d_weights.astype(weights.dtype),
            _int_zeros(pair_row), _int_zeros(row_pair))


_combine_rows.defvjp(_combine_fwd, _combine_bwd)


def _int_zeros(index):
    return np.zeros(index.shape, jax.dtypes.float0)


def _held_keys(chosen, first, held):
    """``[n*k]``: the held expert (0..held-1) a (token, choice) pair chose,
    or ``held`` where it chose another chip's."""
    local = chosen.reshape(-1) - first
    return jnp.where((local >= 0) & (local < held), local, held)


def _chose(index, size):
    """``[..., size]`` booleans: does ``index[...]`` name this one of ``size``?

    How an index over a small axis (an expert of E) is applied in this file: by
    comparison, in a dense pass that XLA fuses with what selects or counts by
    it, and never by a gather or a scatter of one scalar an element, which
    costs a v5e 7 to 10 ns an element whatever the table's size (0.5 ms for
    the 65,536 pairs of 8,192 tokens; PERF.md sections 5 and 6, PR 49), where
    the ``size`` compares an element cost an eighth of that at E = 128 (a
    layer's picks, their transpose and its counts: 0.28 ms against 2.18) and
    less at the presets' smaller E. With experts in the thousands the dense
    pass would cost what the gather does; nothing here branches on it."""
    return index[..., None] == jnp.arange(size, dtype=index.dtype)


def _count(index, size):
    """How many of ``index`` name each of ``size``, ``[size]`` int32
    (``bincount``'s integers; an index of ``size`` or more is counted
    nowhere)."""
    return jnp.sum(_chose(index, size), axis=tuple(range(index.ndim)),
                   dtype=jnp.int32)


def _held_counts(key, held):
    return _count(key, held)


def _plan(chosen, first, held, bt, max_tiles, counts):
    """The integer plan ``(tiles, pair_row [k, n], row_pair [P])`` of the held
    experts' padded layout for ``chosen [n, k]``: the (token, choice) pairs
    sorted by held expert, the pairs that chose another chip's expert behind
    them all; integers only. Both directions of every move are gathers."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    n, k = chosen.shape
    key = _held_keys(chosen, first, held)                           # [n*k]
    pairs = jnp.arange(n * k, dtype=jnp.int32)
    _, order = jax.lax.sort((key, pairs), num_keys=1)
    if counts is None:
        counts = _held_counts(key, held)
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    tiles, src, dst = gmm_lib._padded_layout(
        starts, counts, n * k, held, bt, max_tiles)
    row_pair = _rows(order, src) + (src >= n * k) * (n * k)         # [P]
    # ``dst`` is by sorted place; the sort that undoes ``order`` carries it
    # back to the pairs: ``dst[rank]`` with no ``rank`` and no gather (its keys
    # are distinct, and a stable sort would carry an iota more to part ties)
    _, pair_row = jax.lax.sort((order, dst), num_keys=1, is_stable=False)
    return tiles, pair_row.reshape(n, k).T, row_pair                # [k, n]


def _routed_kept(tokens, chosen, weights, experts, first, bt, max_tiles=None,
                 counts=None, act="silu"):
    """The held experts' part of the layer's sum for these tokens, ``[n, d]``
    float32, and what a backward reads of this forward beside its inputs:
    ``experts = (w_gate, w_up, w_down)`` are the experts ``first`` onwards
    (``act`` their gate's function, a key of ``grouped_matmul.GATES``), or
    ``(w_up, w_down)`` ungated ones (``act`` a key of ``grouped_matmul.ACTS``;
    ``grouped_matmul.FFN_FORMS`` has both forms' routines),
    ``chosen [n, k]`` indexes all the router's experts, the padded layout
    takes at most ``max_tiles`` tiles of ``bt`` rows, and ``counts`` are the
    held experts' rows where the caller has them (the router counts all the
    tokens). Kept: the integer plan ``(tiles, pair_row, row_pair)`` and the
    two projections ``(gate, up)`` of the padded rows in the compute dtype
    (``(up,)`` alone of an ungated expert);
    ``x_pad`` is one gather of rows from the plan and ``y_pad`` one grouped
    matmul from ``gate`` and ``up``, and whatever is kept here lives from the
    forward to the backward in the step XLA schedules (151 MB more a layer
    at Trinity's published widths, were both kept)."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    k, held = chosen.shape[1], experts[0].shape[0]
    with jax.named_scope("moe_dispatch"):
        tiles, pair_row, row_pair = _plan(chosen, first, held, bt, max_tiles,
                                          counts)
        x_pad = _dispatch_rows(tokens, row_pair // k, pair_row)
    with jax.named_scope("moe_experts"):
        y_pad, *pre = mesh_lib.manual_call(
            functools.partial(gmm_lib.FFN_FORMS[len(experts)][0], act=act),
            x_pad, *experts, tiles, in_specs=P(), out_specs=P())
    with jax.named_scope("moe_combine"):
        out = _combine_rows(y_pad, weights, pair_row, row_pair)
    return out, ((tiles, pair_row, row_pair), tuple(pre))


def _routed(tokens, chosen, weights, experts, first, bt, max_tiles=None,
            counts=None, act="silu"):
    """``_routed_kept``'s sum alone: under plain AD where every expert is
    held (no ``cond``), and the routine that the bounded layout repeats."""
    return _routed_kept(tokens, chosen, weights, experts, first, bt,
                        max_tiles, counts, act)[0]


def _routed_kept_bwd(kept, tokens, weights, experts, d_out, act="silu"):
    """``(d_tokens, d_weights, d_experts)`` of ``_routed_kept``'s sum from
    what it kept: the transposes that plain AD of ``_routed`` strings
    together, in its order, on the forward's own ``gate`` and ``up``; of the
    forward only the gather of rows and the down projection run again."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    (tiles, pair_row, row_pair), pre = kept
    _, down, bwd = gmm_lib.FFN_FORMS[len(experts)]
    with jax.named_scope("moe_dispatch"):
        row_token = row_pair // weights.shape[1]
        x_pad = _rows(tokens, row_token)
    with jax.named_scope("moe_experts"):
        y_pad = mesh_lib.manual_call(
            functools.partial(down, act=act), *pre, experts[-1], tiles,
            in_specs=P(), out_specs=P())
    with jax.named_scope("moe_combine"):
        dy_pad, d_weights = _combine_bwd(
            (y_pad, weights, pair_row, row_pair), d_out)[:2]
    with jax.named_scope("moe_experts"):
        dx_pad, *d_experts = mesh_lib.manual_call(
            functools.partial(bwd, act=act), x_pad, *pre, *experts, tiles,
            dy_pad, in_specs=P(), out_specs=P())
    with jax.named_scope("moe_dispatch"):
        d_tokens = _dispatch_bwd((row_token, pair_row), dx_pad)[0]
    return d_tokens, d_weights, tuple(d_experts)


def _bounded_tiles(chosen, experts, bt, chunks):
    """Tiles of the bounded layout: the worst case of a ``chunks``-th part
    of the tokens, every choice of each held here."""
    (n, k), held = chosen.shape, experts[0].shape[0]
    return -(-(n // chunks) * k // bt) + held


def _fits(counts, bt, cap):
    """Do the held experts' ``counts`` rows fit ``cap`` tiles of ``bt``?"""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    return gmm_lib.num_tiles(counts, bt) <= cap


def _in_parts(tokens, chosen, weights, experts, first, bt, chunks, cap, act):
    """``_routed`` over ``chunks`` parts of the tokens one after another,
    each counting its own rows; a part's residuals are its inputs."""
    n = chosen.shape[0]
    split = lambda a: a.reshape(chunks, n // chunks, *a.shape[1:])
    one = lambda a: _routed(*a, experts, first, bt, cap, act=act)
    return jax.lax.map(jax.checkpoint(one), (
        split(tokens), split(chosen), split(weights))).reshape(
            n, tokens.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _routed_bounded(tokens, chosen, weights, experts, counts, first, bt,
                    chunks, act="silu"):
    """``_routed`` in a layout of bounded size, whatever the router does.

    The layout's static size is its worst case: every choice of every token
    held here, E/held times the rows a balanced router sends. So the tokens
    are taken whole in a layout of the ``chunks``-th part of that where the
    router's ``counts`` of the held experts' rows say they fit (the rule,
    unless routing collapses), and else in ``chunks`` parts one after
    another, each of whose worst case is that same layout: the same routine
    either way, nothing dropped, and buffers of the smaller size alone.

    Differentiated by hand, because residuals that cross a ``cond`` are
    materialised and plain AD hands out the float32 intermediates of the
    gate and copies of the experts' weights among them (+1.5 GB). The forward
    rule's ``cond`` hands out, beside the sum, what ``_routed_kept`` keeps:
    the integer plan and ``gate`` and ``up`` in the compute dtype (zeros of
    those shapes from the parts, which nobody reads). The backward rule
    branches on the same counts: the whole layout's side strings the
    transposes together from what was kept (``_routed_kept_bwd``), the
    parts' side computes its forward again part by part, as ``lax.map`` over
    a checkpointed part does. This function itself computes the sum alone.
    """
    cap = _bounded_tiles(chosen, experts, bt, chunks)
    return jax.lax.cond(
        _fits(counts, bt, cap),
        lambda: _routed(tokens, chosen, weights, experts, first, bt, cap,
                        counts, act),
        lambda: _in_parts(tokens, chosen, weights, experts, first, bt, chunks,
                          cap, act))


def _routed_bounded_fwd(tokens, chosen, weights, experts, counts, first, bt,
                        chunks, act):
    cap = _bounded_tiles(chosen, experts, bt, chunks)
    whole = lambda: _routed_kept(tokens, chosen, weights, experts, first, bt,
                                 cap, counts, act)
    kept = jax.eval_shape(whole)[1]
    out, kept = jax.lax.cond(
        _fits(counts, bt, cap), whole,
        lambda: (_in_parts(tokens, chosen, weights, experts, first, bt,
                           chunks, cap, act),
                 jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), kept)))
    return out, (tokens, chosen, weights, experts, counts, kept)


def _routed_bounded_bwd(first, bt, chunks, act, res, d_out):
    tokens, chosen, weights, experts, counts, kept = res
    cap = _bounded_tiles(chosen, experts, bt, chunks)

    def parts():
        _, vjp = jax.vjp(
            lambda *a: _in_parts(a[0], chosen, *a[1:], first, bt, chunks,
                                 cap, act), tokens, weights, experts)
        return vjp(d_out)

    d_tokens, d_weights, d_experts = jax.lax.cond(
        _fits(counts, bt, cap),
        lambda: _routed_kept_bwd(kept, tokens, weights, experts, d_out, act),
        parts)
    return (d_tokens, _int_zeros(chosen), d_weights, d_experts,
            _int_zeros(counts))


_routed_bounded.defvjp(_routed_bounded_fwd, _routed_bounded_bwd)


class Route(NamedTuple):
    """A router's plan for ``T`` tokens: what the held experts' routine
    reads."""
    chosen: jax.Array    # [T, k] int32, over all the router's experts
    weights: jax.Array   # [T, k] float32
    load: jax.Array      # [E] the (token, choice) pairs that chose each


def _scores(tokens, kernel):
    """``tokens @ kernel`` as a true float32 product: two scores that nearly
    tie must come out in the order the published float32 router gives."""
    return jnp.dot(tokens.astype(jnp.float32), kernel,
                   precision=jax.lax.Precision.HIGHEST)            # [T, E]


def _load(chosen, num_experts):
    return mesh_lib.constrain(_count(chosen, num_experts), P(None))  # [E]


def _pick(scores, chosen):
    """``take_along_axis(scores [T, E], chosen [T, k])`` by selection: one
    term of each sum over E is not zero, so the sum is that term to the bit
    (a ``-0.0`` comes out ``0.0``); its transpose is a select and a sum over
    the choices (a token's choices are distinct, so an element gets one term
    at most: exact too), where the gather's is a scatter of ``T * k`` scalars
    into a zero ``[T, E]``."""
    return jnp.sum(jnp.where(_chose(chosen, scores.shape[1]),
                             scores[:, None, :], 0.0), axis=-1)


def route_sigmoid_bias(tokens, kernel, bias, k, route_scale,
                       norm_eps=1e-20) -> Route:
    """``s = sigmoid(tokens W_r)``; the ``k`` largest of ``s + bias`` are
    chosen; weights ``route_scale * s_i / (sum of the chosen s + norm_eps)``:
    the bias chooses and nothing more (torchtitan's router as the ``afmoe``
    models configure it; ``norm_eps`` is a family's constant, 1e-20 there
    and 1e-6 in the ``lfm2_moe`` models' released router)."""
    scores = jax.nn.sigmoid(_scores(tokens, kernel))
    _, chosen = jax.lax.top_k(scores + bias, k)                     # [T, k]
    picked = _pick(scores, chosen)                                  # [T, k]
    weights = route_scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + norm_eps)
    return Route(chosen, weights, _load(chosen, kernel.shape[1]))


def route_softmax_chosen(tokens, kernel, k) -> Route:
    """The ``k`` largest logits of ``tokens W_r`` are chosen; weights: the
    softmax over the chosen logits alone, then divided by their sum (the
    identity but for rounding; the published ``norm_topk_prob``). All
    float32 (the ``smallthinker`` models' primary router)."""
    logits = _scores(tokens, kernel)
    _, chosen = jax.lax.top_k(logits, k)                            # [T, k]
    # ``top_k``'s own values are these, and their transpose a scatter
    weights = jax.nn.softmax(_pick(logits, chosen), axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return Route(chosen, weights, _load(chosen, kernel.shape[1]))


class Held(NamedTuple):
    """What ``_held_sum`` made on its way, for its callers' telemetry."""
    experts: tuple       # (w_gate, w_up, w_down) in the compute dtype, or
                         # (w_up, w_down) of ungated experts
    tokens: jax.Array    # [T, d] in the compute dtype
    first: int           # the first held expert
    load: jax.Array      # [held] int32: the held experts' rows
    whole: jax.Array     # 1.0 where the rows went through the layout whole
    bt: int              # the layout's tile rows
    cap: int | None      # the bounded layout's tiles (None: unbounded)
    parts: int           # the column parts its padded rows are gathered in


def _held_sum(module, tokens, route: Route, ffn_dim, held_experts, act,
              gated=True):
    """The held experts' routine inside ``module`` (which gets the stacked
    ``w_gate``, ``w_up``, ``w_down``; ``w_up`` and ``w_down`` alone where the
    experts are not ``gated``: ``act(x W_up) W_down``, ``act`` then a key of
    ``grouped_matmul.ACTS``): the part of ``sum_c weights[t, c] *
    Expert_chosen[t, c](tokens[t])`` that the experts ``held_experts = (how
    many, starting where)`` give, ``[T, d]`` float32, dropless whatever the
    imbalance; and a :class:`Held`.

    Where under half of the experts are held the rows go through
    ``_routed_bounded``: one ``cond`` on the router's own counts of the held
    experts' rows, which in the forward that a backward follows hands out
    the integer plan and the experts' ``gate`` and ``up`` projections in the
    compute dtype, so that the backward runs no routed forward again; no
    float32 intermediate and no copy of a weight crosses it. Where all are
    held (``chunks == 1``) ``_routed`` runs under plain AD."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    (T, d), (_, k), E = tokens.shape, route.chosen.shape, route.load.shape[0]
    held, first = held_experts or (E, 0)
    if not (0 < held and 0 <= first and first + held <= E):
        raise ValueError(f"held_experts={held_experts} of {E}")
    stacked = lambda name, shape: module.param(
        name, nn.initializers.lecun_normal(), (held, *shape),
        module.param_dtype).astype(module.dtype)
    experts = ((stacked("w_gate", (d, ffn_dim)),) if gated else ()) + (
        stacked("w_up", (d, ffn_dim)), stacked("w_down", (ffn_dim, d)))
    bt = min(EXPERT_TILE_ROWS, gmm_lib._block_rows(T * k, held))
    held_load = route.load[first:first + held].astype(jnp.int32)

    # rows in a layout of bounded size: see ``_routed_bounded``
    tokens = tokens.astype(module.dtype)
    chunks = max(1, E // (2 * held))
    if chunks == 1 or T % chunks:
        cap = None
        out = _routed(tokens, route.chosen, route.weights, experts, first, bt,
                      act=act)
        whole = jnp.ones((), jnp.float32)
    else:
        cap = _bounded_tiles(route.chosen, experts, bt, chunks)
        out = _routed_bounded(tokens, route.chosen, route.weights, experts,
                              held_load, first, bt, chunks, act)
        whole = _fits(held_load, bt, cap).astype(jnp.float32)
    tiles = -(-T * k // bt) + held      # ``_padded_layout``'s own bound
    parts = _source_parts(bt * min(tiles, cap or tiles), d,
                          tokens.dtype.itemsize)
    return out, Held(experts, tokens, first, held_load, whole, bt, cap, parts)


def _sow_telemetry(module, **values):
    """Sow each value into ``telemetry`` (fetched at the log cadence) under
    its name with the enclosing block's name behind a dot."""
    layer = "." + module.path[-2] if len(module.path) > 1 else ""
    for name, value in values.items():
        module.sow("telemetry", name + layer, value)


def _held_peak(rows):
    """The fullest held expert's rows over their mean."""
    return jnp.max(rows) / jnp.maximum(jnp.mean(rows), 1.0)


class SharedExpertMoE(nn.Module):
    """A sigmoid-and-bias router and the held experts' routine beside a
    shared expert, on the chip that holds ``held_experts`` of the experts
    (torchtitan's MoE as the ``afmoe`` models configure it; the equations
    are in ``models/afmoe.py``).

    ``s = sigmoid(x W_r)`` over all ``num_experts``, in float32; the ``top_k``
    largest of ``s + b`` are chosen (``b``, ``expert_bias``, is a buffer in
    the ``batch_stats`` collection: no gradient, no optimizer state); the
    weights are ``route_scale * s_i / (sum of the chosen s + route_norm_eps)``:
    the bias chooses and nothing more (``route_sigmoid_bias``). ``y = Shared(x)
    + sum_i w_i Expert_i(x)`` with SwiGLU experts, or where ``gated`` is
    false with two-matrix squared-ReLU ones, ``relu(x W_up)^2 W_down``, the
    shared expert likewise (the ``nemotron_h`` models). After a training step ``b
    += d - mean(d)``, ``d = balance_coeff * sign(mean(c) - c)``, ``c`` the
    tokens of this call that chose each expert (all ``num_experts``, this
    chip's tokens).

    ``held_experts = (how many, starting where)``: the layer routes over all
    the experts and computes the part of the sum that its own give, for the
    tokens that chose them (``_held_sum``); what the others would add is left
    out, and is the business of the chips that hold them (their results
    would be summed over the ``expert`` axis; on one chip the layer runs
    without that exchange). Dropless: every (token, choice) that lands here
    is computed, whatever the imbalance. The rows are gathered straight into
    the grouped matmul's tile layout (``ops/grouped_matmul.py``), whose
    kernels run only the tiles in use: a row that chose no held expert costs
    no matmul tile.

    Sows into ``telemetry`` (fetched at the log cadence): ``moe_held_rows``
    (the rows that landed on held experts), ``moe_held_peak`` (the fullest
    held expert's rows over their mean), ``moe_bias_peak`` (largest |b|) and
    ``moe_whole`` (1.0 where the held rows fit the whole layout and the kept
    residuals serve the backward, 0.0 where the tokens went in parts) and
    ``moe_source_parts`` (the column parts the padded rows are gathered back
    in: ``_source_parts``), each with the enclosing block's name behind a dot;
    an ungated layer also ``moe_gate_zero`` (the share of the held rows' ``up``
    pre-activations that the squared ReLU zeroes: ``_gate_zero_share``), in a
    run that collects ``telemetry``.
    """

    num_experts: int
    ffn_dim: int
    top_k: int
    held_experts: tuple | None = None   # (how many, starting where)
    shared_ffn_dim: int = 0
    route_scale: float = 1.0
    balance_coeff: float = 0.0
    route_norm_eps: float = 1e-20       # the family's constant (its model file)
    gated: bool = True                  # SwiGLU experts; False: squared ReLU
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        """``x [B, S, d]`` in any float dtype: the router reads it as it
        comes (float32 from a caller that keeps it so), the experts in the
        compute dtype."""
        B, S, d = x.shape
        E = self.num_experts
        tokens = x.reshape(B * S, d)
        bias = self.variable("batch_stats", "expert_bias",
                             lambda: jnp.zeros((E,), jnp.float32))

        with jax.named_scope("moe_router"):
            kernel = self.param("router", nn.initializers.lecun_normal(),
                                (d, E), jnp.float32)
            route = route_sigmoid_bias(tokens, kernel, bias.value, self.top_k,
                                       self.route_scale, self.route_norm_eps)
            if train and not self.is_initializing() \
                    and self.is_mutable_collection("batch_stats"):
                mean = jnp.mean(route.load.astype(jnp.float32))
                delta = self.balance_coeff * jnp.sign(mean - route.load)
                bias.value = bias.value + delta - jnp.mean(delta)

        out, held = _held_sum(self, tokens, route, self.ffn_dim,
                              self.held_experts,
                              "silu" if self.gated else "relu2", self.gated)
        if self.shared_ffn_dim:
            with jax.named_scope("moe_shared"):
                shared = SwiGLU if self.gated else SquaredReLU
                out = out + shared(self.shared_ffn_dim, self.dtype,
                                   self.param_dtype, name="shared")(
                    held.tokens).astype(jnp.float32)

        rows = held.load.astype(jnp.float32)
        sown = dict(moe_held_rows=jnp.sum(rows),
                    moe_held_peak=_held_peak(rows),
                    moe_bias_peak=jnp.max(jnp.abs(bias.value)),
                    moe_whole=held.whole,
                    moe_source_parts=jnp.float32(held.parts))
        if not self.gated and self.is_mutable_collection("telemetry"):
            sown["moe_gate_zero"] = _gate_zero_share(route.chosen, held)
        _sow_telemetry(self, **sown)
        return out.reshape(B, S, d).astype(self.dtype)


class TopKSoftmaxRouter(nn.Module):
    """The router alone, for a block that routes on another tensor than its
    experts read (the ``smallthinker`` models route on the attention's input,
    ahead of attention): ``route_softmax_chosen`` over ``x [B, S, d]``
    flattened to tokens, float32 throughout on a float32 input. One
    parameter, ``kernel [d, num_experts]`` float32. Give it the name
    ``moe_router``: the module's name is its scope in the step program."""
    num_experts: int
    top_k: int

    @nn.compact
    def __call__(self, x) -> Route:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.num_experts), jnp.float32)
        return route_softmax_chosen(x.reshape(-1, x.shape[-1]), kernel,
                                    self.top_k)


class HeldExperts(nn.Module):
    """The held experts' routine alone (``_held_sum``), over a plan that a
    router made elsewhere: ``y[t] = sum_c weights[t, c] * Expert_chosen[t, c]
    (x[t])`` over the choices that fell on the experts held here, ``Expert(x)
    = (act(x W_gate) * x W_up) W_down``. No shared expert, no bias, no
    buffer: a token none of whose choices is held gets exactly zero.

    Sows into ``telemetry`` as :class:`SharedExpertMoE` does:
    ``moe_held_rows``, ``moe_held_peak``, ``moe_whole``,
    ``moe_source_parts``, and under a ``relu`` gate ``moe_gate_zero`` (the
    share of the held rows' gate activations that ``relu`` zeroes: what a
    kernel that skipped them would have to gain from; NaN where the rows did
    not fit the layout whole; a ``silu`` gate zeroes nothing, and the
    ``qwen3_next`` models' layers sow no such key). The last costs the plan
    and the gate projection once more, and is computed only in a run that
    collects ``telemetry``."""
    ffn_dim: int
    held_experts: tuple | None = None   # (how many, starting where)
    act: str = "relu"                   # a key of ops.grouped_matmul.GATES
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, route: Route):
        out, held = _held_sum(self, x.reshape(-1, x.shape[-1]), route,
                              self.ffn_dim, self.held_experts, self.act)
        rows = held.load.astype(jnp.float32)
        sown = dict(moe_held_rows=jnp.sum(rows),
                    moe_held_peak=_held_peak(rows), moe_whole=held.whole,
                    moe_source_parts=jnp.float32(held.parts))
        if self.act == "relu" and self.is_mutable_collection("telemetry"):
            sown["moe_gate_zero"] = _gate_zero_share(route.chosen, held)
        _sow_telemetry(self, **sown)
        return out.reshape(x.shape).astype(self.dtype)


def _gate_zero_share(chosen, held: Held):
    """The share of the held rows' gate pre-activations ``x W_gate`` that are
    not positive (``relu`` zeroes them); of an ungated layer, whose first
    matrix is ``W_up``, the share of ``x W_up`` that its squared ReLU zeroes.
    Telemetry only: the plan and the
    gate projection once more, outside the routine and its ``cond``. NaN
    where the rows do not fit the bounded layout whole."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    tokens, w_gate = jax.lax.stop_gradient((held.tokens, held.experts[0]))
    k = chosen.shape[1]
    tiles, _, row_pair = _plan(chosen, held.first, w_gate.shape[0], held.bt,
                               held.cap, held.load)
    gate = mesh_lib.manual_call(
        gmm_lib._gmm_padded, _rows(tokens, row_pair // k), w_gate, tiles,
        in_specs=P(), out_specs=P())
    # padding rows are zero rows and give zeros; the tiles past the last one
    # in use are never written
    live = jnp.arange(gate.shape[0]) // held.bt < tiles[2][0]
    positive = jnp.sum((gate > 0) & live[:, None])
    share = 1.0 - positive / jnp.maximum(
        jnp.sum(held.load) * w_gate.shape[2], 1)
    return jnp.where(held.whole > 0, share, jnp.nan)


class SwiGLU(nn.Module):
    """``down(silu(gate(h)) * up(h))`` without biases, as a module of its
    own (``models.llama.swiglu_mlp`` builds the same three layers into the
    calling block)."""
    ffn_dim: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, h):
        dense = lambda feat, name: nn.Dense(
            feat, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        return dense(h.shape[-1], "down")(
            nn.silu(dense(self.ffn_dim, "gate")(h))
            * dense(self.ffn_dim, "up")(h))


class SquaredReLU(nn.Module):
    """``down(relu(up(h))^2)`` without biases: the ungated two-matrix MLP of
    the ``nemotron_h`` models' shared expert, activated as the routed ones
    are (``ops.grouped_matmul._activated``: in float32, rounded once)."""
    ffn_dim: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, h):
        dense = lambda feat, name: nn.Dense(
            feat, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        from pytorch_distributed_training_example_tpu.ops import (
            grouped_matmul as gmm_lib)

        return dense(h.shape[-1], "down")(
            gmm_lib._activated(dense(self.ffn_dim, "up")(h)))

#!/usr/bin/env python
"""The held experts' moves of rows alone at a cell's shape: each move of
``parallel/moe.py`` (``dispatch``, ``combine``, the two transposes) as its
own jitted program on the chip, in the form the program asks XLA for and in
the forms it does not, with the bytes the move cannot avoid over its device
time. ``flash_micro.py`` / ``ssd_micro.py``'s sibling for the expert layers'
token side; needs the chip; no cell runs it.

Forms: ``program`` (the module's own: choice-major, a ``[T, d / n]`` slab a
choice and column part, ``n`` by the module's rule on the source's bytes: XLA
copies a gather's source into VMEM where it fits, and SmallThinker's 136 MB
``y_pad`` does not; PERF.md section 6, PRs 36 and 40), ``whole`` (the same
with the source in one part whatever its bytes: PR 36's ``program``),
``one_gather`` (choice-major, one ``[k, T, d]`` gather), ``token_major`` (PR
35's: the gathered rows viewed ``[T, k, d]``, the choice axis between the rows
and the lanes; its ``combine_bwd`` makes ``d_weights`` from a third ``[T*k,
d]`` gather), and ``halves`` (``whole`` over each half of the columns with no
order between the halves: what the rule's form was measured against).

One JSON row per (shape, move, form): ``ms`` the device time of the whole
call, ``gbps`` = ``bytes`` / ``ms`` with ``bytes`` the rows that exist read
once in their dtype plus the result written once, ``ops`` the five longest
operations (``ssd_micro.device_ms``), ``relayout`` the row arrays that a
physical ``reshape`` of the compiled text makes, ``vmem`` those placed in VMEM
(``S(1)``). A shape is ``T x d x k x E x held``; the defaults are the two
expert cells' and the two with the widths swapped.

``--sweep`` times the combine alone (8,192 tokens, 6 choices, a quarter of
the pairs present, as SmallThinker's cell) over sources of ``P`` rows on both
sides of the rule's edge at d = 2560 and 2048, ``whole`` and ``program``: a row
each with the source's ``bytes``, ``parts`` (the rule's), ``vmem`` (did the
compiled text place every gathered source in ``S(1)``) and ``ms``: the
measurement that ``parallel/moe.GATHER_SOURCE_BYTES`` stands on.

``--index-ops`` times nothing and needs no chip: it compiles the gradients of
one expert layer of each cell's shape (``LAYERS``; under a block's remat with a
consumer of the layer's output, as a model's block holds it) for the chip, or
in the sandbox for a described v5e, and prints ``index_ops`` of the compiled
text grouped: every ``gather``, ``scatter`` and ``sort`` by inner scope, with
how many places it indexes, the elements a place and whether it carries a
scope at all. These are counts of a compiled text, not times: they say which
operations index a scalar at a time (``each`` 1) and which no reader of a
trace by scope will count (``scope`` null); what each costs is for a traced
run of the cell to say.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_HERE, os.path.dirname(_HERE)]

SHAPES = ("8192x2560x6x64x16,8192x2048x8x128x16,"
          "8192x2048x6x64x16,8192x2560x8x128x16")


def forms(moe):
    """{move: {form: function}} over ``(tokens, y_pad, d_out, d_pad, weights,
    pair_row [k, T], row_pair [P])``; every function returns what the move
    hands on."""
    import jax.numpy as jnp

    f32, rows = jnp.float32, moe._rows

    def columns(move):
        """``move`` over each half of its source's columns."""
        def run(src, *rest):
            half = src.shape[1] // 2
            return jnp.concatenate([move(src[:, :half], *rest),
                                    move(src[:, half:], *rest)], axis=1)
        return run

    def one_gather(x_pad, pair_row, weights=None):
        got = rows(x_pad, pair_row).astype(f32)                    # [k, T, d]
        if weights is not None:
            got = got * weights.T[:, :, None]
        return jnp.sum(got, axis=0)

    def token_major(x_pad, pair_row, weights=None):
        got = rows(x_pad, pair_row.T).astype(f32)                  # [T, k, d]
        if weights is not None:
            got = got * weights[..., None]
        return jnp.sum(got, axis=1)

    def token_major_combine_bwd(res, d_out):
        y_pad, weights, pair_row, row_pair = res
        k = weights.shape[1]
        d_weights = jnp.sum(rows(y_pad, pair_row.T).astype(f32)
                            * d_out[:, None, :], axis=-1)
        d_pad = (rows(d_out.astype(y_pad.dtype), row_pair // k)
                 * rows(weights.reshape(-1, 1), row_pair))
        return d_pad.astype(y_pad.dtype), d_weights

    def whole(x_pad, pair_row, weights=None):
        return moe._choice_sum_in(x_pad, pair_row, weights, 1)

    sums = {"program": moe._choice_sum, "whole": whole,
            "one_gather": one_gather, "token_major": token_major}
    k_of = lambda w: w.shape[1]
    return {
        "dispatch": {
            "program": lambda a: rows(a.tokens, a.row_pair // k_of(a.weights)),
            "halves": lambda a: columns(rows)(
                a.tokens, a.row_pair // k_of(a.weights))},
        "combine": {
            **{name: (lambda a, s=s: s(a.y_pad, a.pair_row, a.weights))
               for name, s in sums.items()},
            "halves": lambda a: columns(whole)(
                a.y_pad, a.pair_row, a.weights)},
        "combine_bwd": {
            "program": lambda a: moe._combine_bwd(
                (a.y_pad, a.weights, a.pair_row, a.row_pair), a.d_out)[:2],
            "token_major": lambda a: token_major_combine_bwd(
                (a.y_pad, a.weights, a.pair_row, a.row_pair), a.d_out)},
        "dispatch_bwd": {
            **{name: (lambda a, s=s: s(a.d_pad, a.pair_row).astype(
                a.d_pad.dtype)) for name, s in sums.items()},
            "halves": lambda a: columns(whole)(
                a.d_pad, a.pair_row).astype(a.d_pad.dtype)},
    }


def operands(moe, T, d, k, E, held, seed):
    """A level router's plan for ``T`` tokens over the bounded layout the
    cell takes, and rows of the program's dtypes."""
    import collections

    import jax
    import jax.numpy as jnp

    Args = collections.namedtuple(
        "Args", "tokens y_pad d_out d_pad weights pair_row row_pair")
    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    chosen = jax.lax.top_k(jax.random.uniform(key[0], (T, E)), k)[1]
    cap = -(-(T // max(1, E // (2 * held))) * k // moe.EXPERT_TILE_ROWS) + held
    _, pair_row, row_pair = jax.jit(
        lambda c: moe._plan(c, 0, held, moe.EXPERT_TILE_ROWS, cap, None))(
            chosen)
    P = row_pair.shape[0]
    bf = jnp.bfloat16
    return Args(jax.random.normal(key[1], (T, d), bf),
                jax.random.normal(key[2], (P, d), bf),
                jax.random.normal(key[3], (T, d)),
                jax.random.normal(key[4], (P, d), bf),
                jax.nn.softmax(jax.random.normal(key[5], (T, k))),
                pair_row, row_pair)


def least_bytes(move, a):
    """The rows that exist read once, the result written once."""
    (T, d), P, k = a.tokens.shape, a.row_pair.shape[0], a.weights.shape[1]
    live = int(np.sum(np.asarray(a.row_pair) < T * k))
    return {"dispatch": live * d * 2 + P * d * 2,
            "combine": live * d * 2 + T * d * 4,
            "combine_bwd": live * d * (2 + 4) + P * d * 2 + T * k * 4,
            "dispatch_bwd": live * d * 2 + T * d * 2}[move]


def row_arrays(text, d, least):
    """(shapes that a physical ``reshape`` makes, shapes placed in VMEM)
    among the unfused instructions of a compiled text whose result is an
    array of ``d`` or ``d / 2`` columns and ``least`` elements or more."""
    unfused = re.sub(r"(?ms)^%?fused_computation[^\n]*\{$.*?^\}$", "", text)
    relayout, vmem = set(), set()
    for shape, dims, layout, op in re.findall(
            r" = (\w+\[([\d,]+)\])(\{[^}]*\})? ([\w-]+)\(", unfused):
        dims = [int(v) for v in dims.split(",")]
        if dims[-1] in (d, d // 2) and np.prod(dims) >= least:
            if op == "reshape":
                relayout.add(shape)
            if "S(1)" in layout:
                vmem.add(shape)
    return sorted(relayout), sorted(vmem)


def _computations(text):
    """{name: body} of a compiled text's computations."""
    return {m.group(1): body for body in text.split("\n\n")
            if (m := re.match(r"(?:ENTRY )?(%[\w.-]+) \(", body.strip()))}


def row_gathers(text, least=1 << 22):
    """``[(scope, rows gathered, source)]`` of a compiled text's gathers of
    ``least`` elements or more outside every ``while``: ``scope`` the
    ``op_name`` of the fusion that holds the gather, ``source`` the gathered
    array's shape and layout as that fusion's caller holds it (``S(1)`` in
    the layout: placed in VMEM)."""
    bodies = _computations(text)
    inside = {}                   # fused computation: [(rows, parameter)]
    for name, body in bodies.items():
        number = dict(re.findall(r"(%[\w.-]+) = \S+ parameter\((\d+)\)", body))
        for rows, dims, source in re.findall(
                r" = (\w+\[([\d,]+)\])\S* gather\((%[\w.-]+),", body):
            if len(dims.split(",")) == 2 and source in number and \
                    math.prod(map(int, dims.split(","))) >= least:
                inside.setdefault(name, []).append((rows, int(number[source])))
    found = []
    for body in bodies.values():
        held = dict(re.findall(
            r"^\s*(?:ROOT )?(%[\w.-]+) = (\w+\[[\d,]*\]\S*) ", body, re.M))
        for operands, callee, scope in re.findall(
                r" fusion\(([^)]*)\), kind=\w+, calls=(%[\w.-]+), "
                r"metadata=\{op_name=\"([^\"]*)\"", body):
            if "/while/" not in scope:
                operands = re.findall(r"%[\w.-]+", operands)
                found += [(scope, rows, held.get(operands[index], "?"))
                          for rows, index in inside.get(callee, [])]
    return found


#: The expert layer's inner scopes, as the benchmark's ``row: "moe"`` line
#: splits its time.
INNER = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
         "moe_shared")


def index_ops(text):
    """Every ``gather``, ``scatter`` and ``sort`` of a compiled text, a dict
    each: ``op``; ``result`` (a sort's first array); ``indices``, how many
    places it looks up, writes to or orders (a gather's slices, a scatter's
    updates, the elements along a sort's dimension times its rows); ``each``,
    the elements a place (1: a scalar at a time); ``arrays`` (a sort's
    operands, else 1); ``scope``, the first of ``INNER`` in its ``op_name``,
    (the ``op_name`` of the fusion that holds it, where one does), else
    ``"other"``, else ``None`` where neither the fusion nor the instruction
    carries an ``op_name`` at all (no reader of a trace by scope counts it);
    ``loop``: under a ``while``. Counts of a compiled
    text, not times: what one costs is for a traced run to say (about 8 ns a
    scalar index on a v5e, 14 ns a row from VMEM; PERF.md section 6)."""
    bodies = _computations(text)
    name_of = lambda line: (re.findall(r'op_name="([^"]*)"', line) or [None])[0]
    callers = {}                  # fused computation: its caller's op_name
    for body in bodies.values():
        for line in body.splitlines():
            if (m := re.search(r" fusion\(.*calls=(%[\w.-]+)", line)):
                callers[m.group(1)] = name_of(line)
    dims_of = lambda shape: [int(v) for v in re.findall(r"\d+", shape.split(
        "[")[1].split("]")[0])]
    found = []
    for name, body in bodies.items():
        held = dict(re.findall(
            r"^\s*(?:ROOT )?(%[\w.-]+) = (\w+\[[\d,]*\])", body, re.M))
        for line in body.splitlines():
            m = re.search(r" = \(?(\w+\[[\d,]*\])[^=]*? (gather|scatter|sort)"
                          r"\(([^)]*)\)", line)
            if not m:
                continue
            result, op, operands = m.groups()
            dims, arrays = dims_of(result), 1
            if op == "gather":
                window = [int(v) for v in re.search(
                    r"offset_dims=\{([\d,]*)\}", line).group(1).split(",") if v]
                indices = math.prod(
                    v for axis, v in enumerate(dims) if axis not in window)
                each = math.prod(dims) // indices
            elif op == "scatter":
                places, updates = (dims_of(held[name]) for name in re.findall(
                    r"%[\w.-]+", operands)[1:3])
                vector = int(re.search(r"index_vector_dim=(\d+)", line).group(1))
                indices = math.prod(
                    v for axis, v in enumerate(places) if axis != vector)
                each = math.prod(updates) // indices
            else:
                indices, each, arrays = math.prod(dims), 1, operands.count("%")
            # a fused instruction counts where its fusion does: a trace has
            # the fusion's time under the fusion's ``op_name``
            scope = callers.get(name) or name_of(line)
            found.append(dict(
                op=op, result=result, indices=indices, each=each,
                arrays=arrays, scope=None if scope is None else next(
                    (part for part in scope.split("/") if part in INNER),
                    "other"),
                loop=bool(scope) and "/while/" in scope))
    return found


#: ``--index-ops``: an expert layer of each expert cell's shape at 8,192
#: tokens: ``(d, k, E, held, the experts' width, the shared expert's, gated,
#: the router's scale; None: the softmax router ahead of ``HeldExperts``)``.
LAYERS = {
    "trinity_mini": (2048, 8, 128, 16, 1024, 1024, True, 2.826),
    "smallthinker_21b": (2560, 6, 64, 16, 768, 0, True, None),
    "glm47_flash": (2048, 4, 64, 8, 1536, 1536, True, 1.8),
    "nemotron3_nano": (2688, 6, 128, 8, 1856, 3712, False, 2.5),
    "lfm2_8b_a1b": (2048, 4, 32, 8, 1792, 0, True, 1.0),
}


def layer_grads(moe, cell, sharding, T=8192):
    """``(fn, args)``: the gradients of ``LAYERS[cell]``'s layer in its
    parameters and input, under ``jax.checkpoint(nothing_saveable)`` with a
    consumer of the layer's output inside it (what a block's closing norm
    is), over shapes placed by ``sharding``."""
    import jax
    import jax.numpy as jnp

    d, k, E, held, ffn, shared, gated, scale = LAYERS[cell]
    bf = jnp.bfloat16
    x = jnp.zeros((1, T, d), bf)
    if scale is None:
        router = moe.TopKSoftmaxRouter(num_experts=E, top_k=k)
        experts = moe.HeldExperts(ffn_dim=ffn, held_experts=(held, 0),
                                  act="relu", dtype=bf)

        def init():
            kernel = router.init(jax.random.key(0), x.astype(jnp.float32))
            route = router.apply(kernel, x.astype(jnp.float32))
            return {"router": kernel,
                    "experts": experts.init(jax.random.key(0), x, route)}

        def apply(variables, x):
            with jax.named_scope("block"), jax.named_scope("moe_router"):
                route = router.apply(variables["router"],
                                     x.astype(jnp.float32))
            return experts.apply(variables["experts"], x, route)
    else:
        layer = moe.SharedExpertMoE(
            num_experts=E, ffn_dim=ffn, top_k=k, held_experts=(held, 0),
            shared_ffn_dim=shared, route_scale=scale, balance_coeff=0.001,
            gated=gated, dtype=bf)
        init = lambda: layer.init(jax.random.key(0), x, train=False)
        apply = lambda variables, x: layer.apply(variables, x, train=False)

    def grads(variables, x):
        block = jax.checkpoint(
            lambda v, x: jnp.sin(apply(v, x).astype(jnp.float32)),
            prevent_cse=False,
            policy=jax.checkpoint_policies.nothing_saveable)
        return jax.grad(lambda v, x: block(v, x).sum(), argnums=(0, 1))(
            variables, x)

    placed = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)
    return grads, placed((jax.eval_shape(init), x))


def print_index_ops(moe, cells):
    """One JSON row a (cell, op, result, scope, ...) with how many such
    instructions the layer's compiled text holds."""
    import collections

    import jax
    from jax.sharding import SingleDeviceSharding

    if jax.default_backend() == "tpu":
        device = jax.devices()[0]
    else:       # compile for a described chip; the program asks the backend
        from jax.experimental import topologies

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
        jax.config.update("jax_enable_compilation_cache", False)
        jax.default_backend = lambda: "tpu"
    print(json.dumps({"device": device.device_kind, "counts": True,
                      "times": False}), flush=True)
    for cell in cells:
        fn, args = layer_grads(moe, cell, SingleDeviceSharding(device))
        found = collections.Counter(
            tuple(op.items())
            for op in index_ops(jax.jit(fn).lower(*args).compile().as_text()))
        for op, n in sorted(found.items(), key=lambda kv: str(kv[0])):
            print(json.dumps({"cell": cell, **dict(op), "n": n}), flush=True)


#: ``--sweep``: {d: the sources' rows}, 72 MiB to 130 MiB; the edge is 112
#: MiB (22,937.6 rows of 2560, 28,672 of 2048 in bf16).
SWEEP = {2560: (14720, 18432, 20480, 21504, 22528, 22912, 23040, 23552, 24576,
                26624),
         2048: (18432, 24576, 26624, 28160, 28672, 28800, 29696, 30720,
                33280)}


def sweep(moe, device_ms, iters, seed, T=8192, k=6, present=0.25):
    """One row a (d, P, form): the combine over a ``bf16[P, d]`` source."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    forms = {"whole": lambda *a: moe._choice_sum_in(*a, 1),
             "program": moe._choice_sum}
    for d, sizes in SWEEP.items():
        for P in sizes:
            pairs = np.full(k * T, P, np.int32)         # P: no padded row
            live = rng.choice(k * T, int(present * k * T), replace=False)
            pairs[live] = rng.permutation(P)[:live.size]
            key = jax.random.split(jax.random.PRNGKey(seed), 2)
            args = (jax.random.normal(key[0], (P, d), jnp.bfloat16),
                    jnp.asarray(pairs.reshape(k, T)),
                    jax.nn.softmax(jax.random.normal(key[1], (T, k))))
            for form, fn in forms.items():
                sources = [source for _, _, source in row_gathers(
                    jax.jit(fn).lower(*args).compile().as_text())]
                ms, _, top = device_ms(fn, args, iters)
                print(json.dumps({
                    "sweep": d, "P": P, "bytes": P * d * 2,
                    "mib": round(P * d * 2 / 2**20, 2), "form": form,
                    "parts": moe._source_parts(P, d, 2) if form == "program"
                    else 1, "vmem": bool(sources) and all(
                        "S(1)" in source for source in sources),
                    "ms": round(ms, 4),
                    "ops": {n: round(v, 4) for n, v in top.items()}}),
                    flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--shapes", default=SHAPES,
                   help="comma-separated T x d x k x E x held")
    p.add_argument("--sweep", action="store_true",
                   help="the combine over sources on both sides of the "
                        "rule's edge, and nothing else")
    p.add_argument("--index-ops", action="store_true",
                   help="no timing, no chip needed: the gathers, scatters and "
                        "sorts of a layer of each of --cells, from its "
                        "compiled text")
    p.add_argument("--cells", default=",".join(LAYERS))
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import jax

    from pytorch_distributed_training_example_tpu.parallel import moe

    if args.index_ops:
        return print_index_ops(moe, args.cells.split(","))
    from ssd_micro import device_ms

    if jax.default_backend() != "tpu":
        sys.exit("moe_rows_micro.py times the moves on the chip; this is "
                 + jax.default_backend())
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    if args.sweep:
        return sweep(moe, device_ms, args.iters, args.seed)
    for shape in args.shapes.split(","):
        T, d, k, E, held = (int(v) for v in shape.split("x"))
        a = operands(moe, T, d, k, E, held, args.seed)
        for move, by_form in forms(moe).items():
            for form, fn in by_form.items():
                row = {"shape": shape, "P": a.row_pair.shape[0],
                       "move": move, "form": form,
                       "bytes": least_bytes(move, a)}
                relayout, vmem = row_arrays(
                    jax.jit(fn).lower(a).compile().as_text(), d, T * d // 2)
                ms, _, top = device_ms(lambda *b: fn(type(a)(*b)), tuple(a),
                                       args.iters)
                row.update(
                    ms=round(ms, 4), gbps=round(row["bytes"] / ms / 1e6, 1),
                    ops={n: round(v, 4) for n, v in top.items()},
                    relayout=relayout, vmem=vmem)
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

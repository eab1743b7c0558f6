#!/usr/bin/env python
"""The gated expert FFN's kernels alone at the expert cells' shapes and
layouts: each program of ``ops/grouped_matmul.py``'s gated form on the chip
(the forward, which keeps ``gate`` and ``up``, and the backward with the
down projection it runs again), every kernel's device time by its
name beside its products' time at the peak, what XLA still runs beside the
kernels, and the ``gmm_plan`` each kernel said. ``flash_micro.py`` /
``ssd_micro.py`` / ``moe_rows_micro.py``'s sibling for the experts' matmuls;
needs the chip; no cell runs it.

A shape is ``T x k x E x held x d x f x act``; the defaults are the five
expert cells' (Nemotron's experts are ungated: its widths, off the lane
tiling, stand in for a gated layer of them). The layout is the bounded one
the cell takes (``parallel/moe._plan`` over a level router's choices): about
half of its tiles are in use.

One JSON row a (shape, program): ``ms`` the device time of the whole call,
``kernels`` {name: ms} (a name's calls summed), ``xla_ms`` the rest,
``peak_ms`` the program's products over the live rows at 197 TFLOP/s,
``plans`` the ``gmm_plan`` records of its trace. ``--composition`` adds the
same two programs as the three-call composition the gated form replaced
(``_gmm_padded`` with ``_gated`` between the calls, XLA's), which also runs
from a checkout that has no gated kernels (``cd _parent && python
../benchmarks/gmm_micro.py --composition``: both read that checkout).
``--budget 1,2,4,8`` repeats the gated programs with ``_BLOCK_BYTES`` at each
of these MiB: what the constant was chosen from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [".", _HERE, os.path.dirname(_HERE)]

SHAPES = ("8192x8x128x16x2048x1024xsilu,8192x6x64x16x2560x768xrelu,"
          "8192x4x64x8x2048x1536xsilu,8192x4x32x8x2048x1792xsilu,"
          "8192x6x128x8x2688x1856xsilu")
PEAK_FLOPS = 197e12     # one v5e chip, bf16 (chipbench/peaks.py has the source)
KERNELS = ("gated_ffn_", "grouped_matmul")


def operands(moe, gmm, T, k, E, held, d, f, seed):
    """``(x_pad, w_gate, w_up, w_down, tiles, dy_pad)`` and the live rows: a
    level router's plan over the bounded layout the cell takes."""
    import jax
    import jax.numpy as jnp

    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    chosen = jax.lax.top_k(jax.random.uniform(key[0], (T, E)), k)[1]
    bt = min(moe.EXPERT_TILE_ROWS, gmm._block_rows(T * k, held))
    chunks = max(1, E // (2 * held))
    cap = -(-(T // chunks) * k // bt) + held if chunks > 1 else None
    tiles, _, row_pair = jax.jit(
        lambda c: moe._plan(c, 0, held, bt, cap, None))(chosen)
    live = np.asarray(row_pair) < T * k
    bf = jnp.bfloat16
    normal = lambda i, shape, scale=1.0: (
        jax.random.normal(key[i], shape) * scale).astype(bf)
    rows = (live.size, d)
    mask = jnp.asarray(live[:, None], bf)
    return ((normal(1, rows) * mask, normal(2, (held, d, f), d ** -0.5),
             normal(3, (held, d, f), d ** -0.5),
             normal(4, (held, f, d), f ** -0.5), tiles,
             normal(5, rows) * mask), int(live.sum()))


def gated_programs(gmm, act):
    """{program: function of ``operands``} of the gated form."""
    def backward(x, wg, wu, wd, tiles, dy):
        _, gate, up = gmm.gated_ffn_padded_kept(x, wg, wu, wd, tiles, act)
        # as the expert layer's rule: the down projection for the combine's
        # weights, then the four transposes
        return (gmm.gated_down_padded(gate, up, wd, tiles, act),
                gmm.gated_ffn_padded_bwd(x, gate, up, wg, wu, wd, tiles, dy,
                                         act))

    return {
        "forward_kept": lambda x, wg, wu, wd, tiles, dy:
            gmm.gated_ffn_padded_kept(x, wg, wu, wd, tiles, act),
        "forward_kept+backward": backward}


def composed_programs(gmm, act):
    """The same two programs as three ``_gmm_padded`` calls with XLA's
    ``_gated`` between them, and the rule's transposes strung together."""
    import functools

    import jax

    def kept(x, wg, wu, wd, tiles):
        gate = gmm._gmm_padded(x, wg, tiles)
        up = gmm._gmm_padded(x, wu, tiles)
        return gmm._gmm_padded(gmm._gated(gate, up, act), wd, tiles), gate, up

    def backward(x, wg, wu, wd, tiles, dy):
        _, gate, up = kept(x, wg, wu, wd, tiles)
        h, gated_vjp = jax.vjp(functools.partial(gmm._gated, act=act),
                               gate, up)
        y = gmm._gmm_padded(h, wd, tiles)
        dh, dw_down, _ = gmm._gmm_padded_bwd((h, wd, tiles), dy)
        dgate, dup = gated_vjp(dh)
        dx_gate, dw_gate, _ = gmm._gmm_padded_bwd((x, wg, tiles), dgate)
        dx_up, dw_up, _ = gmm._gmm_padded_bwd((x, wu, tiles), dup)
        return y, (dx_gate + dx_up, dw_gate, dw_up, dw_down)

    return {
        "composed.forward_kept": lambda x, wg, wu, wd, tiles, dy:
            kept(x, wg, wu, wd, tiles),
        "composed.forward_kept+backward": backward}


def device_ms(fn, args, reps):
    """(device ms of one run of ``jit(fn)``, {kernel name: ms a run}, the
    ``gmm_plan`` records its trace said)."""
    import jax

    from profile_step import collect_ops

    from pytorch_distributed_training_example_tpu.utils import telemetry

    mark = len(telemetry.recorder().records())
    run = jax.jit(fn)
    jax.block_until_ready(run(*args))  # compile + warm
    plans = [r.value for r in telemetry.recorder().records()[mark:]
             if r.name == "gmm_plan"]
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(reps):
                out = run(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        ops, module_ns, runs = collect_ops(trace_dir)
    kernels = {}
    for event, (ns, _) in ops.items():
        name = event.split(" = ", 1)[0].strip().lstrip("%").split(".")[0]
        if name.startswith(KERNELS):
            kernels[name] = kernels.get(name, 0.0) + ns / reps / 1e6
    return module_ns / max(runs, 1) / 1e6, kernels, plans


def peak_ms(program, live, d, f):
    """The program's matrix products over the live rows at the peak: three a
    forward, and in the backward the down projection again and six more."""
    products = 9 + 1 if program.endswith("backward") else 3
    return products * 2 * live * d * f / PEAK_FLOPS * 1e3


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--shapes", default=SHAPES,
                   help="comma-separated T x k x E x held x d x f x act")
    p.add_argument("--composition", action="store_true",
                   help="also the three-call composition (XLA's gate)")
    p.add_argument("--budget", default="",
                   help="comma-separated MiB: the gated programs again with "
                        "_BLOCK_BYTES at each")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import jax

    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm)
    from pytorch_distributed_training_example_tpu.parallel import moe

    if jax.default_backend() != "tpu":
        sys.exit("gmm_micro.py times the kernels on the chip; this is "
                 + jax.default_backend())
    gated, default = hasattr(gmm, "_gated_up"), gmm._BLOCK_BYTES
    budgets = [None] + [int(float(b) * 2**20)
                        for b in args.budget.split(",") if b]
    for shape in args.shapes.split(","):
        *sizes, act = shape.split("x")
        T, k, E, held, d, f = map(int, sizes)
        ops, live = operands(moe, gmm, T, k, E, held, d, f, args.seed)
        for budget in budgets if gated else [None]:
            programs = gated_programs(gmm, act) if gated else {}
            if args.composition and budget is None:
                programs.update(composed_programs(gmm, act))
            gmm._BLOCK_BYTES = budget or default
            for program, fn in programs.items():
                row = {"shape": shape, "rows": ops[0].shape[0], "live": live,
                       "program": program,
                       "block_bytes": gmm._BLOCK_BYTES}
                try:
                    ms, kernels, plans = device_ms(fn, ops, args.iters)
                except Exception as e:  # a budget the chip's VMEM refuses
                    print(json.dumps({**row, "error": repr(e)[:300]}),
                          flush=True)
                    continue
                least = peak_ms(program, live, d, f)
                print(json.dumps({
                    **row, "ms": round(ms, 4),
                    "kernels": {n: round(v, 4) for n, v in kernels.items()},
                    "xla_ms": round(ms - sum(kernels.values()), 4),
                    "peak_ms": round(least, 4),
                    "of_peak_pct": round(100 * least / ms, 2),
                    "plans": [{key: plan[key] for key in (
                        "kernel", "block", "blocks", "vmem")}
                        for plan in plans]}), flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""The Mamba-2 scan alone at a cell's shape: each Pallas kernel by its name
in a device trace, the whole call beside it, and the ``jax.numpy`` scan
(``ops/ssd._scan_xla``) as the yardstick. ``flash_micro.py --kernels``'
sibling for ``ops/ssd.py``; needs the chip.

One JSON row per (impl, pass): ``kernels`` {name: device ms a call} for the
kernel path, ``ms`` the device time of the whole jitted call (kernels, the
cumulative sums and layouts around them, for ``grad`` the forward too),
``others`` the five longest operations that are no kernel.
``--groups`` tries other heads-per-program than the planner's. ``--check N``
prints, on N seeds and for both paths, every cotangent's distance from a float32 scan at
matmul precision ``highest`` (relative error, and the norm gap the chip
benchmark's ``grad_leaf`` is made of).

``--stages`` times the mixer's two elementwise stages alone instead
(``ops/ssd.conv_silu``, and ``ops/ssd.gate_norm`` or, at the delta-rule cell's
shape, its other order ``ops/ssd.norm_gate``), each at the three cells' shapes,
the kernels beside the ``jax.numpy`` forms: ``fwd`` is the call, ``bwd`` every
cotangent from a given one (for the ``jax.numpy`` form whatever XLA runs for
that, its forward's share included); ``floor_ms`` is the bytes the stage must
move at the chip's HBM peak and ``floor_pct`` that over ``ms``. ``--tiles``
tries other [rows x cols] tiles than the planner's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from unittest import mock

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_HERE, os.path.dirname(_HERE)]


#: What a kernel of ``ops/ssd.py`` is called in a trace.
KERNELS = ("ssd_", "conv_silu_", "gate_norm_", "norm_gate_")
#: The chip benchmark's peaks by ``device_kind``, with their source. A device
#: that is not there is an error.
PEAKS = os.path.join(os.path.dirname(_HERE), "chipbench", "peaks.json")
#: The stages at the cells' shapes: S, the conv's channels, the inner
#: channels, the norm's groups, and which order of gate and norm the mixer
#: takes (Qwen3-Next's delta rule: 32 value heads of 128, one scale for all).
STAGE_SHAPES = {"nemotron3_nano": (8192, 6144, 4096, 8, "gate_norm"),
                "granite4_h_micro": (4096, 4352, 4096, 1, "gate_norm"),
                "qwen3_next_80b": (8192, 8192, 4096, 32, "norm_gate")}


def inputs(b, S, H, P, N, seed=0):
    """Operands in the Granite mixer's ranges: dt = softplus(. - 3), A =
    -exp(normal), bf16 x, B, C."""
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf = jnp.bfloat16
    args = (jax.random.normal(k[0], (b, S, H, P), bf),
            jax.nn.softplus(jax.random.normal(k[1], (b, S, H)) - 3.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (b, S, N), bf),
            jax.random.normal(k[4], (b, S, N), bf),
            1.0 + 0.1 * jax.random.normal(k[5], (H,)))
    return args, jax.random.normal(k[6], (b, S, H, P))


def device_ms(fn, args, reps, kernels_named=KERNELS):
    """(device ms of one run of ``jit(fn)``, {kernel: ms a run} for the
    operations whose names start with ``kernels_named``, the five longest
    other operations as {name: ms a run})."""
    import jax

    from profile_step import collect_ops

    run = jax.jit(fn)
    jax.block_until_ready(run(*args))  # compile + warm
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(reps):
                out = run(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        ops, module_ns, runs = collect_ops(trace_dir)
    kernels, others = {}, {}
    for event, (ns, _) in ops.items():
        name = event.split(" = ", 1)[0].strip().lstrip("%")
        into = kernels if name.startswith(kernels_named) else others
        name = name.split(".")[0] if into is kernels else name
        into[name] = into.get(name, 0.0) + ns / reps / 1e6
    top = dict(sorted(others.items(), key=lambda kv: -kv[1])[:5])
    return module_ns / max(runs, 1) / 1e6, kernels, top


def paths(ssd, chunk, groups):
    """{impl: scan function of (x, dt, A, B, C, D)}: the ``jax.numpy`` scan,
    the kernels at the planner's plan, and at each G of ``groups``."""
    def xla(x, dt, A, B, C, D):
        return ssd._scan_xla(x, dt, A, B, C, D, chunk)

    def kernels(G):
        def scan(*args):
            with mock.patch.object(ssd, "_kernel_plan", lambda *a: G):
                return ssd.ssd(*args, chunk=chunk)
        return scan

    out = {"xla": xla,
           "kernels": lambda *a: ssd.ssd(*a, chunk=chunk)}
    out.update({"kernels:G%d" % G: kernels(G) for G in groups})
    return out


def check(fns, args, w, seed):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    grads = lambda fn, a: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=range(6)))(*a)
    with jax.default_matmul_precision("highest"):
        want = grads(fns["xla"], tuple(a.astype(f32) for a in args))
    norm = lambda a: float(jnp.linalg.norm(a.astype(f32).ravel()))
    for impl, fn in fns.items():
        got = grads(fn, args)
        row = {"impl": impl, "check": "against float32 at highest",
               "seed": seed}
        for name, g, t in zip("x dt A B C D".split(), got, want):
            row["d" + name] = {
                "rel_err": norm(g.astype(f32) - t) / norm(t),
                "norm_gap": abs(norm(g) - norm(t)) / norm(t)}
        print(json.dumps(row), flush=True)


def stage_errors(fns, exact, args, ct):
    """One row an impl: the result's and every cotangent's distance from
    ``exact`` (the ``jax.numpy`` form on the same operands held in float32,
    float32 out), relative to the exact one's norm."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def both(fn, operands, ct):
        out, back = jax.vjp(fn, *operands)
        return (out,) + back(ct.astype(out.dtype))

    want = jax.jit(lambda *a: both(exact, a, ct.astype(f32)))(
        *(a.astype(f32) for a in args))
    norm = lambda a: float(jnp.linalg.norm(a.astype(f32).ravel()))
    for impl, fn in fns.items():
        got = jax.jit(lambda *a: both(fn, a, ct))(*args)
        yield impl, [norm(g.astype(f32) - t) / norm(t)
                     for g, t in zip(got, want)]


def stages(ssd, iters, tiles, seed, check=0):
    """One row a (stage, cell's shape, impl, pass); with ``check``, one more
    an impl: ``rel_err`` of the result and of each cotangent."""
    import jax
    import jax.numpy as jnp

    f32, bf = jnp.float32, jnp.bfloat16
    with open(PEAKS) as fh:
        peak = json.load(fh)["kinds"][jax.devices()[0].device_kind][
            "hbm_bytes_per_s"]
    conv_body = lambda x, w, c: jax.nn.silu(ssd.causal_conv1d(x, w, c))
    norm_bodies = {
        "gate_norm": lambda groups, dtype: lambda y, z, g: ssd.group_rms_norm(
            y * jax.nn.silu(z.astype(f32)), g, groups, 1e-5, dtype),
        "norm_gate": lambda groups, dtype: lambda y, z, g: (
            ssd.group_rms_norm(y, jnp.tile(g, groups), groups, 1e-5, f32)
            * jax.nn.silu(z.astype(f32))).astype(dtype)}
    for cell, (S, conv_dim, inner, groups, order) in STAGE_SHAPES.items():
        gate_body = norm_bodies[order]
        # norm_gate's scale is one vector that every group shares
        shared = order == "norm_gate"
        wide = lambda g, n=groups if shared else 1: jnp.tile(g, n)
        k = jax.random.split(jax.random.PRNGKey(seed), 8)
        normal = lambda i, C, dtype: jax.random.normal(k[i], (1, S, C), dtype)
        # channels, bytes an element (fwd, bwd), operands, the cotangent,
        # the jax.numpy form, the same with float32 out, the stage as
        # planned, and at a given tile
        cases = {
            "conv_silu": (
                conv_dim, (2 * 2, 3 * 2),
                (normal(0, conv_dim, bf),
                 0.5 * jax.random.normal(k[1], (4, conv_dim)),
                 0.1 * jax.random.normal(k[2], (conv_dim,))),
                normal(3, conv_dim, bf),
                conv_body, conv_body, ssd.conv_silu,
                lambda plan: lambda x, w, c: ssd._conv_silu_kernels(
                    x, x, w, c, (plan, 0))),
            order: (
                inner, (4 + 2 + 2, 4 + 2 + 2 + 4 + 2),
                (normal(4, inner, f32), normal(5, inner, bf),
                 1.0 + 0.1 * jax.random.normal(
                     k[6], (inner // groups if shared else inner,))),
                normal(7, inner, bf),
                gate_body(groups, bf), gate_body(groups, f32),
                lambda *a, groups=groups, order=order: getattr(ssd, order)(
                    *a, groups=groups, epsilon=1e-5, dtype=bf),
                lambda plan: lambda y, z, g: ssd._gate_norm_kernels(
                    y, z, None, wide(g),
                    (order, plan, 0, groups, 1e-5, jnp.dtype(bf)))),
        }
        for stage, (C, per_elem, args, ct, xla, exact, planned,
                    at) in cases.items():
            fns = {"xla": xla, "kernels": planned}
            if check:
                for impl, errors in stage_errors(fns, exact, args, ct):
                    print(json.dumps({
                        "stage": stage, "cell": cell, "impl": impl,
                        "check": "against float32 operands",
                        "rel_err": dict(zip(("out", "d0", "d1", "d2"),
                                            errors))}), flush=True)
            # a tile's columns divide the channels and hold whole groups
            width = 128 if stage == "conv_silu" else C // groups
            fns.update({"kernels:%dx%d" % t: at(t) for t in tiles
                        if C % t[1] == 0 and t[1] % width == 0})
            for impl, fn in fns.items():
                for tag, nbytes, run, operands in (
                        ("fwd", per_elem[0], fn, args),
                        ("bwd", per_elem[1],
                         lambda *a, fn=fn: jax.vjp(fn, *a[:-1])[1](a[-1]),
                         args + (ct,))):
                    floor = S * C * nbytes / peak * 1e3
                    row = {"stage": stage, "cell": cell, "shape": [S, C],
                           "groups": groups, "impl": impl, "pass": tag,
                           "floor_ms": round(floor, 4)}
                    try:
                        ms, kernels, others = device_ms(run, operands, iters)
                        rounded = lambda d: {n: round(v, 4)
                                             for n, v in d.items()}
                        row.update(ms=round(ms, 4),
                                   floor_pct=round(100 * floor / ms, 2),
                                   kernels=rounded(kernels),
                                   others=rounded(others))
                    except Exception as e:  # a tile the compiler refuses
                        row["error"] = str(e).strip().splitlines()[-1][-300:]
                    print(json.dumps(row), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--shape", default="1x4096x64x64x128",
                   help="b x S x H x P x N (default: granite-4.0-h-micro's "
                        "mixer at one sequence of 4096)")
    p.add_argument("--chunk", type=int, default=256)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--groups", default="",
                   help="comma-separated heads per program to try besides "
                        "the planner's")
    p.add_argument("--check", type=int, default=0, metavar="SEEDS",
                   help="compare the cotangents with float32 at highest on "
                        "this many seeds")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--stages", action="store_true",
                   help="time conv_silu and gate_norm (norm_gate) alone at "
                        "the cells' shapes instead of the scan")
    p.add_argument("--tiles", default="",
                   help="with --stages: comma-separated ROWSxCOLS tiles to "
                        "try besides the planner's")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.ops import ssd

    if jax.default_backend() != "tpu":
        sys.exit("ssd_micro.py times kernels on the chip; this is "
                 + jax.default_backend())
    if args.stages:
        print(json.dumps({"stages": STAGE_SHAPES,
                          "device": jax.devices()[0].device_kind}), flush=True)
        tiles = [tuple(int(v) for v in t.split("x"))
                 for t in args.tiles.split(",") if t]
        return stages(ssd, args.iters, tiles, args.seed, args.check)
    b, S, H, P, N = (int(v) for v in args.shape.split("x"))
    groups = [int(g) for g in args.groups.split(",") if g]
    operands, w = inputs(b, S, H, P, N, args.seed)
    fns = paths(ssd, args.chunk, groups)
    print(json.dumps({"shape": args.shape, "chunk": args.chunk,
                      "plan": ssd._kernel_plan(H, P, N, args.chunk,
                                               operands[0].dtype),
                      "device": jax.devices()[0].device_kind}), flush=True)
    for impl, fn in fns.items():
        for tag, run in (
                ("fwd", fn),
                ("grad", jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                  argnums=range(6)))):
            row = {"impl": impl, "pass": tag}
            try:
                ms, kernels, others = device_ms(run, operands, args.iters)
                rounded = lambda d: {n: round(v, 4) for n, v in d.items()}
                row.update(ms=round(ms, 4), kernels=rounded(kernels),
                           others=rounded(others))
            except Exception as e:  # a group the compiler refuses
                row["error"] = str(e).strip().splitlines()[-1][-300:]
            print(json.dumps(row), flush=True)
    for seed in range(args.seed, args.seed + args.check):
        check(fns, *inputs(b, S, H, P, N, seed), seed)


if __name__ == "__main__":
    main()

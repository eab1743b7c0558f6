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


def device_ms(fn, args, reps):
    """(device ms of one run of ``jit(fn)``, {ssd_* kernel: ms a run}, the
    five longest other operations as {name: ms a run})."""
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
        into = kernels if name.startswith("ssd_") else others
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
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.ops import ssd

    if jax.default_backend() != "tpu":
        sys.exit("ssd_micro.py times kernels on the chip; this is "
                 + jax.default_backend())
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

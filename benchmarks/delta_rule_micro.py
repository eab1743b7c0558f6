#!/usr/bin/env python
"""The gated delta rule alone at the Qwen3-Next cell's shape: one forward and
one backward pass of ``ops/gated_delta.gated_delta_rule``, the Pallas kernels
(``delta_rule_fwd`` / ``delta_rule_bwd``) by their names in a device trace, by
the key heads a program holds (``--groups``), beside the ``jax.numpy`` body
(``_rule_xla``, the yardstick). ``ssd_micro.py``'s sibling for
``ops/gated_delta.py``; needs the chip, and is no code a cell runs.

One JSON row per (impl, pass): ``ms`` the device time of the whole jitted call
(for ``grad`` the forward too), ``kernels`` {name: device ms a call},
``others`` the five longest operations that are no kernel, and ``count_pct``:
the share of ``chipbench``'s count of the rule's work (``least_seconds`` of
``qwen3n_delta_rule_roofline``: one layer, a forward at a third of it, a
backward at two thirds) that ``ms`` is. ``--check N`` prints, on N seeds and
for every impl, ``o``'s and every cotangent's distance from the ``jax.numpy``
body on float32 operands at matmul precision ``highest``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from unittest import mock

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_HERE, os.path.dirname(_HERE)]

import ssd_micro  # noqa: E402  (device_ms, PEAKS)

#: What a kernel of ``ops/gated_delta.py`` is called in a trace.
KERNELS = ("delta_rule_",)


def inputs(b, S, Hk, Hv, Dk, Dv, seed=0):
    """Operands in the mixer's ranges: unit keys, queries a ``sqrt(Dk)``-th of
    that, ``g = -softplus(. - 3)`` (``A_log`` 0 and ``dt_bias`` -3, the init),
    ``beta`` a sigmoid; bf16 q, k, v. And a cotangent for ``o``."""
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))
    args = ((unit(jax.random.normal(k[0], (b, S, Hk, Dk))) / Dk ** 0.5
             ).astype(bf),
            unit(jax.random.normal(k[1], (b, S, Hk, Dk))).astype(bf),
            jax.random.normal(k[2], (b, S, Hv, Dv), bf),
            -jax.nn.softplus(jax.random.normal(k[3], (b, S, Hv)) - 3.0),
            jax.nn.sigmoid(jax.random.normal(k[4], (b, S, Hv))))
    return args, jax.random.normal(k[5], (b, S, Hv, Dv))


def paths(gd, chunk, groups):
    """{impl: rule of (q, k, v, g, beta)}: the ``jax.numpy`` body, the
    kernels at the planner's plan, and at each G of ``groups``."""
    def at(plan):
        def rule(*args):
            with mock.patch.object(gd, "_kernel_plan", lambda *a: plan):
                return gd.gated_delta_rule(*args, chunk=chunk)
        return rule

    out = {"xla": at(None),
           "kernels": lambda *a: gd.gated_delta_rule(*a, chunk=chunk)}
    out.update({"kernels:G%d" % G: at(G) for G in groups})
    return out


def check(fns, args, w, seed):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def both(fn, operands):
        out, back = jax.vjp(fn, *operands)
        return (out,) + back(w)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: both(fns["xla"], a))(
            *(a.astype(f32) for a in args))
    norm = lambda a: float(jnp.linalg.norm(a.astype(f32).ravel()))
    for impl, fn in fns.items():
        got = jax.jit(lambda *a: both(fn, a))(*args)
        row = {"impl": impl, "check": "against float32 at highest",
               "seed": seed}
        for name, g, t in zip("o dq dk dv dg dbeta".split(), got, want):
            row[name] = {"rel_err": norm(g.astype(f32) - t) / norm(t),
                         "norm_gap": abs(norm(g) - norm(t)) / norm(t)}
        print(json.dumps(row), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--shape", default="1x8192x16x32x128x128",
                   help="b x S x Hk x Hv x Dk x Dv (default: one sequence of "
                        "the Qwen3-Next cell)")
    p.add_argument("--chunk", type=int, default=64)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--groups", default="",
                   help="comma-separated key heads per program to try "
                        "besides the planner's")
    p.add_argument("--check", type=int, default=0, metavar="SEEDS")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from chipbench.layer_metrics import qwen3n_delta_rule_roofline as count
    from pytorch_distributed_training_example_tpu.ops import gated_delta as gd

    if jax.default_backend() != "tpu":
        sys.exit("delta_rule_micro.py times kernels on the chip; this is "
                 + jax.default_backend())
    b, S, Hk, Hv, Dk, Dv = (int(v) for v in args.shape.split("x"))
    with open(ssd_micro.PEAKS) as fh:
        peak = json.load(fh)["kinds"][jax.devices()[0].device_kind]
    # the benchmark's count for one delta-rule layer: layer 0 of a period
    least = count.least_seconds(
        {"linear_num_key_heads": Hk, "linear_num_value_heads": Hv,
         "linear_key_head_dim": Dk, "linear_value_head_dim": Dv,
         "full_attention_interval": 4, "held_layers": [0]},
        {"seq_len": S}, b, peak)
    operands, w = inputs(b, S, Hk, Hv, Dk, Dv, args.seed)
    fns = paths(gd, args.chunk, [int(g) for g in args.groups.split(",") if g])
    print(json.dumps({
        "shape": args.shape, "chunk": args.chunk,
        "plan": gd._kernel_plan(Hk, Hv, Dk, Dv, args.chunk, jnp.bfloat16),
        "count_ms": {"fwd": least["seconds"] / 3 * 1e3,
                     "grad": least["seconds"] * 1e3}, "bound": least["bound"],
        "device": jax.devices()[0].device_kind}), flush=True)
    for impl, fn in fns.items():
        for tag, run in (
                ("fwd", fn),
                ("grad", jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                  argnums=range(5)))):
            row = {"impl": impl, "pass": tag}
            try:
                ms, kernels, others = ssd_micro.device_ms(
                    run, operands, args.iters, KERNELS)
                rounded = lambda d: {n: round(v, 4) for n, v in d.items()}
                row.update(
                    ms=round(ms, 4),
                    count_pct=round(100 * least["seconds"] * 1e3
                                    / (3 if tag == "fwd" else 1) / ms, 2),
                    kernels=rounded(kernels), others=rounded(others))
            except Exception as e:  # a group the compiler refuses
                row["error"] = str(e).strip().splitlines()[-1][-300:]
            print(json.dumps(row), flush=True)
    for seed in range(args.seed, args.seed + args.check):
        check(fns, *inputs(b, S, Hk, Hv, Dk, Dv, seed), seed)


if __name__ == "__main__":
    main()

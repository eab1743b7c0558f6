#!/usr/bin/env python
"""Microbenchmark: fused BN-apply/ReLU->matmul->BN-stats Pallas kernel vs
XLA's unfused schedule, on the real chip (VERDICT r2 #1).

Measures the ResNet-50 bottleneck 1x1-conv segment as a matmul:

    unfused (what XLA runs today):  xn = relu(x*scale+bias)   (elementwise pass)
                                    y  = xn @ w               (conv)
                                    s  = sum(y,0), ss = sum(y^2,0)  (stats pass)
    fused (ops/fused_bn_matmul.py): one pass, stats from the VMEM-resident y.

Timing is SLOPE-BASED: the remote attachment adds a large fixed dispatch
cost per executable call (~75 ms measured — see BENCH_FLASH_MICRO.json),
so each arm is compiled as a chained ``lax.scan`` at two trip counts and
the per-iteration time is (t_long - t_short) / (iters_long - iters_short),
which cancels the fixed cost exactly. Iterations are chained through a
scalar so nothing is dead-code-eliminated or overlapped.

    python benchmarks/fused_bn_bench.py [--out BENCH_FUSED_BN.json]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ITERS_SHORT = 20
ITERS_LONG = 120

# [B*H*W, Cin, Cout] instances of the bottleneck 1x1 convs at batch 128
# (stage2 reduce/expand, stage3 reduce), PROFILE_RN50.md's canonical shapes.
SHAPES = [
    (128 * 56 * 56, 256, 64),    # stage2 reduce: 206 MB activation
    (128 * 56 * 56, 64, 256),    # stage2 expand
    (128 * 28 * 28, 512, 128),   # stage3 reduce
]


def _timed_at(fn, *args):
    """Compile fn(*args), return best wall seconds over 3 synced runs."""
    import jax
    import numpy as np

    compiled = jax.jit(fn).lower(*args).compile()
    out = compiled(*args)
    np.asarray(jax.tree.leaves(out)[0])  # force
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = compiled(*args)
        np.asarray(jax.tree.leaves(out)[0])
        best = min(best, time.perf_counter() - t0)
    return best


def _timed_pair(make_un, make_fu, *args, reps=3):
    """Interleaved A/B slope timing: [unfused, fused] per-iter seconds.

    Host load drifts on the scale of a single measurement, so the two
    arms are measured back-to-back in each repetition (A,B,A,B,...) and the
    per-arm slope uses the min over repetitions at each trip count —
    uncorrelated drift then inflates both arms equally instead of flipping
    the ratio between runs.
    """
    loops = {}
    for tag, mk in (("un", make_un), ("fu", make_fu)):
        for L in (ITERS_SHORT, ITERS_LONG):
            loops[tag, L] = mk(L)
    best = {k: float("inf") for k in loops}
    times = {("un", ITERS_SHORT): [], ("un", ITERS_LONG): [],
             ("fu", ITERS_SHORT): [], ("fu", ITERS_LONG): []}
    for _ in range(reps):
        for key, fn in loops.items():
            t = _timed_at(fn, *args)
            best[key] = min(best[key], t)
            times[key].append(round(t * 1e3, 1))
    out = []
    for tag in ("un", "fu"):
        slope = max(best[tag, ITERS_LONG] - best[tag, ITERS_SHORT], 1e-9)
        out.append(slope / (ITERS_LONG - ITERS_SHORT))
    return out[0], out[1], {k[0] + str(k[1]): v for k, v in times.items()}


def bench_shape(N, K, C, dtype_name="bfloat16"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_example_tpu.ops import fused_bn_matmul as fbm

    dtype = jnp.dtype(dtype_name)
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(N, K), dtype)
    w = jnp.asarray(r.randn(K, C) / np.sqrt(K), dtype)
    scale = jnp.asarray(1 + 0.1 * r.randn(1, K), dtype)
    bias = jnp.asarray(0.1 * r.randn(1, K), dtype)
    Cp = max(128, -(-C // 128) * 128)
    wp = jnp.pad(w, ((0, 0), (0, Cp - C))) if Cp != C else w

    def unfused_once(x, carry):
        xn = jnp.maximum(x * scale + bias, 0.0)
        y = jnp.dot(xn, w, preferred_element_type=jnp.float32).astype(dtype)
        yf = y.astype(jnp.float32)
        s, ss = jnp.sum(yf, 0), jnp.sum(yf * yf, 0)
        return y, s, ss

    def fused_once(x, carry):
        y, stats = fbm.fused_stats_matmul(x, wp, scale, bias, relu=True)
        return y, stats[0], stats[1]

    def loop(once):
        def make(iters):
            def body(carry, _):
                # Chain: perturb x by a scalar of the previous stats so each
                # iteration depends on the last (no overlap/DCE), ~1 vadd.
                xi = x + (carry * 1e-30).astype(dtype)
                y, s, ss = once(xi, carry)
                return s[0] + ss[0], y[0, 0]

            def run(x0):
                c, ys = jax.lax.scan(body, x0, None, length=iters)
                return c, ys

            return run

        return make

    t_un, t_fu, raw = _timed_pair(loop(unfused_once), loop(fused_once),
                                  jnp.float32(0))

    bpe = jnp.finfo(dtype).bits // 8
    # Logical HBM traffic per iteration (reads of x + write/read of y):
    unfused_bytes = (N * K * bpe) * 2 + (N * K * bpe) + 2 * (N * C * bpe)
    fused_bytes = N * K * bpe + N * C * bpe
    return {
        "shape": {"N": N, "K": K, "C": C, "dtype": dtype_name},
        "unfused_ms": round(t_un * 1e3, 3),
        "fused_ms": round(t_fu * 1e3, 3),
        "speedup": round(t_un / t_fu, 3),
        "raw_wall_ms": raw,
        "unfused_logical_gb": round(unfused_bytes / 1e9, 3),
        "fused_logical_gb": round(fused_bytes / 1e9, 3),
        "unfused_gbps": round(unfused_bytes / t_un / 1e9, 1),
        "fused_gbps": round(fused_bytes / t_fu / 1e9, 1),
    }


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="BENCH_FUSED_BN.json")
    args = p.parse_args(argv)
    import jax

    rows = [bench_shape(*s) for s in SHAPES]
    out = {
        "bench": "fused_bn_matmul_vs_xla",
        "device": jax.devices()[0].device_kind,
        "iters": [ITERS_SHORT, ITERS_LONG],
        "timing": "two-trip-count slope (cancels fixed dispatch cost), "
                  "chained scan, best of 3 per point",
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"rows": [{**r["shape"], "speedup": r["speedup"]}
                               for r in rows], "out": args.out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python
"""MoE dispatch microbenchmark: gather vs einsum vs dropless at real
token counts (VERDICT r2 #8; dropless added r14).

Times one MoE block — router + dispatch + stacked-expert FFN + combine —
fwd+bwd at GPT-2-scale dims (d=768, ffn=3072, E=8, top-2) across token
counts, for the dispatch implementations in ``parallel/moe.py``. The
einsum path's O(T*E*C) dispatch mask is measured against the gather
path's O(E*C*d + T*k) slot table and the dropless path's ragged grouped
matmul (ops/grouped_matmul.py — no capacity buffer at all).

Slope-timed (two scan trip counts — cancels the host's fixed dispatch
cost per executable call, as benchmarks/flash_micro.py does).

A second, chipless section reports the AOT routed-region byte model per
impl at the llama_moe bench shape (b4 s2048) via profile_step.aot_report
— the same numbers check_regression.py --aot-bytes gates. "Routed-region
bytes" = the sum over the moe_* named-scope regions of one train step
(everything inside the MoE block: router + dispatch + experts + combine
+ aux), as opposed to the dense trunk (non_moe).

``--ep-sweep`` (r17) benches the dropless EP transports instead: per
EP degree, the AOT collective byte census at the moe_tiny train-step
shape (llama_moe_tiny b2 s128 — the golden.json ``... ep2 *`` rows) and
a measured MoE-block step time at moe_tiny dims under an
``{"expert": ep}`` mesh for each ``ep_dispatch`` mode. Bytes are
chipless facts; the ms column is this host's devices (fake CPU devices
off-chip — relative, not headline, numbers).

    python benchmarks/moe_bench.py [--out BENCH_MOE.json]
    python benchmarks/moe_bench.py --ep-sweep [--ep-degrees 1,2,4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

D_MODEL = 768
FFN = 3072
EXPERTS = 8


def bench_point(T, impl):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_example_tpu.parallel.moe import MoEBlock

    block = MoEBlock(EXPERTS, FFN, dispatch_impl=impl, dtype=jnp.bfloat16,
                     param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, D_MODEL),
                          jnp.bfloat16)
    variables = block.init({"params": jax.random.PRNGKey(1)}, x, train=False)
    params = variables["params"]

    def loss_fn(params, x):
        out, _ = block.apply({"params": params}, x, train=False,
                             mutable=["losses"])
        return jnp.sum(out.astype(jnp.float32)) * 1e-3

    grad_fn = jax.grad(loss_fn, argnums=(0, 1))

    def at_length(L):
        def body(carry, _):
            gp, gx = grad_fn(params, x + carry.astype(x.dtype))
            s = sum(jnp.sum(g.astype(jnp.float32))
                    for g in jax.tree.leaves(gp))
            return (s * 1e-30 + jnp.float32(jnp.sum(
                gx.astype(jnp.float32)) * 1e-30)).astype(jnp.float32), ()

        @jax.jit
        def run(c0):
            c, _ = jax.lax.scan(body, c0, None, length=L)
            return c

        np.asarray(run(jnp.float32(0)))
        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(run(jnp.float32(0)))
            dt = min(dt, time.perf_counter() - t0)
        return dt

    L1, L2 = 10, 40
    sec = max(at_length(L2) - at_length(L1), 1e-9) / (L2 - L1)
    # per-token expert FLOPs: top-2 x (3 matmuls of d*ffn) x 2 MAC x fwd+2bwd
    flops = T * 2 * 3 * D_MODEL * FFN * 2 * 3
    return {"tokens": T, "dispatch": impl, "ms": round(sec * 1e3, 3),
            "tokens_per_sec": round(T / sec),
            "expert_tflops": round(flops / sec / 1e12, 1)}


def aot_bytes_rows(impls):
    """Routed-region AOT byte model per dispatch impl at the llama_moe
    bench shape — chipless, so it runs (and means the same thing) on the
    CI host and next to the chip timings."""
    from benchmarks import profile_step

    rows = []
    for impl in impls:
        r = profile_step.aot_report("llama_moe", per_chip_batch=4,
                                    seq_len=2048, moe_dispatch_impl=impl)
        regions = {tag: row["gbytes_modeled"]
                   for tag, row in r["regions"].items()}
        rows.append({
            "dispatch": impl,
            "routed_gb": round(sum(v for tag, v in regions.items()
                                   if tag.startswith("moe_")), 3),
            "regions_gb": regions,
            "xla_flops_per_step": r["xla_flops_per_step"],
        })
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    return rows


# moe_tiny block dims (models/llama.py llama_moe_tiny): the EP sweep's
# measured leg times one MoE block at these dims so the rows line up with
# the chipless AOT census at the llama_moe_tiny train-step shape.
TINY = {"d_model": 128, "ffn": 256, "experts": 8, "top_k": 2}


def ep_bench_point(T, ep, ep_dispatch):
    """Slope-timed fwd+bwd of one dropless MoE block at moe_tiny dims
    under an ``{"expert": ep}`` mesh (first ep local devices)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_example_tpu.core import (
        mesh as mesh_lib)
    from pytorch_distributed_training_example_tpu.parallel.moe import (
        MoEBlock)

    if len(jax.devices()) < ep:
        return {"tokens": T, "ep": ep, "ep_dispatch": ep_dispatch,
                "ok": False,
                "error": f"needs {ep} devices, have {len(jax.devices())}"}
    mesh = mesh_lib.build_mesh({"expert": ep}, devices=jax.devices()[:ep])
    block = MoEBlock(TINY["experts"], TINY["ffn"], top_k=TINY["top_k"],
                     capacity_factor=1.0, dispatch_impl="dropless",
                     ep_dispatch=ep_dispatch, dtype=jnp.bfloat16,
                     param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, TINY["d_model"]),
                          jnp.bfloat16)
    with mesh_lib.use_mesh(mesh):
        variables = block.init({"params": jax.random.PRNGKey(1)}, x,
                               train=False)
        params = variables["params"]

        def loss_fn(params, x):
            out, _ = block.apply({"params": params}, x, train=False,
                                 mutable=["losses"])
            return jnp.sum(out.astype(jnp.float32)) * 1e-3

        grad_fn = jax.grad(loss_fn, argnums=(0, 1))

        def at_length(L):
            def body(carry, _):
                gp, gx = grad_fn(params, x + carry.astype(x.dtype))
                s = sum(jnp.sum(g.astype(jnp.float32))
                        for g in jax.tree.leaves(gp))
                return (s * 1e-30 + jnp.float32(jnp.sum(
                    gx.astype(jnp.float32)) * 1e-30)).astype(jnp.float32), ()

            @jax.jit
            def run(c0):
                c, _ = jax.lax.scan(body, c0, None, length=L)
                return c

            np.asarray(run(jnp.float32(0)))
            dt = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(run(jnp.float32(0)))
                dt = min(dt, time.perf_counter() - t0)
            return dt

        L1, L2 = 5, 20
        sec = max(at_length(L2) - at_length(L1), 1e-9) / (L2 - L1)
    return {"tokens": T, "ep": ep, "ep_dispatch": ep_dispatch,
            "ms": round(sec * 1e3, 3), "tokens_per_sec": round(T / sec)}


def ep_sweep_rows(degrees, modes, T):
    """Per-EP-degree rows: chipless routed/a2a collective bytes from the
    AOT census (llama_moe_tiny b2 s128, the golden-gated shape) joined
    with the measured moe_tiny block step time on this host."""
    from benchmarks import profile_step

    rows = []
    for ep in degrees:
        ep_modes = ["replicated"] if ep == 1 else modes
        for mode in ep_modes:
            row = {"ep": ep, "ep_dispatch": mode}
            try:
                r = profile_step.aot_report(
                    "llama_moe_tiny", per_chip_batch=2, seq_len=128,
                    moe_dispatch_impl="dropless", moe_capacity_factor=1.0,
                    moe_ep_dispatch=mode, ep_degree=ep)
                coll = r["collectives"]
                opb = {op: v["bytes"]
                       for op, v in coll["by_opcode"].items()}
                row.update(
                    routed_mb=round(coll["moe_bytes"] / 1e6, 3),
                    a2a_mb=round(opb.get("all-to-all", 0) / 1e6, 3),
                    allgather_mb=round(opb.get("all-gather", 0) / 1e6, 3),
                    collective_total_mb=round(coll["total_bytes"] / 1e6, 3))
            except Exception as e:  # chipless leg short on devices, etc.
                row.update(ok=False, error=str(e)[:200])
            row.update(ep_bench_point(T, ep, mode))
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
    return rows


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="BENCH_MOE.json")
    p.add_argument("--tokens", default="4096,16384,65536")
    p.add_argument("--aot-impls", default="gather,sort,dropless",
                   help="dispatch impls for the routed-region AOT byte "
                        "section (empty string skips it)")
    p.add_argument("--ep-sweep", action="store_true",
                   help="bench the dropless EP transports per EP degree "
                        "(AOT collective bytes + measured moe_tiny block "
                        "step time) instead of the dispatch sweep")
    p.add_argument("--ep-degrees", default="1,2,4",
                   help="EP degrees for --ep-sweep (must divide the "
                        "expert count and the local device count)")
    p.add_argument("--ep-modes", default="replicated,a2a,a2a_overlap",
                   help="ep_dispatch modes per degree for --ep-sweep")
    p.add_argument("--ep-tokens", type=int, default=4096,
                   help="token count for the --ep-sweep measured leg")
    args = p.parse_args(argv)
    if args.ep_sweep:
        degrees = [int(x) for x in args.ep_degrees.split(",") if x]
        if "jax" not in sys.modules and max(degrees) > 1:
            # chipless hosts: the EP meshes need that many devices, and the
            # flag only takes effect before jax initializes
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={max(degrees)}")
        import jax

        rows = ep_sweep_rows(degrees,
                             [s for s in args.ep_modes.split(",") if s],
                             args.ep_tokens)
        out = {
            "bench": "moe_dropless_ep_dispatch_sweep",
            "device": jax.devices()[0].device_kind,
            "dims": {**TINY, "capacity_factor": 1.0},
            "aot_shape": {"model": "llama_moe_tiny", "per_chip_batch": 2,
                          "seq_len": 128},
            "pass": "fwd+bwd (params and input grads)",
            "timing": "two-trip-count slope, chained scan, best of 3",
            "rows": rows,
        }
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps({"rows": rows, "out": args.out}))
        return 0
    import jax

    rows = []
    for T in [int(x) for x in args.tokens.split(",")]:
        for impl in ("gather", "einsum", "dropless"):
            try:
                rows.append(bench_point(T, impl))
            except Exception as e:
                msg = str(e)
                rows.append({"tokens": T, "dispatch": impl, "ok": False,
                             "error": ("OOM" if "RESOURCE_EXHAUSTED" in msg
                                       else msg[:200])})
            print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    aot_impls = [s for s in args.aot_impls.split(",") if s]
    out = {
        "bench": "moe_dispatch_gather_vs_einsum_vs_dropless",
        "device": jax.devices()[0].device_kind,
        "dims": {"d_model": D_MODEL, "ffn": FFN, "experts": EXPERTS,
                 "top_k": 2, "capacity_factor": 1.25},
        "pass": "fwd+bwd (params and input grads)",
        "timing": "two-trip-count slope, chained scan, best of 3 per point",
        "rows": rows,
        "aot_routed_bytes": {
            "model": "llama_moe", "per_chip_batch": 4, "seq_len": 2048,
            "note": "chipless profile_step.aot_report; routed_gb = sum "
                    "of moe_* region modeled bytes",
            "rows": aot_bytes_rows(aot_impls),
        } if aot_impls else None,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"rows": rows, "out": args.out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

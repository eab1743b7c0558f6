#!/usr/bin/env python
"""Isolated flash-attention timing at LM shapes (fwd and fwd+bwd).

Prints per-config: measured ms, attention-FLOPs, achieved TF/s and
fraction-of-peak, flash kernel vs XLA dot-product attention. Informs the
GPT-2 MFU ceiling analysis (LM_SWEEP.json).

Timing is SLOPE-BASED: chained iterations inside one ``lax.scan`` under
jit, synced by a host transfer, measured at two trip counts; the
per-iteration time is the slope, which cancels the host's fixed dispatch
cost per executable call.

``--kernels`` times each direction alone, by kernel name in a device trace
(no transposes, no glue): what ``auto`` dispatches to, the forced impls, and
the causal kernels at the planner's plan or at each ``--plans`` entry.
``--layer`` times one GPT-2 attention layer, the q, k, v and out projections
around the kernels, forward and backward in one program: the layout between
the two is what it reads. Run from another checkout's root (``cd _parent &&
python <this file>``) both modes import that checkout's package.

``--kernels`` prints beside each online kernel's ms its ``flash_schedule``
record: the schedule's static counts a head (grid steps, blocks computed,
masked and fetched, sub-tiles left out, pairs computed over pairs the mask
leaves). ``--schedule-parts`` times the causal schedule's three parts one at a
time at the blocks dispatch picks (no step and no copy for a block above the
diagonal; no mask inside a block below it; a crossed block in sub-tiles), each
beside the whole rectangle under a mask everywhere ("parent") and all three
together, and says whether each variant's results are "parent"'s bitwise.

``--window W`` puts the three modes under a sliding window (row r sees the W
keys up to its own): ``--kernels`` times what dispatch gives the window call
(from a checkout that still has window kernels of its own, theirs),
``--block-sweep`` the online kernels by name at each pair of ``--blocks`` in
both directions, ``--schedule-parts`` the window's schedule a part at a time.
Each row carries ms a call, ps a computed (row, key) pair and the share of the
FLOP floor (2 products forward, 5 backward, over the pairs the mask leaves).
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import numpy as np


def attn_flops(B, H, S, D, causal=True, bwd=False):
    """MAC-counted FLOPs for qk+pv; bwd adds recompute + dq/dk/dv dots."""
    f = 2 * 2 * B * H * S * S * D  # qk and pv, 2 FLOPs per MAC
    if causal:
        f /= 2
    return f * (3.5 if bwd else 1.0)


def window_rates(row, took, B, H, S, D, window, schedule, peak_tflops):
    """Into ``row``, a direction: ps a computed pair (where the kernel's
    schedule says how many it computes; dq computes dkv's again) and the share
    of the FLOP floor, the least time of the direction's products (2 forward,
    5 backward) over the pairs the window leaves."""
    needed = (window * S - window * (window - 1) // 2) * B * H
    row["ps_per_pair"], row["floor_share"] = {}, {}
    for tag, products, counted in (("fwd", 2, "flash_fwd_window"),
                                   ("bwd", 5, "flash_bwd_window_dkv")):
        ms = sum(t for n, t in took.items() if ("_fwd_" in n) == (tag == "fwd"))
        if not ms:
            continue
        floor_ms = products * 2 * D * needed / (peak_tflops * 1e12) * 1e3
        row["floor_share"][tag] = round(floor_ms / ms, 4)
        said = (schedule or {}).get(counted)
        if said:
            row["ps_per_pair"][tag] = round(
                ms * 1e9 / (said["pairs_computed"] * B * H), 3)
    return row


def device_ms(fn, args, reps):
    """(device ms per call of the whole of ``jit(fn)(*args)``, {kernel name:
    device ms per call} of its ``flash_*`` kernels), from a profiler trace of
    ``reps`` calls."""
    import tempfile

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
        ops, module_ns, _ = collect_ops(trace_dir)
    took = {}
    for event, (ns, _) in ops.items():
        # "%flash_fwd_online.3 = (...) custom-call(...)" -> flash_fwd_online
        name = event.split(" = ", 1)[0].strip().lstrip("%").split(".")[0]
        if name.startswith("flash_"):
            took[name] = took.get(name, 0.0) + ns / reps / 1e6
    if not took:
        raise RuntimeError("no flash_* kernel in the device trace; it holds "
                           + ", ".join(sorted(e[:40] for e in ops)[:8]))
    return module_ns / reps / 1e6, took


def schedules_since(mark):
    """{kernel: its ``flash_schedule`` record's counts} of the calls traced
    since the recorder held ``mark`` records."""
    from pytorch_distributed_training_example_tpu.utils import telemetry

    return {r.value["kernel"]: r.value
            for r in telemetry.recorder().records()[mark:]
            if r.name == "flash_schedule"}


#: ``--schedule-parts``: ``online_schedule``'s switches a variant. "parent" is
#: the schedule before the causal one: every block of the rectangle a step and
#: a copy, every computed block masked by position.
SCHEDULE_PARTS = {
    "parent": dict(walk=False, split=False, sub=0),
    "walk": dict(split=False, sub=0),
    "split": dict(walk=False, sub=0),
    "sub": dict(walk=False, split=False),
    "all": dict(),
}


def parts_rows(fa, shapes, reps, subs, window=None, peak_tflops=197.0):
    """One row per (shape, variant): the three online kernels' device ms
    under that variant of the schedule, its counts, and whether its results
    are the "parent" variant's bit for bit. ``subs``: further sub-tile sides
    to time the whole schedule at. ``window``: the schedule's second edge."""
    import jax
    import jax.numpy as jnp

    variants = dict(SCHEDULE_PARTS)
    variants.update({"all_sub%d" % s: dict(sub=s) for s in subs})
    rows = []
    for (B, H, S, D) in shapes:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
                      for key in ks)
        edge = {} if window is None else {"window": window}
        fwd_blocks, bwd_blocks = (
            fa._online_blocks(bwd, S, D, fa.DEFAULT_BLOCK_Q,
                              fa.DEFAULT_BLOCK_KV, **edge)
            for bwd in (False, True))
        o, lse = jax.jit(lambda q, k, v: fa._flash_fwd(
            q, k, v, causal=True, block_q=fwd_blocks[0],
            block_kv=fwd_blocks[1], **edge))(q, k, v)
        want = None
        for name, parts in variants.items():
            parts = dict(parts, **edge)

            def both(q, k, v, o, lse, g, parts=parts):
                return (*fa._flash_fwd(q, k, v, causal=True,
                                       block_q=fwd_blocks[0],
                                       block_kv=fwd_blocks[1], **parts),
                        *fa._flash_bwd(q, k, v, o, lse, g, causal=True,
                                       block_q=bwd_blocks[0],
                                       block_kv=bwd_blocks[1], **parts))

            row = {"parts": name, "B": B, "H": H, "S": S, "D": D, **edge}
            try:
                _, took = device_ms(both, (q, k, v, o, lse, g), reps)
                got = jax.jit(both)(q, k, v, o, lse, g)
            except Exception as e:  # a variant the compiler refuses
                row["error"] = str(e).strip().splitlines()[-1][-300:]
                rows.append(row)
                print(json.dumps(row), flush=True)
                continue
            want = got if want is None else want
            row["kernels"] = {n: round(ms, 4) for n, ms in took.items()}
            row["ms"] = round(sum(took.values()), 4)
            row["bitwise"] = {n: bool(jnp.array_equal(a, b)) for n, a, b in
                              zip(("o", "lse", "dq", "dk", "dv"), got, want)}
            row["schedule"] = {
                kernel: fa.online_schedule(kernel, True, S, S, *blocks,
                                           **parts).record(D)
                for kernel, blocks in zip(
                    fa.ONLINE_KERNELS, (fwd_blocks, bwd_blocks, bwd_blocks))}
            if window is not None:
                window_rates(row, took, B, H, S, D, window, {
                    s["kernel"]: s for s in row["schedule"].values()},
                    peak_tflops)
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def window_sweep_rows(fa, shapes, reps, window, blocks, peak_tflops):
    """One row per (shape, direction, block_q, block_kv): the online kernels
    under the window at that pair of blocks, by name in a device trace."""
    import jax
    import jax.numpy as jnp

    rows = []
    for (B, H, S, D) in shapes:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
                      for key in ks)
        o, lse = fa._fwd_dispatch(q, k, v, True, fa.DEFAULT_BLOCK_Q,
                                  fa.DEFAULT_BLOCK_KV, "auto", None, window)
        for bq in blocks:
            for bkv in blocks:
                call = dict(causal=True, block_q=bq, block_kv=bkv,
                            window=window)
                for tag, fn, args, kernels in (
                        ("fwd", lambda *a, call=call: fa._flash_fwd(*a, **call),
                         (q, k, v), fa.ONLINE_KERNELS[:1]),
                        ("bwd", lambda *a, call=call: fa._flash_bwd(*a, **call),
                         (q, k, v, o, lse, g), fa.ONLINE_KERNELS[1:])):
                    row = {"impl": "online", "pass": tag, "B": B, "H": H,
                           "S": S, "D": D, "window": window, "block_q": bq,
                           "block_kv": bkv}
                    try:
                        took = device_ms(fn, args, reps)[1]
                    except Exception as e:  # a pair the compiler refuses
                        row["error"] = str(e).strip().splitlines()[-1][-300:]
                    else:
                        schedule = {}
                        for kernel in kernels:
                            plan = fa.online_schedule(kernel, True, S, S, bq,
                                                      bkv, window=window)
                            schedule[plan.name] = plan.record(D)
                        row["kernels"] = {n: round(ms, 4)
                                          for n, ms in took.items()}
                        row["ms"] = round(sum(took.values()), 4)
                        row["sub"] = [s["sub"] for s in schedule.values()]
                        row["steps"] = [s["steps"] for s in schedule.values()]
                        window_rates(row, took, B, H, S, D, window, schedule,
                                     peak_tflops)
                    rows.append(row)
                    print(json.dumps(row), flush=True)
    return rows


def kernel_rows(fa, shapes, plans, reps, window=None, peak_tflops=197.0):
    """One row per (shape, impl, direction): the flash kernels that ran and
    their device time. ``plans``: (G, T) pairs for the causal kernels; empty
    means the planner's own choice per direction. ``window``: the window
    call alone, as dispatch serves it."""
    import jax
    import jax.numpy as jnp
    from pytorch_distributed_training_example_tpu.utils import telemetry

    blocks = (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_KV)
    rows = []
    for (B, H, S, D) in shapes:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
                      for key in ks)
        o, lse = jax.jit(lambda q, k, v: fa._fwd_dispatch(
            q, k, v, True, *blocks, "online", None, window))(q, k, v)
        fwd_args, bwd_args = (q, k, v), (q, k, v, o, lse, g)
        cases = []
        for impl in ("auto",) if window else ("auto", "online", "oneshot"):
            cases.append((impl, "fwd", fwd_args,
                          lambda q, k, v, impl=impl: fa._fwd_dispatch(
                              q, k, v, True, *blocks, impl, None, window)))
            cases.append((impl, "bwd", bwd_args,
                          lambda q, k, v, o, lse, g, impl=impl: fa._vjp_bwd(
                              True, *blocks, impl, None, window,
                              (q, k, v, o, lse), g)))
        fwd_plan = fa._causal_plan(H, S, D)
        for bwd in (False, True):
            own = None if window else fa._causal_plan(H, S, D, bwd=bwd)
            for plan in plans or ([own] if own else []):
                if bwd:  # the causal backward reads the causal forward's lse
                    args = (q, k, v, *fa._causal_fwd(
                        q, k, v, plan=fwd_plan or plan), g)
                cases.append((
                    "causal:%d:%d" % plan, "bwd" if bwd else "fwd",
                    args if bwd else fwd_args,
                    functools.partial(fa._causal_bwd if bwd else fa._causal_fwd,
                                      plan=plan)))
        for impl, tag, args, fn in cases:
            row = {"impl": impl, "pass": tag, "B": B, "H": H, "S": S, "D": D}
            mark = len(telemetry.recorder().records())
            try:
                took = device_ms(fn, args, reps)[1]
                row["kernels"] = {n: round(ms, 4) for n, ms in took.items()}
                row["ms"] = round(sum(row["kernels"].values()), 4)
                schedule = schedules_since(mark)
                if schedule:
                    row["schedule"] = schedule
                if window:
                    row["window"] = window
                    window_rates(row, took, B, H, S, D, window, schedule,
                                 peak_tflops)
            except Exception as e:  # a plan the compiler refuses
                row["error"] = str(e).strip().splitlines()[-1][-300:]
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def layer_rows(shapes, reps):
    """One row per shape: GPT-2's ``SelfAttention`` at width H*D under the
    bf16 policy, forward and backward in one program. ``ms`` is the device
    time of the whole program, ``kernels`` the flash kernels' part of it,
    ``around_ms`` the rest: the four projections, their weight gradients and
    whatever XLA puts between them and the kernels."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.models import gpt2

    rows = []
    for (B, H, S, D) in shapes:
        module = gpt2.SelfAttention(H, jnp.bfloat16, jnp.float32,
                                    attn_impl="flash")
        kx, kg, kp = jax.random.split(jax.random.PRNGKey(0), 3)
        x, g = (jax.random.normal(key, (B, S, H * D), jnp.bfloat16)
                for key in (kx, kg))
        params = module.init(kp, x[:1], False)

        def fwd_bwd(params, x, g):
            _, vjp = jax.vjp(lambda p, x: module.apply(p, x, False), params, x)
            return vjp(g)

        ms, kernels = device_ms(fwd_bwd, (params, x, g), reps)
        rows.append({"layer": "gpt2.SelfAttention", "pass": "fwd+bwd", "B": B,
                     "H": H, "S": S, "D": D, "ms": round(ms, 4),
                     "kernels": {n: round(t, 4) for n, t in kernels.items()},
                     "around_ms": round(ms - sum(kernels.values()), 4)})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--peak-tflops", type=float, default=197.0)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--out", default=None)
    p.add_argument("--shapes", default=None,
                   help="comma-separated BxHxSxD entries to run (default: "
                        "all three LM shapes); lets long runs split across "
                        "invocations — with --merge, rows append into --out")
    p.add_argument("--merge", action="store_true",
                   help="append rows into an existing --out file")
    p.add_argument("--block-sweep", action="store_true",
                   help="sweep block_q x block_kv for the online kernel "
                        "instead of comparing impls — the D=128 long-S "
                        "tile-size search (PROFILE_LLAMA.md lever 1); rows "
                        "carry block_q/block_kv and merge by that key")
    p.add_argument("--blocks", default="256,512,1024",
                   help="comma-separated candidate block sizes for "
                        "--block-sweep (applied to both axes)")
    p.add_argument("--kernels", action="store_true",
                   help="device time of each flash kernel by its name, "
                        "forward and backward apart (see the docstring)")
    p.add_argument("--layer", action="store_true",
                   help="device time of one GPT-2 attention layer, the "
                        "projections around the kernels, forward + backward")
    p.add_argument("--schedule-parts", action="store_true",
                   help="device time of the three online kernels under each "
                        "part of the causal schedule alone (see the "
                        "docstring)")
    p.add_argument("--subs", default="",
                   help="with --schedule-parts: comma-separated sub-tile "
                        "sides to time the whole schedule at, beside the "
                        "rule's")
    p.add_argument("--window", type=int, default=None,
                   help="with --kernels, --block-sweep or --schedule-parts: "
                        "the sliding window the calls are under (see the "
                        "docstring)")
    p.add_argument("--plans", default="",
                   help="with --kernels: comma-separated G:T plans for the "
                        "causal kernels (default: the planner's choice)")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.ops import (
        flash_attention as fa)
    from pytorch_distributed_training_example_tpu.ops import (
        attention as attn_lib)

    def xla_attn(q, k, v, causal=True):
        return attn_lib.dot_product_attention(q, k, v, causal=causal)

    def timed(fn_one, q, k, v):
        """ms per iteration of q <- fn_one(q, k, v): two-length slope."""
        def at_length(L):
            def body(qq, _):
                return fn_one(qq, k, v), ()

            @jax.jit
            def run(q):
                out, _ = jax.lax.scan(body, q, None, length=L)
                return jnp.float32(out[0, 0, 0, 0])

            np.asarray(run(q))  # compile + warm
            dt = float("inf")
            for _ in range(4):
                t0 = time.perf_counter()
                np.asarray(run(q))
                dt = min(dt, time.perf_counter() - t0)
            return dt

        L1, L2 = args.iters, 4 * args.iters
        return max(at_length(L2) - at_length(L1), 1e-9) / (L2 - L1) * 1e3

    # Stock JAX TPU Pallas kernel (jax.experimental.pallas.ops.tpu) as an
    # INDEPENDENT yardstick for the in-repo kernels (VERDICT r4 missing
    # #3): if the stock kernel beats ours at a shape, the gap is closable
    # in-kernel; if it lands in the same band, the thin-contraction-wall
    # claim (PROFILE_GPT2.md) gets outside confirmation. Measured at its
    # native BHSD layout (no transpose overhead charged to it).
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as stock_fa)
    except Exception:
        stock_fa = None

    import math

    shapes = ((16, 12, 1024, 64), (4, 12, 2048, 64), (2, 16, 4096, 128))
    if args.shapes:
        shapes = tuple(tuple(int(x) for x in s.split("x"))
                       for s in args.shapes.split(","))
    if (args.kernels or args.layer or args.schedule_parts
            or (args.block_sweep and args.window)):
        plans = [tuple(int(x) for x in p.split(":"))
                 for p in args.plans.split(",") if p]
        rows = (kernel_rows(fa, shapes, plans, args.iters, args.window,
                            args.peak_tflops) if args.kernels else [])
        if args.block_sweep:
            rows += window_sweep_rows(
                fa, shapes, args.iters, args.window,
                [int(x) for x in args.blocks.split(",")], args.peak_tflops)
        if args.schedule_parts:
            rows += parts_rows(fa, shapes, args.iters,
                               [int(s) for s in args.subs.split(",") if s],
                               args.window, args.peak_tflops)
        if args.layer:
            rows += layer_rows(shapes, args.iters)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"rows": rows}, f, indent=1)
        return
    rows = []
    for (B, H, S, D) in shapes:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)
        # BHSD copies for the stock kernel's native layout
        qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))

        def oneshot(q, k, v):
            return fa.flash_attention(q, k, v, True, fa.DEFAULT_BLOCK_Q,
                                      fa.DEFAULT_BLOCK_KV, "oneshot")

        def online(q, k, v):
            return fa.flash_attention(q, k, v, True, fa.DEFAULT_BLOCK_Q,
                                      fa.DEFAULT_BLOCK_KV, "online")

        def stock(q, k, v, _scale=1.0 / math.sqrt(D)):
            return stock_fa(q, k, v, causal=True, sm_scale=_scale)

        if args.block_sweep:
            # Tile-size search for the online kernel only: the oneshot path
            # picks its own plan and XLA has no block knob. Winning entries
            # graduate into fa.ONLINE_BLOCK_TABLE.
            cand = [int(x) for x in args.blocks.split(",")]
            impls = []
            for bq in cand:
                for bkv in cand:
                    if bq > S or bkv > S:
                        continue

                    def online_b(q, k, v, bq=bq, bkv=bkv):
                        return fa.flash_attention(q, k, v, True, bq, bkv,
                                                  "online")

                    impls.append(("online", online_b, (q, k, v),
                                  {"block_q": bq, "block_kv": bkv}))
        else:
            impls = [("oneshot", oneshot, (q, k, v), {}),
                     ("online", online, (q, k, v), {}),
                     ("xla", xla_attn, (q, k, v), {})]
            if stock_fa is not None:
                impls.append(("stock_jax_pallas", stock, (qh, kh, vh), {}))
        for name, fn, (qi, ki, vi), tags in impls:
            def timed_or_refused(tag, fn_one):
                """ms, or None with a row saying so where a sweep's block
                pair does not compile."""
                try:
                    return timed(fn_one, qi, ki, vi)
                except Exception as e:  # noqa: BLE001 - recorded, not hidden
                    if not args.block_sweep:
                        raise
                    rows.append({"impl": name, "pass": tag, "B": B, "H": H,
                                 "S": S, "D": D, **tags,
                                 "refused": str(e).splitlines()[0][:160]})
                    print(json.dumps(rows[-1]), file=sys.stderr, flush=True)

            ms_f = timed_or_refused("fwd", fn)
            if ms_f is None:
                continue

            def grad_step(qq, k, v, fn=fn):
                # All three grads consumed: taking only dq lets XLA DCE the
                # online path's separate dk/dv kernel and understates bwd.
                dq, dk, dv = jax.grad(
                    lambda q3, k3, v3: jnp.sum(
                        fn(q3, k3, v3).astype(jnp.float32)) * 1e-3,
                    argnums=(0, 1, 2))(qq, k, v)
                return (dq + dk + dv).astype(qq.dtype)

            ms_b = timed_or_refused("fwd+bwd", grad_step)

            for tag, ms, bwd in (("fwd", ms_f, False),
                                 ("fwd+bwd", ms_b, True)):
                if ms is None:
                    continue
                fl = attn_flops(B, H, S, D, bwd=bwd)
                tf = fl / (ms / 1e3) / 1e12
                rows.append({"impl": name, "pass": tag, "B": B, "H": H,
                             "S": S, "D": D, **tags, "ms": round(ms, 3),
                             "tflops": round(tf, 1),
                             "frac_peak": round(tf / args.peak_tflops, 3)})
                print(json.dumps(rows[-1]), file=sys.stderr, flush=True)

    measured = len(rows)
    if args.out:
        doc = {"peak_tflops": args.peak_tflops}
        if args.merge:
            import os
            if os.path.exists(args.out):
                with open(args.out) as f:
                    doc = json.load(f)  # preserve unknown sections verbatim
                if doc.get("peak_tflops", args.peak_tflops) != args.peak_tflops:
                    raise SystemExit(
                        f"--merge: existing {args.out} is normalized to "
                        f"peak_tflops={doc['peak_tflops']}, this run to "
                        f"{args.peak_tflops}; frac_peak values would mix")
                key = lambda r: (r["impl"], r["pass"], r["B"], r["H"],
                                 r["S"], r["D"], r.get("block_q"),
                                 r.get("block_kv"))
                fresh = {key(r) for r in rows}
                # re-measured keys REPLACE stale rows instead of duplicating
                rows = [r for r in doc.get("rows", [])
                        if key(r) not in fresh] + rows
        doc["rows"] = rows
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    print(json.dumps({"rows_measured": measured, "rows_total": len(rows)}))


if __name__ == "__main__":
    sys.path.insert(0, ".")
    main()

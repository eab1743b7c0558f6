#!/usr/bin/env python
"""Serving benchmark: continuous batching under open-loop Poisson load.

Chipless by design — the whole pipeline (paged KV cache, bucketed AOT
prefill/decode, admission/eviction) runs on CPU exactly as it would on a
TPU pod, so this doubles as the end-to-end CI leg. Two measured phases:

- ``batch1``: closed-loop, one request at a time — the interactive
  latency floor (tokens/sec/chip at batch 1).
- ``saturation``: the full request set under the open-loop arrival
  schedule (``--rate`` req/s Poisson, or everything at t=0 when 0) — the
  throughput ceiling plus honest p50/p99 TTFT and inter-token latency,
  because an open loop keeps arriving while the engine is saturated.

``--aot`` emits the chipless byte/FLOP model of the decode step instead:
``jit(...).lower(abstract).compile()`` read by profile_step.py's HLO
readers, with per-region HBM bytes attributed by the serve_*
named-scope tags (serve_cache / serve_attn / serve_mlp / serve_head) and
gated in CI by ``check_regression.py --aot-bytes`` against the
``aot_regions`` golden (key
``<model>_decode b<bucket> s<max_len> -``).

``--spec-decode ngram|draft`` (r19) runs saturation a second time with
speculative decoding ON over the same seeded stream, asserts greedy
token identity request-by-request, and reports the acceptance rate,
accepted-length histogram, and a modeled tokens/sec multiplier: mean
tokens emitted per verify step times the decode/verify byte ratio from
the AOT census (verify golden key ``<model>_verify b<bucket> s<K+1> -``).

Human-readable progress goes to stderr; the result JSON to stdout
(pipeable into check_regression.py).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: Named-scope tags the decode forward emits (models/llama.py decode path).
SERVE_TAG_RE = re.compile(r"\bserve_(embed|cache|attn|mlp|head)\b")


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build_serving(model_name: str, *, page_size: int, num_pages: int,
                  max_model_len: int, precision: str = "fp32", seed: int = 0):
    """Model + initialized params + cache geometry for serving."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.serve import engine as engine_lib

    dtype = jnp.float32 if precision == "fp32" else jnp.bfloat16
    bundle = registry.create_model(model_name, seq_len=max_model_len,
                                   dtype=dtype, param_dtype=dtype)
    module = bundle.module
    params = module.init(jax.random.PRNGKey(seed),
                         jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    spec = engine_lib.spec_for_module(module, num_pages=num_pages,
                                      page_size=page_size)
    return module, params, spec


def _pct_ms(xs, q) -> float | None:
    return round(float(np.percentile(np.asarray(xs), q)) * 1e3, 3) if xs \
        else None


def latency_summary(done, wall_s: float, num_chips: int) -> dict:
    tokens = sum(len(r.generated) for r in done)
    ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
    itls = [d for r in done for d in r.inter_token_s()]
    tps = tokens / max(wall_s, 1e-9)
    return {
        "requests": len(done),
        "tokens_generated": tokens,
        "wall_s": round(wall_s, 4),
        "tokens_per_s": round(tps, 2),
        "tokens_per_s_per_chip": round(tps / max(num_chips, 1), 2),
        "ttft_ms": {"p50": _pct_ms(ttfts, 50), "p99": _pct_ms(ttfts, 99)},
        "inter_token_ms": {"p50": _pct_ms(itls, 50), "p99": _pct_ms(itls, 99)},
    }


def _make_proposer(args):
    """Fresh proposer per engine — draft proposers own a paged cache pool,
    so replicas must not share one. "ngram" is resolved by the engine;
    "draft" builds the registry model named by --draft-model (default: the
    target model itself with the same init seed — the self-draft acceptance
    ceiling, useful for exercising the full verify/rollback path)."""
    if args.spec_decode == "ngram":
        return "ngram"
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.serve import spec_decode

    dtype = jnp.float32 if args.precision == "fp32" else jnp.bfloat16
    name = args.draft_model or args.model
    bundle = registry.create_model(name, seq_len=args.max_model_len,
                                   dtype=dtype, param_dtype=dtype)
    dparams = bundle.module.init(jax.random.PRNGKey(args.seed),
                                 jnp.zeros((1, 8), jnp.int32),
                                 train=False)["params"]
    return spec_decode.DraftModelProposer(bundle.module, dparams,
                                          draft_len=args.draft_len)


def _build_engine(module, params, spec, args, *, closed_loop: bool,
                  cached: bool, spec_on: bool = False, telemetry=None,
                  metrics=None, reqtrace=None, slo=None):
    from pytorch_distributed_training_example_tpu.serve import engine as engine_lib

    kw = dict(decode_buckets=(1,) if closed_loop else args.decode_buckets,
              prompt_buckets=args.prompt_buckets,
              max_model_len=args.max_model_len, telemetry=telemetry,
              metrics=metrics, reqtrace=reqtrace, slo=slo)
    mk = lambda **extra: engine_lib.ContinuousBatchingEngine(
        module, params, spec, **kw, **extra)
    spec_kw = (dict(spec_decode=_make_proposer(args),
                    draft_len=args.draft_len) if spec_on else {})
    if args.disaggregate:
        return engine_lib.DisaggregatedServe(
            mk(role="prefill", prefix_cache=cached,
               prefill_chunk=args.prefill_chunk),
            mk(role="decode", **spec_kw))
    return mk(prefix_cache=cached, prefill_chunk=args.prefill_chunk,
              **spec_kw)


def _parse_chaos(text: str | None) -> tuple[str, int] | None:
    """``sigterm@completed=K`` / ``kill@completed=K``: drain or hard-kill
    the second replica once K requests have completed fleet-wide."""
    if not text:
        return None
    mode, _, trigger = text.partition("@")
    if mode not in ("sigterm", "kill") or \
            not trigger.startswith("completed="):
        raise SystemExit(f"bad --chaos-replica {text!r} "
                         f"(want sigterm@completed=K or kill@completed=K)")
    return mode, int(trigger.split("=", 1)[1])


def run_phase(module, params, spec, args, requests, *, closed_loop: bool,
              cached: bool = False, spec_on: bool = False, telemetry=None,
              metrics=None, slo=None,
              reqtrace_factory=None) -> tuple[dict, list]:
    """One measured phase; returns (summary dict, completed Requests).

    ``slo`` (an SLOTracker) and ``reqtrace_factory`` (replica name ->
    RequestTrace) instrument the phase's engines with r20 request-level
    observability — a disaggregated pair shares its replica's tracer."""
    from pytorch_distributed_training_example_tpu.serve import loadgen

    submitted = len(requests)
    replicas = 1 if closed_loop else args.replicas
    chaos = None if closed_loop else _parse_chaos(args.chaos_replica)
    rt_for = reqtrace_factory or (lambda name: None)
    if replicas > 1:
        from pytorch_distributed_training_example_tpu.serve import (
            router as router_lib)

        fleet = {f"replica{i}": _build_engine(
                     module, params, spec, args, closed_loop=closed_loop,
                     cached=cached, spec_on=spec_on, telemetry=telemetry,
                     metrics=metrics, reqtrace=rt_for(f"replica{i}"),
                     slo=slo)
                 for i in range(replicas)}
        n_exec = sum(rep.warmup() for rep in fleet.values())
        eng = router_lib.PrefixAffinityRouter(
            fleet, page_size=args.page_size, policy=args.route)
    else:
        eng = _build_engine(module, params, spec, args,
                            closed_loop=closed_loop, cached=cached,
                            spec_on=spec_on, telemetry=telemetry,
                            metrics=metrics, reqtrace=rt_for("replica0"),
                            slo=slo)
        n_exec = eng.warmup()
    chaos_fired = False
    t0 = time.perf_counter()
    if closed_loop:
        for req in requests:
            eng.submit(req)
            eng.run()
    else:
        driver = loadgen.OpenLoopDriver(requests)
        while driver.remaining or eng.has_work:
            driver.pump(eng, time.perf_counter() - t0)
            if chaos and not chaos_fired \
                    and len(eng.completed) >= chaos[1]:
                chaos_fired = True
                target = "replica1"
                _say(f"serve_bench: chaos {chaos[0]} -> {target} "
                     f"(completed={len(eng.completed)})")
                if chaos[0] == "sigterm":
                    eng.drain(target)
                else:
                    eng.kill(target)
            if eng.has_work:
                eng.step()
            else:
                time.sleep(0.0005)  # idle until the next scheduled arrival
    wall = time.perf_counter() - t0
    import jax

    done = eng.completed
    out = latency_summary(done, wall, jax.device_count())
    stats = eng.stats if replicas == 1 else None
    if stats is None:
        stats = {}
        for rep in fleet.values():
            for k, v in rep.stats.items():
                stats[k] = stats.get(k, 0) + v
    out.update(submitted=submitted, executables=n_exec,
               compiles=stats["compiles"], decode_steps=stats["decode_steps"],
               evictions=stats["evictions"])
    assert stats["compiles"] == n_exec, \
        f"steady-state recompile: {stats['compiles']} > {n_exec}"
    assert len(done) == submitted, \
        f"dropped requests: completed {len(done)} of {submitted}"
    if cached:
        out["prefix"] = {
            "hit_rate": round(stats["cached_tokens"]
                              / max(stats["prompt_tokens"], 1), 4),
            "cached_tokens": stats["cached_tokens"],
            "prompt_tokens": stats["prompt_tokens"],
            "cow_copies": stats["cow_copies"],
        }
    if spec_on:
        drafted = stats.get("draft_tokens", 0)
        out["spec"] = {
            "spec_steps": stats.get("spec_steps", 0),
            "draft_tokens": drafted,
            "accepted_tokens": stats.get("accepted_tokens", 0),
            "accept_rate": round(stats.get("accepted_tokens", 0)
                                 / max(drafted, 1), 4),
            "accepted_len_hist": {
                str(n): stats.get(f"spec_accept_{n}", 0)
                for n in range(args.draft_len + 1)},
        }
    if args.disaggregate:
        out["handoffs"] = stats.get("handoffs_out", 0)
    if replicas > 1:
        out["router"] = dict(eng.stats)
        out["router"]["per_replica_completed"] = {
            name: len(rep.completed) for name, rep in fleet.items()}
        out["chaos_fired"] = chaos_fired
    return out, done


def aot_decode_report(model_name: str, *, batch: int, page_size: int,
                      num_pages: int, max_model_len: int,
                      precision: str = "fp32") -> dict:
    """Chipless AOT byte/FLOP model of ONE decode step at one batch bucket.

    Lower the exact engine decode program with abstract inputs, tabulate modeled HBM bytes per serve_*
    named-scope region with proportional fusion attribution, and stamp the
    lowering backend so goldens never compare across backends."""
    import collections

    import jax
    import jax.numpy as jnp

    import profile_step

    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.serve.kv_cache import (
        pages_for_tokens)

    dtype = jnp.float32 if precision == "fp32" else jnp.bfloat16
    bundle = registry.create_model(model_name, seq_len=max_model_len,
                                   dtype=dtype, param_dtype=dtype)
    module = bundle.module
    table_width = pages_for_tokens(max_model_len, page_size)
    sds = jax.ShapeDtypeStruct
    tok = sds((batch, 1), jnp.int32)
    pos = sds((batch, 1), jnp.int32)
    table = sds((batch, table_width), jnp.int32)
    last = sds((batch,), jnp.int32)

    def ctx(positions, page_table, last_index):
        return dict(positions=positions, page_table=page_table,
                    cache_spec=(num_pages, page_size),
                    last_index=last_index, attn_impl="auto")

    def init_fn(rng, tokens, positions, page_table, last_index):
        return module.init(rng, tokens, train=False,
                           decode_ctx=ctx(positions, page_table, last_index))

    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0), tok, pos, table,
                            last)
    params_abs, cache_abs = shapes["params"], shapes["cache"]

    def run(params, cache, tokens, positions, page_table, last_index):
        logits, vs = module.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            decode_ctx=ctx(positions, page_table, last_index),
            mutable=["cache"])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), vs["cache"]

    compiled = jax.jit(run, donate_argnums=1).lower(
        params_abs, cache_abs, tok, pos, table, last).compile()
    regions, ca = _tabulate_regions(compiled)
    return {
        "mode": "aot_hlo_model",
        "attribution": "proportional_bytes",
        "backend_lowering": jax.default_backend(),
        "model": f"{model_name}_decode",
        "per_chip_batch": batch,
        "seq_len": max_model_len,       # KV capacity: the decode shape knob
        "page_size": page_size,
        "num_pages": num_pages,
        "precision": precision,
        "xla_flops_per_step": ca.get("flops"),
        "xla_bytes_accessed": ca.get("bytes accessed"),
        "regions": regions,
    }


def _tabulate_regions(compiled) -> tuple[dict, dict]:
    """Per-region modeled HBM bytes for one compiled serve program (the
    profile_step scheme with serve_* named-scope tags)."""
    import collections

    import profile_step

    hlo_text = compiled.as_text()
    op_cat, _ = profile_step.build_op_categories(hlo_text)
    op_bytes = profile_step.build_op_bytes(hlo_text)
    op_tag = profile_step.build_op_moe_tags(hlo_text, tag_re=SERVE_TAG_RE)
    op_w = profile_step.build_op_moe_weights(hlo_text, tag_re=SERVE_TAG_RE)
    op_interior = profile_step.build_pallas_interior(hlo_text)

    regions: dict[str, dict] = {}

    def row(tag):
        return regions.setdefault(tag, {"ops": 0, "gbytes_modeled": 0.0,
                                        "by_category": collections.Counter()})

    for op, b in op_bytes.items():
        if op in op_interior:
            continue
        assigned = 0.0
        for tag, frac in op_w.get(op, {}).items():
            row(tag)["gbytes_modeled"] += b * frac / 1e9
            assigned += frac
        if assigned < 1.0:
            row("other")["gbytes_modeled"] += b * (1.0 - assigned) / 1e9
        r = row(op_tag.get(op, "other"))
        r["ops"] += 1
        if b or op_cat.get(op) not in (None, "copy_layout"):
            r["by_category"][op_cat.get(op, "?")] += 1
    for r in regions.values():
        r["gbytes_modeled"] = round(r["gbytes_modeled"], 4)
        r["by_category"] = dict(r["by_category"].most_common(6))
    try:
        ca = compiled.cost_analysis() or {}
    except Exception:
        ca = {}
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    return (dict(sorted(regions.items(),
                        key=lambda kv: -kv[1]["gbytes_modeled"])), ca)


def aot_prefill_report(model_name: str, *, prompt_bucket: int, page_size: int,
                       num_pages: int, max_model_len: int,
                       precision: str = "fp32") -> dict:
    """Chipless AOT byte model of ONE batch-1 prefill program at one prompt
    bucket — the unit of work a prefix-cache hit AVOIDS. The cached-run
    summary converts (report gbytes / bucket) into per-token prefill cost
    to model prefill-bytes-avoided; CI gates the census through the same
    ``check_regression.py --aot-bytes`` golden as the decode rows (key
    ``<model>_prefill b1 s<bucket> -``)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.serve.kv_cache import (
        pages_for_tokens)

    dtype = jnp.float32 if precision == "fp32" else jnp.bfloat16
    bundle = registry.create_model(model_name, seq_len=max_model_len,
                                   dtype=dtype, param_dtype=dtype)
    module = bundle.module
    table_width = pages_for_tokens(max_model_len, page_size)
    sds = jax.ShapeDtypeStruct
    tok = sds((1, prompt_bucket), jnp.int32)
    pos = sds((1, prompt_bucket), jnp.int32)
    table = sds((1, table_width), jnp.int32)
    last = sds((1,), jnp.int32)

    def ctx(positions, page_table, last_index):
        return dict(positions=positions, page_table=page_table,
                    cache_spec=(num_pages, page_size),
                    last_index=last_index, attn_impl="auto")

    def init_fn(rng, tokens, positions, page_table, last_index):
        return module.init(rng, tokens, train=False,
                           decode_ctx=ctx(positions, page_table, last_index))

    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0), tok, pos, table,
                            last)
    params_abs, cache_abs = shapes["params"], shapes["cache"]

    def run(params, cache, tokens, positions, page_table, last_index):
        logits, vs = module.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            decode_ctx=ctx(positions, page_table, last_index),
            mutable=["cache"])
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), vs["cache"]

    compiled = jax.jit(run, donate_argnums=1).lower(
        params_abs, cache_abs, tok, pos, table, last).compile()
    regions, ca = _tabulate_regions(compiled)
    return {
        "mode": "aot_hlo_model",
        "attribution": "proportional_bytes",
        "backend_lowering": jax.default_backend(),
        "model": f"{model_name}_prefill",
        "per_chip_batch": 1,
        "seq_len": prompt_bucket,       # the prefill window: its shape knob
        "page_size": page_size,
        "num_pages": num_pages,
        "precision": precision,
        "xla_flops_per_step": ca.get("flops"),
        "xla_bytes_accessed": ca.get("bytes accessed"),
        "regions": regions,
    }


def aot_verify_report(model_name: str, *, batch: int, width: int,
                      page_size: int, num_pages: int, max_model_len: int,
                      precision: str = "fp32") -> dict:
    """Chipless AOT byte model of ONE speculative VERIFY step.

    The verify program is the engine's multi-token history-attention
    forward with ``all_logits`` — it scores all ``width = draft_len + 1``
    positions in one pass and returns the per-position argmax stacked with
    the echoed input tokens (the engine's one-fetch acceptance contract).
    Lowered here exactly as ``_get_step("verify", batch, width)`` lowers
    it, so the byte census is the program serving actually runs. CI gates
    it through the same ``check_regression.py --aot-bytes`` golden as the
    decode rows (key ``<model>_verify b<batch> s<width> -``); the spec
    summary divides decode bytes by verify bytes to model the tokens/sec
    multiplier (verify reads the weights once for up to ``width`` emitted
    tokens — that amortization IS the speedup)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.serve.kv_cache import (
        pages_for_tokens)

    dtype = jnp.float32 if precision == "fp32" else jnp.bfloat16
    bundle = registry.create_model(model_name, seq_len=max_model_len,
                                   dtype=dtype, param_dtype=dtype)
    module = bundle.module
    table_width = pages_for_tokens(max_model_len, page_size)
    sds = jax.ShapeDtypeStruct
    tok = sds((batch, width), jnp.int32)
    pos = sds((batch, width), jnp.int32)
    table = sds((batch, table_width), jnp.int32)
    last = sds((batch,), jnp.int32)

    def ctx(positions, page_table, last_index):
        return dict(positions=positions, page_table=page_table,
                    cache_spec=(num_pages, page_size),
                    last_index=last_index, history=True, all_logits=True,
                    attn_impl="auto")

    def init_fn(rng, tokens, positions, page_table, last_index):
        return module.init(rng, tokens, train=False,
                           decode_ctx=ctx(positions, page_table, last_index))

    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0), tok, pos, table,
                            last)
    params_abs, cache_abs = shapes["params"], shapes["cache"]

    def run(params, cache, tokens, positions, page_table, last_index):
        logits, vs = module.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            decode_ctx=ctx(positions, page_table, last_index),
            mutable=["cache"])
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.stack([out, tokens.astype(jnp.int32)], axis=1), \
            vs["cache"]

    compiled = jax.jit(run, donate_argnums=1).lower(
        params_abs, cache_abs, tok, pos, table, last).compile()
    regions, ca = _tabulate_regions(compiled)
    return {
        "mode": "aot_hlo_model",
        "attribution": "proportional_bytes",
        "backend_lowering": jax.default_backend(),
        "model": f"{model_name}_verify",
        "per_chip_batch": batch,
        "seq_len": width,               # verify window: draft_len + 1
        "max_model_len": max_model_len,
        "page_size": page_size,
        "num_pages": num_pages,
        "precision": precision,
        "xla_flops_per_step": ca.get("flops"),
        "xla_bytes_accessed": ca.get("bytes accessed"),
        "regions": regions,
    }


def _report_gbytes(report: dict) -> float:
    return sum(r["gbytes_modeled"] for r in report["regions"].values())


def _int_tuple(text: str) -> tuple[int, ...]:
    return tuple(int(t) for t in text.split(",") if t)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama_tiny")
    p.add_argument("--precision", default="fp32", choices=("fp32", "bf16"))
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--num-pages", type=int, default=128)
    p.add_argument("--max-model-len", type=int, default=128)
    p.add_argument("--decode-buckets", type=_int_tuple, default=(1, 2, 4, 8))
    p.add_argument("--prompt-buckets", type=_int_tuple, default=(16, 32))
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--rate", type=float, default=0.0,
                   help="open-loop Poisson arrivals per second; 0 = all "
                        "requests arrive at t=0 (saturation)")
    p.add_argument("--prompt-len", default="4:24", help="min:max prompt len")
    p.add_argument("--max-new", default="4:24", help="min:max new tokens")
    p.add_argument("--eos-id", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--skip-batch1", action="store_true")
    p.add_argument("--templates", type=int, default=0,
                   help="shared-prefix prompt templates (Zipf-popular); "
                        "0 = fully random prompts")
    p.add_argument("--zipf-a", type=float, default=1.2,
                   help="Zipf exponent for template popularity")
    p.add_argument("--prefix-len", default="16:16",
                   help="min:max template prefix length in tokens")
    p.add_argument("--prefix-cache", action="store_true",
                   help="run saturation twice (uncached baseline, then "
                        "prefix cache ON), verify token identity, report "
                        "hit rate + TTFT/ITL deltas + modeled "
                        "prefill-bytes-avoided")
    p.add_argument("--spec-decode", default="off",
                   choices=("off", "ngram", "draft"),
                   help="run saturation again with speculative decoding ON "
                        "(same seeded stream), assert greedy token "
                        "identity, report acceptance rate + accepted-length "
                        "histogram + modeled tokens/s multiplier from the "
                        "AOT byte census")
    p.add_argument("--draft-len", type=int, default=4,
                   help="speculation window: tokens drafted per slot-step")
    p.add_argument("--draft-model", default=None,
                   help="with --spec-decode draft: registry model name for "
                        "the draft proposer (default: the target model "
                        "itself — self-draft acceptance ceiling)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked-prefill window (tokens, multiple of the "
                        "page size); 0 = whole prompt")
    p.add_argument("--disaggregate", action="store_true",
                   help="prefill-role + decode-role engine pair per replica")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve replicas behind the prefix-affinity router")
    p.add_argument("--route", default="affinity",
                   choices=("affinity", "least_loaded"))
    p.add_argument("--chaos-replica", default=None,
                   help="sigterm@completed=K (drain) or kill@completed=K "
                        "(hard loss + re-route) against replica1 during "
                        "saturation; needs --replicas >= 2")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="start a fleetobs MetricsServer (0 = ephemeral) and "
                        "export pdtx_serve_* gauges")
    p.add_argument("--trace-dir", default=None,
                   help="write trace_events.json/goodput.json here")
    p.add_argument("--slo", action="store_true",
                   help="instrument the saturation phase with per-request "
                        "span tracing + sliding-window TTFT/ITL quantiles "
                        "(serve/slo.py); artifacts go to --slo-dir")
    p.add_argument("--slo-dir", default=None,
                   help="write slo.jsonl + reqtrace.*.json here "
                        "(default: --trace-dir)")
    p.add_argument("--slo-window", type=int, default=256,
                   help="sliding-window size in samples per replica/role")
    p.add_argument("--slo-ttft-ms", type=float, default=0.0,
                   help="TTFT SLO target in ms (0 = quantiles only)")
    p.add_argument("--slo-itl-ms", type=float, default=0.0,
                   help="inter-token-latency SLO target in ms (0 = "
                        "quantiles only)")
    p.add_argument("--trace-events", type=int, default=4096,
                   help="request-span ring capacity per replica")
    p.add_argument("--trace-overhead", action="store_true",
                   help="with --slo: run saturation once untraced first, "
                        "assert greedy token identity traced vs untraced, "
                        "and report host-side tracing overhead in µs per "
                        "decode step")
    p.add_argument("--aot", action="store_true",
                   help="emit the chipless AOT decode-step byte model "
                        "instead of running load")
    p.add_argument("--aot-bucket", type=int, default=None,
                   help="with --aot: single-bucket report JSON on stdout "
                        "(pipe into check_regression.py --aot-bytes)")
    p.add_argument("--aot-prefill-bucket", type=int, default=None,
                   help="with --aot: single batch-1 PREFILL report at this "
                        "prompt bucket on stdout (pipe into "
                        "check_regression.py --aot-bytes)")
    p.add_argument("--aot-verify-bucket", type=int, default=None,
                   help="with --aot: single speculative VERIFY report at "
                        "this decode bucket (width --draft-len + 1) on "
                        "stdout (pipe into check_regression.py --aot-bytes)")
    p.add_argument("--json", default=None, help="also write result JSON here")
    args = p.parse_args(argv)

    result: dict = {"mode": "serve_bench", "model": args.model,
                    "page_size": args.page_size, "num_pages": args.num_pages,
                    "max_model_len": args.max_model_len,
                    "decode_buckets": list(args.decode_buckets),
                    "prompt_buckets": list(args.prompt_buckets),
                    "seed": args.seed}

    if args.aot:
        if args.aot_verify_bucket:
            _say(f"serve_bench: AOT verify model, bucket "
                 f"{args.aot_verify_bucket}, width {args.draft_len + 1}")
            print(json.dumps(aot_verify_report(
                args.model, batch=args.aot_verify_bucket,
                width=args.draft_len + 1, page_size=args.page_size,
                num_pages=args.num_pages, max_model_len=args.max_model_len,
                precision=args.precision), indent=2))
            return 0
        if args.aot_prefill_bucket:
            _say(f"serve_bench: AOT prefill model, "
                 f"bucket {args.aot_prefill_bucket}")
            print(json.dumps(aot_prefill_report(
                args.model, prompt_bucket=args.aot_prefill_bucket,
                page_size=args.page_size, num_pages=args.num_pages,
                max_model_len=args.max_model_len,
                precision=args.precision), indent=2))
            return 0
        buckets = ([args.aot_bucket] if args.aot_bucket
                   else list(args.decode_buckets))
        reports = []
        for b in buckets:
            _say(f"serve_bench: AOT decode model, bucket {b}")
            reports.append(aot_decode_report(
                args.model, batch=b, page_size=args.page_size,
                num_pages=args.num_pages, max_model_len=args.max_model_len,
                precision=args.precision))
        if args.aot_bucket:
            print(json.dumps(reports[0], indent=2))
            return 0
        for sp in args.prompt_buckets:
            _say(f"serve_bench: AOT prefill model, bucket {sp}")
            reports.append(aot_prefill_report(
                args.model, prompt_bucket=sp, page_size=args.page_size,
                num_pages=args.num_pages, max_model_len=args.max_model_len,
                precision=args.precision))
        if args.spec_decode != "off":
            for b in buckets:
                _say(f"serve_bench: AOT verify model, bucket {b}, "
                     f"width {args.draft_len + 1}")
                reports.append(aot_verify_report(
                    args.model, batch=b, width=args.draft_len + 1,
                    page_size=args.page_size, num_pages=args.num_pages,
                    max_model_len=args.max_model_len,
                    precision=args.precision))
        result["aot"] = reports
        print(json.dumps(result, indent=2))
        if args.json:
            with open(args.json, "w") as fh:
                json.dump(result, fh, indent=2)
        return 0

    from pytorch_distributed_training_example_tpu.serve import loadgen
    from pytorch_distributed_training_example_tpu.utils import telemetry as tele

    pl_min, pl_max = (int(t) for t in args.prompt_len.split(":"))
    mn_min, mn_max = (int(t) for t in args.max_new.split(":"))
    pfx_min, pfx_max = (int(t) for t in args.prefix_len.split(":"))
    module, params, spec = build_serving(
        args.model, page_size=args.page_size, num_pages=args.num_pages,
        max_model_len=args.max_model_len, precision=args.precision,
        seed=args.seed)
    vocab = int(module.vocab_size)
    if args.templates and pl_max + pfx_max > max(args.prompt_buckets):
        raise SystemExit(
            f"--templates: prefix {pfx_max} + prompt {pl_max} exceeds the "
            f"largest prompt bucket {max(args.prompt_buckets)}")
    mkload = lambda rate, n, seed: loadgen.generate_requests(loadgen.LoadSpec(
        num_requests=n, rate=rate, prompt_len_min=pl_min,
        prompt_len_max=pl_max, max_new_min=mn_min, max_new_max=mn_max,
        vocab_size=vocab, eos_id=args.eos_id, seed=seed,
        num_templates=args.templates, zipf_a=args.zipf_a,
        prefix_len_min=pfx_min, prefix_len_max=pfx_max))

    metrics = None
    if args.metrics_port is not None:
        from pytorch_distributed_training_example_tpu.utils import fleetobs

        metrics = fleetobs.MetricsServer(port=args.metrics_port).start()
        _say(f"serve_bench: /metrics on port {metrics.port}")
        result["metrics_port"] = metrics.port
    recorder = tele.SpanRecorder(run_id=f"serve_bench_s{args.seed}")

    # r20 SLO kit: one tracker for the bench, one request-trace ring per
    # replica of the saturation phase. The run id matches the SpanRecorder
    # stamp so trace_merge accepts both artifact families as one run.
    slo_tracker = None
    tracers: dict = {}
    reqtrace_factory = None
    if args.slo:
        from pytorch_distributed_training_example_tpu.serve import (
            slo as slo_lib)

        slo_tracker = slo_lib.SLOTracker(
            window=args.slo_window, ttft_target_ms=args.slo_ttft_ms,
            itl_target_ms=args.slo_itl_ms)

        def reqtrace_factory(name):
            rt = slo_lib.RequestTrace(
                name, run_id=f"serve_bench_s{args.seed}",
                capacity=args.trace_events)
            tracers[name] = rt
            return rt
    elif args.trace_overhead:
        raise SystemExit("--trace-overhead needs --slo")

    if not args.skip_batch1:
        _say("serve_bench: phase batch1 (closed loop)")
        result["batch1"], _ = run_phase(
            module, params, spec, args, mkload(0.0, min(args.requests, 8),
                                               args.seed + 1),
            closed_loop=True, telemetry=recorder, metrics=metrics)
        _say(f"  batch1: {result['batch1']['tokens_per_s_per_chip']} tok/s/chip")
    if args.trace_overhead:
        # Baseline for the zero-intrusion contract: the same seeded
        # stream, tracing OFF. Greedy decode is deterministic per request
        # regardless of batching interleave, so the traced run below must
        # reproduce these exact tokens.
        _say("serve_bench: phase saturation_untraced (overhead baseline)")
        result["saturation_untraced"], untraced_done = run_phase(
            module, params, spec, args, mkload(args.rate, args.requests,
                                               args.seed),
            closed_loop=False, telemetry=recorder, metrics=metrics)
    _say(f"serve_bench: phase saturation (open loop, rate={args.rate})")
    result["saturation"], base_done = run_phase(
        module, params, spec, args, mkload(args.rate, args.requests,
                                           args.seed),
        closed_loop=False, telemetry=recorder, metrics=metrics,
        slo=slo_tracker, reqtrace_factory=reqtrace_factory)
    sat = result["saturation"]
    if args.trace_overhead:
        untraced_by_id = {r.request_id: r.generated for r in untraced_done}
        for r in base_done:
            assert r.generated == untraced_by_id[r.request_id], \
                f"tracing changed tokens for {r.request_id}"
        ut = result["saturation_untraced"]
        overhead_us = (sat["wall_s"] - ut["wall_s"]) \
            / max(sat["decode_steps"], 1) * 1e6
        result["trace_overhead"] = {
            "token_identity": "ok",
            "untraced_wall_s": ut["wall_s"],
            "traced_wall_s": sat["wall_s"],
            "decode_steps": sat["decode_steps"],
            "overhead_us_per_step": round(overhead_us, 2),
        }
        _say(f"  trace overhead: {result['trace_overhead']}")
    _say(f"  saturation: {sat['tokens_per_s_per_chip']} tok/s/chip, "
         f"ttft p50/p99 {sat['ttft_ms']['p50']}/{sat['ttft_ms']['p99']} ms, "
         f"itl p50/p99 {sat['inter_token_ms']['p50']}"
         f"/{sat['inter_token_ms']['p99']} ms")
    if args.prefix_cache:
        _say("serve_bench: phase saturation_cached (prefix cache ON, "
             "same seeded stream)")
        result["saturation_cached"], cached_done = run_phase(
            module, params, spec, args, mkload(args.rate, args.requests,
                                               args.seed),
            closed_loop=False, cached=True, telemetry=recorder,
            metrics=metrics)
        csat = result["saturation_cached"]
        base_by_id = {r.request_id: r.generated for r in base_done}
        for r in cached_done:
            assert r.generated == base_by_id[r.request_id], \
                f"token identity broken for {r.request_id}"
        prefill_report = aot_prefill_report(
            args.model, prompt_bucket=max(args.prompt_buckets),
            page_size=args.page_size, num_pages=args.num_pages,
            max_model_len=args.max_model_len, precision=args.precision)
        per_tok_gb = _report_gbytes(prefill_report) / max(args.prompt_buckets)
        delta = lambda k, q: (None if sat[k][q] is None or csat[k][q] is None
                              else round(csat[k][q] - sat[k][q], 3))
        result["prefix_cache"] = {
            **csat["prefix"],
            "token_identity": "ok",
            "ttft_ms_delta": {"p50": delta("ttft_ms", "p50"),
                              "p99": delta("ttft_ms", "p99")},
            "inter_token_ms_delta": {
                "p50": delta("inter_token_ms", "p50"),
                "p99": delta("inter_token_ms", "p99")},
            "prefill_gbytes_avoided_modeled": round(
                per_tok_gb * csat["prefix"]["cached_tokens"], 4),
            "prefill_bucket_gbytes_modeled": round(
                _report_gbytes(prefill_report), 4),
        }
        _say(f"  prefix cache: hit {result['prefix_cache']['hit_rate']}, "
             f"ttft p50 delta {result['prefix_cache']['ttft_ms_delta']['p50']}"
             f" ms, modeled prefill GB avoided "
             f"{result['prefix_cache']['prefill_gbytes_avoided_modeled']}")
    if args.spec_decode != "off":
        _say(f"serve_bench: phase saturation_spec ({args.spec_decode}, "
             f"draft_len={args.draft_len}, same seeded stream)")
        result["saturation_spec"], spec_done = run_phase(
            module, params, spec, args, mkload(args.rate, args.requests,
                                               args.seed),
            closed_loop=False, spec_on=True, telemetry=recorder,
            metrics=metrics)
        ssat = result["saturation_spec"]
        base_by_id = {r.request_id: r.generated for r in base_done}
        for r in spec_done:
            assert r.generated == base_by_id[r.request_id], \
                f"spec token identity broken for {r.request_id}"
        # Modeled multiplier: the unsped engine pays one decode step's
        # bytes per emitted token; the sped one pays one verify step's
        # bytes per (mean accepted + 1 bonus) tokens. Draft cost is not
        # in the ratio — zero device work for ngram, and the draft
        # model's census is the plain decode row of --draft-model.
        bucket = max(args.decode_buckets)
        verify_report = aot_verify_report(
            args.model, batch=bucket, width=args.draft_len + 1,
            page_size=args.page_size, num_pages=args.num_pages,
            max_model_len=args.max_model_len, precision=args.precision)
        decode_report = aot_decode_report(
            args.model, batch=bucket, page_size=args.page_size,
            num_pages=args.num_pages, max_model_len=args.max_model_len,
            precision=args.precision)
        hist = ssat["spec"]["accepted_len_hist"]
        slot_steps = sum(hist.values())
        mean_emitted = (ssat["spec"]["accepted_tokens"] + slot_steps) \
            / max(slot_steps, 1)
        vg = _report_gbytes(verify_report)
        dg = _report_gbytes(decode_report)
        result["spec_decode"] = {
            **ssat["spec"],
            "token_identity": "ok",
            "mean_emitted_per_verify": round(mean_emitted, 4),
            "decode_step_gbytes_modeled": round(dg, 4),
            "verify_step_gbytes_modeled": round(vg, 4),
            "modeled_tokens_per_s_multiplier": round(
                mean_emitted * dg / max(vg, 1e-12), 4),
        }
        _say(f"  spec decode: accept rate "
             f"{result['spec_decode']['accept_rate']}, mean emitted/verify "
             f"{result['spec_decode']['mean_emitted_per_verify']}, modeled "
             f"tok/s multiplier "
             f"{result['spec_decode']['modeled_tokens_per_s_multiplier']}")
    result["goodput"] = {k: recorder.goodput()[k]
                         for k in ("goodput_fraction", "coverage", "wall_s",
                                   "categories_s")}
    if args.trace_dir:
        recorder.write(args.trace_dir)
        _say(f"serve_bench: wrote trace/goodput to {args.trace_dir}")
    if slo_tracker is not None:
        dropped = sum(rt.dropped_spans for rt in tracers.values())
        slo_dir = args.slo_dir or args.trace_dir
        if slo_dir:
            run_id = f"serve_bench_s{args.seed}"
            slo_path = slo_tracker.flush(slo_dir, run_id,
                                         dropped_spans=dropped)
            for rt in tracers.values():
                rt.write(slo_dir)
            _say(f"serve_bench: wrote {slo_path} + {len(tracers)} "
                 f"reqtrace file(s)")
        if metrics is not None:
            metrics.update(**slo_tracker.gauges(extra_dropped=dropped))
            metrics.update_histograms(**slo_tracker.histograms())
        result["slo"] = {
            "run_id": f"serve_bench_s{args.seed}",
            "attainment": round(slo_tracker.overall_attainment(), 4),
            "breaches": slo_tracker.breaches,
            "dropped_spans": dropped,
            "windows": slo_tracker.snapshot(),
        }
    if metrics is not None:
        result["metrics_snapshot"] = {
            k: v for k, v in metrics.snapshot().items()
            if k.startswith("serve_")}
        metrics.stop()
    print(json.dumps(result, indent=2))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(result, fh, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())

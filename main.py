#!/usr/bin/env python
"""Training entrypoint — CLI-compatible with the reference's ``main.py``.

The north-star contract (BASELINE.json): ``python main.py --distributed``
launches unchanged on a TPU slice. Flag surface follows the reference's
argparse conventions (SURVEY.md §2a #1): epochs/batch-size/lr/data-path/
workers/resume, plus ``--config`` presets for the five reference workloads
and mesh/strategy flags for the TPU-native parallelism that replaces DDP.

Single-process mode (no ``--distributed``) is the reference's CPU-runnable
dev path (SURVEY.md §3.5): same compiled step on whatever single host
process + devices exist, no rendezvous.
"""

from __future__ import annotations

import argparse
import dataclasses


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU-native distributed training")
    p.add_argument("--distributed", action="store_true",
                   help="multi-host mode: rendezvous via jax.distributed.initialize "
                        "(the init_process_group('nccl') equivalent)")
    p.add_argument("--config", "--preset", default=None, dest="config",
                   help="preset name (resnet18_cifar10, resnet50_imagenet, "
                        "vit_b16_imagenet, gpt2_124m, llama3_8b, "
                        "granite4_h_micro_share)")
    p.add_argument("--model", default=None)
    p.add_argument("--dataset", default=None)
    p.add_argument("--data-path", default=None)
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None, dest="global_batch_size",
                   help="GLOBAL batch size (split across hosts/chips)")
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--lr-schedule", default=None,
                   choices=["cosine", "step", "constant"],
                   help="step = the reference ImageNet StepLR recipe "
                        "(lr * gamma^(epoch // step-epochs))")
    p.add_argument("--lr-step-epochs", type=int, default=None)
    p.add_argument("--lr-gamma", type=float, default=None)
    p.add_argument("--weight-decay", type=float, default=None)
    p.add_argument("--optimizer", default=None, choices=["sgd", "adamw"])
    p.add_argument("--precision", default=None,
                   choices=["fp32", "bf16", "pure_bf16", "fp16"])
    p.add_argument("--strategy", default=None,
                   help="dp | fsdp | model-specific (e.g. fsdp_tp)")
    p.add_argument("--mesh", default=None,
                   help="axis sizes as k=v pairs, e.g. 'data=2,fsdp=4' "
                        "(-1 absorbs remaining devices; aliases seq/cp/tp/"
                        "ep/pp map to context/model/expert/stage)")
    p.add_argument("--mesh-seq", type=int, default=None, dest="mesh_context",
                   help="sequence/context-parallel degree (shorthand for "
                        "--mesh seq=N; ring attention shards S over it)")
    p.add_argument("--remat", action="store_true", default=None,
                   help="gradient checkpointing")
    p.add_argument("--remat-policy", default=None, dest="remat_policy",
                   choices=["nothing", "dots", "dots_no_batch", "attn_out"],
                   help="checkpoint policy under --remat (Llama family): "
                        "what to save across the backward recompute")
    p.add_argument("--grad-accum", type=int, default=None,
                   dest="grad_accum_steps",
                   help="gradient-accumulation microbatches per step")
    p.add_argument("--attn-impl", default=None,
                   choices=["auto", "xla", "flash", "ring", "ring_zigzag",
                            "ring_allgather", "ulysses"],
                   help="attention kernel: Pallas flash, ring (context-"
                        "parallel ppermute; ring_allgather = all-gather-KV "
                        "fallback), Ulysses all-to-all, or plain XLA")
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--dropout", type=float, default=None,
                   help="model dropout rate (families that support it)")
    p.add_argument("--tensorboard-dir", type=str, default=None,
                   dest="tensorboard_dir",
                   help="export metric scalars as TensorBoard events here")
    p.add_argument("--image-size", type=int, default=None)
    p.add_argument("--workers", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--log-every", type=int, default=None)
    p.add_argument("--steps-per-epoch", type=int, default=None,
                   help="cap steps per epoch (smoke/bench runs)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every-steps", type=int, default=None,
                   help="also checkpoint every N optimizer steps (mid-epoch; "
                        "resume continues at the exact next sample)")
    p.add_argument("--resume", default=None, nargs="?", const="auto",
                   help="checkpoint dir or 'auto' (newest committed)")
    p.add_argument("--elastic", action="store_true", default=None,
                   help="elastic resume: accept a checkpoint written under a "
                        "different world size — rebuild the mesh at the "
                        "surviving device set and rescale the batch geometry "
                        "under --elastic-policy (utils/elastic.py)")
    p.add_argument("--elastic-policy", default=None, dest="elastic_policy",
                   choices=["keep_global_batch", "scale_lr"],
                   help="batch policy on a world-size change: keep the "
                        "global batch via gradient accumulation (exact "
                        "trajectory) or shrink/grow it with linear LR "
                        "scaling")
    p.add_argument("--evaluate", action="store_true",
                   help="evaluation only (use with --resume to score a "
                        "checkpoint); no training")
    p.add_argument("--telemetry", action="store_true", default=None,
                   help="unified telemetry: on-device health pack in the "
                        "metrics rows, span timeline + goodput accounting "
                        "(trace_events.json/goodput.json in the checkpoint "
                        "dir), anomaly guard")
    p.add_argument("--health-every", type=int, default=None,
                   dest="health_every",
                   help="with --telemetry: also fetch/check the health pack "
                        "every N steps (0 = ride the log-every fetch only)")
    p.add_argument("--anomaly-action", default=None, dest="anomaly_action",
                   choices=["abort", "continue", "rollback"],
                   help="on a non-finite health scalar: dump a diagnostic "
                        "bundle then abort (raise), keep training, or "
                        "rollback (restore last committed checkpoint and "
                        "continue past the poisoned batches, bounded by "
                        "--rollback-budget)")
    p.add_argument("--rollback-budget", type=int, default=None,
                   dest="rollback_budget",
                   help="max anomaly rollbacks per run before escalating "
                        "to abort")
    p.add_argument("--watchdog-timeout", type=float, default=None,
                   dest="watchdog_timeout",
                   help="seconds without step progress before the watchdog "
                        "dumps stacks and aborts")
    p.add_argument("--chaos", default=None,
                   help="deterministic fault injection spec, e.g. "
                        "'sigterm@step=7,ckpt_io_error@save=2,"
                        "nan_grad@step=5,loader_stall@batch=3,"
                        "truncate_ckpt@save=1' (utils/chaos.py); "
                        "append :rank=R to fire on one rank only")
    p.add_argument("--straggler-threshold", type=float, default=None,
                   dest="straggler_threshold",
                   help="warn when a step's host-local wait exceeds "
                        "(threshold-1) x the median step time "
                        "(utils/fleetobs.py; default 2.0)")
    p.add_argument("--flightrec-steps", type=int, default=None,
                   dest="flightrec_steps",
                   help="flight-recorder ring size: last-N step records "
                        "dumped on anomaly/preemption/host-loss exits")
    p.add_argument("--metrics-port", type=int, default=None,
                   dest="metrics_port",
                   help="rank-0 Prometheus endpoint port (0 = ephemeral, "
                        "logged at startup); also enables progress.json")
    p.add_argument("--chaos-seed", type=int, default=None, dest="chaos_seed",
                   help="seed for chaos randomness (defaults to --seed)")
    p.add_argument("--profile-steps", default=None,
                   help="'start:stop' global-step range to trace")
    p.add_argument("--fault-inject", default=None,
                   help="'rank:step' — hard-kill that process before the "
                        "given global step (recovery testing)")
    p.add_argument("--num-classes", type=int, default=None)
    p.add_argument("--warmup-epochs", type=float, default=None,
                   help="linear LR warmup length (fractions allowed)")
    p.add_argument("--momentum", type=float, default=None,
                   help="SGD momentum")
    p.add_argument("--label-smoothing", type=float, default=None)
    p.add_argument("--grad-clip", type=float, default=None,
                   help="global-norm gradient clip (0 disables)")
    p.add_argument("--pp-microbatches", type=int, default=None,
                   dest="pp_microbatches",
                   help="GPipe microbatches for --strategy pp")
    p.add_argument("--no-native-loader", action="store_false", default=None,
                   dest="native_loader",
                   help="disable the C++ batch engine even when available")
    p.add_argument("--eval-every-epochs", type=int, default=None)
    p.add_argument("--checkpoint-every-epochs", type=int, default=None)
    p.add_argument("--profile-dir", default=None,
                   help="where --profile-steps traces are written")
    p.add_argument("--coordinator", default=None,
                   help="coordinator address host:port (else env)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--serve", action="store_true", default=None,
                   help="run the continuous-batching decode engine (serve/) "
                        "instead of training; --resume restores params only")
    p.add_argument("--serve-page-size", type=int, default=None,
                   dest="serve_page_size",
                   help="KV cache page size in tokens (default 16)")
    p.add_argument("--serve-num-pages", type=int, default=None,
                   dest="serve_num_pages",
                   help="KV cache pool size in pages (default 128)")
    p.add_argument("--serve-max-model-len", type=int, default=None,
                   dest="serve_max_model_len",
                   help="per-request token cap; 0 = model/cache capacity")
    p.add_argument("--serve-decode-buckets", default=None,
                   dest="serve_decode_buckets",
                   help="comma-separated padded decode batch sizes")
    p.add_argument("--serve-prompt-buckets", default=None,
                   dest="serve_prompt_buckets",
                   help="comma-separated padded prefill prompt lengths")
    p.add_argument("--serve-requests", type=int, default=None,
                   dest="serve_requests",
                   help="number of synthetic requests to drain")
    p.add_argument("--serve-rate", type=float, default=None, dest="serve_rate",
                   help="open-loop Poisson arrival rate (req/s); 0 = all at "
                        "t=0 (saturation)")
    p.add_argument("--serve-drain-timeout", type=float, default=None,
                   dest="serve_drain_timeout",
                   help="on SIGTERM, seconds to let in-flight sequences "
                        "finish decoding before exiting 75 (graceful "
                        "preemption of a serving session)")
    p.add_argument("--serve-prefix-cache", action="store_true", default=None,
                   dest="serve_prefix_cache",
                   help="share prompt-prefix KV pages across requests "
                        "(copy-on-write; serve/prefix_cache.py)")
    p.add_argument("--serve-prefill-chunk", type=int, default=None,
                   dest="serve_prefill_chunk",
                   help="chunked prefill window in tokens (multiple of the "
                        "page size); 0 = whole prompt in one program")
    p.add_argument("--serve-disaggregate", action="store_true", default=None,
                   dest="serve_disaggregate",
                   help="split serving into a prefill-role and a decode-role "
                        "engine with explicit KV-page handoff")
    p.add_argument("--serve-replicas", type=int, default=None,
                   dest="serve_replicas",
                   help="serve replicas behind the prefix-affinity router "
                        "(serve/router.py); 1 = no router")
    p.add_argument("--serve-route", default=None, dest="serve_route",
                   choices=["affinity", "least_loaded"],
                   help="replica placement policy")
    p.add_argument("--serve-templates", type=int, default=None,
                   dest="serve_templates",
                   help="shared-prefix prompt templates in the synthetic "
                        "stream (0 = fully random prompts)")
    p.add_argument("--serve-zipf-a", type=float, default=None,
                   dest="serve_zipf_a",
                   help="Zipf exponent for template popularity")
    p.add_argument("--serve-prefix-len", default=None, dest="serve_prefix_len",
                   help="template length range, \"min:max\" tokens")
    p.add_argument("--serve-spec-decode", default=None,
                   dest="serve_spec_decode",
                   choices=["off", "ngram", "draft"],
                   help="speculative decoding proposer: self-drafting n-gram "
                        "lookup or a separate draft model "
                        "(serve/spec_decode.py; greedy output stays "
                        "bit-identical to the unsped engine)")
    p.add_argument("--serve-draft-len", type=int, default=None,
                   dest="serve_draft_len",
                   help="max draft tokens verified per step (default 4)")
    p.add_argument("--serve-draft-model", default=None,
                   dest="serve_draft_model",
                   help="draft model name for --serve-spec-decode draft, "
                        "optionally \"name@ckpt_dir\" to restore its params")
    p.add_argument("--serve-slo", action="store_true", default=None,
                   dest="serve_slo",
                   help="record per-request span traces and sliding-window "
                        "TTFT/ITL quantiles (serve/slo.py); artifacts land "
                        "in the checkpoint dir (slo.jsonl, reqtrace.*.json)")
    p.add_argument("--serve-slo-window", type=int, default=None,
                   dest="serve_slo_window",
                   help="sliding-window size in samples per replica/role "
                        "(default 256)")
    p.add_argument("--serve-slo-ttft-ms", type=float, default=None,
                   dest="serve_slo_ttft_ms",
                   help="TTFT SLO target in ms (0 = track quantiles only)")
    p.add_argument("--serve-slo-itl-ms", type=float, default=None,
                   dest="serve_slo_itl_ms",
                   help="inter-token-latency SLO target in ms (0 = track "
                        "quantiles only)")
    p.add_argument("--serve-trace-events", type=int, default=None,
                   dest="serve_trace_events",
                   help="request-span ring-buffer capacity per replica; "
                        "overflow rotates generations and counts "
                        "dropped_spans (default 4096)")
    p.add_argument("--xcache", action="store_true", default=None,
                   help="persistent executable cache (core/xcache.py): "
                        "serialize the compiled train step under "
                        "<checkpoint-dir>/xcache keyed by a topology/knob "
                        "fingerprint so elastic relaunches at a seen "
                        "topology skip XLA compilation")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="force a jax platform (dev: run the TPU code path on CPU)")
    p.add_argument("--fake-devices", type=int, default=None,
                   help="with --platform cpu: number of fake host devices")
    return p


def config_from_args(args) -> "Config":
    from pytorch_distributed_training_example_tpu.utils.config import Config, from_preset

    cfg = from_preset(args.config) if args.config else Config()
    field_names = {f.name for f in dataclasses.fields(Config)}
    overrides = {k: v for k, v in vars(args).items()
                 if k in field_names and v is not None}
    cfg = cfg.replace(**overrides)
    if args.mesh:
        from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib

        axes = mesh_lib.normalize_axes(
            dict(kv.split("=") for kv in args.mesh.split(",")))
        cfg = cfg.replace(**{f"mesh_{k}": int(v) for k, v in axes.items()})
    return cfg


def main(argv=None):
    args = build_parser().parse_args(argv)

    import os

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}"
        ).strip()
    # --platform outvotes the JAX_PLATFORMS env var (which jax honours on
    # its own when the flag is absent).
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    # Persistent compile cache: repeat invocations (dev loops, restarts,
    # --resume) skip XLA recompilation. JAX_COMPILATION_CACHE_DIR places it
    # from outside; unset, it is <repo>/.jax_cache — or, under --xcache,
    # <checkpoint-dir>/xcache/jaxcache beside the serialized executables so
    # it survives with the run and doubles as the warm-restart fallback
    # where executable serialization is unsupported (core/xcache.py).
    from pytorch_distributed_training_example_tpu.core import xcache

    if args.xcache and args.checkpoint_dir:
        xcache.place_compile_cache(
            os.path.join(args.checkpoint_dir, "xcache", "jaxcache"))
    else:
        xcache.place_compile_cache()

    # Bootstrap BEFORE touching jax.devices(): in multi-host mode every
    # process must rendezvous first (SURVEY.md §3.1 boundary).
    from pytorch_distributed_training_example_tpu.core import distributed

    if args.distributed:
        distributed.init_process_group(args.coordinator, args.num_processes,
                                       args.process_id)

    cfg = config_from_args(args)

    if cfg.serve:
        from pytorch_distributed_training_example_tpu.serve import run as serve_run

        serve_run.main(cfg)
        return 0

    from pytorch_distributed_training_example_tpu.core.trainer import Trainer

    trainer = Trainer(cfg)
    if args.evaluate:
        # Reference-CLI parity: the canonical ImageNet example's --evaluate
        # runs validation on the (resumed) model and exits. Scoring a fresh
        # init is never what the user meant — fail loudly.
        if not trainer.resumed:
            raise SystemExit(
                "--evaluate needs restored weights: pass --resume with a "
                "committed checkpoint (nothing was loaded)")
        trainer.evaluate(max(trainer.start_epoch - 1, 0))
        trainer.metric_logger.close()
        return 0
    trainer.train()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python
"""Driver benchmark: ResNet-50/ImageNet images/sec/chip + MFU (BASELINE.json metric).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N}

The reference publishes no numbers (BASELINE.json ``published: {}``), so
``vs_baseline`` reports achieved MFU / 0.55 — the north star's MFU target —
which is hardware-normalized and therefore comparable across chip types.

Measures the compiled train step on device-resident synthetic batches
(input pipeline excluded, as a synthetic-data reference run would). The
``--steps`` chained steps run inside ONE compiled ``lax.scan`` launch: steps
stay truly sequential (each consumes the previous state; per-step losses are
returned so nothing dead-code-eliminates), while the host's per-launch
dispatch cost is paid once instead of per step (not measured on the current
machine). This is the device-throughput number MFU is defined over.

A measurement path: it needs a TPU and fails without one (``require_chip``)
— a CPU run yields no device metric.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time


def require_chip():
    """Fail unless jax sees a TPU: nothing here may be measured on the CPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip and found platform "
            f"{dev.platform!r} ({dev.device_kind}); run it on a TPU")


def make_synthetic_batch(bundle, global_batch, image_size, seq_len, num_classes):
    import numpy as np

    rng = np.random.RandomState(0)
    if bundle.task == "lm":
        vocab = getattr(bundle.module, "vocab_size", 50257)
        toks = rng.randint(0, vocab, (global_batch, seq_len + 1)).astype(np.int32)
        return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    return {
        "image": rng.randn(global_batch, image_size, image_size, 3).astype(np.float32),
        "label": (np.arange(global_batch) % num_classes).astype(np.int32),
    }


def setup_step(model_name: str = "resnet50", image_size: int = 224,
               per_chip_batch: int = 128, precision: str = "bf16",
               seq_len: int = 1024, strategy: str | None = None,
               mesh_spec: dict | None = None, remat: bool = False,
               devices=None, attn_impl: str = "auto",
               moe_capacity_factor: float = 1.25,
               moe_top_k: int = 2, moe_dispatch_impl: str = "gather",
               moe_combine_dtype: str = "fp32",
               moe_router_dtype: str = "fp32",
               moe_router_impl: str = "reference",
               moe_ep_dispatch: str = "replicated",
               moe_ep_overlap_chunks: int = 2,
               remat_policy: str = "nothing", telemetry: bool = False):
    """Build (mesh, state, step_fn, device batch, bundle) exactly as the
    benchmark measures them — shared by bench() and benchmarks/profile_step.py
    so profiles describe the same program the headline numbers time."""
    from pytorch_distributed_training_example_tpu.core import (
        mesh as mesh_lib, optim, precision as precision_lib, train_loop)
    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.parallel import sharding as sharding_lib
    from pytorch_distributed_training_example_tpu.utils.config import from_preset

    mesh = mesh_lib.build_mesh(mesh_spec or {"data": -1}, devices=devices)
    global_batch = per_chip_batch * mesh_lib.dp_size(mesh)
    cfg = from_preset("resnet50_imagenet", global_batch_size=global_batch,
                      precision=precision)
    strategy = strategy or ("fsdp" if "llama" in model_name or "gpt" in model_name
                            else cfg.strategy)

    policy = precision_lib.get_policy(cfg.precision)
    bundle = registry.create_model(model_name, num_classes=cfg.num_classes,
                                   image_size=image_size, seq_len=seq_len,
                                   dtype=policy.compute_dtype,
                                   param_dtype=policy.param_dtype, remat=remat,
                                   remat_policy=remat_policy,
                                   attn_impl=attn_impl,
                                   moe_capacity_factor=moe_capacity_factor,
                                   moe_top_k=moe_top_k,
                                   moe_dispatch_impl=moe_dispatch_impl,
                                   moe_combine_dtype=moe_combine_dtype,
                                   moe_router_dtype=moe_router_dtype,
                                   moe_router_impl=moe_router_impl,
                                   moe_ep_dispatch=moe_ep_dispatch,
                                   moe_ep_overlap_chunks=moe_ep_overlap_chunks,
                                   logits_dtype=policy.logits_dtype)
    tx, _ = optim.build_optimizer(cfg, steps_per_epoch=1000)
    rules = sharding_lib.strategy_rules(strategy, bundle.rules)
    state = train_loop.create_train_state(bundle.module, tx,
                                          bundle.input_template, mesh, rules,
                                          seed=0)
    task = train_loop.get_task(bundle.task)
    step = train_loop.make_train_step(task, health=telemetry)

    batch = make_synthetic_batch(bundle, global_batch, image_size, seq_len,
                                 cfg.num_classes)
    from pytorch_distributed_training_example_tpu.data import prefetch
    batch = prefetch.shard_batch(batch, mesh_lib.batch_sharding(mesh))
    return {"mesh": mesh, "state": state, "step": step, "batch": batch,
            "bundle": bundle, "cfg": cfg, "strategy": strategy,
            "global_batch": global_batch}


def bench(model_name: str = "resnet50", image_size: int = 224,
          per_chip_batch: int = 128, steps: int = 200, warmup: int = 10,
          precision: str = "bf16", quiet: bool = True, seq_len: int = 1024,
          strategy: str | None = None, mesh_spec: dict | None = None,
          remat: bool = False, devices=None, attn_impl: str = "auto",
          moe_capacity_factor: float = 1.25, moe_top_k: int = 2,
          moe_dispatch_impl: str = "gather", moe_combine_dtype: str = "fp32",
          moe_router_dtype: str = "fp32", moe_router_impl: str = "reference",
          moe_ep_dispatch: str = "replicated",
          moe_ep_overlap_chunks: int = 2,
          remat_policy: str = "nothing", telemetry: bool = False,
          fleet_obs: bool = False):
    import jax
    import numpy as np

    from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
    from pytorch_distributed_training_example_tpu.utils import metrics as metrics_lib

    require_chip()
    su = setup_step(model_name, image_size, per_chip_batch, precision, seq_len,
                    strategy, mesh_spec, remat, devices, attn_impl,
                    moe_capacity_factor=moe_capacity_factor,
                    moe_top_k=moe_top_k, moe_dispatch_impl=moe_dispatch_impl,
                    moe_combine_dtype=moe_combine_dtype,
                    moe_router_dtype=moe_router_dtype,
                    moe_router_impl=moe_router_impl,
                    moe_ep_dispatch=moe_ep_dispatch,
                    moe_ep_overlap_chunks=moe_ep_overlap_chunks,
                    remat_policy=remat_policy, telemetry=telemetry)
    mesh, state, step, batch, bundle = (su["mesh"], su["state"], su["step"],
                                        su["batch"], su["bundle"])
    strategy, global_batch = su["strategy"], su["global_batch"]
    n_chips = mesh.size

    # Donate the state like the real trainer does (core/trainer.py
    # donate_argnums=0): without it the scan holds input AND output state
    # resident, which alone put the 520M-param MoE row out of HBM.
    @functools.partial(jax.jit, donate_argnums=0)
    def run_steps(state, batch):
        def body(s, _):
            s, metrics = step(s, batch)
            # With telemetry on, return the WHOLE metrics dict: returning
            # only the loss would let XLA dead-code-eliminate the health
            # pack, and the "telemetry overhead" measurement would time
            # nothing. All entries are scalars, so the stacked output is
            # a few KB either way.
            return s, (metrics if telemetry else metrics["loss"])
        return jax.lax.scan(body, state, None, length=steps)

    def fetch(out):
        # Force execution (and a host round-trip, like the trainer's
        # log_every device_get). With telemetry, `out` is the full metrics
        # dict — fetching all of it keeps the health pack live.
        return {k: np.asarray(v) for k, v in out.items()} if telemetry \
            else np.asarray(out)

    # Fleet-observability overhead mode (--fleet-obs): run the EXACT host-side
    # per-step work the trainer adds for utils/fleetobs.py — flight-recorder
    # ring append, buffered step-row write, straggler-monitor median check —
    # inside the timed region, once per scanned step, with a live /metrics
    # HTTP server scrape-able throughout. The step_ms delta vs a plain run is
    # the measured fleet-layer tax (BASELINE.md; expected ~0: the ring is a
    # deque append and the writer batches 32 rows per syscall).
    fleet = None
    if fleet_obs:
        import tempfile

        from pytorch_distributed_training_example_tpu.utils import fleetobs

        fdir = tempfile.mkdtemp(prefix="bench_fleetobs_")
        fleet = {
            "server": fleetobs.MetricsServer(port=0).start(),
            "flight": fleetobs.FlightRecorder(256),
            "monitor": fleetobs.StragglerMonitor(),
            "writer": fleetobs.StepRowWriter(fdir, rank=0, attempt=1,
                                             meta={"bench": model_name}),
            "dir": fdir, "gstep": 0, "host_s": float("inf"),
        }

    def fleet_step_work(rep_s: float) -> float:
        """The trainer's per-step fleetobs host work, repeated ``steps``
        times (the scan ran that many device steps); returns seconds spent.
        Per-rep (= the trainer's log cadence) it also refreshes the gauges
        behind the live endpoint and the atomic progress.json."""
        from pytorch_distributed_training_example_tpu.utils import fleetobs

        per_step = rep_s / steps
        f0 = time.perf_counter()
        for _ in range(steps):
            g = fleet["gstep"]
            fleet["gstep"] = g + 1
            row = {"total_s": per_step, "input_wait_s": 0.0,
                   "compute_s": per_step, "checkpoint_s": 0.0}
            fleet["flight"].record_timing(g, **row)
            fleet["writer"].add({"step": g, **row})
            fleet["monitor"].observe(g, total_s=per_step, input_wait_s=0.0)
        fleet["server"].update(step=fleet["gstep"], step_time_s=per_step)
        fleetobs.write_progress(fleet["dir"],
                               {"step": fleet["gstep"], "status": "bench"})
        return time.perf_counter() - f0

    with mesh_lib.use_mesh(mesh):
        compiled = run_steps.lower(state, batch).compile()
        state, out = compiled(state, batch)  # warm (first run pays setup)
        fetch(out)
        dt = float("inf")
        for _ in range(max(warmup // max(steps, 1), 2)):
            t0 = time.perf_counter()
            state, out = compiled(state, batch)
            fetch(out)  # forces execution; per-step losses are real
            if fleet is not None:
                fleet["host_s"] = min(
                    fleet["host_s"],
                    fleet_step_work(time.perf_counter() - t0))
            dt = min(dt, time.perf_counter() - t0)
    if fleet is not None:
        import shutil

        fleet["writer"].flush()
        fleet["server"].stop()
        shutil.rmtree(fleet["dir"], ignore_errors=True)
    try:
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, list):  # XLA:CPU returns [dict], TPU a dict
            ca = ca[0] if ca else {}
    except Exception:
        ca = {}

    examples_per_sec = global_batch * steps / dt
    per_chip = examples_per_sec / n_chips
    mfu = metrics_lib.mfu(per_chip, bundle.fwd_flops_per_example)
    unit = f"{bundle.examples_unit}/sec/chip"

    # Roofline placement from XLA's own cost model: is this program compute-
    # or HBM-bound on this chip, and how close to the bandwidth peak does it
    # run? (SURVEY.md §6; the ResNet-50/v5e step measures ~95% of peak HBM
    # BW at arithmetic intensity ~70 flops/byte vs a ~240 ridge point.)
    roofline = {}
    step_s = dt / steps
    if ca.get("bytes accessed") and ca.get("flops"):
        # XLA's cost model counts a lax.scan body ONCE regardless of trip
        # count (verified: the 1-step and 10-step lowerings of this program
        # both report flops 3.06e12, bytes 4.5e10), and reports PER-DEVICE
        # (post-GSPMD-partitioning) numbers — so these are already per-step,
        # per-chip.
        bytes_step = ca["bytes accessed"]
        flops_step = ca["flops"]
        peak_bw = metrics_lib.peak_hbm_gbps()
        intensity = flops_step / bytes_step
        ridge = metrics_lib.peak_flops_per_chip() / (peak_bw * 1e9)
        # "bytes accessed" counts LOGICAL operand bytes; fused reads are
        # double-counted, so bytes/time is an UPPER BOUND on real HBM
        # traffic rate and can exceed the physical peak. Name the field for
        # what it is and carry the source tag, so the artifact is
        # self-describing (ADVICE r2 / VERDICT r2 #6).
        modeled_gbps = bytes_step / step_s / 1e9
        roofline = {
            "hbm_bytes_per_step": round(bytes_step / 1e9, 3),
            "bytes_source": "xla_cost_model_upper_bound",
            "modeled_hbm_gbps": round(modeled_gbps, 1),
            "modeled_bw_fraction_of_peak": round(
                min(modeled_gbps / peak_bw, 1.0), 3),
            "peak_hbm_gbps": peak_bw,
            "xla_flops_per_step": round(flops_step / 1e12, 3),
            "arithmetic_intensity": round(intensity, 1),
            "ridge_intensity": round(ridge, 1),
            "bound": "hbm" if intensity < ridge else "compute",
        }
    if not quiet:
        print(f"# {n_chips} chip(s) ({jax.devices()[0].device_kind}), "
              f"global batch {global_batch}, {dt/steps*1e3:.1f} ms/step, "
              f"mfu {100*mfu:.1f}%", file=sys.stderr)
    workload = "imagenet" if bundle.task == "classification" else f"lm{seq_len}"
    return {
        "metric": f"{model_name}_{workload}_train_throughput",
        "value": round(per_chip, 2),
        "unit": unit,
        "vs_baseline": round(mfu / 0.55, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "chips": n_chips,
            "device": jax.devices()[0].device_kind,
            "global_batch": global_batch,
            "step_ms": round(dt / steps * 1e3, 2),
            "precision": precision,
            "strategy": strategy,
            "attn_impl": attn_impl,
            **({"telemetry": True} if telemetry else {}),
            **({"fleet_obs": True,
                "fleetobs_host_us_per_step": round(
                    fleet["host_s"] / steps * 1e6, 2)}
               if fleet is not None else {}),
            **({"moe_dispatch_impl": moe_dispatch_impl,
                "moe_top_k": moe_top_k,
                "moe_combine_dtype": moe_combine_dtype,
                "moe_router_dtype": moe_router_dtype,
                "moe_router_impl": moe_router_impl,
                "moe_ep_dispatch": moe_ep_dispatch,
                "moe_ep_overlap_chunks": moe_ep_overlap_chunks,
                "moe_capacity_factor": moe_capacity_factor}
               if "moe" in model_name else {}),
            **({"remat_policy": remat_policy}
               if remat_policy != "nothing" else {}),
            **({"roofline": roofline} if roofline else {}),
        },
    }


def _synthetic_jpeg_tree(root: str, num_images: int = 256, classes: int = 8,
                         size=(500, 375)) -> str:
    """Write an ImageNet-shaped JPEG tree (typical ~500x375 images) once."""
    import os

    import numpy as np
    from PIL import Image

    marker = os.path.join(root, f".complete_{num_images}_{size[0]}")
    if os.path.exists(marker):
        return root
    rng = np.random.default_rng(0)
    w, h = size
    for i in range(num_images):
        cdir = os.path.join(root, f"class_{i % classes:03d}")
        os.makedirs(cdir, exist_ok=True)
        yy, xx = np.mgrid[0:h, 0:w]
        base = np.stack([(xx + i * 7) % 256, (yy + i * 13) % 256,
                         np.full_like(xx, (i * 29) % 256)], -1)
        arr = np.clip(base + rng.normal(0, 8, base.shape), 0, 255).astype("uint8")
        Image.fromarray(arr).save(os.path.join(cdir, f"img_{i:05d}.jpg"),
                                  quality=90)
    open(marker, "w").close()
    return root


def bench_input(data_path: str | None, image_size: int = 224,
                batch_size: int = 128, batches: int = 8, workers: int = 8,
                native: bool = True):
    """Input pipeline alone: decode+augment+collate images/sec on this host."""
    import os

    from pytorch_distributed_training_example_tpu.data import (
        datasets as ds_lib, loader as loader_lib, native_loader,
        sampler as sampler_lib)

    if not data_path:
        # Cover the full measured run: with only ~2 batches on disk, the
        # prefetcher would decode everything during warmup and the timed
        # loop would measure buffer copies, not decode throughput.
        data_path = _synthetic_jpeg_tree(
            "/tmp/bench_jpeg_tree",
            num_images=max(256, (batches + 1) * batch_size))
    ds = ds_lib.build_dataset("imagenet", data_path, train=True,
                              image_size=image_size)
    n_batches = min(batches, len(ds) // batch_size)
    if n_batches < 2:
        raise ValueError(
            f"dataset at {data_path!r} has {len(ds)} images; need at least "
            f"2*batch_size={2 * batch_size} to measure input throughput")
    sampler = sampler_lib.ShardedSampler(len(ds), shuffle=True, drop_last=True)
    dl = loader_lib.build_image_loader(ds, sampler, batch_size,
                                       workers=workers, native=native)
    use_native = isinstance(dl, native_loader.NativeDataLoader)
    it = iter(dl)
    next(it)  # warm: thread spin-up, first-touch page faults
    t0 = time.perf_counter()
    n = 0
    for b in it:
        n += len(b["label"])
        if n >= (n_batches - 1) * batch_size:
            break
    dt = time.perf_counter() - t0
    out = {"input_images_per_sec": round(n / dt, 1),
           "input_loader": "native_jpeg" if use_native else "python",
           "input_workers": workers,
           "host_cpus": os.cpu_count()}
    if use_native:
        out["input_decode_errors"] = dl.engine.decode_errors()
    return out


def bench_e2e(data_path: str | None, image_size: int = 224,
              per_chip_batch: int = 128, steps: int = 8,
              precision: str = "bf16", workers: int = 8):
    """End-to-end: real JPEG loader -> device_put -> compiled train step.

    The number SURVEY.md §7(a) asks for: throughput INCLUDING the input
    pipeline, vs the device-only number the headline measures.
    """
    import jax

    require_chip()
    from pytorch_distributed_training_example_tpu.core import (
        mesh as mesh_lib, optim, precision as precision_lib, train_loop)
    from pytorch_distributed_training_example_tpu.data import (
        datasets as ds_lib, loader as loader_lib, prefetch,
        sampler as sampler_lib)
    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.parallel import (
        sharding as sharding_lib)
    from pytorch_distributed_training_example_tpu.utils.config import from_preset

    mesh = mesh_lib.build_mesh({"data": -1})
    global_batch = per_chip_batch * mesh_lib.dp_size(mesh)
    if not data_path:
        data_path = _synthetic_jpeg_tree("/tmp/bench_jpeg_tree",
                                         num_images=max(256, 2 * global_batch))
    cfg = from_preset("resnet50_imagenet", global_batch_size=global_batch,
                      precision=precision)
    policy = precision_lib.get_policy(cfg.precision)
    bundle = registry.create_model("resnet50", num_classes=cfg.num_classes,
                                   image_size=image_size,
                                   dtype=policy.compute_dtype,
                                   param_dtype=policy.param_dtype)
    tx, _ = optim.build_optimizer(cfg, steps_per_epoch=1000)
    rules = sharding_lib.strategy_rules("dp", bundle.rules)
    state = train_loop.create_train_state(bundle.module, tx,
                                          bundle.input_template, mesh, rules,
                                          seed=0)
    step = jax.jit(train_loop.make_train_step(train_loop.get_task(bundle.task)),
                   donate_argnums=0)

    ds = ds_lib.build_dataset("imagenet", data_path, train=True,
                              image_size=image_size)
    if len(ds) < global_batch:
        raise ValueError(
            f"dataset at {data_path!r} has {len(ds)} images < global batch "
            f"{global_batch}; point --data-path at a larger tree")
    sampler = sampler_lib.ShardedSampler(len(ds), shuffle=True, drop_last=True)
    dl = loader_lib.build_image_loader(ds, sampler, global_batch,
                                       workers=workers)
    total = steps + 2
    t0 = None
    n = 0
    done = 0
    with mesh_lib.use_mesh(mesh):
        while done < total:
            dl.set_epoch(done)  # cycle epochs if the tree is small
            for batch in prefetch.device_prefetch(
                    dl, mesh_lib.batch_sharding(mesh)):
                state, metrics = step(state, batch)
                done += 1
                if done == 2:  # past compile + warmup
                    jax.tree.map(lambda x: x.block_until_ready(), metrics)
                    t0 = time.perf_counter()
                elif done > 2:
                    n += global_batch
                if done >= total:
                    break
        jax.tree.map(lambda x: x.block_until_ready(), metrics)
    dt = time.perf_counter() - t0

    # Measured host->device bandwidth for one batch (device_put + forced
    # consumption — transfers may complete lazily); reporting it makes the
    # e2e figure interpretable.
    import numpy as np

    probe = np.zeros((global_batch, image_size, image_size, 3), np.float32)
    consume = jax.jit(lambda b: b["x"].sum())
    with mesh_lib.use_mesh(mesh):
        # Same-shape warmup (jit caches per shape) on a distinct array, so
        # the timed run measures pure transfer, not compilation.
        warm = prefetch.shard_batch(
            {"x": np.ones_like(probe)}, mesh_lib.batch_sharding(mesh))
        consume(warm).block_until_ready()
        t0 = time.perf_counter()
        dev = prefetch.shard_batch({"x": probe}, mesh_lib.batch_sharding(mesh))
        consume(dev).block_until_ready()
        h2d = probe.nbytes / (time.perf_counter() - t0)
    return {"e2e_images_per_sec_per_chip": round(n / dt / mesh.size, 1),
            "e2e_global_batch": global_batch,
            "e2e_h2d_gbytes_per_sec": round(h2d / 1e9, 3)}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--per-chip-batch", type=int, default=128)
    p.add_argument("--steps", type=int, default=200,
                   help="scan length; long scans amortize the host's fixed "
                        "per-launch dispatch cost")
    p.add_argument("--warmup", type=int, default=10)
    p.add_argument("--precision", default="bf16")
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--strategy", default=None)
    p.add_argument("--remat", action="store_true")
    p.add_argument("--remat-policy", default="nothing",
                   choices=["nothing", "dots", "dots_no_batch", "attn_out"],
                   help="checkpoint policy under --remat (Llama family): "
                        "A/B the save-list for the backward recompute")
    p.add_argument("--moe-top-k", type=int, default=2,
                   help="experts routed per token (llama_moe family)")
    p.add_argument("--moe-dispatch", default="gather",
                   choices=["sort", "gather", "einsum", "dropless"],
                   dest="moe_dispatch",
                   help="MoE dispatch formulation (parallel/moe.py)")
    p.add_argument("--moe-router-dtype", default="fp32",
                   choices=["fp32", "bf16"], dest="moe_router_dtype",
                   help="router logits-matmul precision (fp32 = ST-MoE "
                        "exact default; bf16 keeps fp32 accumulation and "
                        "softmax/top-k)")
    p.add_argument("--moe-router-impl", default="reference",
                   choices=["reference", "fused"], dest="moe_router_impl",
                   help="router softmax/top-k/gates: reference XLA chain or "
                        "the fused single-pass Pallas kernel "
                        "(ops/fused_router.py)")
    p.add_argument("--moe-combine", default="fp32", choices=["fp32", "bf16"],
                   help="combine-einsum precision (router stays fp32)")
    p.add_argument("--moe-ep-dispatch", default="replicated",
                   choices=["replicated", "a2a", "a2a_overlap"],
                   dest="moe_ep_dispatch",
                   help="dropless EP transport: replicated weights, "
                        "all-to-all token shards, or chunked a2a/gmm "
                        "overlap (parallel/moe.py)")
    p.add_argument("--moe-ep-overlap-chunks", type=int, default=2,
                   dest="moe_ep_overlap_chunks",
                   help="a2a_overlap double-buffer windows over the token dim")
    p.add_argument("--moe-capacity-factor", type=float, default=1.25,
                   help="MoE expert capacity factor (llama_moe rows)")
    p.add_argument("--attn-impl", default="auto",
                   choices=["auto", "xla", "flash", "ring", "ring_zigzag",
                            "ring_allgather", "ulysses"])
    p.add_argument("--telemetry", action="store_true",
                   help="compile the on-device health pack into the step "
                        "(utils/telemetry.py) — measures its overhead vs "
                        "the default row")
    p.add_argument("--fleet-obs", action="store_true", dest="fleet_obs",
                   help="run the fleet-observability host work "
                        "(utils/fleetobs.py flight recorder + step rows + "
                        "straggler monitor + live /metrics endpoint) inside "
                        "the timed loop — measures its overhead vs the "
                        "default row")
    p.add_argument("--no-measured-roofline", action="store_true",
                   help="skip the xplane-measured roofline pass (resnet50 "
                        "headline only; ~2 min extra)")
    p.add_argument("--include-input", action="store_true",
                   help="also measure loader-only and end-to-end throughput "
                        "over a real JPEG tree (synthetic if no --data-path)")
    p.add_argument("--no-lm", action="store_true",
                   help="skip the compute-bound GPT-2 companion row")
    p.add_argument("--data-path", default=None)
    p.add_argument("--workers", type=int, default=8)
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args(argv)
    require_chip()
    from pytorch_distributed_training_example_tpu.core import xcache

    xcache.place_compile_cache()
    result = bench(args.model, args.image_size, args.per_chip_batch,
                   args.steps, args.warmup, args.precision,
                   quiet=not args.verbose, seq_len=args.seq_len,
                   strategy=args.strategy, remat=args.remat,
                   attn_impl=args.attn_impl,
                   moe_capacity_factor=args.moe_capacity_factor,
                   moe_top_k=args.moe_top_k,
                   moe_dispatch_impl=args.moe_dispatch,
                   moe_combine_dtype=args.moe_combine,
                   moe_router_dtype=args.moe_router_dtype,
                   moe_router_impl=args.moe_router_impl,
                   moe_ep_dispatch=args.moe_ep_dispatch,
                   moe_ep_overlap_chunks=args.moe_ep_overlap_chunks,
                   remat_policy=args.remat_policy, telemetry=args.telemetry,
                   fleet_obs=args.fleet_obs)
    if (args.model == "resnet50" and not args.no_measured_roofline):
        # Measured-bytes roofline (VERDICT r3 #3): per-executed-op buffer
        # traffic from the scheduled HLO joined with xplane durations —
        # replaces the cost-model upper bound that could exceed physical
        # peak (the r3 936>819 GB/s inconsistency).
        import os
        import sys as _sys
        _sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
        from profile_step import profile as _profile

        prof = _profile(args.model, image_size=args.image_size,
                        per_chip_batch=args.per_chip_batch,
                        precision=args.precision, steps=3,
                        strategy=args.strategy, remat=args.remat,
                        attn_impl=args.attn_impl)
        result["extra"]["roofline_measured"] = prof["roofline_measured"]
    if args.model == "resnet50" and not args.no_lm:
        # The ResNet-50 step is HBM-bound on small chips (see roofline
        # extras); record the compute-bound LM headline alongside it.
        # per-chip batch 24: r4 sweep peak with the chunked-bwd flash
        # kernels (63.6% MFU vs 62.4% at the r3 batch of 16).
        lm = bench("gpt2", per_chip_batch=24, steps=200, warmup=4,
                   precision=args.precision, seq_len=1024, quiet=True)
        result["extra"]["lm"] = {
            "metric": lm["metric"], "value": lm["value"],
            "unit": lm["unit"], "mfu": lm["extra"]["mfu"],
            "step_ms": lm["extra"]["step_ms"],
            "global_batch": lm["extra"]["global_batch"],
        }
    if args.include_input:
        result["extra"].update(bench_input(
            args.data_path, args.image_size, args.per_chip_batch,
            workers=args.workers))
        result["extra"].update(bench_e2e(
            args.data_path, args.image_size, args.per_chip_batch,
            precision=args.precision, workers=args.workers))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

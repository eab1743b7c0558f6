#!/usr/bin/env python
"""Llama-3 8B feasibility proof (VERDICT r2 #4; BASELINE.json configs[4]).

AOT-lowers and compiles the FULL fsdp+remat train step for ``llama3_8b`` on a
virtual CPU mesh (16 and 32 devices) with ABSTRACT inputs — no 32 GB of
parameters is ever materialized — and records the compiled executable's own
``memory_analysis()`` per-device byte counts against the v5p HBM budget
(95 GB/chip). This is the scaled-up version of the pattern
``tests/test_transformers.py::test_sp_reduces_activation_memory`` uses.

Caveat recorded in the artifact: the executable is compiled by the CPU
backend, so temp-buffer sizes reflect XLA:CPU's buffer assignment, not
XLA:TPU's (which fuses more aggressively); argument/output sizes (params,
optimizer state, batch) are backend-independent sharded-shape facts.

    python benchmarks/feasibility_8b.py [--out FEASIBILITY_8B.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V5P_HBM_BYTES = 95e9
MAX_DEVICES = 32


def analyze(n_devices: int, seq_len: int, per_device_batch: int = 1,
            devices=None, mesh_spec=None, attn_impl="auto", remat=True):
    """One feasibility row: AOT-compile the 8B step and read its memory.

    ``mesh_spec`` overrides the default ``{"fsdp": n_devices}`` mesh for
    composed-topology rows (r22) — e.g. ``{"fsdp": 8, "context": 4}`` with
    ``attn_impl="ring"`` models sequence parallelism, where per-device
    activation temps scale ~1/seq and the ``[S, S]`` score block is never
    materialized."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.core import (
        mesh as mesh_lib, optim, train_loop)
    from pytorch_distributed_training_example_tpu.core.train_state import TrainState
    from pytorch_distributed_training_example_tpu.models import llama as llama_lib
    from pytorch_distributed_training_example_tpu.parallel import (
        sharding as sharding_lib)
    from pytorch_distributed_training_example_tpu.utils.config import Config

    if devices is None:
        devices = jax.devices("cpu")[:n_devices]
    mesh = mesh_lib.build_mesh(mesh_spec or {"fsdp": n_devices},
                               devices=devices)
    module = llama_lib.llama3_8b(dtype=jnp.bfloat16, param_dtype=jnp.float32,
                                 remat=remat, scan_layers=True,
                                 attn_impl=attn_impl,
                                 max_seq_len=seq_len)
    n_params = llama_lib.num_params(module)
    tx, _ = optim.build_optimizer(
        Config(lr=3e-4, optimizer="adamw", weight_decay=0.1),
        steps_per_epoch=1000)
    rules = sharding_lib.strategy_rules("fsdp", llama_lib.TP_RULES)

    # Batch rows live on the data/fsdp axes only; seq/pp/ep replicate them.
    B = per_device_batch * mesh_lib.dp_size(mesh)
    tokens = jax.ShapeDtypeStruct((B, seq_len), jnp.int32)

    def init_fn(rng):
        variables = module.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                                train=False)
        return TrainState.create(apply_fn=module.apply,
                                 params=variables["params"], tx=tx,
                                 rng=jax.random.PRNGKey(0))

    t0 = time.perf_counter()
    state_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    shardings = train_loop.state_shardings(state_shape, mesh, rules)
    abstract_state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_shape, shardings)
    batch_sh = mesh_lib.batch_sharding(mesh)
    abstract_batch = {
        "tokens": jax.ShapeDtypeStruct((B, seq_len), jnp.int32,
                                       sharding=batch_sh),
        "targets": jax.ShapeDtypeStruct((B, seq_len), jnp.int32,
                                        sharding=batch_sh),
    }
    step = jax.jit(train_loop.make_train_step(train_loop.get_task("lm")),
                   donate_argnums=0)
    with mesh_lib.use_mesh(mesh):
        lowered = step.lower(abstract_state, abstract_batch)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    arg_b = ma.argument_size_in_bytes
    out_b = ma.output_size_in_bytes
    temp_b = ma.temp_size_in_bytes
    alias_b = ma.alias_size_in_bytes
    # Donation aliases outputs onto arguments, so resident = args + temps
    # (outputs overlap args); without donation it would be args+outs+temps.
    resident = arg_b + temp_b
    row_head = {"fsdp_devices": n_devices}
    if mesh_spec:
        row_head = {"mesh": dict(mesh_spec), "attn_impl": attn_impl,
                    "remat": remat}
    return {
        **row_head,
        "seq_len": seq_len,
        "global_batch": B,
        "n_params": n_params,
        "per_device": {
            "argument_bytes": arg_b,
            "output_bytes": out_b,
            "alias_bytes": alias_b,
            "temp_bytes": temp_b,
            "resident_bytes": resident,
            "resident_gb": round(resident / 1e9, 2),
        },
        "hbm_budget_gb": V5P_HBM_BYTES / 1e9,
        "fits": resident < V5P_HBM_BYTES,
        "headroom_gb": round((V5P_HBM_BYTES - resident) / 1e9, 2),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    }


def analyze_topology(topo_name: str, seq_len: int):
    """AOT-compile the full v5p program with XLA:TPU via a topology
    description (no v5p hardware needed) — the real buffer assignment for
    the real target, not a CPU approximation (VERDICT r3 missing #2)."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(topo_name)
    devices = list(topo.devices)
    row = analyze(len(devices), seq_len, devices=devices)
    row["compiler"] = f"XLA:TPU AOT topology {topo_name} ({devices[0].device_kind})"
    return row


def calibration_case(seq_len: int = 8192):
    """Same fsdp+remat train-step program at a scale that fits one v5e:
    llama_400m (full Llama block: GQA, RoPE, SwiGLU, RMSNorm; d_model 1024)
    at the 8B preset's own seq_len, batch 1, 1 device. Returns
    memory_analysis() numbers for whichever backend this process runs."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_example_tpu.core import (
        mesh as mesh_lib, optim, train_loop)
    from pytorch_distributed_training_example_tpu.core.train_state import TrainState
    from pytorch_distributed_training_example_tpu.models import llama as llama_lib
    from pytorch_distributed_training_example_tpu.parallel import (
        sharding as sharding_lib)
    from pytorch_distributed_training_example_tpu.utils.config import Config

    mesh = mesh_lib.build_mesh({"fsdp": 1}, devices=jax.devices()[:1])
    module = llama_lib.llama_400m(dtype=jnp.bfloat16, param_dtype=jnp.float32,
                                  remat=True, scan_layers=True,
                                  max_seq_len=seq_len)
    tx, _ = optim.build_optimizer(
        Config(lr=3e-4, optimizer="adamw", weight_decay=0.1),
        steps_per_epoch=1000)
    rules = sharding_lib.strategy_rules("fsdp", llama_lib.TP_RULES)

    def init_fn(rng):
        variables = module.init({"params": rng}, jnp.zeros((1, 8), jnp.int32),
                                train=False)
        return TrainState.create(apply_fn=module.apply,
                                 params=variables["params"], tx=tx,
                                 rng=jax.random.PRNGKey(0))

    state_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    shardings = train_loop.state_shardings(state_shape, mesh, rules)
    abstract_state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_shape, shardings)
    batch_sh = mesh_lib.batch_sharding(mesh)
    abstract_batch = {
        "tokens": jax.ShapeDtypeStruct((1, seq_len), jnp.int32,
                                       sharding=batch_sh),
        "targets": jax.ShapeDtypeStruct((1, seq_len), jnp.int32,
                                        sharding=batch_sh),
    }
    step = jax.jit(train_loop.make_train_step(train_loop.get_task("lm")),
                   donate_argnums=0)
    with mesh_lib.use_mesh(mesh):
        compiled = step.lower(abstract_state, abstract_batch).compile()
    ma = compiled.memory_analysis()
    return {
        "backend": jax.default_backend(),
        "device": jax.devices()[0].device_kind,
        "seq_len": seq_len,
        "argument_bytes": ma.argument_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
    }


def run_calibration(seq_len: int):
    """Compile the calibration case under XLA:CPU and XLA:TPU (separate
    processes — platform choice is process-wide) and report the temp-bytes
    ratio that converts CPU buffer-assignment temps into TPU ones."""
    import subprocess

    rows = {}
    for backend in ("cpu", "tpu"):
        env = dict(os.environ)
        if backend == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
            env["FEAS_FORCE_CPU"] = "1"
        else:
            env.pop("JAX_PLATFORMS", None)
            env.pop("FEAS_FORCE_CPU", None)
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--calibrate-worker", "--seq-len", str(seq_len)],
            capture_output=True, text=True, env=env, timeout=1800)
        if res.returncode != 0:
            rows[backend] = {"error": (res.stderr or res.stdout)[-400:]}
            continue
        rows[backend] = json.loads(res.stdout.strip().splitlines()[-1])
    ratio = None
    if (all("temp_bytes" in rows.get(b, {}) for b in ("cpu", "tpu"))
            and rows["tpu"].get("backend") != "cpu"):
        # On a machine without a TPU the "tpu" worker silently falls back
        # to the CPU backend; a CPU/CPU ratio of ~1.0 must not be stamped
        # onto rows as "tpu_calibrated".
        ratio = rows["tpu"]["temp_bytes"] / max(rows["cpu"]["temp_bytes"], 1)
    return {"case": "llama_400m fsdp=1 remat seq_len=%d batch=1" % seq_len,
            "cpu": rows.get("cpu"), "tpu": rows.get("tpu"),
            "tpu_over_cpu_temp_ratio": round(ratio, 3) if ratio else None}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="FEASIBILITY_8B.json")
    p.add_argument("--seq-len", type=int, default=8192)
    p.add_argument("--no-calibrate", action="store_true",
                   help="skip the XLA:CPU-vs-TPU temp-bytes calibration")
    p.add_argument("--composed", action="store_true",
                   help="add/refresh the composed-topology memory model "
                        "(rows_composed: long-context fsdp x context rows, "
                        "ring vs unsharded) in an EXISTING --out artifact "
                        "without recompiling the base rows")
    p.add_argument("--calibrate-worker", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--topology-worker", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.calibrate_worker:
        print(json.dumps(calibration_case(args.seq_len)))
        return 0
    if args.topology_worker:
        print(json.dumps(analyze_topology(args.topology_worker, args.seq_len)))
        return 0

    if args.composed:
        # Composed-topology memory model (r22): the same 8B program at
        # S=32768 with the context axis in the mesh. The unsharded fsdp-32
        # row is the motivation — its modeled activation temps blow the
        # budget even under remat — and the fsdp x seq ring rows show the
        # ~1/seq per-device temp shrink that puts fsdp=4 x seq=8 under
        # budget once the measured CPU-vs-TPU temp ratio is applied.
        S = 32768
        rows_c = [
            analyze(MAX_DEVICES, S),
            analyze(MAX_DEVICES, S,
                    mesh_spec={"fsdp": 8, "context": 4}, attn_impl="ring"),
            analyze(MAX_DEVICES, S,
                    mesh_spec={"fsdp": 4, "context": 8}, attn_impl="ring"),
        ]
        with open(args.out) as f:
            doc = json.load(f)
        # 8B-scale CPU->TPU temp calibration from the artifact's own
        # matched pairs (rows_tpu_topology vs rows at the same fsdp
        # degree) — the 400m calibration ratio is documented as
        # non-transferable.
        pairs = [
            (t["per_device"]["temp_bytes"], c["per_device"]["temp_bytes"])
            for t in doc.get("rows_tpu_topology", []) if "per_device" in t
            for c in doc.get("rows", [])
            if c.get("fsdp_devices") == t.get("fsdp_devices")]
        ratio_8b = (round(sum(t / c for t, c in pairs) / len(pairs), 3)
                    if pairs else None)
        if ratio_8b:
            for row in rows_c:
                t = row["per_device"]["temp_bytes"] * ratio_8b
                resident = row["per_device"]["argument_bytes"] + t
                row["per_device"]["temp_bytes_tpu_calibrated"] = int(t)
                row["per_device"]["resident_gb_tpu_calibrated"] = round(
                    resident / 1e9, 2)
                row["fits_tpu_calibrated"] = resident < V5P_HBM_BYTES
        doc["rows_composed"] = {
            "_note": (
                "XLA:CPU memory_analysis at seq_len=32768 (same CPU "
                "buffer-assignment caveat as `rows`): the unsharded fsdp "
                "row exceeds the v5p budget on modeled bytes alone — "
                "calibrated OR raw — while ring attention over the "
                "context axis shards activations [B, S/seq, d] and never "
                "materializes the [S, S] score block, shrinking "
                "per-device temps ~1/seq (argument bytes grow as fsdp "
                "shrinks: params shard over fewer devices — the "
                "fsdp-vs-seq split is a real trade, and fsdp=4 x seq=8 "
                "is the first calibrated fit). tpu_calibrated columns "
                "use the 8B-scale temp ratio measured between this "
                "artifact's own XLA:TPU topology rows and their XLA:CPU "
                "twins. Gate lives in check_regression.py --aot-bytes "
                "(aot_seq_shrink)."),
            "tpu_over_cpu_temp_ratio_8b": ratio_8b,
            "rows": rows_c,
        }
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(json.dumps([{k: r[k] for k in ("seq_len", "fits")}
                          | {"mesh": r.get("mesh", {"fsdp": MAX_DEVICES})}
                          | r["per_device"] for r in rows_c]))
        return 0

    rows = [analyze(16, args.seq_len), analyze(32, args.seq_len)]

    # Primary result: real XLA:TPU buffer assignment via AOT topology
    # compiles of the actual v5p targets (v5p-32 = 16 chips = 2x2x4;
    # v5p-64 = 32 chips = 2x4x4), run in a TPU-backend subprocess.
    import subprocess
    topo_rows = []
    for topo in ("v5p:2x2x4", "v5p:2x4x4"):
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "FEAS_FORCE_CPU")}
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--topology-worker",
             topo, "--seq-len", str(args.seq_len)],
            capture_output=True, text=True, env=env, timeout=3600)
        if res.returncode != 0:
            topo_rows.append({"topology": topo,
                              "error": (res.stderr or res.stdout)[-400:]})
        else:
            topo_rows.append(json.loads(res.stdout.strip().splitlines()[-1]))
        print(json.dumps(topo_rows[-1])[:400], file=sys.stderr, flush=True)

    cal = None if args.no_calibrate else run_calibration(args.seq_len)
    topo_ok = [r for r in topo_rows if "per_device" in r]
    out = {
        "model": "llama3_8b",
        "strategy": "fsdp + per-block remat + scan_layers",
        "precision": "bf16 compute / fp32 params / adamw fp32 m+v",
        "memory_source": ("jax compiled.memory_analysis() from XLA:TPU AOT "
                          "topology compiles of the actual v5p targets "
                          "(primary, rows_tpu_topology); XLA:CPU rows kept "
                          "as a cross-check with a measured CPU-vs-TPU "
                          "temp-bytes calibration" if topo_ok else
                          "jax compiled.memory_analysis() on XLA:CPU, "
                          "calibrated against a real XLA:TPU compile at "
                          "v5e scale (topology AOT failed — see "
                          "rows_tpu_topology errors)"),
        "hardware_target": "v5p-32 (95 GB HBM/chip)",
        "rows_tpu_topology": topo_rows,
        "calibration": cal,
        "rows": rows,
    }
    if cal and cal.get("tpu_over_cpu_temp_ratio"):
        r = cal["tpu_over_cpu_temp_ratio"]
        for row in rows:
            t = row["per_device"]["temp_bytes"] * r
            resident = row["per_device"]["argument_bytes"] + t
            row["per_device"]["temp_bytes_tpu_calibrated"] = int(t)
            row["per_device"]["resident_gb_tpu_calibrated"] = round(
                resident / 1e9, 2)
            row["fits_tpu_calibrated"] = resident < V5P_HBM_BYTES
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({
        "rows_tpu_topology": [
            {k: r[k] for k in ("fsdp_devices", "fits")} | r["per_device"]
            if "per_device" in r else r for r in topo_rows],
        "rows_cpu": [{k: r[k] for k in ("fsdp_devices", "fits")}
                     | r["per_device"] for r in rows],
        "calibration_ratio": (cal or {}).get("tpu_over_cpu_temp_ratio"),
        "out": args.out}))
    return 0


if __name__ == "__main__":
    # The TPU calibrate-worker must keep the real backend; everything else
    # (the 16/32-device AOT analysis, the CPU worker) runs on CPU fakes.
    _tpu_worker = ("--calibrate-worker" in sys.argv
                   and not os.environ.get("FEAS_FORCE_CPU"))
    if not _tpu_worker:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={MAX_DEVICES}").strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
    raise SystemExit(main())

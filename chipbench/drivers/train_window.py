"""Driver ``train_window``: the trainer's own loop, warmed up, then measured.

One process that holds the chips. It builds the ``Trainer`` as ``main.py``
does, puts the benchmark's seeded weights into it, and runs
``Trainer.train_epoch`` with two wrappers set on the instance from here: one
around ``train_step`` (times the enqueue, hands each step's loss to a watcher
thread that stamps its completion without blocking the loop) and one around
the batch iterator (times ``next()``, ends the iteration when the window is
over). The first steps are set-up and are also what ``correct`` is read from:
the same compiled step, fed by the same loop, is followed for three steps by
the plain reference once the window has closed and the program's state is
freed. Nothing here knows a cell or a configuration.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.metadata
import json
import math
import os
import queue
import shutil
import statistics
import sys
import tempfile
import threading
import time

from chipbench import compare, weights, xplane

#: The program's own seed stays fixed: its init closes over a key made from
#: it, so every new seed would compile that program again (26 s for GPT-2
#: 124M on the v5e, PERF.md Findings PR 25). The benchmark's weights and
#: rows come from ``--seed``; the program's init is overwritten.
PROGRAM_SEED = 0


def say(**row):
    print(json.dumps(row, default=float), flush=True)


class Compiles:
    """Backend-compile seconds and persistent-cache hits and misses, from
    jax's own monitoring events (as ``chip_smoke._Compiles``)."""

    def __init__(self, jax):
        self.compile_s, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def row(self):
        return {"compile_s": round(self.compile_s, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}


def place_cache(jax):
    """The persistent compilation cache, placed by the program's own helper
    (the one place that sets the directory: ``JAX_COMPILATION_CACHE_DIR`` if
    set, else ``.jax_cache`` inside the checkout) but keeping every program,
    however quick its compile: a run after the first compiles nothing."""
    from pytorch_distributed_training_example_tpu.core import xcache

    path = xcache.place_compile_cache(min_compile_secs=0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _find_field(tree, field):
    """The first optimizer state in a chain that has ``field``."""
    if hasattr(tree, field):
        return getattr(tree, field)
    if isinstance(tree, (tuple, list)):
        for sub in tree:
            found = _find_field(sub, field)
            if found is not None:
                return found
    return None


class Window:
    """The wrappers, the watcher and what they recorded."""

    def __init__(self, jax, trainer, ctx, compiles, key):
        self.jax, self.ctx = jax, ctx
        self.compiles, self.key = compiles, key
        self.seconds = ctx["seconds"]
        self.warmup = max(int(ctx["traffic"]["warmup_steps"]), 3)
        self.rules = ctx["config"]["init"]
        self.jitted, self.shapes = trainer.train_step, None
        self.make_iter = trainer._make_step_iter
        self.enqueued = 0
        self.rows = []            # (kind, t0, t1) host seconds, window only
        self.stamps, self.losses, self.errors = [], [], []
        self.t0 = None            # completion of the last warm-up step
        self.compiles_at_window = None
        self.first = {"batches": []}
        self.trace_dir, self.trace_state, self.trace_span = None, 0, None
        self.annotate = (jax.profiler.TraceAnnotation if ctx["trace"]
                         else lambda name: contextlib.nullcontext())
        self.queue = queue.Queue()
        self.watcher = threading.Thread(target=self._watch, daemon=True)
        self.watcher.start()
        trainer.train_step = self.train_step
        trainer._make_step_iter = self.step_iter

    # -- wrappers ----------------------------------------------------------

    def train_step(self, state, batch):
        t0 = time.perf_counter()
        if self.shapes is None and self.ctx["trace"]:
            self.shapes = self.jax.tree.map(
                lambda x: self.jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
                (state, batch))
        with self.annotate("chipbench.train_step"):
            out = self.jitted(state, batch)  # the trainer's own jitted step
        t1 = time.perf_counter()
        self.enqueued += 1
        n = self.enqueued
        if n > self.warmup:
            if self.compiles_at_window is None:
                self.compiles_at_window = (self.compiles.hits
                                           + self.compiles.misses)
            self.rows.append(("train_step", t0, t1))
        self.queue.put((n, out[1]["loss"]))
        if n <= 3:
            self._capture(n, batch, out[0])
        return out

    def step_iter(self, epoch, start):
        it = self.make_iter(epoch, start)
        try:
            while not self.over():
                self._trace_tick()
                t0 = time.perf_counter()
                with self.annotate("chipbench.next"):
                    try:
                        batch = next(it)
                    except StopIteration:
                        return
                if self.t0 is not None:
                    self.rows.append(("next", t0, time.perf_counter()))
                yield batch
        finally:
            it.close()

    def over(self):
        return (self.t0 is not None
                and time.perf_counter() >= self.t0 + self.seconds)

    def _watch(self):
        while True:
            item = self.queue.get()
            if item is None:
                return
            n, loss = item
            try:
                loss.block_until_ready()
            except Exception as e:  # noqa: BLE001 - a failed step is counted
                self.errors.append(f"step {n}: {type(e).__name__}: {e}")
            now = time.perf_counter()
            self.stamps.append(now)
            self.losses.append(loss)
            if n == self.warmup:
                self.t0 = now

    # -- the first three steps, for the comparison ---------------------------

    def _capture(self, n, batch, state):
        import numpy as np

        jax = self.jax
        self.first["batches"].append(
            {k: np.asarray(v) for k, v in batch.items()})
        norms = lambda tree: {k: jax.numpy.sqrt(jax.numpy.sum(jax.numpy.square(
            v.astype(jax.numpy.float32)))) for k, v in
            weights.flatten(tree).items()}
        if n == 1:
            field = self.ctx["config"]["optimizer"]["first_moment_field"]
            moment = _find_field(state.opt_state, field)
            if moment is None:
                raise RuntimeError(f"no optimizer state has {field!r}")
            self.first["moment"] = jax.jit(norms)(moment)
        if n == 3:
            rules = self.rules
            self.first["dparam"] = jax.jit(lambda p, key: norms(jax.tree.map(
                lambda a, b: a - b, p, weights.make_like(p, rules, key))))(
                    state.params, self.key)

    # -- the trace: the last seconds of the window ------------------------------

    def _trace_tick(self):
        """Start the trace ``trace_seconds`` before the window ends. It is
        stopped after the loop has left (``close``): stopping takes seconds
        (25 s with four loader threads busy, PERF.md Findings PR 25), and
        inside the window that would be a stall of the benchmark's making."""
        if not self.ctx["trace"] or self.t0 is None or self.trace_state:
            return
        length = float(self.ctx["traffic"].get("trace_seconds", 3.0))
        now = time.perf_counter()
        if now >= self.t0 + self.seconds - length:
            jax = self.jax
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            # level 1 keeps the benchmark's own annotations; level 2 adds
            # every futex of every loader thread (345 MB and a 50 s stop)
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.trace_state, self.trace_span = 1, (now, None)

    def stop_trace(self):
        if self.trace_state == 1:
            stop = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.trace_span = (self.trace_span[0], stop,
                               time.perf_counter() - stop)
            self.trace_state = 2

    def compiled_step(self):
        """The step program once more, ahead of time from the shapes of its
        first call, for the compiler's byte counts and the program's text: a
        traced run only, after the window, and from the persistent cache."""
        return self.jitted.lower(*self.shapes).compile()

    def close(self):
        self.queue.put(None)
        self.watcher.join()   # every enqueued step has completed
        self.stop_trace()


def _quantile(values, q):
    values = sorted(values)
    at = q * (len(values) - 1)
    lo = int(math.floor(at))
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (at - lo)


def _breakdown(trace):
    """The device operations that took most time, and the longest idle gaps
    on device 0 by what the host's loop was inside."""
    first = xplane.first_device(trace)
    if first is None:
        return None
    dev, lo, hi, _ = first
    by_op = {}
    for e in dev.ops:
        if lo <= e.start and e.end <= hi:
            name = e.name.split(".")[0]
            by_op[name] = by_op.get(name, 0) + e.end - e.start
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    by_host = {}
    for a, b in xplane.gaps(xplane.union(xplane.spans(dev.ops)), lo, hi):
        mid = (a + b) // 2
        inside = next((h.name[len(xplane.HOST_PREFIX):] for h in trace.host
                       if h.start <= mid <= h.end), "other")
        by_host[inside] = by_host.get(inside, 0) + b - a
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[n, t / 1e9] for n, t in idle]}


def measure(ctx, peaks):
    """A run from the Trainer's construction to the last line's object,
    whatever the device: ``run`` decides whether the device counts."""
    import jax

    from pytorch_distributed_training_example_tpu.core.trainer import Trainer
    from pytorch_distributed_training_example_tpu.utils.config import (
        from_preset)

    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    devs = jax.devices()
    compiles = Compiles(jax)
    cfg = from_preset(config["preset"], **{
        **config["overrides"], **traffic.get("overrides", {}),
        "seed": PROGRAM_SEED})
    trainer = Trainer(cfg)
    rows = ctx["load_module"](ctx["search"], "rows", traffic["data"]["kind"])
    trainer.train_loader.dataset = rows.Rows(
        traffic["data"], ctx["seed"], len(trainer.train_loader.dataset))
    key = weights.seed_key(ctx["seed"])
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trainer.state.params)
    shapes = weights.flatten(abstract)
    rules = config["init"]
    params = jax.jit(
        lambda k: weights.make_like(abstract, rules, k),
        out_shardings=jax.tree.map(lambda x: x.sharding,
                                   trainer.state.params))(key)
    trainer.state = trainer.state.replace(params=params)
    del params
    global_batch = cfg.global_batch_size
    say(row="built", since_start_s=time.perf_counter() - ctx["t_start"],
        steps_per_epoch=trainer.steps_per_epoch, global_batch=global_batch,
        mesh={k: v for k, v in trainer.mesh.shape.items() if v > 1},
        loader=type(trainer.train_loader).__name__, **compiles.row())

    window = Window(jax, trainer, ctx, compiles, key)
    epoch = 0
    try:
        while not window.over():
            trainer.train_epoch(epoch)
            epoch += 1
    finally:
        window.close()

    # -- the window's numbers -----------------------------------------------
    warm = window.warmup
    stamps = window.stamps
    t0 = stamps[warm - 1]
    inside = [i for i in range(warm, len(stamps))
              if stamps[i] <= t0 + window.seconds]
    losses = [float(x) for x in jax.device_get(window.losses)]
    attempted = window.enqueued - warm
    failed = sum(not math.isfinite(x) for x in losses[warm:]) \
        + len(window.errors)
    in_window_compiles = (compiles.hits + compiles.misses
                          - (window.compiles_at_window or 0))
    values = {"setup_s": t0 - ctx["t_start"]}
    window_s = 0.0
    if inside:
        last = inside[-1]
        window_s = stamps[last] - t0
        intervals = [stamps[i] - stamps[i - 1] for i in range(warm, last + 1)]
        values["examples_per_s_chip"] = (
            len(inside) * global_batch / window_s / cell["chips"])
        values["step_p95_ms"] = 1e3 * _quantile(intervals, 0.95)
        say(row="window", steps=len(inside), window_s=window_s,
            epochs_entered=epoch, step_median_ms=1e3 * statistics.median(
                intervals), step_max_ms=1e3 * max(intervals),
            examples_per_s=len(inside) * global_batch / window_s,
            tokens_per_s=(len(inside) * global_batch * cfg.seq_len / window_s
                          if trainer.bundle.task == "lm" else None),
            loss_first=losses[0], loss_last=losses[-1],
            compiles_in_window=in_window_compiles, errors=window.errors[:3],
            **compiles.row())

    # -- the device, before the program's state goes ---------------------------
    # The allocator keeps a compiled program's temporaries as "reserved", not
    # "in use" (PERF.md Findings, PR 25): a chip's peak is the two together.
    stats = [d.memory_stats() or {} for d in devs]
    allocator = [int(s.get("peak_bytes_in_use", 0))
                 + int(s.get("peak_bytes_reserved", 0)) for s in stats]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(allocator)}
    say(row="memory", allocator_peak_bytes=allocator, allocator_stats=stats[0])

    reference = ctx["load_module"](ctx["search"], "references",
                                   config["reference"])
    layer_ctx = {
        "peaks": peaks, "chips": cell["chips"], "global_batch": global_batch,
        "config": config, "traffic": traffic,
        "fwd_flops_per_example": reference.forward_flops(config["model"],
                                                         traffic),
    }
    if ctx["trace"]:
        compiled = window.compiled_step()
        mem = compiled.memory_analysis()
        layer_ctx["step_bytes"] = (
            mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        layer_ctx["step_text"] = compiled.as_text()
        say(row="step_program", arguments=mem.argument_size_in_bytes,
            temp=mem.temp_size_in_bytes, output=mem.output_size_in_bytes,
            alias=mem.alias_size_in_bytes, **compiles.row())
        del compiled, mem
    program = {
        "loss": losses[:3],
        "moment_norms": {
            k: float(v) * config["optimizer"]["first_moment_scale"]
            for k, v in jax.device_get(window.first["moment"]).items()},
        "dparam_norms": {k: float(v) for k, v in
                         jax.device_get(window.first["dparam"]).items()},
    }
    batches = window.first["batches"]
    host = {"rows": window.rows, "window_s": window_s}
    trace_dir, trace_span = window.trace_dir, window.trace_span

    # free the program's state: the reference has the device to itself
    trainer.state = None
    trainer.train_step = trainer._make_step_iter = None
    window.jitted = window.first = window.losses = None
    del trainer, window
    gc.collect()

    # -- the per-layer metrics ---------------------------------------------------
    trace = None
    if trace_dir is not None:
        trace = xplane.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    if ctx["trace"]:
        for name in ctx["per_layer"]:
            reader = ctx["load_module"](ctx["search"], "layer_metrics", name)
            value = reader.read(trace, host, layer_ctx)
            if value is not None:
                values[name] = value
        if trace is not None and trace.devices:
            traced = [(d, w) for d in trace.devices
                      if (w := xplane.step_window(d))]
            if traced:
                device["busy_s"] = statistics.mean(
                    xplane.busy(d, w[0], w[1]) for d, w in traced) / 1e9
                device["window_s"] = statistics.mean(
                    w[1] - w[0] for _, w in traced) / 1e9
            say(row="trace", traced_s=trace_span[1] - trace_span[0],
                stop_trace_s=trace_span[2], devices=len(trace.devices),
                step_runs=[w[2] for _, w in traced])

    # -- the comparison ----------------------------------------------------------
    t_ref = time.perf_counter()
    with jax.default_device(devs[0]):
        ref_params = jax.jit(
            lambda k: weights.make_flat(shapes, rules, k))(key)
        ref = reference.run(config, ref_params, batches)
    numbers = compare.readings(program, ref)
    same, compared = compare.judge(numbers, config["limits"])
    say(row="comparison", reference_s=time.perf_counter() - t_ref,
        program_loss=program["loss"], reference_loss=ref["loss"],
        grad_leaf_at=numbers["grad_leaf_at"],
        dparam_leaf_at=numbers["dparam_leaf_at"], compared=compared)
    say(row="reference_leaf_norms", moment=ref["moment_norms"])
    ok = bool(same and failed == 0 and attempted > 0 and inside
              and in_window_compiles == 0)
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "values": values, "device": device, "compared": compared}
    if ctx["trace"]:
        result["breakdown"] = _breakdown(trace)
    return result, {"program": program, "reference": ref, "batches": batches,
                    "shapes": shapes, "key": key}


def run(ctx):
    """The last line's object, or None where there is no chip to measure."""
    import jax

    cell = ctx["cell"]
    say(row="versions", jax=jax.__version__,
        libtpu=_version("libtpu"),
        cell=cell["name"], seed=ctx["seed"], seconds=ctx["seconds"],
        trace=ctx["trace"], compile_cache=place_cache(jax))
    devs = jax.devices()
    with open(os.path.join(ctx["here"], "peaks.json")) as fh:
        peaks = json.load(fh)["kinds"].get(devs[0].device_kind)
    on_chip = (devs[0].platform == "tpu" and peaks is not None
               and len(devs) == cell["chips"])
    if not on_chip and not ctx["rehearsal"]:
        print(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s) of a kind in peaks.json; jax found {len(devs)} x "
              f"{devs[0].platform} {devs[0].device_kind!r}", file=sys.stderr)
        return None
    result, _ = measure(ctx, peaks)
    wanted = ctx["per_layer"] if ctx["trace"] else ctx["end_to_end"]
    unit = ctx["units"]
    values = result.pop("values")
    result.pop("compared")
    result["metrics"] = {n: {"value": values[n], "unit": unit[n]}
                         for n in wanted if n in values}
    if result.get("breakdown") is None:
        result.pop("breakdown", None)
    if not on_chip:
        # a rehearsal: control flow only, no number of this machine is a metric
        say(row="rehearsal", computed=sorted(result["metrics"]),
            compared_ok=result["correct"])
        result["correct"], result["metrics"] = False, {}
        result.pop("breakdown", None)
    return result


def _version(package):
    try:
        return importlib.metadata.version(package)
    except importlib.metadata.PackageNotFoundError:
        return None

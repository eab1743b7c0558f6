"""Epoch/step orchestration — the reference's ``train()``/``validate()`` loop.

Reference call stack parity (SURVEY.md §3.2/§3.3): per-epoch
``sampler.set_epoch`` -> per-step forward/backward/update -> periodic eval
with cross-replica metric reduction -> rank-0 logging -> checkpoint. The
host-side loop here never blocks on step results (async dispatch); metrics
are fetched every ``log_every`` steps.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_training_example_tpu.core import (
    checkpoint as checkpoint_lib,
    distributed,
    mesh as mesh_lib,
    optim,
    precision as precision_lib,
    train_loop,
    xcache as xcache_lib,
)
from pytorch_distributed_training_example_tpu.data import (
    datasets as datasets_lib,
    loader as loader_lib,
    prefetch,
    sampler as sampler_lib,
)
from pytorch_distributed_training_example_tpu.models import registry
from pytorch_distributed_training_example_tpu.parallel import sharding as sharding_lib
from pytorch_distributed_training_example_tpu.utils import chaos as chaos_lib
from pytorch_distributed_training_example_tpu.utils import elastic as elastic_lib
from pytorch_distributed_training_example_tpu.utils import fleetobs
from pytorch_distributed_training_example_tpu.utils import metrics as metrics_lib
from pytorch_distributed_training_example_tpu.utils import resilience
from pytorch_distributed_training_example_tpu.utils import telemetry as telemetry_lib
from pytorch_distributed_training_example_tpu.utils import watchdog as watchdog_lib
from pytorch_distributed_training_example_tpu.utils.config import Config
from pytorch_distributed_training_example_tpu.utils.logging import (
    AverageMeter, MetricLogger, Throughput, log, setup_logging,
)


@dataclasses.dataclass(frozen=True)
class StepProgram:
    """What a ``Config`` on a mesh compiles to, before any data or array."""
    mesh: Any
    tx: Any
    schedule: Callable
    rules: tuple
    init_state: Callable          # () -> the sharded TrainState (traced init)
    train_step: Callable          # jitted, the state donated
    eval_step: Callable           # jitted
    batch_sharding: Any

    def abstract_state(self):
        """The state's shapes with their shardings and no array behind them:
        what a tool lowers ``train_step`` on."""
        shape = jax.eval_shape(self.init_state)
        shardings = train_loop.state_shardings(shape, self.mesh, self.rules)
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shape, shardings)


def build_model(cfg: Config) -> registry.ModelBundle:
    """The registry's bundle for ``cfg``: the model under the precision
    policy, with every model-shaping field of ``Config``. The first half of
    the one path from a ``Config`` to the step program: the datasets want
    the model's vocabulary before ``build_step_program`` can be given the
    loader's steps per epoch."""
    policy = precision_lib.get_policy(cfg.precision)
    return registry.create_model(
        cfg.model, num_classes=cfg.num_classes, image_size=cfg.image_size,
        seq_len=cfg.seq_len, dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype, logits_dtype=policy.logits_dtype,
        **cfg.model_options())


def build_step_program(cfg: Config, mesh, steps_per_epoch: int,
                       bundle: registry.ModelBundle) -> StepProgram:
    """Optimizer, sharding rules, state constructor and the jitted steps for
    ``build_model(cfg)``'s bundle on ``mesh``. The ``Trainer`` runs what
    this returns; ``benchmarks/graftlint.py`` lowers it."""
    tx, schedule = optim.build_optimizer(cfg, steps_per_epoch,
                                         task=bundle.task)
    # Warm the schedule's op-by-op dispatch here, inside the init span:
    # the first eager evaluation costs ~0.2s of tracing that would
    # otherwise land UNATTRIBUTED between the first step's spans and
    # drag goodput coverage below its gate.
    float(schedule(0))
    policy = precision_lib.get_policy(cfg.precision)
    scaler = (precision_lib.ScalerState.create()
              if precision_lib.needs_loss_scaling(policy) else None)
    model = bundle.module
    if cfg.strategy == "pp":
        from pytorch_distributed_training_example_tpu.parallel import pp_lm

        if not hasattr(model, "scan_layers"):
            raise ValueError("strategy 'pp' currently supports the Llama "
                             "family (scan-stacked blocks)")
        model = pp_lm.PipelinedLlama(model, mesh, cfg.pp_microbatches)
        rules = pp_lm.PP_RULES
    else:
        rules = sharding_lib.strategy_rules(cfg.strategy, bundle.rules)

    def init_state():
        return train_loop.create_train_state(
            model, tx, bundle.input_template, mesh, rules, seed=cfg.seed,
            scaler=scaler)

    task = train_loop.get_task(bundle.task, cfg.label_smoothing)
    return StepProgram(
        mesh=mesh, tx=tx, schedule=schedule, rules=rules,
        init_state=init_state,
        train_step=jax.jit(
            train_loop.make_train_step(task, cfg.grad_accum_steps,
                                       health=cfg.telemetry),
            donate_argnums=0),
        eval_step=jax.jit(train_loop.make_eval_step(task)),
        batch_sharding=mesh_lib.batch_sharding(mesh))


class Trainer:
    def __init__(self, cfg: Config, mesh=None):
        self.cfg = cfg
        self.metric_logger = setup_logging(
            jsonl_path=os.path.join(cfg.checkpoint_dir, "metrics.jsonl")
            if cfg.checkpoint_dir else None,
            tensorboard_dir=cfg.tensorboard_dir)

        # The process's span recorder (utils/telemetry.py): always there,
        # host-side only. The telemetry layer below (health pack in the
        # compiled step, anomaly guard, goodput/timeline files, flight
        # recorder) adopts it when cfg.telemetry is on. Both come FIRST so
        # the init/compile/restore phases are on the timeline too.
        self.recorder = telemetry_lib.recorder()
        self.telemetry = None
        self._watchdog: watchdog_lib.Watchdog | None = None
        self._compiled = False
        # "warm" when the first step ran an xcache-deserialized executable,
        # else "cold" — lands in goodput.json as ttfs_mode (core/xcache.py).
        self._xcache_mode = "cold"
        if cfg.telemetry:
            tdir = cfg.checkpoint_dir or os.path.join(
                tempfile.gettempdir(), "pdtx_telemetry")
            self.telemetry = telemetry_lib.Telemetry(
                tdir, run_id=self.metric_logger.run_id,
                anomaly_action=cfg.anomaly_action, config=cfg,
                allow_scaler_skips=(cfg.precision == "fp16"),
                resume=bool(cfg.resume),
                straggler_threshold=cfg.straggler_threshold,
                flightrec_steps=cfg.flightrec_steps)
            log.info("telemetry on: health pack in metrics, spans/goodput/"
                     "anomaly bundles -> %s", tdir)

        # Live metrics surface (utils/fleetobs.py): Prometheus endpoint on
        # rank 0 plus an atomically-replaced progress.json in the checkpoint
        # dir — both fed at the log cadence, so they cost nothing extra.
        self._metrics_server: fleetobs.MetricsServer | None = None
        self._progress_dir = cfg.checkpoint_dir or (
            self.telemetry.directory if self.telemetry is not None else None)
        self._progress: dict = {}
        if cfg.metrics_port is not None and distributed.is_main_process():
            try:
                self._metrics_server = fleetobs.MetricsServer(
                    cfg.metrics_port).start()
            except OSError as e:
                log.warning("metrics endpoint disabled (%s)", e)

        # Chaos harness (utils/chaos.py): armed BEFORE the workload builds so
        # the loader batch hook is installed before any batch is yielded.
        self._chaos: chaos_lib.ChaosEngine | None = None
        if cfg.chaos:
            self._chaos = chaos_lib.ChaosEngine(
                cfg.chaos,
                seed=(cfg.chaos_seed if cfg.chaos_seed is not None
                      else cfg.seed),
                log_dir=cfg.checkpoint_dir, rank=jax.process_index())
            loader_lib.set_batch_hook(self._chaos.batch_hook)
            log.warning("chaos harness armed: %s (seed %d)", cfg.chaos,
                        self._chaos.seed)
        self._rollbacks = 0

        init_span = self._span("init")
        init_span.__enter__()
        try:
            self._init_workload(cfg, mesh)
        finally:
            init_span.__exit__(None, None, None)

    def _span(self, name: str, **kw):
        return self.recorder.span(name, **kw)

    def _context(self) -> dict:
        """The watchdog's dump: where the loop was (the recorder's newest
        spans, and which step compiled what), plus the telemetry layer's
        snapshot (last health row, goodput) when that is on."""
        snap = self.telemetry.snapshot() if self.telemetry is not None else {}
        return {**snap, "last_spans": self.recorder.tail(16, kind="span"),
                "last_compiles": self.recorder.tail(
                    8, kind="compile", names=telemetry_lib.BACKEND_RECORDS)}

    def _init_workload(self, cfg: Config, mesh=None):
        # ``init`` is split where it crosses a layer. The children have no
        # goodput bucket: ``init`` accrues, they are the timeline's detail.
        with self._span("build_mesh", bucket=None):
            self.mesh = mesh if mesh is not None else mesh_lib.build_mesh(
                cfg.mesh_config(), elastic=cfg.elastic)
        # Elastic resume: BEFORE anything batch-dependent is built, peek the
        # newest committed manifest for the geometry that wrote it; if the
        # world size changed, rescale this run's batch geometry under the
        # configured policy (utils/elastic.py) so the restore continues
        # sample-exact at the surviving device count.
        self._elastic_plan = None
        if cfg.elastic and cfg.resume:
            cfg = self._plan_elastic(cfg)
            self.cfg = cfg
        with self._span("build_model", bucket=None):
            self.bundle = build_model(cfg)
        with self._span("build_data", bucket=None):
            self._build_data(cfg)

        # optimizer / state / steps -----------------------------------------
        program = build_step_program(cfg, self.mesh, self.steps_per_epoch,
                                     self.bundle)
        self.schedule = program.schedule
        with self._span("init_state", bucket=None):
            self.state = program.init_state()
        self.train_step = program.train_step
        self.eval_step = program.eval_step
        self.batch_sharding = program.batch_sharding
        self._init_run(cfg)

    def _build_data(self, cfg: Config):
        """Both datasets, the sampler and both loaders; the steps an epoch."""
        vocab = getattr(self.bundle.module, "vocab_size", 50257)
        data_kw = dict(image_size=cfg.image_size, seq_len=cfg.seq_len,
                       seed=cfg.seed, vocab_size=vocab)
        self.train_data = datasets_lib.build_dataset(
            cfg.dataset, cfg.data_path, train=True, **data_kw)
        self.eval_data = datasets_lib.build_dataset(
            cfg.dataset, cfg.data_path, train=False,
            require_split=cfg.evaluate, **data_kw)
        if isinstance(self.train_data, datasets_lib.TokenFileDataset):
            # Out-of-vocab ids don't crash an embedding gather — they clamp
            # and train to NaN. Fail loudly on a wrong model/data pairing.
            head = np.asarray(self.train_data.tokens[:1_000_000])
            if head.size and int(head.max()) >= vocab:
                raise ValueError(
                    f"token file {cfg.data_path!r} contains id "
                    f"{int(head.max())} >= model vocab {vocab} — wrong "
                    f"--model / --data-path pairing?")
        nproc = jax.process_count()
        dp = mesh_lib.dp_size(self.mesh)
        if cfg.global_batch_size % dp:
            raise ValueError(
                f"--batch-size {cfg.global_batch_size} must be divisible by the "
                f"data-parallel degree {dp} (mesh data x fsdp); e.g. use "
                f"{(cfg.global_batch_size // dp + 1) * dp}")
        if nproc <= dp and cfg.global_batch_size % max(nproc, 1):
            raise ValueError(
                "global batch size must divide evenly across hosts")
        # Shard the sample stream by the process's data-parallel COORDINATE
        # (loader.dp_shard): with seq/pp/ep/tp axes in the mesh, processes
        # sharing a dp coordinate must feed identical rows — otherwise each
        # host feeds its own rows into a "replicated" array and devices
        # silently compute on inconsistent copies.
        loader_shards, loader_rank = loader_lib.dp_shard(
            nproc, dp, jax.process_index())
        if cfg.grad_accum_steps > 1 and cfg.global_batch_size % (
                dp * cfg.grad_accum_steps):
            raise ValueError(
                f"--batch-size {cfg.global_batch_size} must be divisible by "
                f"data-parallel degree ({dp}) x --grad-accum "
                f"({cfg.grad_accum_steps})")
        self.local_batch = cfg.global_batch_size // loader_shards
        train_sampler = sampler_lib.ShardedSampler(
            len(self.train_data), loader_shards, loader_rank, shuffle=True,
            seed=cfg.seed, drop_last=True)
        self.train_loader = self._make_train_loader(train_sampler)
        self.eval_loader = loader_lib.DataLoader(
            self.eval_data, self.local_batch,
            sampler_lib.ShardedSampler(len(self.eval_data), loader_shards,
                                       loader_rank, shuffle=False),
            num_workers=cfg.workers, drop_last=False)

        self.steps_per_epoch = len(self.train_loader)
        if cfg.steps_per_epoch:
            self.steps_per_epoch = min(self.steps_per_epoch, cfg.steps_per_epoch)
        # epoch-keyed eval rows land on the global-step TensorBoard axis
        self.metric_logger.steps_per_epoch = self.steps_per_epoch
        if self._chaos is not None:
            # Batch-site chaos events key on the same global index as the
            # step-site ones: epoch * steps_per_epoch + batch.
            self._chaos.steps_per_epoch = self.steps_per_epoch

    def _init_run(self, cfg: Config):
        """Checkpointing and the restore, the profile and fault options."""
        self.checkpointer = (checkpoint_lib.Checkpointer(cfg.checkpoint_dir)
                             if cfg.checkpoint_dir else None)
        self.start_epoch = 0
        self.start_step_offset = 0
        self._last_saved_step = -1
        self.resumed = False
        if cfg.resume and self.checkpointer is None:
            # --resume <path> without --checkpoint-dir: restore from (and
            # keep saving into) that path instead of silently ignoring it.
            if cfg.resume == "auto":
                raise ValueError("--resume auto needs --checkpoint-dir (or "
                                 "pass an explicit checkpoint path)")
            root, _ = checkpoint_lib.split_resume_path(cfg.resume)
            if not os.path.isdir(root):
                # Validate BEFORE Checkpointer() mkdirs it: a typo'd path
                # must not become a fresh empty checkpoint dir.
                raise FileNotFoundError(f"--resume path not found: {cfg.resume}")
            self.checkpointer = checkpoint_lib.Checkpointer(root)
        # After the resume path may have provided a save directory: the
        # step cadence needs SOMEWHERE to write (mid-epoch resume + keep
        # saving into the resume path is a supported combination).
        if cfg.checkpoint_every_steps and self.checkpointer is None:
            raise ValueError("--checkpoint-every-steps needs --checkpoint-dir "
                             "or --resume <path> (step-granular saves were "
                             "requested but there is nowhere to write them)")
        if cfg.resume and self.checkpointer:
            self._resume()

        self.profile_range = None
        if cfg.profile_steps:
            a, b = cfg.profile_steps.split(":")
            self.profile_range = (int(a), int(b))

        self.fault_inject = None
        if cfg.fault_inject:  # "rank:step" — SURVEY.md §5 fault injector
            try:
                r, s = cfg.fault_inject.split(":")
                self.fault_inject = (int(r), int(s))
            except ValueError:
                raise ValueError(
                    f"--fault-inject expects 'rank:step' (two integers "
                    f"separated by a colon), got {cfg.fault_inject!r}") from None

        n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.state.params))
        log.info("model=%s params=%.2fM devices=%d mesh=%s strategy=%s precision=%s",
                 cfg.model, n_params / 1e6, jax.device_count(),
                 dict(self.mesh.shape), cfg.strategy, cfg.precision)

    def _make_train_loader(self, sampler):
        """Prefer the C++ batch engine: in-memory uint8 arrays (CIFAR) and
        JPEG directory trees (ImageNet) both have native fast paths."""
        cfg = self.cfg
        ldr = loader_lib.build_image_loader(
            self.train_data, sampler, self.local_batch, workers=cfg.workers,
            native=cfg.native_loader)
        from pytorch_distributed_training_example_tpu.data import native_loader

        if isinstance(ldr, native_loader.NativeDataLoader):
            log.info("using native C++ batch engine for the input pipeline")
        return ldr

    # -- checkpoint glue ---------------------------------------------------

    def _plan_elastic(self, cfg: Config) -> Config:
        """Rescale the batch geometry when resuming at a changed world size.

        Reads the newest committed manifest (JSON only — no array I/O, runs
        before the model exists) and compares the recorded data-parallel
        degree against this run's mesh. All the policy math lives in
        ``utils/elastic.py``; this method just threads it into the config.
        The relaunch command always carries the ORIGINAL launch geometry
        (same argv + ``--resume auto``), so caps like ``--steps-per-epoch``
        are remapped from the launched batch size, while the plan itself
        starts from the RECORDED geometry so repeated shrinks compose.
        """
        root = cfg.checkpoint_dir
        if cfg.resume not in ("auto", None):
            root, _ = checkpoint_lib.split_resume_path(cfg.resume)
        manifest = checkpoint_lib.peek_manifest(root) if root else None
        if not manifest:
            return cfg
        recorded = dict(manifest.get("extra") or {})
        geom = manifest.get("geometry") or {}
        if "mesh_shape" not in recorded and geom.get("mesh_shape"):
            recorded["mesh_shape"] = geom["mesh_shape"]
        new_dp = mesh_lib.dp_size(self.mesh)
        if elastic_lib.recorded_world(recorded) is None:
            log.warning(
                "elastic resume: checkpoint records no source geometry "
                "(pre-elastic save) — resuming without batch rescale")
            return cfg
        plan = elastic_lib.plan_from_record(
            recorded, policy=cfg.elastic_policy, new_world=new_dp,
            fallback_global_batch=cfg.global_batch_size,
            fallback_grad_accum=cfg.grad_accum_steps)
        if plan is None:
            return cfg  # world size unchanged
        updates = {"global_batch_size": plan.global_batch_size,
                   "grad_accum_steps": plan.grad_accum_steps,
                   "lr": float(recorded.get("lr", cfg.lr)) * plan.lr_scale}
        if cfg.steps_per_epoch and plan.global_batch_size != cfg.global_batch_size:
            updates["steps_per_epoch"] = elastic_lib.remap_step_count(
                cfg.steps_per_epoch, cfg.global_batch_size,
                plan.global_batch_size)
        self._elastic_plan = plan
        log.warning("%s", plan.describe())
        return cfg.replace(**updates)

    def _resume(self):
        """``--resume`` accepts 'auto', a checkpoint root, or a step_NNN dir."""
        step = None
        directory = self.checkpointer.directory
        if self.cfg.resume not in ("auto", None):
            directory, step = checkpoint_lib.split_resume_path(self.cfg.resume)
            if step is None and not os.path.isdir(directory):
                raise FileNotFoundError(
                    f"--resume path not found: {self.cfg.resume}")
            if directory != self.checkpointer.directory:
                self.checkpointer = checkpoint_lib.Checkpointer(directory)
        if step is None and not checkpoint_lib.all_checkpoints(directory):
            log.info("resume requested but no committed checkpoint in %s", directory)
            return
        # step=None lets restore() pick the newest USABLE step: a corrupted
        # or manifest-less latest checkpoint falls back to the previous
        # committed one (with a loud warning) instead of crashing the resume.
        with self._span("checkpoint_restore"):
            self.state, extra = self.checkpointer.restore(self.state, step)
        step = self.checkpointer.last_restored_step
        epoch = int(extra.get("epoch", -1))
        # Epoch-boundary checkpoints carry no step_offset (the epoch is
        # complete); mid-epoch ones record how many steps of `epoch` were
        # already applied, and the sampler — a pure function of
        # (seed, epoch) — regenerates the identical permutation, so
        # fast-forwarding the index stream is sample-exact.
        raw_offset = extra.get("step_offset")
        offset = (self.steps_per_epoch if raw_offset is None
                  else int(raw_offset))
        if raw_offset is not None and self._elastic_plan is not None:
            # Elastic resume: the recorded offset counts optimizer steps of
            # the SAVING geometry. Convert it through the sample position
            # (offset * old_gb must be a whole number of new batches —
            # remap_step_offset raises otherwise), so the loader continues
            # at the exact next unconsumed sample.
            rec_gb = int(extra.get("global_batch_size",
                                   self.cfg.global_batch_size))
            if rec_gb != self.cfg.global_batch_size:
                remapped = elastic_lib.remap_step_offset(
                    offset, rec_gb, self.cfg.global_batch_size)
                log.warning(
                    "elastic resume: mid-epoch offset %d (gb %d) -> %d "
                    "(gb %d); sample position %d preserved", offset, rec_gb,
                    remapped, self.cfg.global_batch_size, offset * rec_gb)
                offset = remapped
            rec_spe = extra.get("steps_per_epoch")
            if rec_spe is not None and (
                    int(rec_spe) * rec_gb !=
                    self.steps_per_epoch * self.cfg.global_batch_size):
                log.warning(
                    "elastic resume: epoch sample count changed (%d -> %d "
                    "samples/epoch) — epoch boundaries shift at the dataset "
                    "tail", int(rec_spe) * rec_gb,
                    self.steps_per_epoch * self.cfg.global_batch_size)
        if offset < self.steps_per_epoch:
            if self._elastic_plan is None:
                # Mid-epoch restore: the offset counts optimizer steps of the
                # SAVING run's batch geometry. Resuming with a different
                # --batch-size (or a loader that slices the epoch differently)
                # would fast-forward to the wrong sample silently — refuse
                # (pass --elastic to convert the offset instead).
                for key, current in (("global_batch_size",
                                      self.cfg.global_batch_size),
                                     ("steps_per_epoch", self.steps_per_epoch)):
                    recorded = extra.get(key)
                    if recorded is None:
                        log.warning(
                            "checkpoint predates %s recording; cannot verify "
                            "the mid-epoch offset matches this run's batch "
                            "geometry", key)
                    elif int(recorded) != current:
                        raise ValueError(
                            f"mid-epoch resume with mismatched {key}: checkpoint "
                            f"was saved with {int(recorded)}, this run uses "
                            f"{current}. The step offset {offset} would land on "
                            "the wrong sample; resume with the original batch "
                            "geometry, restart from an epoch boundary, or pass "
                            "--elastic to rescale under a batch policy.")
            self.start_epoch = epoch
            self.start_step_offset = offset
            log.info("resumed from step %d (epoch %d, step offset %d)",
                     step, epoch, offset)
        else:
            self.start_epoch = epoch + 1
            self.start_step_offset = 0
            log.info("resumed from step %d (epoch %d)", step, self.start_epoch)
        self.resumed = True

    def _save(self, epoch: int, step_offset: int | None = None,
              block: bool = False) -> float:
        """Returns the seconds its ``checkpoint_save`` span took (0.0 when
        there was nothing to write)."""
        if self.checkpointer is None:
            return 0.0
        step = int(jax.device_get(self.state.step))
        if step == self._last_saved_step:
            return 0.0  # the step cadence already wrote this exact state
        # Batch geometry travels with the checkpoint: a mid-epoch resume
        # fast-forwards the sampler by step_offset * global_batch samples,
        # which is only sample-exact if the restore run slices the epoch
        # the same way (_resume validates).
        extra = {"epoch": epoch,
                 "global_batch_size": self.cfg.global_batch_size,
                 "steps_per_epoch": self.steps_per_epoch,
                 # Elastic-resume provenance (utils/elastic.py): the geometry
                 # that produced this state, so a different-world relaunch can
                 # rescale from what was actually running — repeated shrinks
                 # compose, and scaled LR carries forward.
                 "mesh_shape": {str(k): int(v)
                                for k, v in dict(self.mesh.shape).items()},
                 "grad_accum": self.cfg.grad_accum_steps,
                 "lr": self.cfg.lr}
        if step_offset is not None:
            extra["step_offset"] = step_offset
        # One retry: save() first joins the previous background write, so a
        # CheckpointWriteError here may be THAT save's failure surfacing —
        # either way the right response is to try writing the current state
        # once more, then let a persistent failure propagate.
        for attempt in (1, 2):
            try:
                with self._span("checkpoint_save") as save_span:
                    if self._chaos is not None:
                        self._chaos.before_save()
                    self.checkpointer.save(self.state, step, extra=extra,
                                           block=block)
                    if self._chaos is not None:
                        self._chaos.after_save(self.checkpointer)
                break
            except checkpoint_lib.CheckpointWriteError as e:
                if attempt == 2:
                    raise
                log.error("checkpoint save for step %d failed (%s) — "
                          "retrying once", step, e)
        self._last_saved_step = step
        if self.telemetry is not None:
            # Flush the goodput/timeline files alongside every durable save:
            # an ABRUPT host loss (chaos kill_host, real hardware) writes no
            # shutdown summary, so the restart-tax merge in the next attempt
            # measures its gap from the last flush here.
            self.telemetry.write_artifacts()
        return save_span.seconds

    # -- resilience --------------------------------------------------------

    def _graceful_shutdown(self, epoch: int, step_offset: int):
        """Act on a preemption signal at a step/epoch boundary: make the
        current state durable, then exit with the distinct preemption code.

        Raises :class:`resilience.PreemptedExit` (a SystemExit), so
        ``train()``'s finally still emits the telemetry goodput summary and
        closes the metric logger on the way out; a supervisor
        (``launch.py --restart-policy``) relaunches ``--resume auto`` on
        :data:`resilience.PREEMPTED_EXIT_CODE`.
        """
        log.warning(
            "preemption (signal %s): emergency checkpoint at epoch %d step "
            "offset %d, then exit %d", resilience.preempt_signal(), epoch,
            step_offset, resilience.PREEMPTED_EXIT_CODE)
        if self.checkpointer is not None:
            try:
                self.checkpointer.wait()  # join any in-flight background save
            except checkpoint_lib.CheckpointWriteError as e:
                # That save never committed — its step id must not dedupe
                # the emergency save below.
                log.error("in-flight save failed during shutdown (%s)", e)
                self._last_saved_step = -1
            self._save(epoch, step_offset=step_offset, block=True)
            log.warning("emergency checkpoint committed — exiting")
        if self.telemetry is not None:
            # Post-mortems of preempted runs start from the flight recorder,
            # not an empty log: dump the last-N step records before exiting.
            self.telemetry.flight_dump("preempt", epoch=int(epoch),
                                       step_offset=int(step_offset))
        raise resilience.PreemptedExit()

    def _anomaly_rollback(self, epoch: int, i: int) -> int:
        """``anomaly_action="rollback"``: restore the last committed
        checkpoint and return the batch index to continue from.

        The poisoned batch was consumed exactly once (its update is being
        discarded with the restore), so continuing at ``i + 1`` keeps the
        run's yielded-index log identical to an uninterrupted run's.
        Escalates to :class:`AnomalyError` once ``rollback_budget`` is
        exhausted or when there is nothing to restore — a model that keeps
        going non-finite after restores has a real problem, not a blip.
        """
        cfg = self.cfg
        self._rollbacks += 1
        if self._rollbacks > cfg.rollback_budget:
            raise telemetry_lib.AnomalyError(
                f"anomaly rollback budget exhausted "
                f"({cfg.rollback_budget}): still hitting non-finite health "
                f"scalars after {cfg.rollback_budget} restore(s) — aborting")
        if self.checkpointer is None:
            raise telemetry_lib.AnomalyError(
                "anomaly_action=rollback needs --checkpoint-dir (nothing "
                "to restore from)")
        try:
            self.checkpointer.wait()  # don't race an in-flight save
        except checkpoint_lib.CheckpointWriteError as e:
            log.error("in-flight save failed before rollback (%s)", e)
            self._last_saved_step = -1
        # Newest-first over committed steps, VALIDATING each restored state:
        # a step-cadence save that landed at/after the poisoned batch is
        # committed and CRC-clean yet contains non-finite params — restoring
        # it would just re-trip the guard until the budget aborts. Such a
        # checkpoint is quarantined so a later --resume cannot pick it either.
        restored_step = None
        for cand in sorted(checkpoint_lib.all_checkpoints(
                self.checkpointer.directory), reverse=True):
            try:
                with self._span("checkpoint_restore"):
                    state, _ = self.checkpointer.restore(self.state, cand)
            except (checkpoint_lib.CheckpointCorruptError, OSError,
                    json.JSONDecodeError, KeyError) as e:
                log.error("rollback: checkpoint step %d unusable (%s: %s) — "
                          "trying an older one", cand, type(e).__name__, e)
                continue
            if all(bool(jnp.isfinite(x).all())
                   for x in jax.tree.leaves(state.params)):
                self.state = state
                restored_step = cand
                break
            log.warning(
                "rollback: checkpoint step %d itself has non-finite params "
                "(saved after the poisoned batch) — quarantining and trying "
                "an older one", cand)
            if distributed.is_main_process():
                self.checkpointer.quarantine(cand)
        if restored_step is None:
            raise telemetry_lib.AnomalyError(
                "anomaly_action=rollback: no committed checkpoint with "
                "finite params to restore")
        log.warning(
            "anomaly rollback %d/%d: restored step %d, continuing at epoch "
            "%d batch %d", self._rollbacks, cfg.rollback_budget,
            restored_step, epoch, i + 1)
        # The restored optimizer step count will re-pass ids the cadence
        # already saved; clear the dedupe so those saves are not skipped.
        self._last_saved_step = -1
        return i + 1

    # -- loops -------------------------------------------------------------

    def train(self):
        cfg = self.cfg
        # Preemption-safe shutdown: SIGTERM/SIGINT only set a flag here; the
        # step loop polls it at step boundaries and runs _graceful_shutdown
        # (finish in-flight step -> blocking emergency checkpoint -> goodput
        # emit via the finally below -> exit PREEMPTED_EXIT_CODE). No-op off
        # the main thread (install() warns and returns False).
        resilience.install()
        # One run-level watchdog spanning train AND eval (both loops beat it,
        # so a long eval never false-triggers); its timeout dump carries the
        # recorder's last spans, and the telemetry snapshot — last step,
        # last health row, goodput — when that is on.
        self._watchdog = watchdog_lib.Watchdog(
            timeout_s=cfg.watchdog_timeout,
            context_fn=self._context).start()
        try:
            for epoch in range(self.start_epoch, cfg.epochs):
                self.train_epoch(epoch)
                if resilience.preempted():
                    # Tripped during the epoch's tail or between loops (e.g.
                    # mid-eval next iteration): the epoch is complete, so the
                    # emergency save is an epoch-boundary one.
                    self._graceful_shutdown(epoch, self.steps_per_epoch)
                if (epoch + 1) % cfg.eval_every_epochs == 0:
                    self.evaluate(epoch)
                if (epoch + 1) % cfg.checkpoint_every_epochs == 0:
                    self._save(epoch)
                if self.telemetry is not None:
                    g = self.telemetry.emit(f"epoch {epoch}")
                    self.metric_logger.write(
                        kind="goodput", epoch=epoch, wall_s=g["wall_s"],
                        goodput_fraction=g["goodput_fraction"],
                        badput_fraction=g["badput_fraction"],
                        coverage=g["coverage"],
                        **{f"frac_{k}": v for k, v in g["fractions"].items()})
            if self.checkpointer:
                self.checkpointer.wait()
        finally:
            self._watchdog.stop()
            self._watchdog = None
            if self.telemetry is not None:
                # Shutdown emit runs even on an anomaly abort, so the
                # timeline + goodput files always reflect the full run.
                self.telemetry.emit("shutdown")
            if distributed.is_main_process() and self._progress_dir:
                try:
                    fleetobs.write_progress(
                        self._progress_dir,
                        {**self._progress, "status": "shutdown"})
                except OSError:
                    pass
            if self._metrics_server is not None:
                self._metrics_server.stop()
                self._metrics_server = None
            self.metric_logger.close()
        return self.state

    def train_epoch(self, epoch: int):
        cfg = self.cfg
        self.train_loader.set_epoch(epoch)
        # Resumed mid-epoch: skip the already-trained prefix of this epoch's
        # (deterministic) index stream; every later epoch starts at 0.
        self.train_loader.start_batch = (
            self.start_step_offset if epoch == self.start_epoch else 0)
        loss_m = AverageMeter("loss")
        tput = Throughput()
        t_step = time.perf_counter()
        # train() owns the run-level watchdog; a direct train_epoch() call
        # (tests, notebooks) gets a per-epoch one with the same context hook.
        watchdog = self._watchdog
        own_watchdog = watchdog is None
        if own_watchdog:
            watchdog = watchdog_lib.Watchdog(
                timeout_s=cfg.watchdog_timeout,
                context_fn=self._context).start()
        try:
            self._train_epoch_inner(epoch, loss_m, tput, t_step, watchdog)
        finally:
            if own_watchdog:
                watchdog.stop()
            errs = getattr(getattr(self.train_loader, "engine", None),
                           "decode_errors", None)
            if errs is not None and errs() > 0:
                log.warning("native loader: %d image(s) failed to decode "
                            "(zero-filled)", errs())

    def _make_step_iter(self, epoch, start):
        """(Re)build the prefetched batch iterator from batch ``start``.

        Separate from the epoch loop so the anomaly-rollback path can tear
        the pipeline down and rebuild it past the poisoned batch window —
        the loader's index stream is a pure function of (seed, epoch, start),
        so this is sample-exact.
        """
        self.train_loader.start_batch = start
        return prefetch.device_prefetch(self.train_loader, self.batch_sharding)

    def _train_epoch_inner(self, epoch, loss_m, tput, t_step, watchdog):
        cfg = self.cfg
        tele = self.telemetry
        it = self._make_step_iter(epoch, self.train_loader.start_batch)
        with mesh_lib.use_mesh(self.mesh):
            i = self.train_loader.start_batch
            while i < self.steps_per_epoch:
                gstep = epoch * self.steps_per_epoch + i
                if self.profile_range and gstep == self.profile_range[0]:
                    jax.profiler.start_trace(cfg.profile_dir)
                profiled = None
                # Every span of this iteration carries gstep; the step
                # marker groups them (and the device's work) in xprof.
                self.recorder.step = gstep
                with jax.profiler.StepTraceAnnotation("train", step_num=gstep), \
                        self._span("iteration", bucket=None) as iteration:
                    # Host wait on the input pipeline is its own badput
                    # bucket — with the prefetcher keeping up this span is
                    # ~0. data/prefetch.py splits it: loader_wait, device_put.
                    with self._span("input_wait") as input_wait:
                        try:
                            batch = next(it)
                        except StopIteration:
                            break
                    watchdog.beat()
                    if (self.fault_inject
                            and jax.process_index() == self.fault_inject[0]
                            and gstep == self.fault_inject[1]):
                        # Simulated host failure: no cleanup, no flushes — the
                        # hardest crash shape recovery must handle.
                        log.error("fault injection: killing process %d at "
                                  "step %d", *self.fault_inject)
                        os._exit(57)
                    if not self._compiled:
                        # First dispatch ever traces + compiles; block so the
                        # "compile" span covers it (dispatch is async — without
                        # the block the cost would leak into later step spans).
                        # ``compile``'s self time is the host's (trace,
                        # lower, compile or load); the child is the device's
                        # first execution of the step.
                        with self._span("compile"):
                            metrics = self._first_dispatch(batch)
                            with self._span("first_step_wait", bucket=None):
                                jax.tree.map(lambda x: x.block_until_ready(),
                                             metrics)
                        self._compiled = True
                        if tele is not None:
                            # The first completed optimizer step, cold vs
                            # warm. ``time_to_first_step_s`` counts from the
                            # telemetry layer's start (``Telemetry`` adopts
                            # the recorder above, in ``__init__``), and
                            # ``process_to_first_step_s`` from the process's.
                            tele.mark_first_step(self._xcache_mode)
                    else:
                        # The enqueue. Productive time: goodput's "step".
                        with self._span("dispatch", bucket="step"):
                            self.state, metrics = self.train_step(self.state,
                                                                  batch)
                    if self.profile_range and gstep + 1 == self.profile_range[1]:
                        profiled = (metrics, batch)
                    tput.update(cfg.global_batch_size)
                    is_log = ((i + 1) % cfg.log_every == 0
                              or i + 1 == self.steps_per_epoch)
                    is_health = (tele is not None and cfg.health_every > 0
                                 and (i + 1) % cfg.health_every == 0)
                    if is_log or is_health:
                        # The fetch drains the async step queue: that wait IS
                        # device step time, so it stays in the "step" bucket.
                        with self._span("metrics_fetch", bucket="step"):
                            m = {k: float(v)
                                 for k, v in jax.device_get(metrics).items()}
                        if tele is not None:
                            # May raise AnomalyError (anomaly_action="abort")
                            # after writing the diagnostic bundle.
                            tripped = tele.observe(gstep, {"epoch": epoch, **m})
                            if tripped and cfg.anomaly_action == "rollback":
                                if profiled is not None:
                                    self._stop_profile(*profiled)
                                it.close()
                                i = self._anomaly_rollback(epoch, i)
                                it = self._make_step_iter(epoch, i)
                                t_step = time.perf_counter()
                                continue
                        if not is_log:
                            self.metric_logger.write(kind="health", epoch=epoch,
                                                     step=gstep, **m)
                    checkpoint_s = 0.0
                    if (cfg.checkpoint_every_steps
                            and (gstep + 1) % cfg.checkpoint_every_steps == 0):
                        # Step-cadence save: records (epoch, steps applied) so
                        # resume fast-forwards to the exact next sample. Runs
                        # even at the epoch boundary — eval may take a long
                        # time, and the boundary state must be durable before
                        # it; the per-epoch save then dedupes on step id.
                        # AFTER the health fetch above: a state the anomaly
                        # guard just flagged (rollback `continue`d, abort
                        # raised) must never be the checkpoint a restart
                        # resumes into.
                        checkpoint_s = self._save(epoch, step_offset=i + 1)
                    if is_log:
                        loss_m.update(m["loss"])
                        lr = float(self.schedule(gstep))
                        dt = (time.perf_counter() - t_step) / cfg.log_every
                        t_step = time.perf_counter()
                        rate = tput.rate
                        per_chip = rate / max(jax.device_count(), 1)
                        mfu = metrics_lib.mfu(per_chip,
                                              self.bundle.fwd_flops_per_example)
                        log.info(
                            "epoch %d step %d/%d loss %.4f lr %.2e %s/s %.1f "
                            "(%.1f/chip) mfu %s %s",
                            epoch, i + 1, self.steps_per_epoch, m["loss"], lr,
                            self.bundle.examples_unit, rate, per_chip,
                            "not measured" if mfu is None
                            else f"{100 * mfu:.1f}%",
                            " ".join(f"{k} {v:.4f}" for k, v in m.items()
                                     if k not in ("loss",)),
                        )
                        self.metric_logger.write(kind="train", epoch=epoch,
                                                 step=gstep, lr=lr, rate=rate,
                                                 mfu=mfu, **m)
                        self._publish(gstep, epoch, m, dt)
                if profiled is not None:
                    # after the step marker has closed, so the trace holds it
                    self._stop_profile(*profiled)
                if tele is not None:
                    # Feed the fleet layer every step from the spans just
                    # closed: flight-recorder ring, buffered step rows, live
                    # straggler monitor (warn-only). No extra clock reads.
                    tele.observe_timing(gstep, total_s=iteration.seconds,
                                        input_wait_s=input_wait.seconds,
                                        checkpoint_s=checkpoint_s,
                                        epoch=epoch)
                if self._chaos is not None:
                    self._chaos.step_boundary(gstep)
                # Preemption poll — the ONLY place the SIGTERM flag is acted
                # on, so the in-flight step always completes first and the
                # emergency checkpoint is taken at a clean step boundary.
                if resilience.preempted():
                    self._graceful_shutdown(epoch, i + 1)
                i += 1

    def _stop_profile(self, metrics, batch):
        """End the ``--profile-steps`` trace once its last step has run, and
        leave the step's map beside it (utils/stepmap.py): which part of the
        program and which pass each ``fusion.1234`` of the trace is."""
        with self._span("stop_profile", bucket=None):
            jax.tree.map(lambda x: x.block_until_ready(), metrics)
            jax.profiler.stop_trace()
            log.info("profile written to %s", self.cfg.profile_dir)
            try:
                self._write_step_map(batch)
            except Exception as e:  # noqa: BLE001 - a diagnostic, never fatal
                log.warning("no step map beside the profile (%s: %s)",
                            type(e).__name__, e)

    def _write_step_map(self, batch):
        """The step that just ran, compiled once more for the state and the
        batch it ran on (an AOT executable has its text already; the
        persistent cache serves the jitted one), read into ``step_map.json`` in the profile's
        directory, a line of the log and one ``step_map`` record."""
        if not distributed.is_main_process():
            return
        from pytorch_distributed_training_example_tpu.utils import stepmap

        step = self.train_step
        if not hasattr(step, "as_text"):
            step = step.lower(self.state, batch).compile()
        os.makedirs(self.cfg.profile_dir, exist_ok=True)
        path = os.path.join(self.cfg.profile_dir, "step_map.json")
        summary = stepmap.write(step.as_text(), path)
        self.recorder.compile_event("step_map", 0.0, summary)
        log.info("step map written to %s: kernel calls by pass %s; "
                 "instructions by pass %s; %d compiler clones, %d mixed "
                 "fusions", path, json.dumps(summary["kernel_calls"]),
                 json.dumps(summary["instructions"]),
                 summary["compiler_clones"], summary["mixed_fusions"])

    def _first_dispatch(self, batch):
        """Run the first step, consulting the persistent executable cache.

        With ``--xcache`` + a checkpoint dir, the ``lower().compile()``
        front-end is keyed on a topology/knob/aval fingerprint
        (core/xcache.py): a hit deserializes the compiled executable and
        skips XLA entirely; a miss compiles AOT and serializes the result
        for the next attempt. Either way the compiled executable replaces
        ``self.train_step`` for the rest of the run — an AOT call never
        populates jit's dispatch cache, so leaving the jit wrapper in
        place would re-trace on step 2.
        """
        cfg = self.cfg
        root = (self.checkpointer.directory
                if cfg.xcache and self.checkpointer is not None else None)
        if root is None:
            self.state, metrics = self.train_step(self.state, batch)
            return metrics
        fields = xcache_lib.fingerprint(mesh=self.mesh, config=cfg,
                                        example_args=(self.state, batch))
        compiled = xcache_lib.load(root, fields, example=(self.state, batch))
        if compiled is not None:
            try:
                self.state, metrics = compiled(self.state, batch)
                self.train_step = compiled
                self._xcache_mode = "warm"
                return metrics
            except Exception as e:  # noqa: BLE001 — never a stale executable
                # The fingerprint should make this unreachable; if the
                # deserialized executable still rejects our inputs, refuse
                # it loudly and compile cold rather than trust it.
                log.error("xcache: cached executable rejected our inputs "
                          "(%s: %s) — falling back to cold compile",
                          type(e).__name__, e)
        lowered = self.train_step.lower(self.state, batch)
        compiled = lowered.compile()
        self.state, metrics = compiled(self.state, batch)
        # Save AFTER the first execution: the metrics pytree is part of the
        # entry (reconstruct tree mode) and only exists once the step ran.
        xcache_lib.save(root, fields, compiled,
                        example=(self.state, batch), metrics=metrics)
        self.train_step = compiled
        return metrics

    def _publish(self, gstep: int, epoch: int, m: dict, dt: float):
        """Refresh the live metrics surface (rank 0, log cadence): the
        Prometheus gauges and the atomically-replaced progress.json."""
        if not distributed.is_main_process() or self._progress_dir is None:
            return
        row = {"step": int(gstep), "epoch": int(epoch),
               "loss": float(m.get("loss", 0.0)), "step_time_s": float(dt)}
        if self.telemetry is not None:
            g = self.telemetry.recorder.goodput()
            row.update(
                run_id=self.telemetry.run_id,
                goodput_fraction=g["goodput_fraction"],
                goodput_coverage=g["coverage"],
                attempt=g["attempts"],
                straggler_warnings=self.telemetry.guard.warnings,
                anomaly_count=self.telemetry.guard.trips)
            if g.get("time_to_first_step_s") is not None:
                # Renders as the pdtx_ttfs_seconds gauge on /metrics.
                row["ttfs_seconds"] = g["time_to_first_step_s"]
        self._progress = row
        if self._metrics_server is not None:
            self._metrics_server.update(**row)
        try:
            fleetobs.write_progress(self._progress_dir,
                                    {**row, "status": "training"})
        except OSError as e:
            log.warning("progress.json write failed (%s)", e)

    def evaluate(self, epoch: int):
        sums: dict[str, float] = {}
        n_batches = 0
        padded = (prefetch.pad_batch(b, self.local_batch) for b in self.eval_loader)
        with self._span("eval"), mesh_lib.use_mesh(self.mesh):
            for batch in prefetch.device_prefetch(padded, self.batch_sharding):
                if self._watchdog is not None:
                    self._watchdog.beat()
                stats = self.eval_step(self.state, batch)
                m = {k: float(v) for k, v in jax.device_get(stats).items()}
                for k, v in m.items():
                    sums[k] = sums.get(k, 0.0) + v
                n_batches += 1
                if self.cfg.steps_per_epoch and n_batches >= self.cfg.steps_per_epoch:
                    break
        if n_batches:
            count = max(sums.get("count", 0.0), 1.0)
            avg = metrics_lib.finalize_eval_sums(sums)
            log.info("eval epoch %d %s (n=%d)", epoch,
                     " ".join(f"{k} {v:.4f}" for k, v in avg.items()), int(count))
            self.metric_logger.write(kind="eval", epoch=epoch, count=count, **avg)
            return avg
        return {}

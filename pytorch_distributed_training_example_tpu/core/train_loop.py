"""The compiled train/eval step and state construction.

This is where the reference's whole hot loop (SURVEY.md §3.2) — forward under
autocast, scaled backward, bucketed all-reduce overlapped with backward,
optimizer step — collapses into ONE ``jax.jit``-compiled XLA program:

- forward/backward: ``jax.value_and_grad`` traced at compute dtype (bf16);
- the DDP all-reduce: *implicit* — the loss is a mean over the globally
  sharded batch, so GSPMD emits the gradient ``psum`` and XLA's latency-
  hiding scheduler overlaps it with the backward, which is exactly what
  DDP's C++ reducer does by hand with buckets (SURVEY.md §2b N2);
- optimizer update: fused into the same program; the state is donated so
  updates happen in-place in HBM.

Strategy (DP/FSDP/TP/...) enters only through the shardings of the state and
batch — the step function is strategy-agnostic.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.core import precision as precision_lib
from pytorch_distributed_training_example_tpu.core.train_state import TrainState
from pytorch_distributed_training_example_tpu.parallel import sharding as sharding_lib
from pytorch_distributed_training_example_tpu.utils import metrics as metrics_lib


# ---------------------------------------------------------------------------
# Tasks: how a batch turns into (loss, metrics) given model outputs.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ClassificationTask:
    label_smoothing: float = 0.0

    inputs = ("image",)

    def loss(self, logits, batch):
        return metrics_lib.cross_entropy(logits, batch["label"], self.label_smoothing)

    def metrics(self, logits, batch):
        """LINEAR per-batch metrics only (averaged across grad-accum
        microbatches); nonlinear ones go in :meth:`metrics_from_loss`."""
        counts = metrics_lib.topk_correct(logits, batch["label"])
        n = jnp.asarray(batch["label"].shape[0], jnp.float32)
        return {f"acc_{k}": v / n for k, v in counts.items()}

    def metrics_from_loss(self, loss):
        return {}

    def eval_stats(self, logits, batch):
        """Exact global sums (mask-aware for padded final eval batches)."""
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(batch["label"].shape[0], jnp.float32)
        logits32 = logits.astype(jnp.float32)
        per_ex = metrics_lib.per_example_cross_entropy(logits32, batch["label"])
        counts = metrics_lib.topk_correct(logits32, batch["label"], mask=mask)
        return {
            "count": jnp.sum(mask),
            "loss_sum": jnp.sum(per_ex * mask),
            **{f"acc_{k}_sum": v for k, v in counts.items()},
        }


@dataclasses.dataclass(frozen=True)
class LanguageModelingTask:
    inputs = ("tokens",)

    def loss(self, logits, batch):
        # the same region name as the model's head: the head's matmul and the
        # loss over its logits fuse, and are read as one (``head_loss_ms``)
        with jax.named_scope("head_loss"):
            return metrics_lib.cross_entropy(logits, batch["targets"])

    def metrics(self, logits, batch):
        return {}

    def metrics_from_loss(self, loss):
        # Derived AFTER loss averaging: mean(exp(l_i)) over microbatches
        # would be Jensen-biased upward vs exp(mean(l_i)).
        return {"perplexity": jnp.exp(loss)}

    def eval_stats(self, logits, batch):
        mask = batch.get("mask")
        seq_weight = jnp.ones(batch["targets"].shape, jnp.float32)
        if mask is not None:
            seq_weight = seq_weight * mask[:, None]
        per_tok = metrics_lib.per_example_cross_entropy(
            logits.astype(jnp.float32), batch["targets"])
        return {
            "count": jnp.sum(seq_weight),
            "loss_sum": jnp.sum(per_tok * seq_weight),
        }


def get_task(kind: str, label_smoothing: float = 0.0):
    if kind == "classification":
        return ClassificationTask(label_smoothing)
    if kind == "lm":
        return LanguageModelingTask()
    raise ValueError(f"unknown task {kind!r}")


# ---------------------------------------------------------------------------
# State construction (sharded init — params are born sharded, never
# materialized replicated; the FSDP-at-init requirement).
# ---------------------------------------------------------------------------


def state_shardings(state_shape, mesh: Mesh, rules: Sequence = ()):
    """Infer a NamedSharding for every leaf of a TrainState shape tree."""
    specs = sharding_lib.infer_specs(state_shape, rules, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def create_train_state(
    model,
    tx,
    input_template: tuple,
    mesh: Mesh,
    rules: Sequence = (),
    seed: int = 0,
    scaler=None,
) -> TrainState:
    """Init model params directly into their target shardings (jit + out_shardings)."""
    root = jax.random.PRNGKey(seed)
    init_rng, state_rng = jax.random.split(root)

    def init_fn(rng):
        variables = model.init(
            {"params": rng, "dropout": jax.random.fold_in(rng, 1)},
            *input_template, train=False,
        )
        params = variables["params"]
        batch_stats = variables.get("batch_stats")
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=tx, rng=state_rng,
            batch_stats=batch_stats, scaler=scaler,
        )

    state_shape = jax.eval_shape(init_fn, init_rng)
    shardings = state_shardings(state_shape, mesh, rules)
    with mesh_lib.use_mesh(mesh):
        return jax.jit(init_fn, out_shardings=shardings)(init_rng)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def make_train_step(task, grad_accum: int = 1, health: bool = False) -> Callable:
    """Build the pure ``(state, batch) -> (state, metrics)`` function.

    Callers wrap it in ``jax.jit(..., donate_argnums=0)`` under the mesh:
    sharding propagates from the state/batch, so one builder serves every
    strategy. Precision is carried by the model's dtypes and, for fp16, by
    ``state.scaler`` (presence enables GradScaler semantics at trace time).

    ``grad_accum > 1`` splits the batch into that many microbatches inside
    the compiled step (``lax.scan``), averaging gradients before ONE
    optimizer update — same numbers as the large batch (equivalence-tested)
    at 1/G the activation memory. BatchNorm running stats chain through the
    microbatches sequentially.

    ``health=True`` adds the telemetry health pack to the metrics dict:
    update/param norms, finite flags (utils/telemetry.health_pack) and any
    scalars the model sows under the ``"telemetry"`` collection (the expert
    layers' held rows and layouts). All on-device; the scalars ride
    the same device_get the loss already takes, so there is no extra host
    sync — only the small fused reductions inside the step. Downstream the
    fetched row feeds the anomaly guard AND the fleet layer: the
    flight-recorder ring merges it into the matching step record and the
    per-rank step rows behind the straggler detector ride the same cadence
    (utils/fleetobs.py) — so fleet observability inherits the same
    zero-extra-syncs contract.
    """
    from pytorch_distributed_training_example_tpu.utils import (
        telemetry as telemetry_lib)

    def compute_grads(state: TrainState, batch: dict, step_rng, batch_stats):
        def loss_fn(params):
            variables = {"params": params}
            # "losses" collects model-internal auxiliary terms (GLM's
            # multi-token-prediction loss); "batch_stats" is BatchNorm's
            # running stats;
            # "telemetry" (health runs only) collects model diagnostics —
            # sow() is a no-op when the collection isn't mutable.
            mutable = ["losses"]
            if health:
                mutable.append("telemetry")
            if batch_stats is not None:
                variables["batch_stats"] = batch_stats
                mutable.append("batch_stats")
            inputs = [batch[k] for k in task.inputs]
            logits, new_vars = state.apply_fn(
                variables, *inputs, train=True,
                rngs={"dropout": step_rng}, mutable=mutable)
            loss = task.loss(logits, batch)
            for aux in jax.tree.leaves(new_vars.get("losses", {})):
                loss = loss + aux
            tele = (telemetry_lib.collect_sowed(new_vars["telemetry"])
                    if health and "telemetry" in new_vars else {})
            scaled = state.scaler.scale_loss(loss) if state.scaler is not None else loss
            return scaled, (loss, logits, new_vars.get("batch_stats"), tele)

        return jax.grad(loss_fn, has_aux=True)(state.params)

    def train_step(state: TrainState, batch: dict):
        step_rng = (jax.random.fold_in(state.rng, state.step)
                    if state.rng is not None else jax.random.PRNGKey(0))

        if grad_accum <= 1:
            grads, (loss, logits, new_batch_stats, tele) = compute_grads(
                state, batch, step_rng, state.batch_stats)
            task_metrics = task.metrics(logits, batch)
        else:
            G = grad_accum
            bad = {k: v.shape[0] for k, v in batch.items()
                   if hasattr(v, "shape") and v.ndim and v.shape[0] % G}
            if bad:
                raise ValueError(
                    f"grad_accum={G} does not divide the batch dimension of "
                    f"{bad} — after an elastic rescale the global batch must "
                    f"remain a multiple of grad_accum x data-parallel degree "
                    f"(utils/elastic.py guarantees this for its plans)")
            micro = jax.tree.map(
                lambda x: mesh_lib.constrain(
                    x.reshape(G, x.shape[0] // G, *x.shape[1:]),
                    P(None, mesh_lib.BATCH_AXES)), batch)

            def body(carry, xs):
                g_acc, l_acc, m_acc, t_acc, bs, i = carry
                mb, = xs
                g, (l, logits, new_bs, t) = compute_grads(
                    state, mb, jax.random.fold_in(step_rng, i), bs)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                m_acc = jax.tree.map(jnp.add, m_acc, task.metrics(logits, mb))
                t_acc = jax.tree.map(jnp.add, t_acc, t)
                bs = new_bs if new_bs is not None else bs
                return (g_acc, l_acc + l, m_acc, t_acc, bs, i + 1), None

            # Zero-seeded carry (shapes via eval_shape, so the traced program
            # contains ONE copy of forward+backward, not an unrolled first
            # microbatch plus the scan body).
            mb0 = jax.tree.map(lambda x: x[0], micro)
            m_shape = jax.eval_shape(
                lambda: task.metrics(
                    state.apply_fn(
                        {"params": state.params, **(
                            {"batch_stats": state.batch_stats}
                            if state.batch_stats is not None else {})},
                        *[mb0[k] for k in task.inputs], train=False), mb0))
            t_shape = jax.eval_shape(compute_grads, state, mb0, step_rng,
                                     state.batch_stats)[1][3]
            zeros = lambda s: jnp.zeros(s.shape, s.dtype)
            carry0 = (
                jax.tree.map(jnp.zeros_like, state.params),
                jnp.zeros((), jnp.float32),
                jax.tree.map(zeros, m_shape),
                jax.tree.map(zeros, t_shape),
                state.batch_stats,
                jnp.int32(0),
            )
            (grads, loss, task_metrics, tele, new_batch_stats, _), _ = \
                jax.lax.scan(body, carry0, (micro,))
            inv = 1.0 / G
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv
            task_metrics = jax.tree.map(lambda m: m * inv, task_metrics)
            tele = jax.tree.map(lambda t: t * inv, tele)

        bn_update = ({"batch_stats": new_batch_stats}
                     if new_batch_stats is not None else {})
        if state.scaler is not None:
            grads = state.scaler.unscale(grads)
            finite = precision_lib.all_finite(grads)
            new_scaler = state.scaler.update(finite)
            with jax.named_scope("optimizer"):
                candidate = state.apply_gradients(
                    grads, scaler=new_scaler, **bn_update)
            # GradScaler.step parity: on overflow skip the optimizer update
            # entirely (params AND optimizer state hold) but still advance
            # step/scaler so the schedule and backoff progress.
            pick = lambda n, o: jnp.where(finite, n, o)
            new_state = candidate.replace(
                params=jax.tree.map(pick, candidate.params, state.params),
                opt_state=jax.tree.map(pick, candidate.opt_state, state.opt_state),
            )
        else:
            # clipping lives in the optax chain, so it is inside this region
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients(grads, **bn_update)
        with jax.named_scope("optimizer"):
            grad_norm = global_norm(grads)

        metrics = {"loss": loss, **task_metrics,
                   **task.metrics_from_loss(loss), "grad_norm": grad_norm}
        if health:
            metrics.update(tele)
            metrics.update(telemetry_lib.health_pack(
                loss, grads, state.params, new_state.params))
        if state.scaler is not None:
            metrics["loss_scale"] = new_scaler.scale
            metrics["grads_finite"] = finite.astype(jnp.float32)
        return new_state, metrics

    return train_step


def make_eval_step(task) -> Callable:
    """Eval step returns exact SUMS + count; the host loop divides at the end
    (reference: all_reduce of metric sums then rank-0 division, SURVEY.md §3.3)."""

    def eval_step(state: TrainState, batch: dict):
        variables = {"params": state.params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
        inputs = [batch[k] for k in task.inputs]
        logits = state.apply_fn(variables, *inputs, train=False)
        return task.eval_stats(logits, batch)

    return eval_step


def global_norm(tree) -> jax.Array:
    import optax

    return optax.global_norm(jax.tree.map(lambda x: x.astype(jnp.float32), tree))


def jit_train_step(train_step, mesh: Mesh):
    """jit with state donation under the mesh (in-place HBM update)."""
    return jax.jit(train_step, donate_argnums=0)


def jit_eval_step(eval_step, mesh: Mesh):
    return jax.jit(eval_step)

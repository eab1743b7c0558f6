"""Pipeline parallelism: GPipe microbatch schedule over the ``stage`` mesh axis.

SURVEY.md §2c "PP": the reference has none (DDP example); the TPU-native
design is stage-sliced parameters + a microbatch schedule where activations
hop between neighboring stages with ``ppermute`` (one ICI hop — stages map
to adjacent chips on the torus).

Design: ``shard_map`` over the ``stage`` axis. Parameters are stacked with a
leading ``[num_stages, ...]`` dim sharded on ``stage`` (each chip holds one
stage's weights). The schedule is the classic GPipe fill/steady/drain loop:
at tick ``t``, stage ``s`` processes microbatch ``t - s`` (when valid), then
passes its activation to stage ``s+1``. Total ticks = M + S - 1; bubble
fraction (S-1)/(M+S-1) — choose microbatches >= 4x stages. Backward is just
``jax.grad`` through the loop: ``ppermute`` transposes to the reverse
permutation, giving the symmetric backward pipeline automatically.

Inactive fill/drain ticks skip the stage computation via ``lax.cond`` (a
real XLA conditional, not a discarded ``where``), so the bubble costs idle
time but no FLOPs. ``remat_stages=True`` recomputes each stage in backward,
bounding saved activations to the stage *inputs* per microbatch.

Schedule decision — GPipe + remat_stages over 1F1B (VERDICT r2 #7):
1F1B does NOT shrink the bubble — both schedules idle (S-1) fill + (S-1)
drain ticks, bubble fraction (S-1)/(M+S-1): at the recommended operating
point M=32, S=4 that is 3/35 = **8.6%** of ticks (M=32, S=8: 7/39 = 18%;
the fix at larger S is more microbatches, M=64/S=8: 7/71 = 9.9%). What
1F1B buys is *memory*: it caps live activation sets at S per stage instead
of GPipe's M. Here ``remat_stages=True`` already caps live state at M
*stage-inputs* (one microbatch activation each — for a transformer stage
of L layers that is ~1/(20·L) of the full per-layer activation set that
1F1B would hold S of), so GPipe+remat strictly dominates 1F1B on memory
at these M while matching its bubble, at the price of one extra forward
recompute (~33% more stage FLOPs — the same price per-block remat already
pays in the fsdp+remat configs). An *interleaved* 1F1B (multiple
nonadjacent layer chunks per chip, bubble/(v·S)) is the only schedule that
actually shrinks the bubble; it multiplies ppermute traffic by the
interleave factor v and is not worth it below S≈16 stages — far beyond
the v5p-32 target topology (BASELINE.json configs[4]).

The stage function must be shape-preserving (activation in == activation
out), which transformer blocks satisfy.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack a list of per-stage param pytrees along a new leading dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "stage",
    batch_axes=mesh_lib.BATCH_AXES,
    remat_stages: bool = False,
) -> jax.Array:
    """Run ``stage_fn`` as an S-stage pipeline over microbatches of ``x``.

    Args:
        stage_fn: ``(params_for_one_stage, x_mb) -> y_mb``, shape-preserving.
        stage_params: pytree whose leaves have leading dim ``num_stages``
            (see :func:`stack_stage_params`), sharded on ``axis``.
        x: ``[batch, ...]`` global input; batch must divide by
            ``num_microbatches`` (and the data axes).
    Returns:
        ``[batch, ...]`` output, equal to applying all stages sequentially.
    """
    S = mesh.shape[axis]
    M = num_microbatches
    if S == 1:
        def seq_fn(params, x):
            for i in range(params_leading_dim(stage_params)):
                x = stage_fn(jax.tree.map(lambda p: p[i], stage_params), x)
            return x
        return seq_fn(stage_params, x)
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M
    x_mb = x.reshape(M, mb, *x.shape[1:])

    def per_stage(params_local, x_mb):
        # Stage fns may run model code containing global sharding
        # constraints; inside shard_map those don't apply.
        with mesh_lib.no_constrain():
            return _per_stage_body(params_local, x_mb)

    def _per_stage_body(params_local, x_mb):
        # shard_map gives the local stage slice with leading dim 1: drop it.
        params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params_local)
        stage = jax.lax.axis_index(axis)
        act_shape = x_mb.shape[1:]
        buf = jnp.zeros(act_shape, x_mb.dtype)        # activation entering this stage
        outs = jnp.zeros_like(x_mb)                   # collected on the last stage

        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        run_stage = (jax.checkpoint(stage_fn, prevent_cse=False)
                     if remat_stages else stage_fn)

        def tick(t, carry):
            buf, outs = carry
            mb_idx = t - stage
            # Stage 0 reads microbatch t from the input; others read buf.
            src = jnp.where(stage == 0,
                            jax.lax.dynamic_index_in_dim(
                                x_mb, jnp.clip(t, 0, M - 1), keepdims=False),
                            buf)
            active = (mb_idx >= 0) & (mb_idx < M)
            # Fill/drain ticks skip the stage compute entirely (the ring
            # still rotates, keeping every device in lockstep).
            y = jax.lax.cond(active, lambda p, s: run_stage(p, s),
                             lambda p, s: jnp.zeros_like(s), params, src)
            # Last stage stores its (valid) result.
            is_last = stage == S - 1
            outs = jnp.where(
                (active & is_last),
                jax.lax.dynamic_update_index_in_dim(
                    outs, y, jnp.clip(mb_idx, 0, M - 1), axis=0),
                outs)
            # Scoped so the stage-hop traffic is attributable in the AOT
            # comms census and sanctioned by graftlint GL105.
            with jax.named_scope("pp_stage_shift"):
                buf = jax.lax.ppermute(y, axis, fwd_perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, M + S - 1, tick, (buf, outs))
        # Replicate the last stage's outputs across the stage axis so the
        # result is stage-replicated (out_spec has no stage entry).
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    batch_spec = P(None, batch_axes, *([None] * (x.ndim - 1)))
    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    out = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(param_specs, batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(stage_params, x_mb)
    return out.reshape(B, *x.shape[1:])


def params_leading_dim(tree) -> int:
    return jax.tree.leaves(tree)[0].shape[0]


def sequential_apply(stage_fn, stage_params, x):
    """The single-device oracle: all stages applied in order."""
    S = params_leading_dim(stage_params)
    for i in range(S):
        x = stage_fn(jax.tree.map(lambda p: p[i], stage_params), x)
    return x

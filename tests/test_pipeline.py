"""Pipeline (GPipe/shard_map) vs its sequential oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.parallel import pipeline as pp

D = 16


def _stage_fn(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return x + h @ params["w2"]


def _stage_params(n_stages, seed=0):
    r = np.random.RandomState(seed)
    per = [
        {"w1": jnp.asarray(r.randn(D, 32) * 0.1, jnp.float32),
         "b1": jnp.zeros(32, jnp.float32),
         "w2": jnp.asarray(r.randn(32, D) * 0.1, jnp.float32)}
        for _ in range(n_stages)
    ]
    return pp.stack_stage_params(per)


@pytest.mark.parametrize("mesh_cfg,microbatches", [
    ({"stage": 8}, 8),
    ({"stage": 4, "data": 2}, 8),
    ({"stage": 2, "data": 2, "fsdp": 2}, 4),
])
def test_pipeline_matches_sequential(devices, mesh_cfg, microbatches):
    mesh = mesh_lib.build_mesh(mesh_cfg)
    S = mesh.shape["stage"]
    params = _stage_params(S)
    x = jnp.asarray(np.random.RandomState(1).randn(32, D), jnp.float32)
    ref = pp.sequential_apply(_stage_fn, params, x)
    out = pp.pipeline_apply(_stage_fn, params, x, mesh=mesh,
                            num_microbatches=microbatches)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_grads_match(devices):
    mesh = mesh_lib.build_mesh({"stage": 4, "data": 2})
    params = _stage_params(4)
    x = jnp.asarray(np.random.RandomState(1).randn(16, D), jnp.float32)

    g_ref = jax.grad(lambda p: pp.sequential_apply(_stage_fn, p, x).sum())(params)
    g_out = jax.grad(lambda p: pp.pipeline_apply(
        _stage_fn, p, x, mesh=mesh, num_microbatches=4).sum())(params)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_single_stage_fallback(devices):
    mesh = mesh_lib.build_mesh({"data": 8})
    params = _stage_params(3)
    x = jnp.asarray(np.random.RandomState(1).randn(8, D), jnp.float32)
    ref = pp.sequential_apply(_stage_fn, params, x)
    out = pp.pipeline_apply(_stage_fn, params, x, mesh=mesh, num_microbatches=2)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-6)


@pytest.mark.slow  # ~40-105s compile on the 1-core CI host (r4 suite-budget pass)
def test_pipelined_llama_matches_sequential(devices):
    """Strategy 'pp': full Llama forward/backward through the GPipe schedule
    equals the plain scan-layers model."""
    from pytorch_distributed_training_example_tpu.core import optim, train_loop
    from pytorch_distributed_training_example_tpu.data import prefetch
    from pytorch_distributed_training_example_tpu.models import llama as llama_lib
    from pytorch_distributed_training_example_tpu.parallel import pp_lm
    from pytorch_distributed_training_example_tpu.utils.config import Config

    module = llama_lib.llama_tiny(scan_layers=True, num_layers=4)
    cfg = Config(lr=1e-2, warmup_epochs=0.0, optimizer="sgd", weight_decay=0.0)
    tx, _ = optim.build_optimizer(cfg, steps_per_epoch=10)
    r = np.random.RandomState(0)
    toks = r.randint(0, 512, (16, 33)).astype(np.int32)
    batch_np = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    task = train_loop.get_task("lm")
    step = jax.jit(train_loop.make_train_step(task), donate_argnums=0)

    def run(mesh, model, rules):
        state = train_loop.create_train_state(
            model, tx, (jnp.zeros((2, 32), jnp.int32),), mesh, rules, seed=0)
        with mesh_lib.use_mesh(mesh):
            b = prefetch.shard_batch(batch_np, mesh_lib.batch_sharding(mesh))
            state, m = step(state, b)
            b = prefetch.shard_batch(batch_np, mesh_lib.batch_sharding(mesh))
            state, m2 = step(state, b)
        return float(m["loss"]), float(m2["loss"])

    ref_mesh = mesh_lib.single_device_mesh()
    ref = run(ref_mesh, module, ())

    pp_mesh = mesh_lib.build_mesh({"stage": 4, "data": 2})
    wrapper = pp_lm.PipelinedLlama(module, pp_mesh, num_microbatches=4)
    got = run(pp_mesh, wrapper, pp_lm.PP_RULES)

    # stacked block params shard over 'stage'
    assert np.isclose(ref[0], got[0], rtol=1e-4), (ref, got)
    assert np.isclose(ref[1], got[1], rtol=1e-3), (ref, got)

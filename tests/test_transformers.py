"""Transformer model family: shapes, TP/FSDP-TP equivalence, remat, CP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.core import optim, train_loop
from pytorch_distributed_training_example_tpu.data import prefetch
from pytorch_distributed_training_example_tpu.models import registry
from pytorch_distributed_training_example_tpu.parallel import sharding as sharding_lib
from pytorch_distributed_training_example_tpu.utils.config import Config

SEQ = 64


def _lm_batch(n=8, seed=0, vocab=512):
    r = np.random.RandomState(seed)
    toks = r.randint(0, vocab, (n, SEQ + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def _build(model_name, mesh, strategy, seq_len=SEQ, **model_kw):
    # SGD for the equivalence oracle: Adam's per-element normalization turns
    # benign reduction-order noise (~1e-6) on near-zero grads into full-lr
    # sign flips, which is a property of Adam, not of the sharding.
    cfg = Config(lr=1e-2, warmup_epochs=0.0, optimizer="sgd", grad_clip=0.0,
                 weight_decay=0.0)
    bundle = registry.create_model(model_name, seq_len=seq_len,
                                   dtype=jnp.float32, param_dtype=jnp.float32,
                                   sp=strategy.endswith("_sp"), **model_kw)
    tx, _ = optim.build_optimizer(cfg, steps_per_epoch=100)
    rules = sharding_lib.strategy_rules(strategy, bundle.rules)
    state = train_loop.create_train_state(bundle.module, tx,
                                          bundle.input_template, mesh, rules,
                                          seed=0)
    task = train_loop.get_task(bundle.task)
    step = jax.jit(train_loop.make_train_step(task), donate_argnums=0)
    return state, step


def _run(model_name, mesh, strategy, n_steps=2, **model_kw):
    state, step = _build(model_name, mesh, strategy, **model_kw)
    with mesh_lib.use_mesh(mesh):
        sh = mesh_lib.batch_sharding(mesh)
        for i in range(n_steps):
            batch = prefetch.shard_batch(_lm_batch(seed=i), sh)
            state, metrics = step(state, batch)
        params = jax.device_get(state.params)
    return params, {k: float(v) for k, v in metrics.items()}


@pytest.mark.parametrize("model_name", ["gpt2_tiny", "llama_tiny"])
@pytest.mark.parametrize("mesh_cfg,strategy", [
    ({"data": 2, "model": 4}, "fsdp_tp"),
    ({"data": 2, "fsdp": 2, "model": 2}, "fsdp_tp"),
])
def test_tp_matches_single_device(devices, model_name, mesh_cfg, strategy):
    ref_params, ref_m = _run(model_name, mesh_lib.single_device_mesh(), "dp")
    par_params, par_m = _run(model_name, mesh_lib.build_mesh(mesh_cfg), strategy)
    assert np.isclose(ref_m["loss"], par_m["loss"], rtol=1e-3), (ref_m, par_m)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(par_params)):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-4)


def test_tp_actually_shards(devices):
    mesh = mesh_lib.build_mesh({"data": 2, "model": 4})
    state, _ = _build("llama_tiny", mesh, "fsdp_tp")
    shardings = {
        sharding_lib.param_path(p): leaf.sharding.spec
        for p, leaf in jax.tree_util.tree_leaves_with_path(state.params)
    }
    qk = [s for p, s in shardings.items() if "query/kernel" in p]
    assert qk and all("model" in str(s) for s in qk), shardings


def test_context_parallel_train_step(devices):
    """Ring attention engages via mesh shape alone (context axis > 1)."""
    mesh = mesh_lib.build_mesh({"data": 2, "context": 4})
    ref_params, ref_m = _run("llama_tiny", mesh_lib.single_device_mesh(), "dp")
    par_params, par_m = _run("llama_tiny", mesh, "dp")
    assert np.isclose(ref_m["loss"], par_m["loss"], rtol=1e-3)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(par_params)):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("model_name", ["gpt2_tiny", "llama_tiny"])
def test_composed_seq_tp_train_step(devices, model_name):
    """The composed mesh, dp x seq x tp on a dense model, one train step
    program: ring attention over 'context' composed with Megatron column/row
    splits over 'model' (under GQA and RoPE for ``llama_tiny``) against the
    single-device oracle."""
    mesh = mesh_lib.build_mesh({"data": 2, "seq": 2, "model": 2})
    ref_params, ref_m = _run(model_name, mesh_lib.single_device_mesh(), "dp")
    par_params, par_m = _run(model_name, mesh, "fsdp_tp")
    assert np.isclose(ref_m["loss"], par_m["loss"], rtol=1e-3), (ref_m, par_m)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(par_params)):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-4)


def test_wpe_shards_over_context(devices):
    """gpt2's position embedding (the one seq-dim param) shards over the
    context axis — SNIPPETS.md [3]'s '"seq": None' TODO, filled."""
    mesh = mesh_lib.build_mesh({"data": 2, "context": 4})
    state, _ = _build("gpt2_tiny", mesh, "fsdp_tp")
    specs = {
        sharding_lib.param_path(p): leaf.sharding.spec
        for p, leaf in jax.tree_util.tree_leaves_with_path(state.params)
    }
    wpe = [s for p, s in specs.items() if "wpe" in p]
    assert wpe and all("context" in str(s) for s in wpe), specs


def test_seq_rules_cover_constrain_sites():
    """The shared activation table carries the sequence dim on 'context' in
    every entry, and folds 'model' in only under SP."""
    from jax.sharding import PartitionSpec as P

    rules = sharding_lib.seq_rules()
    assert set(rules) == {"residual", "qkv", "ffn_hidden", "logits"}
    assert rules["residual"] == P(mesh_lib.BATCH_AXES, "context", None)
    sp = sharding_lib.seq_rules(sp=True)
    assert sp["residual"] == P(mesh_lib.BATCH_AXES, ("context", "model"), None)
    # Matmul-region entries keep 'model' on the hidden/head dim regardless.
    assert sp["qkv"] == rules["qkv"]


def test_ulysses_end_to_end_train_step(devices):
    """Ulysses (all-to-all seq<->head) as the CP implementation of a full
    train step, selected the way a user would: attn_impl='ulysses'."""
    mesh = mesh_lib.build_mesh({"data": 2, "context": 4})
    cfg = Config(lr=1e-2, warmup_epochs=0.0, optimizer="sgd", grad_clip=0.0,
                 weight_decay=0.0)
    # llama_tiny: 4 q-heads / 2 kv-heads over 4 context shards (GQA broadcast
    # path inside ulysses_attention).
    bundle = registry.create_model("llama_tiny", seq_len=SEQ,
                                   dtype=jnp.float32, param_dtype=jnp.float32,
                                   attn_impl="ulysses")
    tx, _ = optim.build_optimizer(cfg, steps_per_epoch=100)
    rules = sharding_lib.strategy_rules("dp", bundle.rules)
    state = train_loop.create_train_state(bundle.module, tx,
                                          bundle.input_template, mesh, rules,
                                          seed=0)
    step = jax.jit(train_loop.make_train_step(train_loop.get_task("lm")),
                   donate_argnums=0)
    with mesh_lib.use_mesh(mesh):
        sh = mesh_lib.batch_sharding(mesh)
        for i in range(2):
            state, m = step(state, prefetch.shard_batch(_lm_batch(seed=i), sh))
        params = jax.device_get(state.params)
    # oracle: same run on one device with plain attention
    ref_params, ref_m = _run("llama_tiny", mesh_lib.single_device_mesh(), "dp")
    assert np.isclose(ref_m["loss"], float(m["loss"]), rtol=1e-3)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(params)):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-4)


def test_sp_matches_non_sp(devices):
    """Megatron SP is a resharding of activations, not a different program:
    loss/params must match the plain TP run exactly (SURVEY.md §2c SP)."""
    mesh = mesh_lib.build_mesh({"data": 2, "model": 4})
    ref_params, ref_m = _run("llama_tiny", mesh, "fsdp_tp")
    sp_params, sp_m = _run("llama_tiny", mesh, "fsdp_tp_sp")
    assert np.isclose(ref_m["loss"], sp_m["loss"], rtol=1e-4), (ref_m, sp_m)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(sp_params)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-4)


def test_sp_reduces_activation_memory(devices):
    """The point of SP: residual-stream activations between matmul regions
    shard over the TP axis -> per-device temp memory drops."""
    mesh = mesh_lib.build_mesh({"model": 8})
    seq = 256

    def temp_bytes(strategy):
        state, step = _build("llama_tiny", mesh, strategy, seq_len=seq)
        r = np.random.RandomState(0)
        toks = r.randint(0, 512, (8, seq + 1)).astype(np.int32)
        with mesh_lib.use_mesh(mesh):
            batch = prefetch.shard_batch(
                {"tokens": toks[:, :-1], "targets": toks[:, 1:]},
                mesh_lib.batch_sharding(mesh))
            compiled = step.lower(state, batch).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    plain, sp = temp_bytes("fsdp_tp"), temp_bytes("fsdp_tp_sp")
    assert sp < plain * 0.9, (sp, plain)


def test_remat_matches_no_remat(devices):
    mesh = mesh_lib.build_mesh({"data": 8})
    bundle = registry.create_model("llama_tiny", seq_len=SEQ,
                                   dtype=jnp.float32, param_dtype=jnp.float32)
    bundle_r = registry.create_model("llama_tiny", seq_len=SEQ,
                                     dtype=jnp.float32, param_dtype=jnp.float32,
                                     remat=True)
    cfg = Config(lr=1e-2, warmup_epochs=0.0, optimizer="adamw")
    tx, _ = optim.build_optimizer(cfg, steps_per_epoch=10)
    rules = sharding_lib.strategy_rules("dp", bundle.rules)
    s1 = train_loop.create_train_state(bundle.module, tx, bundle.input_template,
                                       mesh, rules, seed=0)
    s2 = train_loop.create_train_state(bundle_r.module, tx, bundle.input_template,
                                       mesh, rules, seed=0)
    task = train_loop.get_task("lm")
    step = jax.jit(train_loop.make_train_step(task), donate_argnums=0)
    with mesh_lib.use_mesh(mesh):
        b = prefetch.shard_batch(_lm_batch(), mesh_lib.batch_sharding(mesh))
        _, m1 = step(s1, b)
        b = prefetch.shard_batch(_lm_batch(), mesh_lib.batch_sharding(mesh))
        _, m2 = step(s2, b)
    assert np.isclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)


def test_vit_tp_matches_single_device(devices):
    """ViT family TP rules: fsdp_tp equals the single-device oracle."""
    def run(mesh, strategy):
        cfg = Config(lr=1e-2, warmup_epochs=0.0, optimizer="sgd",
                     grad_clip=0.0, weight_decay=0.0)
        bundle = registry.create_model("vit_tiny", num_classes=10,
                                       image_size=32, dtype=jnp.float32,
                                       param_dtype=jnp.float32)
        tx, _ = optim.build_optimizer(cfg, steps_per_epoch=100)
        rules = sharding_lib.strategy_rules(strategy, bundle.rules)
        state = train_loop.create_train_state(
            bundle.module, tx, bundle.input_template, mesh, rules, seed=0)
        step = jax.jit(train_loop.make_train_step(
            train_loop.get_task(bundle.task)), donate_argnums=0)
        r = np.random.RandomState(0)
        b = {"image": r.randn(16, 32, 32, 3).astype(np.float32),
             "label": (np.arange(16) % 10).astype(np.int32)}
        with mesh_lib.use_mesh(mesh):
            state, m = step(state, prefetch.shard_batch(
                b, mesh_lib.batch_sharding(mesh)))
            return jax.device_get(state.params), float(m["loss"])

    ref_params, ref_loss = run(mesh_lib.single_device_mesh(), "dp")
    par_params, par_loss = run(mesh_lib.build_mesh({"data": 2, "model": 4}),
                               "fsdp_tp")
    assert np.isclose(ref_loss, par_loss, rtol=1e-3)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(par_params)):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-4)


def test_vit_train_step(devices):
    mesh = mesh_lib.build_mesh({"data": 8})
    cfg = Config(lr=1e-3, optimizer="adamw")
    bundle = registry.create_model("vit_tiny", num_classes=10, image_size=32,
                                   dtype=jnp.float32, param_dtype=jnp.float32)
    tx, _ = optim.build_optimizer(cfg, steps_per_epoch=10)
    rules = sharding_lib.strategy_rules("dp", bundle.rules)
    state = train_loop.create_train_state(bundle.module, tx,
                                          bundle.input_template, mesh, rules,
                                          seed=0)
    task = train_loop.get_task(bundle.task)
    step = jax.jit(train_loop.make_train_step(task), donate_argnums=0)
    r = np.random.RandomState(0)
    batch = {"image": r.randn(16, 32, 32, 3).astype(np.float32),
             "label": (np.arange(16) % 10).astype(np.int32)}
    with mesh_lib.use_mesh(mesh):
        b = prefetch.shard_batch(batch, mesh_lib.batch_sharding(mesh))
        state, m = step(state, b)
    assert np.isfinite(m["loss"])


def test_gpt2_param_count():
    from pytorch_distributed_training_example_tpu.models import gpt2

    assert abs(gpt2.num_params(gpt2.gpt2_124m()) - 124.4e6) < 1e6


def test_scan_layers_runs_with_tp_rules(devices):
    """nn.scan-stacked Llama trains; stacked params get rank-shifted TP specs."""
    from pytorch_distributed_training_example_tpu.models import llama

    mesh = mesh_lib.build_mesh({"model": 2, "fsdp": 2, "data": 2})
    module = llama.llama_tiny(scan_layers=True, num_layers=3)
    cfg = Config(lr=1e-2, warmup_epochs=0.0)
    tx, _ = optim.build_optimizer(cfg, steps_per_epoch=10)
    state = train_loop.create_train_state(
        module, tx, (jnp.zeros((2, SEQ), jnp.int32),), mesh,
        llama.TP_RULES, seed=0)
    qk = state.params["blocks"]["block"]["attn"]["query"]["kernel"]
    assert qk.ndim == 4 and "model" in str(qk.sharding.spec)
    step = jax.jit(train_loop.make_train_step(train_loop.get_task("lm")),
                   donate_argnums=0)
    with mesh_lib.use_mesh(mesh):
        b = prefetch.shard_batch(_lm_batch(), mesh_lib.batch_sharding(mesh))
        state, m = step(state, b)
    assert np.isfinite(float(m["loss"]))

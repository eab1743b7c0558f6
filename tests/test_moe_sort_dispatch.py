"""Sort-based MoE dispatch vs the gather and einsum oracles.

``dispatch_impl="sort"`` (argsort by expert id + segment offsets,
MegaBlocks-style) replaces the one-hot/scatter formulations on perf grounds
only, so it must reproduce them EXACTLY: same routing decisions, same
capacity-overflow drops (priority: k=0 choices before k=1, earlier tokens
first), same outputs and gradients. The EP suite at the bottom also guards
the SPMD gather miscompile worked around in parallel/moe.py
(_combine/_sort_route pin gather operands replicated — without that, the
partitioner silently produces wrong VALUES for gathers with sharded
operands).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib
from pytorch_distributed_training_example_tpu.parallel import sharding as sharding_lib

D = 16


def _blocks(E, k, cf, **kw):
    def mk(impl):
        return moe_lib.MoEBlock(num_experts=E, ffn_dim=32, top_k=k,
                                capacity_factor=cf, dispatch_impl=impl, **kw)
    return mk("sort"), mk("gather"), mk("einsum")


def _x(seed=7, b=2, t=32):
    return jnp.asarray(np.random.RandomState(seed).randn(b, t, D), jnp.float32)


@pytest.mark.parametrize("E,k,cf", [
    (4, 2, 2.0),    # no overflow: every routed token fits
    (4, 2, 0.5),    # heavy overflow: the drop priority is exercised
    (4, 1, 1.0),    # top-1 (Switch) regime
    (8, 2, 0.25),   # many experts, tiny capacity
])
def test_sort_matches_gather_and_einsum(E, k, cf):
    """Forward + param/input grads agree across all three formulations."""
    s, g, e = _blocks(E, k, cf)
    x = _x()
    variables = {"params": g.init(jax.random.PRNGKey(0), x)["params"]}

    outs, grads = {}, {}
    for name, block in (("sort", s), ("gather", g), ("einsum", e)):
        outs[name] = block.apply(variables, x)

        def loss(p, xx, block=block):
            return jnp.sum(block.apply({"params": p}, xx) ** 2)

        grads[name] = jax.grad(loss, argnums=(0, 1))(variables["params"], x)
    for other in ("gather", "einsum"):
        np.testing.assert_allclose(np.asarray(outs["sort"]),
                                   np.asarray(outs[other]),
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(grads["sort"]),
                        jax.tree.leaves(grads[other])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def test_sort_overflow_drops_same_tokens():
    """Under overflow the sort path drops the SAME tokens as the legacy
    paths (zero output rows match positionally), and some are dropped."""
    s, g, _ = _blocks(E=2, k=1, cf=0.25)
    x = _x(seed=0, b=2, t=16)
    variables = {"params": g.init(jax.random.PRNGKey(0), x)["params"]}
    zero_s = np.abs(np.asarray(s.apply(variables, x))).max(-1) == 0.0
    zero_g = np.abs(np.asarray(g.apply(variables, x))).max(-1) == 0.0
    assert zero_s.sum() > 0
    np.testing.assert_array_equal(zero_s, zero_g)


def test_bf16_combine_parity():
    """combine_dtype=bf16 changes only the combine einsum's precision: the
    output must track the fp32-combine result to bf16 resolution."""
    ref = moe_lib.MoEBlock(num_experts=4, ffn_dim=32, top_k=2,
                           capacity_factor=2.0, dispatch_impl="sort")
    b16 = moe_lib.MoEBlock(num_experts=4, ffn_dim=32, top_k=2,
                           capacity_factor=2.0, dispatch_impl="sort",
                           combine_dtype=jnp.bfloat16)
    x = _x(seed=11)
    variables = {"params": ref.init(jax.random.PRNGKey(0), x)["params"]}
    a = np.asarray(ref.apply(variables, x))
    b = np.asarray(b16.apply(variables, x))
    # bf16 eps = 2^-8; the combine is a k=2 weighted sum, so a few ULP
    np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-2)


def test_sort_expert_parallel_matches_replicated(devices):
    """Sort dispatch under an expert×data mesh == unsharded oracle, forward
    AND grads. This is the regression guard for the sharded-
    operand gather miscompile (see module docstring)."""
    block = moe_lib.MoEBlock(num_experts=4, ffn_dim=32, top_k=2,
                             capacity_factor=2.0, dispatch_impl="sort")
    x = _x(seed=0, b=4, t=8)
    variables = {"params": block.init(jax.random.PRNGKey(0), x)["params"]}
    ref = block.apply(variables, x)

    def loss(p, xx):
        return jnp.sum(block.apply({"params": p}, xx) ** 2)

    g_ref = jax.grad(loss)(variables["params"], x)

    mesh = mesh_lib.build_mesh({"expert": 4, "data": 2})
    shardings = sharding_lib.make_shardings(variables["params"], mesh,
                                            moe_lib.EP_RULES)
    params_sharded = jax.tree.map(jax.device_put, variables["params"],
                                  shardings)
    assert "expert" in str(params_sharded["experts"]["w_up"].sharding.spec)
    with mesh_lib.use_mesh(mesh):
        out = jax.jit(lambda p, xx: block.apply({"params": p}, xx))(
            params_sharded, x)
        g_out = jax.jit(jax.grad(loss))(params_sharded, x)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_sort_dispatch_llama_gqa_fsdp_ep(devices):
    """Full MoE-Llama (GQA trunk) one train step under fsdp×ep: the sort
    and gather programs produce the same loss and the same updated params
    through the registry -> config plumbing."""
    from pytorch_distributed_training_example_tpu.core import optim, train_loop
    from pytorch_distributed_training_example_tpu.data import prefetch
    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.utils.config import Config

    mesh = mesh_lib.build_mesh({"data": 2, "fsdp": 2, "expert": 2})
    r = np.random.RandomState(0)
    toks = r.randint(0, 512, (8, 33)).astype(np.int32)
    results = {}
    for impl in ("gather", "sort"):
        bundle = registry.create_model("llama_moe_tiny", seq_len=32,
                                       dtype=jnp.float32,
                                       param_dtype=jnp.float32,
                                       moe_dispatch_impl=impl)
        tx, _ = optim.build_optimizer(
            Config(lr=1e-2, warmup_epochs=0.0, optimizer="sgd",
                   weight_decay=0.0), steps_per_epoch=10)
        rules = sharding_lib.strategy_rules("fsdp_tp", bundle.rules)
        state = train_loop.create_train_state(bundle.module, tx,
                                              bundle.input_template, mesh,
                                              rules, seed=0)
        step = jax.jit(train_loop.make_train_step(train_loop.get_task("lm")),
                       donate_argnums=0)
        with mesh_lib.use_mesh(mesh):
            b = prefetch.shard_batch(
                {"tokens": toks[:, :-1], "targets": toks[:, 1:]},
                mesh_lib.batch_sharding(mesh))
            state, m = step(state, b)
        results[impl] = (float(m["loss"]),
                         np.asarray(state.params["block_0"]["moe"]["experts"]
                                    ["w_up"]))
    assert np.isfinite(results["sort"][0])
    np.testing.assert_allclose(results["sort"][0], results["gather"][0],
                               rtol=1e-5)
    np.testing.assert_allclose(results["sort"][1], results["gather"][1],
                               rtol=1e-4, atol=1e-5)

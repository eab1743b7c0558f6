"""The ``glm_moe_lite`` family (``models/glm_moe_lite.py``: latent attention,
``parallel/moe.SharedExpertMoE`` as it is, the multi-token-prediction module
riding the ``losses`` collection) and the online flash kernels' block plan at
head width 256: the model against the benchmark's plain reference (loss, every
leaf's gradient, three AdamW steps, the biases after them), the latent pair
against the training form's keys and values, the second depth's positions and
targets, the published entry and the chip's share, the eight shares adding up
to the uncut layer, and the preset through the ``Trainer``. Float32 on the CPU
at toy widths."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import weights  # noqa: E402
from chipbench.references import glm47_flash as reference  # noqa: E402
from pytorch_distributed_training_example_tpu.core import (  # noqa: E402
    mesh as mesh_lib, train_loop)
from pytorch_distributed_training_example_tpu.core.trainer import Trainer  # noqa: E402
from pytorch_distributed_training_example_tpu.models import (  # noqa: E402
    glm_moe_lite, registry)
from pytorch_distributed_training_example_tpu.ops import (  # noqa: E402
    attention as attn_lib, flash_attention as flash_lib)
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.utils.config import from_preset  # noqa: E402

HIGHEST = jax.default_matmul_precision("highest")
RULES = [["scale$", "const", 1.0], [".*", "normal", 0.02]]


def _model_dict(module: glm_moe_lite.GlmMoeLite) -> dict:
    """The reference's ``model`` group for a program module."""
    held, first = module.held_experts or (module.num_experts, 0)
    return {
        "hidden_size": module.d_model,
        "num_attention_heads": module.num_heads,
        "q_lora_rank": module.q_rank, "kv_lora_rank": module.kv_rank,
        "qk_nope_head_dim": module.nope_dim,
        "qk_rope_head_dim": module.rope_dim, "v_head_dim": module.v_dim,
        "intermediate_size": module.dense_ffn_dim,
        "moe_intermediate_size": module.expert_ffn_dim,
        "n_routed_experts": held, "held_experts_start": first,
        "routed_experts": module.num_experts,
        "num_experts_per_tok": module.top_k,
        "n_shared_experts": module.shared_experts,
        "first_k_dense_replace": module.num_dense_layers,
        "num_hidden_layers": module.num_layers,
        "num_nextn_predict_layers": module.mtp_layers,
        "mtp_loss_coeff": module.mtp_coeff,
        "held_layers": list(range(module.num_layers)),
        "rope_theta": module.rope_theta, "rms_norm_eps": module.epsilon,
        "routed_scaling_factor": module.route_scale,
        "load_balance_coeff": module.balance_coeff,
        "vocab_size": module.vocab_size}


def _seeded(module, S, seed=3, batch=2):
    tokens = jax.random.randint(jax.random.key(seed), (batch, S + 1), 0,
                                module.vocab_size)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens[:, :-1], train=False))
    params = weights.make_like(shapes["params"], RULES, weights.seed_key(seed))
    stats = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["batch_stats"])
    return params, stats, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def _biases(stats, module):
    """The reference's ``[blocks + mtp, routed]`` biases from the program's."""
    none = jnp.zeros((module.num_experts,))
    rows = [stats.get(f"block_{i}", {}).get("moe", {}).get("expert_bias", none)
            for i in range(module.num_layers)]
    if module.mtp_layers:
        rows.append(stats["mtp"]["mtp_block"]["moe"]["expert_bias"])
    return jnp.stack(rows)


def _program(module, stats, batch):
    """``p -> (loss as the step adds it up, new batch_stats)``."""
    task = train_loop.get_task("lm")

    def program(p):
        logits, new = module.apply({"params": p, "batch_stats": stats},
                                   batch["tokens"], train=True,
                                   mutable=["batch_stats", "losses"])
        loss = task.loss(logits, batch)
        for aux in jax.tree.leaves(new.get("losses", {})):
            loss = loss + aux
        return loss, new["batch_stats"]
    return program


def _moved(stats):
    """A bias that changes who is chosen, so that a test sees it."""
    return jax.tree.map(lambda b: 0.3 * jnp.cos(jnp.arange(b.size) * 1.7),
                        stats)


# -- the model against the plain reference --------------------------------------


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "share"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_the_plain_reference(held, remat):
    """Both depths' loss, every leaf's gradient and every bias after the
    step, in float32. The tolerances are float32 rounding through four
    blocks (the afmoe test's): 1e-5 on the loss, 2e-3 of a leaf's largest
    entry on a gradient."""
    module = glm_moe_lite.glm_moe_lite_tiny(remat=remat, held_experts=held)
    params, stats, batch = _seeded(module, 48)
    stats = _moved(stats)
    model = _model_dict(module)
    with HIGHEST:
        (loss, new_stats), grads = jax.jit(jax.value_and_grad(
            _program(module, stats, batch), has_aux=True))(params)
        flat = weights.flatten(params)
        (want_loss, (counts, main, mtp)), want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, _biases(stats, module), batch,
                                        model), has_aux=True))(flat)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(want_loss, main + 0.3 * mtp, rtol=1e-6)
    assert abs(float(mtp) - np.log(96)) < 0.2    # a loss of its own size
    grads = weights.flatten(grads)
    assert set(grads) == set(want)
    for path, g in grads.items():
        scale = float(jnp.max(jnp.abs(want[path])))
        assert scale > 0, path  # every leaf is alive at this init
        np.testing.assert_allclose(g, want[path], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=path)
    np.testing.assert_allclose(
        _biases(new_stats, module),
        reference.next_biases(_biases(stats, module), counts, model),
        atol=1e-7)
    # two expert blocks and the module's, every row of every sequence
    assert float(jnp.sum(counts)) == 3 * 2 * 48 * module.top_k
    assert float(jnp.sum(counts[0])) == 0        # the dense block counts none


def test_three_adamw_steps_and_the_biases_after_them():
    """The step the ``Trainer`` builds (``make_train_step`` with the preset's
    AdamW chain) for three steps against the reference's own three: each
    step's loss, every leaf's change, every bias."""
    cfg = from_preset("glm47_flash_share", model="glm_moe_lite_tiny",
                      seq_len=32, global_batch_size=8, precision="fp32",
                      lr=3e-4, lr_schedule="constant", warmup_epochs=0.0,
                      workers=0, steps_per_epoch=4, log_every=1000,
                      checkpoint_dir=None, mesh_fsdp=1, mesh_data=8,
                      strategy="dp", remat=False)
    trainer = Trainer(cfg)
    module = trainer.bundle.module
    key = weights.seed_key(11)
    start = jax.device_get(jax.jit(lambda k: weights.make_like(
        jax.eval_shape(lambda: trainer.state.params), RULES, k))(key))
    trainer.state = trainer.state.replace(params=jax.device_put(
        start, jax.tree.map(lambda x: x.sharding, trainer.state.params)))
    tokens = np.asarray(jax.random.randint(jax.random.key(5), (3, 8, 33), 0,
                                           module.vocab_size))
    batches = [{"tokens": t[:, :-1], "targets": t[:, 1:]} for t in tokens]
    config = {"model": _model_dict(module), "reference_micro_batch": 1,
              "optimizer": {"kind": "adamw", "lr": 3e-4, "b1": 0.9,
                            "b2": 0.95, "eps": 1e-8,
                            "weight_decay": cfg.weight_decay,
                            "grad_clip": cfg.grad_clip,
                            "first_moment_scale": 1.0}}
    losses, state = [], trainer.state
    with HIGHEST, mesh_lib.use_mesh(trainer.mesh):
        for batch in batches:
            state, metrics = trainer.train_step(state, {
                k: jax.device_put(v, trainer.batch_sharding)
                for k, v in batch.items()})
            losses.append(float(metrics["loss"]))
    want = reference.run(config, weights.flatten(start), batches)
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    assert want["loss"][0] == pytest.approx(
        want["loss_main"][0] + 0.3 * want["loss_mtp"][0], rel=1e-6)
    moved = weights.flatten(jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
        jax.device_get(state.params), start))
    assert set(moved) == set(want["dparam_norms"])
    for path, norm in moved.items():
        # Adam's first steps are sign-like: a gradient entry near zero may
        # step either way, so a leaf's change agrees to a few percent
        assert float(norm) == pytest.approx(want["dparam_norms"][path],
                                            rel=5e-2), path
    got = _biases(jax.device_get(state.batch_stats), module)
    np.testing.assert_allclose(got, want["biases"], atol=1e-6)
    assert float(np.abs(want["biases"][1:]).max()) > 0.05   # they moved
    assert not np.abs(want["biases"][0]).any()


@pytest.mark.parametrize("left_out,change", [
    ("the shared expert", {"n_shared_experts": 0}),
    ("the bias in the choice", "zero_bias"),
    ("the prediction module's loss", {"mtp_loss_coeff": 0.0}),
    ("the rotary term", {"rope_theta": 1.0 + 1e-9}),
    ("the scaling factor", {"routed_scaling_factor": 1.0})])
def test_reference_sees_what_a_step_leaves_out(left_out, change):
    """The comparison's other side: a reference without the piece is not the
    program's model."""
    module = glm_moe_lite.glm_moe_lite_tiny()
    params, stats, batch = _seeded(module, 48)
    # livelier weights than the 0.02 of the other tests, so that the loss
    # itself feels each piece
    params = weights.make_like(params, [["scale$", "const", 1.0],
                                        [".*", "normal", 0.2]],
                               weights.seed_key(3))
    stats = _moved(stats)
    model, biases = _model_dict(module), _biases(stats, module)
    if change == "zero_bias":
        biases = jnp.zeros_like(biases)
    else:
        model = dict(model, **change)
    with HIGHEST:
        loss, _ = jax.jit(_program(module, stats, batch))(params)
        want, _ = jax.jit(lambda p: reference.loss_fn(
            p, biases, batch, model))(weights.flatten(params))
    assert abs(float(loss) - float(want)) > 1e-4 * float(want), left_out


# -- the second depth --------------------------------------------------------------


def test_mtp_positions_and_targets_are_the_ones_stated():
    """``L_mtp`` written out: position ``i`` of ``0..S-3`` merges the main
    model's normed output at ``i`` with the embedding of ``tokens[i + 1]`` and
    is scored against ``tokens[i + 2]``; ``S - 2`` positions a sequence; what
    stands behind the last token does not reach the loss."""
    module = glm_moe_lite.glm_moe_lite_tiny()
    params, stats, batch = _seeded(module, 24)
    model, flat = _model_dict(module), weights.flatten(params)
    tokens = batch["tokens"]
    S = tokens.shape[1]
    scored = jnp.broadcast_to(
        (jnp.arange(S) < S - 2).astype(jnp.float32), tokens.shape)
    sow = jax.jit(lambda toks: module.apply(
        {"params": params, "batch_stats": stats}, toks, train=True,
        mutable=["losses", "telemetry"])[1])

    @jax.jit
    def by_hand_fn(flat):
        biases = _biases(stats, module)
        normed, _ = reference.hidden_fn(flat, biases, tokens, model)
        x, _ = reference.mtp_hidden_fn(flat, biases[-1], normed, tokens, model)
        logits = x @ flat["lm_head/kernel"]
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, jnp.roll(tokens, -2, axis=1)[..., None], -1)[..., 0]
        # what reaches the loss of each merged row
        reach = jax.grad(lambda e: reference.head_loss(
            e, reference.ahead(tokens, 2), scored, flat["lm_head/kernel"],
            lambda a: a))(x)
        return jnp.mean(ce[:, :S - 2]), reach

    with HIGHEST:
        sown = sow(tokens)
        (mtp,) = sown["losses"]["mtp_loss"]
        by_hand, last_two = by_hand_fn(flat)
    np.testing.assert_allclose(mtp, 0.3 * by_hand, rtol=1e-5)
    np.testing.assert_allclose(sown["telemetry"]["loss_mtp"][0], by_hand,
                               rtol=1e-5)
    np.testing.assert_array_equal(reference.ahead(tokens, 1)[:, :-1],
                                  batch["targets"][:, :-1])
    np.testing.assert_array_equal(reference.ahead(tokens, 2)[:, :-2],
                                  batch["targets"][:, 1:-1])
    # the last token is t_{i+2} of position S-3, which is scored: another id
    # there is another loss ...
    other = tokens.at[:, -1].set((tokens[:, -1] + 1) % module.vocab_size)
    with HIGHEST:
        assert float(sow(other)["losses"]["mtp_loss"][0]) != float(mtp)
    # ... but position S-1's merged row (id 0 behind the last) and position
    # S-2's are scored by nothing
    assert not np.asarray(last_two[:, S - 2:]).any()
    assert np.asarray(last_two[:, :S - 2]).any()


def test_with_no_mtp_weight_the_gradients_are_the_main_models():
    """``mtp_coeff`` 0: the loss and every shared leaf's gradient are those of
    the model without the module, and the module's own leaves get none; with
    the weight, the embedding and the head get both depths' sum."""
    module = glm_moe_lite.glm_moe_lite_tiny(num_layers=2)
    params, stats, batch = _seeded(module, 16)
    main_only = module.clone(mtp_layers=0)
    with HIGHEST:
        grads = {}
        for name, mod, p in (
                ("both", module, params),
                ("lambda0", module.clone(mtp_coeff=0.0), params),
                ("none", main_only,
                 {k: v for k, v in params.items() if k != "mtp"})):
            s = stats if mod.mtp_layers else {
                k: v for k, v in stats.items() if k != "mtp"}
            grads[name] = jax.jit(jax.grad(
                lambda q: _program(mod, s, batch)(q)[0]))(p)
        # the module's loss alone, its weight applied
        mtp_only = jax.jit(jax.grad(lambda q: jax.tree.leaves(module.apply(
            {"params": q, "batch_stats": stats}, batch["tokens"], train=True,
            mutable=["losses"])[1]["losses"])[0]))(params)
    for path, g in weights.flatten(grads["none"]).items():
        np.testing.assert_allclose(
            weights.flatten(grads["lambda0"])[path], g, atol=1e-7,
            err_msg=path)
    assert not any(np.asarray(g).any()
                   for g in jax.tree.leaves(grads["lambda0"]["mtp"]))
    assert all(np.asarray(g).any()
               for g in jax.tree.leaves(grads["both"]["mtp"]))
    for path in ("embed/embedding", "lm_head/kernel", "final_norm/scale",
                 "block_1/attn/kv_b/kernel"):
        both, main, second = (weights.flatten(g)[path] for g in (
            grads["both"], grads["none"], mtp_only))
        assert float(jnp.max(jnp.abs(second))) > 0, path
        np.testing.assert_allclose(both, main + second, rtol=1e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(both))),
                                   err_msg=path)


def test_evaluation_runs_the_main_model_alone():
    module = glm_moe_lite.glm_moe_lite_tiny()
    params, stats, batch = _seeded(module, 16)
    variables = {"params": params, "batch_stats": stats}
    apply = lambda train: jax.jit(lambda v: module.apply(
        v, batch["tokens"], train=train,
        mutable=["losses", "intermediates"]))(variables)
    logits, sown = apply(False)
    assert "losses" not in sown and "mtp" not in sown["intermediates"]
    trained, sown = apply(True)
    assert "mtp_loss" in sown["losses"] and "mtp" in sown["intermediates"]
    np.testing.assert_array_equal(logits, trained)


# -- latent attention --------------------------------------------------------------


def test_keys_and_values_rebuilt_from_the_latent_pair_are_the_training_forms():
    """What a latent cache will hold: ``(c_kv, k_r)``, 16 + 4 numbers a token
    here against the expanded ``4 x (16 + 16)``; K and V made from the pair
    alone with ``W_kvb`` are the K and V that attention was given."""
    module = glm_moe_lite.glm_moe_lite_tiny()
    params, stats, batch = _seeded(module, 24)
    with HIGHEST:
        _, seen = module.apply({"params": params, "batch_stats": stats},
                               batch["tokens"], train=False,
                               mutable=["intermediates"])
        for block in ("block_0", "block_2"):
            (c_kv, k_r), = seen["intermediates"][block]["attn"]["latent"]
            (k, v), = seen["intermediates"][block]["attn"]["expanded"]
            assert c_kv.shape == (2, 24, 16) and k_r.shape == (2, 24, 4)
            assert k.shape == v.shape == (2, 24, 4, 16)
            up = params[block]["attn"]["kv_b"]["kernel"]     # [16, 4, 12 + 16]
            kv = jnp.einsum("bsr,rhk->bshk", c_kv, up)
            np.testing.assert_allclose(kv[..., :12], k[..., :12], atol=1e-6)
            np.testing.assert_allclose(kv[..., 12:], v, atol=1e-6)
            for head in range(4):       # one rope key, the same in every head
                np.testing.assert_array_equal(k[..., head, 12:], k_r)
            # and the reference makes the same pair from the same input
            w = {p[len(block) + 1:]: a for p, a in
                 weights.flatten(params).items() if p.startswith(block + "/")}
            z = reference._sizes(_model_dict(module))
            rk, rv = reference.expand(c_kv, k_r, w, z, lambda a: a)
            np.testing.assert_allclose(rk, k, atol=1e-6)
            np.testing.assert_allclose(rv, v, atol=1e-6)


def test_rope_key_depends_on_position_and_nope_does_not():
    module = glm_moe_lite.glm_moe_lite_tiny()
    params, stats, batch = _seeded(module, 8)
    same = jnp.broadcast_to(batch["tokens"][:, :1], batch["tokens"].shape)
    _, seen = module.apply({"params": params, "batch_stats": stats}, same,
                           train=False, mutable=["intermediates"])
    (c_kv, k_r), = seen["intermediates"]["block_0"]["attn"]["latent"]
    np.testing.assert_allclose(c_kv[:, 0], c_kv[:, 5], atol=1e-6)
    assert float(jnp.max(jnp.abs(k_r[:, 0] - k_r[:, 5]))) > 1e-4


def test_unequal_widths_take_the_xla_path_and_flash_refuses():
    """ROADMAP B5 (1), done: a value width that differs from the query/key
    width is the dispatcher's business (the online kernels on the chip, the
    XLA path here), and ``attn_impl="flash"`` no longer refuses it."""
    module = glm_moe_lite.glm_moe_lite_tiny(v_dim=8)
    params, stats, batch = _seeded(module, 16)
    out = module.apply({"params": params, "batch_stats": stats},
                       batch["tokens"], train=False)
    assert out.shape == (2, 16, 96) and bool(jnp.isfinite(out).all())
    forced = module.clone(attn_impl="flash").apply(
        {"params": params, "batch_stats": stats}, batch["tokens"],
        train=False)
    np.testing.assert_allclose(forced, out, atol=1e-6)


# -- the online kernels' block plan --------------------------------------------------


@pytest.mark.parametrize("bwd,d,itemsize,want", [
    (False, 64, 2, (1024, 1024)), (True, 64, 2, (1024, 1024)),
    (False, 128, 2, (1024, 1024)), (True, 128, 2, (1024, 1024)),
    (False, 128, 4, (1024, 1024)), (True, 128, 4, (1024, 1024)),
    (False, 256, 2, (1024, 1024)), (True, 256, 2, (1024, 512)),
    (False, 256, 4, (1024, 512)), (True, 256, 4, (512, 512)),
    (False, 512, 2, (512, 512)), (True, 512, 2, (512, 256))])
def test_online_block_rule(bwd, d, itemsize, want):
    """Nothing changes at D <= 128; above, the blocks halve until the
    ``[block, D]`` rows the kernel holds fit what the compiler has taken."""
    assert flash_lib._online_blocks(bwd, 2048, d, 1024, 1024, itemsize) == want
    held = flash_lib._online_held(bwd, *want, d, itemsize)
    assert held <= flash_lib.ONLINE_HELD_MAX == 7 * 2 ** 20


def test_online_block_rule_leaves_a_callers_choice_and_a_measured_row(
        monkeypatch):
    assert flash_lib._online_blocks(True, 8192, 256, 512, 1024, 2) == (512, 1024)
    assert flash_lib._online_blocks(False, 4096, 128, 1024, 1024) == \
        flash_lib.ONLINE_BLOCK_TABLE[False, 4096, 128]
    # the GLM cell's backward: the sweep's row, not the rule's (1024, 512)
    assert flash_lib._online_blocks(True, 8192, 256, 1024, 1024, 2) == \
        flash_lib.ONLINE_BLOCK_TABLE[True, 8192, 256] == (512, 1024)
    assert flash_lib._online_blocks(False, 8192, 256, 1024, 1024, 2) == \
        (1024, 1024)
    monkeypatch.setitem(flash_lib.ONLINE_BLOCK_TABLE, (True, 2048, 256),
                        (256, 1024))
    assert flash_lib._online_blocks(True, 2048, 256, 1024, 1024, 2) == (256, 1024)


def test_online_kernels_at_width_256_match_the_reference_interpret():
    """The three online kernels at the planned blocks' ratio (q block twice
    the kv block in the backward), D = 256, against ``dot_product_attention``."""
    k = jax.random.split(jax.random.key(0), 4)
    q, kk, v, g = (jax.random.normal(key, (1, 512, 2, 256)) for key in k)
    flash = lambda q, k, v: flash_lib.flash_attention(
        q, k, v, True, 256, 128, "online")
    plain = lambda q, k, v: attn_lib.dot_product_attention(q, k, v,
                                                           causal=True)
    total = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * g)
    with pltpu.force_tpu_interpret_mode(), HIGHEST:
        out = flash(q, kk, v)
        grads = jax.grad(total(flash), (0, 1, 2))(q, kk, v)
        want = jax.grad(total(plain), (0, 1, 2))(q, kk, v)
        np.testing.assert_allclose(out, plain(q, kk, v), atol=5e-6)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


# -- the published entry and the chip's share ------------------------------------


def test_published_entry_and_its_share():
    full = glm_moe_lite.glm47_flash()
    assert (full.num_layers, full.num_dense_layers, full.mtp_layers,
            full.num_experts, full.top_k, full.vocab_size) == (
                47, 1, 1, 64, 4, 154880)
    assert glm_moe_lite.num_params(full) == 30_587_097_088
    share = glm_moe_lite.chip_share(full)
    assert (share.num_layers, share.num_dense_layers, share.mtp_layers,
            share.held_experts, share.vocab_size) == (5, 1, 1, (8, 0), 19360)
    assert glm_moe_lite.chip_share(full, chip=3).held_experts == (8, 24)
    # no width differs
    for field in ("d_model", "num_heads", "q_rank", "kv_rank", "nope_dim",
                  "rope_dim", "v_dim", "dense_ffn_dim", "expert_ffn_dim",
                  "num_experts", "top_k", "route_scale", "rope_theta",
                  "epsilon", "shared_experts"):
        assert getattr(share, field) == getattr(full, field), field
    assert (share.d_model, share.num_heads, share.q_rank, share.kv_rank,
            share.nope_dim, share.rope_dim, share.v_dim, share.dense_ffn_dim,
            share.expert_ffn_dim, share.route_scale) == (
                2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 1.8)
    assert glm_moe_lite.num_params(share) == 706_518_528
    shapes = jax.eval_shape(lambda: share.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = lambda tree: sum(int(np.prod(x.shape))
                              for x in jax.tree.leaves(tree))
    params = shapes["params"]
    assert leaves(params) == 706_518_528
    assert leaves(params["block_0"]) == 84_677_888
    assert leaves(params["block_3"]) == 106_829_056
    assert leaves(params["block_3"]["attn"]) == 21_759_232
    assert leaves(params["mtp"]) == 115_223_808
    assert leaves(params["embed"]) + leaves(params["lm_head"]) == 79_298_560
    attn = params["block_1"]["attn"]
    assert attn["q_a"]["kernel"].shape == (2048, 768)
    assert attn["q_b"]["kernel"].shape == (768, 20, 256)
    assert attn["kv_a"]["kernel"].shape == (2048, 576)
    assert attn["kv_b"]["kernel"].shape == (512, 20, 448)
    assert attn["out"]["kernel"].shape == (20, 256, 2048)
    assert params["block_1"]["moe"]["router"].shape == (2048, 64)
    assert params["block_1"]["moe"]["w_gate"].shape == (8, 2048, 1536)
    assert params["mtp"]["eh_proj"]["kernel"].shape == (4096, 2048)
    assert "moe" not in params["block_0"]
    stats = shapes["batch_stats"]
    assert stats["block_4"]["moe"]["expert_bias"].shape == (64,)
    assert stats["mtp"]["mtp_block"]["moe"]["expert_bias"].shape == (64,)
    tiny = glm_moe_lite.glm_moe_lite_tiny()
    shapes = jax.eval_shape(lambda: tiny.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    assert leaves(shapes["params"]) == glm_moe_lite.num_params(tiny)


def test_forward_flops_agree_with_the_benchmarks_count():
    import json

    share = glm_moe_lite.chip_share(glm_moe_lite.glm47_flash())
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "glm47_flash.json")) as fh:
        model = json.load(fh)["model"]
    want = reference.forward_flops(model, {"seq_len": 8192})
    assert want == pytest.approx(
        8192 * glm_moe_lite.forward_flops_per_token(share, 8192), rel=1e-12)
    assert 3 * want == pytest.approx(29.70e12, rel=1e-3)
    for module, S in ((glm_moe_lite.glm_moe_lite_tiny(), 48),
                      (glm_moe_lite.glm_moe_lite_tiny(mtp_layers=0), 8)):
        assert S * glm_moe_lite.forward_flops_per_token(module, S) == \
            pytest.approx(reference.forward_flops(_model_dict(module),
                                                  {"seq_len": S}), rel=1e-12)
    bundle = registry.create_model(
        "glm47_flash_share", num_classes=0, image_size=0, seq_len=8192,
        dtype=jnp.bfloat16, param_dtype=jnp.float32,
        logits_dtype=jnp.float32, remat=True)
    assert bundle.fwd_flops_per_example == pytest.approx(want, rel=1e-12)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The expert layer at this family's routing (4 of 64, scale 1.8) against
    the reference's uncut layer: eight chips hold 8 experts each; the routed
    parts that the shares give, with the shared expert counted once, are the
    uncut layer's output as the plain reference computes it."""
    d, f, E, k = 32, 16, 64, 4
    layer = lambda held: moe_lib.SharedExpertMoE(
        num_experts=E, ffn_dim=f, top_k=k, held_experts=held,
        shared_ffn_dim=f, route_scale=1.8, balance_coeff=0.001)
    x = jax.random.normal(jax.random.key(5), (2, 32, d))
    whole = layer(None)
    params = weights.make_like(jax.eval_shape(
        lambda: whole.init(jax.random.key(1), x, train=False)["params"]),
        [[".*", "normal", 0.3]], weights.seed_key(5))
    bias = {"expert_bias": 0.2 * jnp.sin(jnp.arange(float(E)))}
    run = lambda mod, p: jax.jit(lambda q: mod.apply(
        {"params": q, "batch_stats": bias}, x, train=False))(p)
    cut = lambda s: {**params, **{n: params[n][s:s + 8]
                                  for n in ("w_gate", "w_up", "w_down")}}
    model = {"routed_scaling_factor": 1.8, "n_shared_experts": 1}
    z = {"k": k, "routed": E, "first": 0, "held": E}
    w = {"moe/" + p: a for p, a in weights.flatten(params).items()}
    with HIGHEST:
        want, counts = reference._experts(x, w, bias["expert_bias"], z, model,
                                          lambda a: a)
        np.testing.assert_allclose(run(whole, params), want, rtol=2e-5,
                                   atol=2e-4)
        no_shared = {n: v for n, v in params.items() if n != "shared"}
        shared = want - run(layer(None).clone(shared_ffn_dim=0), no_shared)
        parts = [run(layer((8, s)), cut(s)) - shared for s in range(0, E, 8)]
        # and the reference given a share is the program given that share
        ref_part, _ = reference._experts(
            x, {**w, **{"moe/" + n: params[n][24:32]
                        for n in ("w_gate", "w_up", "w_down")}},
            bias["expert_bias"], dict(z, first=24, held=8), model,
            lambda a: a)
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(parts[3] + shared, ref_part, rtol=2e-5,
                               atol=2e-4)
    assert float(jnp.sum(counts)) == 2 * 32 * k
    assert all(float(jnp.max(jnp.abs(p))) > 0.01 for p in parts)


# -- the preset ---------------------------------------------------------------------


def test_preset_trains_through_the_trainer_with_named_regions(devices):
    """The preset at toy size through ``Trainer`` (what ``main.py --preset``
    builds): it steps, the biases move (the module's too) and sit in
    ``batch_stats``, the reported loss carries the second depth's term, and
    the step's text carries the scopes that the benchmark's readers look
    for."""
    cfg = from_preset("glm47_flash_share", model="glm_moe_lite_tiny",
                      seq_len=32, global_batch_size=8, precision="fp32",
                      lr=3e-3, lr_schedule="constant", warmup_epochs=0.0,
                      workers=0, steps_per_epoch=4, log_every=1000,
                      checkpoint_dir=None, mesh_fsdp=4, mesh_data=2,
                      telemetry=True)
    trainer = Trainer(cfg)
    assert trainer.bundle.task == "lm" and cfg.remat
    before = jax.device_get(trainer.state.params["mtp"]["eh_proj"]["kernel"])
    trainer.train_epoch(0)
    assert int(trainer.state.step) == 4
    for bias in (trainer.state.batch_stats["block_1"]["moe"]["expert_bias"],
                 trainer.state.batch_stats["mtp"]["mtp_block"]["moe"][
                     "expert_bias"]):
        assert 0 < float(jnp.max(jnp.abs(bias))) <= 4 * 0.05 * 2
    after = jax.device_get(trainer.state.params["mtp"]["eh_proj"]["kernel"])
    assert np.abs(after - before).max() > 0
    moments = weights.flatten(trainer.state.opt_state)
    assert not any("expert_bias" in path for path in moments)
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                     sharding=trainer.batch_sharding)
             for k in ("tokens", "targets")}
    with mesh_lib.use_mesh(trainer.mesh):
        text = trainer.train_step.lower(trainer.state, batch).as_text(
            debug_info=True)
        metrics = jax.eval_shape(trainer.train_step, trainer.state, batch)[1]
    for scope in ("embed", "attn", "mla", "mla_q", "mla_kv", "mla_rope",
                  "mla_out", "mlp", "moe", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "moe_shared", "norm",
                  "head_loss", "mtp", "mtp_merge", "optimizer"):
        assert f"/{scope}/" in text, scope
    assert "/attn/mla/mla_q/" in text and "/mtp/mtp/mtp_block/attn/mla/" in text
    assert "/mtp/head_loss/" in text and "/mtp/mtp/mtp_merge/norm/" in text
    assert "/mtp/mtp/mtp_block/mlp/moe/moe_router" in text
    for name in ("loss_main", "loss_mtp", "moe_held_rows.block_1",
                 "moe_held_rows.mtp_block", "moe_bias_peak.mtp_block",
                 "moe_whole.block_2", "moe_source_parts.mtp_block"):
        assert name in metrics, name


def test_what_the_family_does_not_do_fails_loudly():
    module = glm_moe_lite.glm_moe_lite_tiny()
    tokens = jnp.zeros((1, 8), jnp.int32)
    variables = module.init(jax.random.key(0), tokens, train=False)
    with pytest.raises(NotImplementedError, match="latent"):
        module.apply(variables, tokens, train=False, decode_ctx={})
    with pytest.raises(ValueError, match="sequence-parallel"):
        registry.create_model(
            "glm_moe_lite_tiny", num_classes=0, image_size=0, seq_len=8,
            dtype=jnp.float32, param_dtype=jnp.float32,
            logits_dtype=jnp.float32, remat=False, sp=True)
    for strategy in ("tp", "fsdp_tp"):
        with pytest.raises(ValueError, match="unknown strategy"):
            Trainer(from_preset(
                "glm47_flash_share", model="glm_moe_lite_tiny", seq_len=16,
                global_batch_size=8, precision="fp32", workers=0,
                checkpoint_dir=None, strategy=strategy, mesh_fsdp=1,
                mesh_data=4, mesh_model=2))
    with pytest.raises(ValueError, match="mtp_layers"):
        glm_moe_lite.glm_moe_lite_tiny(mtp_layers=2).init(
            jax.random.key(0), tokens, train=False)
    with pytest.raises(ValueError, match="remat_policy"):
        glm_moe_lite.glm_moe_lite_tiny(remat=True, remat_policy="?").init(
            jax.random.key(0), tokens, train=False)

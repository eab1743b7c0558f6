"""The ``qwen3_next`` family (``models/qwen3_next.py``; ``ops/gated_delta.py``;
``ops/ssd.norm_gate``; ``models/afmoe.GatedAttention`` with a rotary slice;
``parallel/moe.py``'s softmax router and held experts beside a gated shared
expert): the chunked gated delta rule against the token-by-token recurrence
with every gradient and the norm-then-gate stage against its two lines
(``tests/test_gated_delta.py``), the model
against the benchmark's plain reference (loss, gradients, three optimizer
steps), the shares adding up to the uncut layer, the published entry's shape
and the chip's share of it, and the preset through the ``Trainer``. Float32
on the CPU at toy widths."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import weights  # noqa: E402
from chipbench.references import qwen3_next_80b as reference  # noqa: E402
from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.core import train_loop  # noqa: E402
from pytorch_distributed_training_example_tpu.core.trainer import Trainer  # noqa: E402
from pytorch_distributed_training_example_tpu.models import (  # noqa: E402
    qwen3_next, registry)
from pytorch_distributed_training_example_tpu.ops import gated_delta  # noqa: E402
from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.utils.config import from_preset  # noqa: E402

HIGHEST = jax.default_matmul_precision("highest")
RULES = [["scale$", "const", 1.0], ["A_log$", "const", 0.0],
         ["dt_bias$", "const", -1.0], ["conv_kernel$", "normal", 0.3],
         [".*", "normal", 0.1]]


# -- the model against the plain reference ------------------------------------------


def _model_dict(module: qwen3_next.Qwen3Next, held_layers=None) -> dict:
    """The reference's ``model`` group for a program module."""
    held, first = module.held_experts or (module.num_experts, 0)
    return {
        "hidden_size": module.d_model,
        "num_attention_heads": module.num_heads,
        "num_key_value_heads": module.num_kv_heads,
        "head_dim": module.head_dim,
        "partial_rotary_factor": module.rotary_dim / module.head_dim,
        "rope_theta": module.rope_theta, "full_attention_interval": 4,
        "linear_num_key_heads": module.linear_key_heads,
        "linear_num_value_heads": module.linear_value_heads,
        "linear_key_head_dim": module.linear_key_dim,
        "linear_value_head_dim": module.linear_value_dim,
        "linear_conv_kernel_dim": module.conv_taps,
        "moe_intermediate_size": module.expert_ffn_dim,
        "shared_expert_intermediate_size": module.shared_ffn_dim,
        "num_experts": held, "held_experts_start": first,
        "routed_experts": module.num_experts,
        "num_experts_per_tok": module.top_k, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "num_hidden_layers": module.num_layers,
        "held_layers": held_layers or list(range(module.num_layers)),
        "rms_norm_eps": module.epsilon, "vocab_size": module.vocab_size}


def _seeded(module, S, seed=3, batch=2, rules=RULES):
    tokens = jax.random.randint(jax.random.key(seed), (batch, S + 1), 0,
                                module.vocab_size)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens[:, :-1]))
    assert "batch_stats" not in shapes          # no buffer in this family
    params = weights.make_like(shapes["params"], rules, weights.seed_key(seed))
    return params, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


@pytest.mark.parametrize("held", [None, (2, 2)], ids=["whole", "share"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_the_plain_reference(held, remat):
    """Logits, loss and every leaf's gradient in float32: the chunked rule
    against the reference's token-by-token one, the router's softmax over the
    chosen against its softmax over all, the rotary slice, the gated shared
    expert; and the reference's layer-by-layer gradient, which the chip's
    comparison follows, is its ``jax.grad``."""
    module = qwen3_next.qwen3_next_tiny(remat=remat, held_experts=held)
    params, batch = _seeded(module, 40)
    task = train_loop.get_task("lm")
    model = _model_dict(module)
    program = lambda p: task.loss(module.apply(
        {"params": p}, batch["tokens"], train=True), batch)
    with HIGHEST:
        loss, grads = jax.jit(jax.value_and_grad(program))(params)
        logits = module.apply({"params": params}, batch["tokens"])
        flat = weights.flatten(params)
        (want_loss, counts), want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, model), has_aux=True))(flat)
        want_logits = reference.logits_fn(flat, batch["tokens"], model)
        (by_layer_loss, by_layer_counts), by_layer = reference.layerwise(
            model)(flat, batch)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(by_layer_loss, want_loss, rtol=1e-6)
    np.testing.assert_array_equal(by_layer_counts, counts)
    grads = weights.flatten(grads)
    assert set(grads) == set(want) == set(by_layer)
    for path, g in grads.items():
        scale = float(jnp.max(jnp.abs(want[path])))
        assert scale > 0, path  # every leaf is alive at this init
        np.testing.assert_allclose(g, want[path], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=path)
        np.testing.assert_allclose(by_layer[path], want[path], rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=path)
    # four expert blocks, every row of every sequence
    assert counts.shape == (4, 8)
    assert float(jnp.sum(counts)) == 4 * 2 * 40 * module.top_k


def test_three_adamw_steps_through_the_trainers_step():
    """The step the ``Trainer`` builds (``make_train_step`` with the preset's
    AdamW chain) for three steps against the reference's own three: each
    step's loss and every leaf's change."""
    cfg = from_preset("qwen3_next_80b_share", model="qwen3_next_tiny",
                      seq_len=32, global_batch_size=8, precision="fp32",
                      lr=3e-4, lr_schedule="constant", warmup_epochs=0.0,
                      workers=0, steps_per_epoch=4, log_every=1000,
                      checkpoint_dir=None, mesh_fsdp=1, mesh_data=8,
                      strategy="dp", remat=False, attn_impl="xla")
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
    moved = weights.flatten(jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
        jax.device_get(state.params), start))
    assert set(moved) == set(want["dparam_norms"])
    for path, norm in moved.items():
        # Adam's first steps are sign-like: a gradient entry near zero may
        # step either way, so a leaf's change agrees to a few percent
        assert float(norm) == pytest.approx(want["dparam_norms"][path],
                                            rel=5e-2), path


@pytest.mark.parametrize("piece", [
    "the readout", "the decay", "the shared expert's gate",
    "the rotary slice", "the norm before the gate", "a value head's key"])
def test_reference_sees_what_a_step_leaves_out(piece, monkeypatch):
    """The comparison's other side: a program without the piece is not the
    reference's model (the rehearsal twin holds the whole command to the
    same)."""
    module = qwen3_next.qwen3_next_tiny()
    lively = [["scale$", "const", 1.0], ["A_log$", "const", 0.0],
              ["dt_bias$", "const", 0.0], [".*", "normal", 0.2]]
    params, batch = _seeded(module, 48, rules=lively)
    with HIGHEST:   # before anything is left out of anything
        want, _ = reference.loss_fn(weights.flatten(params), batch,
                                    _model_dict(module))
    rule = gated_delta.gated_delta_rule
    if piece == "the readout":
        # no correction: the outer-product state of a plain linear attention
        monkeypatch.setattr(
            gated_delta, "gated_delta_rule",
            lambda q, k, v, g, beta, *, chunk: _outer_only(q, k, v, g, beta))
    elif piece == "the decay":
        monkeypatch.setattr(
            gated_delta, "gated_delta_rule",
            lambda q, k, v, g, beta, *, chunk: rule(
                q, k, v, jnp.zeros_like(g), beta, chunk=chunk))
    elif piece == "the shared expert's gate":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: (
            jnp.ones_like(x) if x.shape[-1] == 1 else jax.lax.logistic(x)))
    elif piece == "the rotary slice":
        module = module.clone(rotary_dim=module.head_dim)
    elif piece == "the norm before the gate":
        monkeypatch.setattr(
            ssd_lib, "norm_gate",
            lambda y, z, scale, *, groups, source, offset, **kw:
            ssd_lib.gate_norm(y, z, jnp.tile(scale, groups), groups=groups,
                              **kw))
    elif piece == "a value head's key":
        # value head h reads key head h % Hk and not h // (Hv / Hk)
        monkeypatch.setattr(
            gated_delta, "gated_delta_rule",
            lambda q, k, v, g, beta, *, chunk: rule(
                q[:, :, ::-1], k[:, :, ::-1], v, g, beta, chunk=chunk))
    task = train_loop.get_task("lm")
    with HIGHEST:
        loss = task.loss(module.apply({"params": params}, batch["tokens"],
                                      train=False), batch)
    assert abs(float(loss) - float(want)) > 1e-4 * float(want), piece


def _outer_only(q, k, v, g, beta):
    """``S_t = exp(g_t) S_{t-1} + k_t (outer) beta_t v_t``: the rule without
    its readout."""
    R = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(a.astype(jnp.float32), R, axis=2) for a in (q, k))

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, b_t[..., None] * v_t)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    first = lambda a: jnp.moveaxis(a, 1, 0)
    b, _, Hv, Dv = v.shape
    _, o = jax.lax.scan(token, jnp.zeros((b, Hv, q.shape[-1], Dv)),
                        tuple(map(first, (q, k, v.astype(jnp.float32), g,
                                          beta))))
    return first(o)


def test_reference_router_is_the_programs_to_a_rounding():
    """The published order (the softmax over all the experts, the ten
    largest, divided by their sum) and ``route_softmax_chosen`` (the softmax
    over the chosen logits alone) choose the same experts and weigh them the
    same to a rounding."""
    u = jax.random.normal(jax.random.key(0), (64, 32))
    kernel = jax.random.normal(jax.random.key(1), (32, 16))
    with HIGHEST:
        chosen, weight = reference.route(u, kernel, 5)
        ours = moe_lib.route_softmax_chosen(u, kernel, 5)
    np.testing.assert_array_equal(chosen, ours.chosen)
    np.testing.assert_allclose(weight, ours.weights, rtol=2e-6)
    np.testing.assert_allclose(jnp.sum(weight, -1), 1.0, rtol=1e-6)


# -- the shares and the uncut layer --------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """Four chips hold two experts each of eight, four a token: the routed
    parts that the shares give, and the gated shared expert counted once, are
    the uncut layer's output; a share's gradient of its own experts is the
    uncut layer's gradient of them."""
    d, E = 32, 8
    x = jax.random.normal(jax.random.key(4), (1, 48, d))

    def layer(held):
        router = moe_lib.TopKSoftmaxRouter(num_experts=E, top_k=4)
        experts = moe_lib.HeldExperts(ffn_dim=24, held_experts=held,
                                      act="silu")
        shared = moe_lib.SwiGLU(24, jnp.float32, jnp.float32)

        @functools.partial(jax.jit, static_argnames="with_shared")
        def run(p, with_shared=True):
            route = router.apply({"params": p["router"]}, x)
            out = experts.apply({"params": p["experts"]}, x, route)
            if with_shared:
                gate = jax.nn.sigmoid(x @ p["gate"])
                out = out + gate * shared.apply({"params": p["shared"]}, x)
            return out
        return run, router, experts, shared

    run, router, experts, shared = layer(None)
    def made():
        route = router.init(jax.random.key(0), x)
        return {"router": route["params"],
                "experts": experts.init(jax.random.key(0), x, router.apply(
                    route, x))["params"],
                "shared": shared.init(jax.random.key(0), x)["params"],
                "gate": jnp.zeros((d, 1))}

    params = weights.make_like(jax.eval_shape(made),
                               [[".*", "normal", 0.3]], weights.seed_key(1))
    part = lambda first, held: {**params, "experts": {
        k: v[first:first + held] for k, v in params["experts"].items()}}
    with HIGHEST:
        want = run(params)
        parts = [layer((2, 2 * c))[0](part(2 * c, 2), with_shared=False)
                 for c in range(4)]
        once = run(params) - run(params, with_shared=False)
        np.testing.assert_allclose(sum(parts) + once, want, rtol=2e-5,
                                   atol=2e-4)
        assert float(jnp.max(jnp.abs(once))) > 0.1
        g = jax.grad(lambda p: jnp.sum(jnp.sin(run(p))))(params)
        rest = want - parts[1]
        mine = jax.grad(lambda p: jnp.sum(jnp.sin(
            layer((2, 2))[0](p, with_shared=False) + rest)))(part(2, 2))
        chosen = router.apply({"params": params["router"]}, x).chosen
    for name in ("w_gate", "w_up", "w_down"):
        scale = float(jnp.max(jnp.abs(g["experts"][name])))
        np.testing.assert_allclose(mine["experts"][name],
                                   g["experts"][name][2:4], atol=1e-5 * scale)
    assert all(float(jnp.max(jnp.abs(p))) > 0.1 for p in parts)
    elsewhere = ~np.any((np.asarray(chosen) >= 2) & (np.asarray(chosen) < 4),
                        axis=-1)
    assert elsewhere.any() and not np.asarray(parts[1])[0, elsewhere].any()


def test_blocks_expert_layer_is_router_held_experts_and_gated_shared():
    """The block composes what the test above composes: its leaves, and its
    output against the reference's expert layer on the same weights with and
    without the gate (the gate is one scalar a token)."""
    module = qwen3_next.qwen3_next_tiny()
    params, batch = _seeded(module, 24)
    leaves = weights.flatten(params["block_0"])
    assert {p for p in leaves if not p.startswith(("gated_delta_net",
                                                   "mixer_norm"))} == {
        "ffn_norm/scale", "moe_router/kernel", "moe/w_gate", "moe/w_up",
        "moe/w_down", "shared_expert/gate/kernel", "shared_expert/up/kernel",
        "shared_expert/down/kernel", "shared_expert_gate/kernel"}
    assert leaves["shared_expert_gate/kernel"].shape == (64, 1)
    assert leaves["moe/w_up"].shape == (2, 64, 32)
    assert leaves["moe_router/kernel"].shape == (64, 8)
    assert leaves["gated_delta_net/norm_scale"].shape == (16,)
    assert leaves["gated_delta_net/conv_kernel"].shape == (4, 2 * 32 + 64)
    assert leaves["gated_delta_net/in_proj_qkvz/kernel"].shape == (64, 192)
    assert leaves["gated_delta_net/in_proj_ba/kernel"].shape == (64, 8)
    u = jax.random.normal(jax.random.key(2), (1, 24, 64))
    z = reference._sizes(_model_dict(module))
    with HIGHEST:
        gated, _ = reference._experts(u, leaves, z, lambda a: a)
        plain, _ = reference._experts(u, leaves, z, lambda a: a,
                                      shared_gate=False)
    assert float(jnp.max(jnp.abs(gated - plain))) > 1e-3


# -- the published entry and the chip's share ------------------------------------


def test_published_entry_and_its_share():
    """The published sizes give 79.7 B (no prediction module is built), and
    the share's count is the configuration file's sum, layer by layer; no
    width differs."""
    full = qwen3_next.qwen3_next_80b()
    kinds = full.layer_types
    assert len(kinds) == 48 and kinds.count("linear_attention") == 36
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == list(
        range(3, 48, 4))
    delta = 25_165_824 + 131_072 + 32_768 + 64 + 128 + 8_388_608
    attn = 2 * 8_388_608 + 2 * 1_048_576 + 8_388_608 + 512
    ffn = lambda held: 1_048_576 + held * 3_145_728 + 3_145_728 + 2_048
    assert (delta, attn, ffn(32)) == (33_718_464, 27_263_488, 104_859_648)
    assert qwen3_next.num_params(full) == (
        36 * (delta + ffn(512) + 4_096) + 12 * (attn + ffn(512) + 4_096)
        + 2 * 151_936 * 2_048 + 2_048) == 79_674_391_296
    share = qwen3_next.chip_share(full)
    linear, attention = delta + ffn(32) + 4_096, attn + ffn(32) + 4_096
    assert (linear, attention) == (138_582_208, 132_127_232)
    assert qwen3_next.num_params(share) == (
        3 * linear + attention + 77_791_232 + 2_048) == 625_667_136
    assert 3 * linear + attention == 547_873_856
    assert share.layer_types == qwen3_next.PERIOD == kinds[:4]
    assert (share.held_experts, share.vocab_size) == ((32, 0), 18_992)
    assert qwen3_next.chip_share(full, chip=15).held_experts == (32, 480)
    widths = lambda m: {f: getattr(m, f) for f in m.__dataclass_fields__
                        if f not in ("layer_types", "held_experts",
                                     "vocab_size", "parent", "name")}
    assert widths(share) == widths(full)
    assert (full.rotary_dim, full.head_dim, full.rope_theta) == (64, 256, 1e7)
    leaves = jax.eval_shape(lambda: share.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    assert sum(x.size for x in jax.tree.leaves(leaves["params"])) \
        == 625_667_136
    tiny = qwen3_next.qwen3_next_tiny()
    made = jax.eval_shape(lambda: tiny.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    assert sum(x.size for x in jax.tree.leaves(made["params"])) \
        == qwen3_next.num_params(tiny)
    assert set(tiny.layer_types) == set(qwen3_next.KINDS)


def test_forward_flops_agree_with_the_benchmarks_count():
    share = qwen3_next.chip_share(qwen3_next.qwen3_next_80b())
    ours = 8192 * qwen3_next.forward_flops_per_token(share, 8192)
    theirs = reference.forward_flops(
        _model_dict(share, held_layers=[0, 1, 2, 3]), {"seq_len": 8192})
    assert ours == pytest.approx(theirs, rel=1e-12)
    assert ours == pytest.approx(3.771e12, rel=1e-3)
    bundle = registry.create_model(
        "qwen3_next_80b_share", seq_len=8192, dtype=jnp.bfloat16,
        param_dtype=jnp.float32, logits_dtype=jnp.float32)
    assert bundle.fwd_flops_per_example == ours
    assert {"qwen3_next_80b", "qwen3_next_80b_share",
            "qwen3_next_tiny"} <= set(registry.list_models())


def test_a_layer_type_the_family_does_not_have_fails_loudly():
    module = qwen3_next.qwen3_next_tiny(
        layer_types=("linear_attention", "sliding_attention"))
    with pytest.raises(ValueError, match="unknown layer type"):
        jax.eval_shape(module.init, jax.random.key(0),
                       jnp.zeros((1, 8), jnp.int32))
    model = _model_dict(qwen3_next.qwen3_next_tiny())
    with pytest.raises(ValueError, match="layers"):
        reference._sizes({**model, "held_layers": [0, 1]})
    with pytest.raises(ValueError, match="every layer"):
        reference._sizes({**model, "mlp_only_layers": [0]})


# -- the preset on the normal path ----------------------------------------------------


def test_preset_trains_through_the_trainer_with_named_regions(devices):
    cfg = from_preset("qwen3_next_80b_share", model="qwen3_next_tiny",
                      seq_len=32, global_batch_size=8, precision="fp32",
                      epochs=1, steps_per_epoch=3, workers=0, log_every=100,
                      checkpoint_dir=None, attn_impl="xla", telemetry=True)
    assert (cfg.strategy, cfg.remat, cfg.optimizer) == ("fsdp", True, "adamw")
    trainer = Trainer(cfg)
    trainer.train_epoch(0)
    assert int(trainer.state.step) == 3
    assert not jax.tree.leaves(trainer.state.batch_stats)
    batch = next(iter(trainer._make_step_iter(0, 0)))
    _, metrics = trainer.train_step(trainer.state, batch)
    sown = {k: float(v) for k, v in jax.device_get(metrics).items()}
    # the delta-rule blocks sow how long their state lives; the attention
    # block has no state to sow of
    assert sorted(k for k in sown if k.startswith("gdn_decay")) == [
        f"gdn_decay.block_{i}" for i in (0, 1, 2)]
    assert all(0.8 < sown[f"gdn_decay.block_{i}"] < 0.99 for i in (0, 1, 2))
    assert sorted(k for k in sown if k.startswith("moe_whole")) == [
        f"moe_whole.block_{i}" for i in range(4)]
    assert not [k for k in sown if k.startswith(("moe_gate_zero",
                                                 "moe_bias_peak"))]
    text = trainer.train_step.lower(trainer.state, batch).as_text(
        debug_info=True)
    for scope in ("gated_delta_net", "in_proj", "conv_silu", "delta_rule",
                  "gate_norm", "out_proj", "attn", "mlp", "moe", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
                  "norm", "embed", "head_loss"):
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope


def test_gdn_decay_is_the_mean_of_the_steps_decays():
    """``gdn_decay`` against its definition on the module's own leaves: the
    mean over tokens and heads of ``exp(-exp(A_log) softplus(a + dt_bias))``."""
    mixer = qwen3_next.GatedDeltaNet(
        key_heads=2, value_heads=4, key_dim=16, value_dim=16, conv_taps=4,
        chunk=16, epsilon=1e-6, dtype=jnp.float32, param_dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(0), (2, 24, 64))
    params = jax.jit(mixer.init)(jax.random.key(1), u)["params"]
    params = {**params, "A_log": jnp.linspace(-1.0, 1.0, 4),
              "dt_bias": jnp.linspace(-2.0, 0.0, 4)}
    with HIGHEST:
        _, sown = jax.jit(lambda p: mixer.apply(
            {"params": p}, u, mutable=["telemetry"]))(params)
        a = (u @ params["in_proj_ba"]["kernel"])[..., 4:]
    want = jnp.mean(jnp.exp(-jnp.exp(params["A_log"]) * jax.nn.softplus(
        a + params["dt_bias"])))
    (got,) = sown["telemetry"]["gdn_decay"]
    assert float(got) == pytest.approx(float(want), rel=1e-6)


def test_what_the_family_does_not_do_fails_loudly():
    module = qwen3_next.qwen3_next_tiny()
    with pytest.raises(NotImplementedError, match="matrix state"):
        module.apply({}, jnp.zeros((1, 4), jnp.int32), decode_ctx={})
    kw = dict(seq_len=32, dtype=jnp.float32, param_dtype=jnp.float32,
              remat=False, logits_dtype=jnp.float32, num_classes=0,
              image_size=0)
    with pytest.raises(ValueError, match="tensor- or sequence-parallel"):
        registry.create_model("qwen3_next_tiny", sp=True, **kw)
    for strategy in ("tp", "fsdp_tp", "pp"):
        cfg = from_preset("qwen3_next_80b_share", model="qwen3_next_tiny",
                          strategy=strategy, seq_len=32, global_batch_size=8,
                          workers=0, checkpoint_dir=None)
        with pytest.raises(ValueError):
            Trainer(cfg)

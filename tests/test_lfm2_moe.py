"""The ``lfm2_moe`` family (``models/lfm2_moe.py``; ``ops/ssd.gated_conv``;
``models/afmoe.GatedAttention`` without its gate; ``parallel/moe.py``'s router
with the family's constant): the doubly gated convolution against a
token-by-token loop with every gradient, the model against the benchmark's
plain reference (loss, gradients, three optimizer steps), the four shares
adding up to the uncut layer, the published entry's shape and the chip's share
of it, and the preset through the ``Trainer``. Float32 on the CPU at toy
widths."""

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
from chipbench.references import lfm2_8b_a1b as reference  # noqa: E402
from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.core import train_loop  # noqa: E402
from pytorch_distributed_training_example_tpu.core.trainer import Trainer  # noqa: E402
from pytorch_distributed_training_example_tpu.models import (  # noqa: E402
    afmoe, lfm2_moe, registry)
from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.utils.config import from_preset  # noqa: E402

HIGHEST = jax.default_matmul_precision("highest")
RULES = [["scale$", "const", 1.0], ["conv_kernel$", "normal", 0.3],
         [".*", "normal", 0.02]]


# -- the doubly gated convolution ------------------------------------------------


def _loop(bcx, kernel):
    """The definition, a token at a time with two rows of history: ``y_t =
    C_t * sum_k w[k] * (B x)_{t-(K-1)+k}``, float64 on the host."""
    bcx, kernel = np.asarray(bcx, np.float64), np.asarray(kernel, np.float64)
    b, S, C3 = bcx.shape
    K, C = kernel.shape
    B, Cg, x = bcx[..., :C], bcx[..., C:2 * C], bcx[..., 2 * C:]
    y = np.zeros((b, S, C))
    history = np.zeros((b, K - 1, C))
    for t in range(S):
        v = B[:, t] * x[:, t]
        rows = np.concatenate([history, v[:, None]], axis=1)    # [b, K, C]
        y[:, t] = Cg[:, t] * np.einsum("bkc,kc->bc", rows, kernel)
        history = rows[:, 1:]
    return y


def _loop_grads(bcx, kernel, w):
    """The gradients of ``sum(w * y)`` by the same loop, written out."""
    bcx, kernel, w = (np.asarray(a, np.float64) for a in (bcx, kernel, w))
    b, S, _ = bcx.shape
    K, C = kernel.shape
    B, Cg, x = bcx[..., :C], bcx[..., C:2 * C], bcx[..., 2 * C:]
    v = np.concatenate([np.zeros((b, K - 1, C)), B * x], axis=1)
    conv = sum(v[:, k:k + S] * kernel[k] for k in range(K))
    dC = w * conv
    dconv = w * Cg
    dkernel = np.stack([np.sum(dconv * v[:, k:k + S], axis=(0, 1))
                        for k in range(K)])
    dv = np.zeros_like(v)
    for k in range(K):
        dv[:, k:k + S] += dconv * kernel[k]
    dv = dv[:, K - 1:]
    return np.concatenate([dv * x, dC, dv * B], axis=-1), dkernel


@pytest.mark.parametrize("S", [1, 2, 37], ids=lambda s: f"S{s}")
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["fp32", "bf16"])
def test_gated_conv_is_the_loop_with_every_gradient(dtype, tol, S):
    """``y``, ``d bcx`` and ``d kernel`` against a token-by-token loop with
    two rows of zero history, at sequences that are no multiple of anything
    (one shorter than the history); float32 inside whatever comes in, the
    input's dtype out."""
    k = jax.random.split(jax.random.key(S), 3)
    bcx = jax.random.normal(k[0], (2, S, 3 * 24)).astype(dtype)
    kernel = 0.5 * jax.random.normal(k[1], (3, 24))
    w = jax.random.normal(k[2], (2, S, 24))
    y, (dbcx, dkernel) = jax.value_and_grad(
        lambda a, b: jnp.sum(ssd_lib.gated_conv(a, b).astype(jnp.float32)
                             * w), argnums=(0, 1))(bcx, kernel)
    got = ssd_lib.gated_conv(bcx, kernel)
    assert got.dtype == dtype and got.shape == (2, S, 24)
    assert dbcx.dtype == dtype and dkernel.dtype == jnp.float32
    want = _loop(bcx.astype(jnp.float32), kernel)
    want_dbcx, want_dkernel = _loop_grads(bcx.astype(jnp.float32), kernel, w)
    close = lambda a, b: np.testing.assert_allclose(
        np.asarray(a.astype(jnp.float32)), b, rtol=0,
        atol=tol * max(1.0, float(np.abs(b).max())))
    close(got, want)
    close(dbcx, want_dbcx)
    close(dkernel, want_dkernel)
    assert float(y) == pytest.approx(float(np.sum(want * np.asarray(w))),
                                     rel=10 * tol, abs=10 * tol)


def test_gated_conv_takes_the_chunks_in_the_published_order():
    """``[B; C; x]``: the first and the third chunk meet before the conv, the
    second gates after it; with one tap of 1 the operator is ``C * B * x``,
    and an older tap reads the *product's* history, not ``x``'s alone."""
    B, C, x = (jnp.full((1, 4, 2), v) for v in (2.0, 3.0, 5.0))
    ramp = jnp.arange(1.0, 5.0)[None, :, None]
    bcx = jnp.concatenate([B * ramp, C, x], -1)
    newest = jnp.array([[0.0, 0.0], [0.0, 0.0], [1.0, 1.0]])
    np.testing.assert_array_equal(ssd_lib.gated_conv(bcx, newest),
                                  3.0 * 2.0 * ramp * 5.0 * jnp.ones((1, 4, 2)))
    oldest = newest[::-1]
    want = 3.0 * 10.0 * jnp.array([0.0, 0.0, 1.0, 2.0])[None, :, None]
    np.testing.assert_array_equal(ssd_lib.gated_conv(bcx, oldest),
                                  want * jnp.ones((1, 4, 2)))
    text = str(jax.make_jaxpr(ssd_lib.gated_conv)(bcx, newest))
    assert "pallas_call" not in text and "conv_general" not in text


# -- the router's constant and the attention without its gate ----------------------


def test_router_at_the_default_constant_gives_the_digits_it_gave():
    """``route_sigmoid_bias`` with no constant named is the 1e-20 it had,
    bit for bit; the family's 1e-6 moves the weights in the seventh digit and
    the choice not at all."""
    k = jax.random.split(jax.random.key(0), 3)
    tokens = jax.random.normal(k[0], (64, 16))
    kernel = jax.random.normal(k[1], (16, 8))
    bias = 0.1 * jax.random.normal(k[2], (8,))
    scores = jax.nn.sigmoid(jnp.dot(tokens, kernel,
                                    precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias, 2)
    picked = jnp.take_along_axis(scores, chosen, -1)
    old = 2.5 * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    got = moe_lib.route_sigmoid_bias(tokens, kernel, bias, 2, 2.5)
    np.testing.assert_array_equal(got.weights, old)
    np.testing.assert_array_equal(got.chosen, chosen)
    ours = moe_lib.route_sigmoid_bias(tokens, kernel, bias, 2, 2.5, 1e-6)
    np.testing.assert_array_equal(ours.chosen, chosen)
    np.testing.assert_array_equal(
        ours.weights, 2.5 * picked / (jnp.sum(picked, -1, keepdims=True)
                                      + 1e-6))
    assert 0 < float(jnp.max(jnp.abs(ours.weights - old))) < 1e-5
    layer = moe_lib.SharedExpertMoE(num_experts=8, ffn_dim=8, top_k=2)
    assert layer.route_norm_eps == 1e-20          # no preset names it
    assert lfm2_moe.ROUTE_NORM_EPS == reference.ROUTE_NORM_EPS == 1e-6


def test_attention_without_its_gate_keeps_the_norms_and_the_rotary():
    """``GatedAttention(gated=False, rotary=True)`` has no ``gate`` leaf and
    rotates without a window; the defaults are the afmoe layers' (a gate, and
    positions only under a window)."""
    h = jax.random.normal(jax.random.key(0), (1, 12, 32))
    sizes = dict(num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e4,
                 epsilon=1e-5, dtype=jnp.float32, param_dtype=jnp.float32,
                 attn_impl="xla")
    ours = afmoe.GatedAttention(**sizes, window=None, gated=False,
                                rotary=True)
    params = ours.init(jax.random.key(1), h)
    assert set(params["params"]) == {"query", "key", "value", "out", "q_norm",
                                     "k_norm"}
    full = afmoe.GatedAttention(**sizes, window=None)
    assert set(full.init(jax.random.key(1), h)["params"]) == set(
        params["params"]) | {"gate"}
    still = afmoe.GatedAttention(**sizes, window=None, gated=False)
    moved = np.asarray(ours.apply(params, h) - still.apply(params, h))
    assert np.abs(moved[0, 0]).max() < 1e-6 < np.abs(moved[0, 5]).max()
    windowed = afmoe.GatedAttention(**sizes, window=64, gated=False)
    np.testing.assert_allclose(windowed.apply(params, h),
                               ours.apply(params, h), atol=1e-6)


# -- the model against the plain reference ------------------------------------------


def _model_dict(module: lfm2_moe.Lfm2Moe, held_layers=None) -> dict:
    """The reference's ``model`` group for a program module."""
    held, first = module.held_experts or (module.num_experts, 0)
    return {
        "hidden_size": module.d_model,
        "num_attention_heads": module.num_heads,
        "num_key_value_heads": module.num_kv_heads,
        "conv_L_cache": module.conv_taps, "conv_bias": False,
        "intermediate_size": module.dense_ffn_dim,
        "moe_intermediate_size": module.expert_ffn_dim,
        "num_experts": held, "held_experts_start": first,
        "routed_experts": module.num_experts,
        "num_experts_per_tok": module.top_k,
        "num_dense_layers": module.num_dense_layers,
        "num_hidden_layers": module.num_layers,
        "layer_types": list(module.layer_types),
        "held_layers": held_layers or list(range(module.num_layers)),
        "rope_theta": module.rope_theta, "norm_eps": module.epsilon,
        "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": module.route_scale,
        "load_balance_coeff": module.balance_coeff,
        "vocab_size": module.vocab_size}


def _seeded(module, S, seed=3, batch=2, rules=RULES):
    tokens = jax.random.randint(jax.random.key(seed), (batch, S + 1), 0,
                                module.vocab_size)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens[:, :-1]))
    params = weights.make_like(shapes["params"], rules, weights.seed_key(seed))
    stats = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["batch_stats"])
    return params, stats, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def _biases(stats, module):
    """The reference's ``[blocks, routed]`` biases from the program's."""
    rows = [stats.get(f"block_{i}", {}).get("moe", {}).get(
        "expert_bias", jnp.zeros((module.num_experts,)))
        for i in range(module.num_layers)]
    return jnp.stack(rows)


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "share"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_the_plain_reference(held, remat):
    """Logits, loss, every leaf's gradient (the tied embedding's from the
    lookup and the head together) and the bias after the step, in float32
    (the Trinity and Nemotron tests' tolerances); and the reference's
    layer-by-layer gradient, which the chip's comparison follows, is its
    ``jax.grad``."""
    module = lfm2_moe.lfm2_moe_tiny(remat=remat, held_experts=held)
    params, stats, batch = _seeded(module, 40)
    stats = jax.tree.map(lambda b: 0.3 * jnp.cos(jnp.arange(b.size) * 1.7),
                         stats)
    task = train_loop.get_task("lm")
    model = _model_dict(module)
    biases = _biases(stats, module)

    def program(p):
        logits, new = module.apply({"params": p, "batch_stats": stats},
                                   batch["tokens"], train=True,
                                   mutable=["batch_stats"])
        return task.loss(logits, batch), (new["batch_stats"], logits)

    with HIGHEST:
        (loss, (new_stats, logits)), grads = jax.jit(
            jax.value_and_grad(program, has_aux=True))(params)
        flat = weights.flatten(params)
        (want_loss, counts), want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, biases, batch, model),
            has_aux=True))(flat)
        want_logits = reference.logits_fn(flat, biases, batch["tokens"], model)
        (by_layer_loss, by_layer_counts), by_layer = reference.layerwise(
            model)(flat, biases, batch)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(by_layer_loss, want_loss, rtol=1e-6)
    np.testing.assert_array_equal(by_layer_counts, counts)
    grads = weights.flatten(grads)
    assert set(grads) == set(want) == set(by_layer)
    assert "lm_head/kernel" not in grads           # the head is the embedding
    for path, g in grads.items():
        scale = float(jnp.max(jnp.abs(want[path])))
        assert scale > 0, path  # every leaf is alive at this init
        np.testing.assert_allclose(g, want[path], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=path)
        np.testing.assert_allclose(by_layer[path], want[path], rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=path)
    np.testing.assert_allclose(
        _biases(new_stats, module),
        reference.next_biases(biases, counts, model), atol=1e-7)
    # four expert blocks, every row of every sequence; the dense one none
    assert float(jnp.sum(counts)) == 4 * 2 * 40 * module.top_k
    assert float(jnp.sum(counts[0])) == 0


def test_three_adamw_steps_and_the_biases_after_them():
    """The step the ``Trainer`` builds (``make_train_step`` with the preset's
    AdamW chain) for three steps against the reference's own three: each
    step's loss, every leaf's change, every bias."""
    cfg = from_preset("lfm2_8b_a1b_share", model="lfm2_moe_tiny",
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
    got = _biases(jax.device_get(state.batch_stats), module)
    np.testing.assert_allclose(got, want["biases"], atol=1e-6)
    assert float(np.abs(want["biases"][1:]).max()) > 0.05   # they moved
    assert not np.abs(want["biases"][0]).any()              # the dense block


def _without(piece):
    """The program's model with one piece left out, as a module the seeded
    weights still fit."""
    module = lfm2_moe.lfm2_moe_tiny()
    if piece == "the rotary term":
        return module.clone(rope_theta=1.0 + 1e-9)
    if piece == "the scaling factor":
        return module.clone(route_scale=2.0)
    return module


@pytest.mark.parametrize("piece", [
    "the gate before the conv", "the gate after the conv", "the oldest tap",
    "the q/k norm", "the rotary term", "the bias in the choice",
    "the scaling factor"])
def test_reference_sees_what_a_step_leaves_out(piece, monkeypatch):
    """The comparison's other side: a program without the piece is not the
    reference's model (the rehearsal twin holds the whole command to the
    same)."""
    module = lfm2_moe.lfm2_moe_tiny()
    lively = [["scale$", "const", 1.0], [".*", "normal", 0.2]]
    params, stats, batch = _seeded(module, 48, rules=lively)
    stats = jax.tree.map(lambda b: 0.3 * jnp.cos(jnp.arange(b.size) * 1.7),
                         stats)
    ones = lambda bcx, keep: jnp.concatenate(
        [jnp.ones_like(c) if i not in keep else c
         for i, c in enumerate(jnp.split(bcx, 3, -1))], -1)
    conv = ssd_lib.gated_conv
    if piece == "the gate before the conv":
        monkeypatch.setattr(ssd_lib, "gated_conv",
                            lambda bcx, k: conv(ones(bcx, (1, 2)), k))
    elif piece == "the gate after the conv":
        monkeypatch.setattr(ssd_lib, "gated_conv",
                            lambda bcx, k: conv(ones(bcx, (0, 2)), k))
    elif piece == "the oldest tap":
        monkeypatch.setattr(ssd_lib, "gated_conv",
                            lambda bcx, k: conv(bcx, k.at[0].set(0.0)))
    elif piece == "the q/k norm":
        params = jax.tree.map(lambda x: x, params)
        params["block_1"]["attn"]["q_norm"]["scale"] = jnp.full((16,), 0.25)
    used = (jax.tree.map(jnp.zeros_like, stats)
            if piece == "the bias in the choice" else stats)
    task = train_loop.get_task("lm")
    with HIGHEST:
        loss = task.loss(_without(piece).apply(
            {"params": params, "batch_stats": used}, batch["tokens"],
            train=False), batch)
        want, _ = reference.loss_fn(
            weights.flatten(_seeded(module, 48, rules=lively)[0]),
            _biases(stats, module), batch, _model_dict(module))
    assert abs(float(loss) - float(want)) > 1e-4 * float(want), piece


# -- the shares and the uncut layer --------------------------------------------------


def _expert_layer(held, num_experts=8):
    return moe_lib.SharedExpertMoE(
        num_experts=num_experts, ffn_dim=24, top_k=4, held_experts=held,
        route_norm_eps=lfm2_moe.ROUTE_NORM_EPS)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold two experts each of eight, four a token and no shared
    expert: the routed parts that the shares give are the uncut layer's
    output (with the family's 1e-6 in every share's normaliser: the sum is
    over all the chosen, held or not); a share's gradient of its own experts
    is the uncut layer's gradient of them; and a token none of whose choices
    is held gets exactly zero."""
    E, d = 8, 32
    x = jax.random.normal(jax.random.key(4), (1, 48, d))
    whole = _expert_layer(None)
    params = weights.make_like(jax.eval_shape(
        lambda: whole.init(jax.random.key(0), x, train=False)["params"]),
        [[".*", "normal", 0.3]], weights.seed_key(1))
    assert set(params) == {"router", "w_gate", "w_up", "w_down"}
    bias = {"expert_bias": 0.2 * jnp.sin(jnp.arange(float(E)))}
    run = lambda layer, p: layer.apply(
        {"params": p, "batch_stats": bias}, x, train=False)
    part = lambda first, held: {**params, **{
        k: params[k][first:first + held]
        for k in ("w_gate", "w_up", "w_down")}}
    with HIGHEST:
        want = run(whole, params)
        parts = [run(_expert_layer((2, 2 * c)), part(2 * c, 2))
                 for c in range(4)]
        np.testing.assert_allclose(sum(parts), want, rtol=2e-5, atol=2e-4)
        g = jax.grad(lambda p: jnp.sum(jnp.sin(run(whole, p))))(params)
        rest = want - parts[1]
        mine = jax.grad(lambda p: jnp.sum(jnp.sin(
            run(_expert_layer((2, 2)), p) + rest)))(part(2, 2))
        scores = jax.nn.sigmoid(x[0] @ params["router"])
        _, chosen = jax.lax.top_k(scores + bias["expert_bias"], 4)
    for name in ("w_gate", "w_up", "w_down"):
        scale = float(jnp.max(jnp.abs(g[name])))
        np.testing.assert_allclose(mine[name], g[name][2:4],
                                   atol=1e-5 * scale)
    assert all(float(jnp.max(jnp.abs(p))) > 0.1 for p in parts)
    elsewhere = ~np.any((np.asarray(chosen) >= 2) & (np.asarray(chosen) < 4),
                        axis=-1)
    assert elsewhere.any() and not np.asarray(parts[1])[0, elsewhere].any()


# -- the published entry and the chip's share ------------------------------------


def test_published_entry_and_its_share():
    """The published sizes give the published 8.34 B with one embedding, and
    the share's count is the configuration file's sum, layer by layer; no
    width differs."""
    full = lfm2_moe.lfm2_8b_a1b()
    kinds = full.layer_types
    assert len(kinds) == 24 and kinds.count("conv") == 18
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    conv, attn, dense, expert = 16_783_360, 10_485_888, 44_040_192, 11_010_048
    layer = lambda op, ffn: op + ffn + 4_096
    assert lfm2_moe.num_params(full) == (
        2 * layer(conv, dense) + 16 * layer(conv, 65_536 + 32 * expert)
        + 6 * layer(attn, 65_536 + 32 * expert) + 65_536 * 2_048 + 2_048
    ) == 8_339_929_856
    share = lfm2_moe.chip_share(full)
    assert lfm2_moe.num_params(share) == (
        60_827_648 + 4 * 104_933_376 + 2 * 98_635_904 + 33_554_432 + 2_048
    ) == 711_389_440
    assert share.layer_types == ("conv", "full_attention", "conv", "conv",
                                 "conv", "full_attention", "conv") == kinds[1:8]
    assert (share.num_dense_layers, share.held_experts, share.vocab_size) == (
        1, (8, 0), 16384)
    assert lfm2_moe.chip_share(full, chip=3).held_experts == (8, 24)
    widths = lambda m: {f: getattr(m, f) for f in m.__dataclass_fields__
                        if f not in ("layer_types", "num_dense_layers",
                                     "held_experts", "vocab_size", "parent",
                                     "name")}
    assert widths(share) == widths(full)
    leaves = jax.eval_shape(lambda: share.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    assert sum(x.size for x in jax.tree.leaves(leaves["params"])) \
        == 711_389_440
    assert "lm_head" not in leaves["params"]
    tiny = lfm2_moe.lfm2_moe_tiny()
    made = jax.eval_shape(lambda: tiny.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    assert sum(x.size for x in jax.tree.leaves(made["params"])) \
        == lfm2_moe.num_params(tiny)
    assert set(tiny.layer_types) == set(lfm2_moe.KINDS)


def test_forward_flops_agree_with_the_benchmarks_count():
    share = lfm2_moe.chip_share(lfm2_moe.lfm2_8b_a1b())
    ours = 8192 * lfm2_moe.forward_flops_per_token(share, 8192)
    theirs = reference.forward_flops(
        _model_dict(share, held_layers=list(range(7))), {"seq_len": 8192})
    assert ours == pytest.approx(theirs, rel=1e-12)
    assert ours == pytest.approx(4.628e12, rel=1e-3)
    bundle = registry.create_model(
        "lfm2_8b_a1b_share", seq_len=8192, dtype=jnp.bfloat16,
        param_dtype=jnp.float32, logits_dtype=jnp.float32)
    assert bundle.fwd_flops_per_example == ours
    assert {"lfm2_8b_a1b", "lfm2_8b_a1b_share", "lfm2_moe_tiny"} <= set(
        registry.list_models())


def test_a_layer_type_the_family_does_not_have_fails_loudly():
    module = lfm2_moe.lfm2_moe_tiny(layer_types=("conv", "sliding_attention"))
    with pytest.raises(ValueError, match="unknown layer type"):
        module.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="layers"):
        reference._sizes({**_model_dict(lfm2_moe.lfm2_moe_tiny()),
                          "layer_types": ["conv", "mamba", "conv", "conv",
                                          "conv"]})


# -- the preset on the normal path ----------------------------------------------------


def test_preset_trains_through_the_trainer_with_named_regions(devices):
    cfg = from_preset("lfm2_8b_a1b_share", model="lfm2_moe_tiny",
                      seq_len=32, global_batch_size=8, precision="fp32",
                      epochs=1, steps_per_epoch=3, workers=0, log_every=100,
                      checkpoint_dir=None, attn_impl="xla")
    assert (cfg.strategy, cfg.remat, cfg.optimizer) == ("fsdp", True, "adamw")
    trainer = Trainer(cfg)
    trainer.train_epoch(0)
    assert int(trainer.state.step) == 3
    bias = weights.flatten(trainer.state.batch_stats)
    assert sorted(bias) == [f"block_{i}/moe/expert_bias" for i in (1, 2, 3, 4)]
    assert all(float(jnp.max(jnp.abs(b))) > 0 for b in bias.values())
    batch = next(iter(trainer._make_step_iter(0, 0)))
    text = trainer.train_step.lower(trainer.state, batch).as_text(
        debug_info=True)
    for scope in ("short_conv", "in_proj", "conv_gate", "out_proj", "attn",
                  "mlp", "moe", "moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "norm", "embed", "head_loss"):
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope
    assert "moe_shared" not in text               # the family has none


def test_what_the_family_does_not_do_fails_loudly():
    module = lfm2_moe.lfm2_moe_tiny()
    with pytest.raises(NotImplementedError, match="rows of history"):
        module.apply({}, jnp.zeros((1, 4), jnp.int32), decode_ctx={})
    kw = dict(seq_len=32, dtype=jnp.float32, param_dtype=jnp.float32,
              remat=False, logits_dtype=jnp.float32, num_classes=0,
              image_size=0)
    with pytest.raises(ValueError, match="tensor- or sequence-parallel"):
        registry.create_model("lfm2_moe_tiny", sp=True, **kw)
    for strategy in ("tp", "fsdp_tp", "pp"):
        cfg = from_preset("lfm2_8b_a1b_share", model="lfm2_moe_tiny",
                          strategy=strategy, seq_len=32, global_batch_size=8,
                          workers=0, checkpoint_dir=None)
        with pytest.raises(ValueError):
            Trainer(cfg)

"""The Granite 4.0-H hybrid family (``models/granite_hybrid.py``,
``ops/ssd.py``): the chunked state-space scan against the step-by-step
recurrence, the model against the benchmark's plain reference, the published
entry's shape, the chip's share of it, ``fsdp`` against one device, and the
optimizer's ``b2`` by task. Float32 on the CPU at toy widths."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import weights  # noqa: E402
from chipbench.references import granite4_h_micro as reference  # noqa: E402
from pytorch_distributed_training_example_tpu.core import (  # noqa: E402
    mesh as mesh_lib, optim, train_loop)
from pytorch_distributed_training_example_tpu.core.trainer import Trainer  # noqa: E402
from pytorch_distributed_training_example_tpu.data import prefetch  # noqa: E402
from pytorch_distributed_training_example_tpu.models import (  # noqa: E402
    granite_hybrid, registry)
from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.parallel import (  # noqa: E402
    sharding as sharding_lib)
from pytorch_distributed_training_example_tpu.utils.config import (  # noqa: E402
    Config, from_preset)

HIGHEST = jax.default_matmul_precision("highest")


# -- ops/ssd.py ----------------------------------------------------------------


def _recurrence(x, dt, A, B, C, D):
    """The definition, one token at a time."""
    def step(state, inputs):
        xt, dtt, Bt, Ct = inputs
        state = jnp.exp(dtt * A)[..., None, None] * state \
            + (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, Ct) + D[:, None] * xt

    b, _, H, P = x.shape
    time_major = [a.swapaxes(0, 1) for a in (x, dt, B, C)]
    _, ys = jax.lax.scan(step, jnp.zeros((b, H, P, B.shape[-1])), time_major)
    return ys.swapaxes(0, 1)


def _scan_inputs(S=19, b=2, H=4, P=8, N=16):
    k = jax.random.split(jax.random.key(0), 5)
    return (jax.random.normal(k[0], (b, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (b, S, H)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (b, S, N)),
            jax.random.normal(k[4], (b, S, N)), jnp.full((H,), 0.5))


@pytest.mark.parametrize("chunk", [4, 8, 19], ids=["c4", "c8", "whole"])
def test_ssd_matches_the_recurrence(chunk):
    """Values and every input's gradient, at a sequence (19) that is no
    multiple of the chunk, so the padding is under test too."""
    args = _scan_inputs()
    loss = lambda fn: lambda *a: jnp.sum(jnp.square(fn(*a)))
    chunked = lambda *a: ssd_lib.ssd(*a, chunk=chunk)
    with HIGHEST:
        np.testing.assert_allclose(chunked(*args), _recurrence(*args),
                                   rtol=1e-5, atol=1e-5)
        got = jax.grad(loss(chunked), argnums=range(6))(*args)
        want = jax.grad(loss(_recurrence), argnums=range(6))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_ssd_bf16_keeps_decays_in_float32():
    x, dt, A, B, C, D = _scan_inputs(S=32)
    low = ssd_lib.ssd(x.astype(jnp.bfloat16), dt, A, B.astype(jnp.bfloat16),
                      C.astype(jnp.bfloat16), D, chunk=8)
    assert low.dtype == jnp.float32  # the accumulator, not rounded again
    np.testing.assert_allclose(low.astype(jnp.float32),
                               _recurrence(x, dt, A, B, C, D),
                               rtol=0.1, atol=0.15)


def test_causal_conv_matches_convolve_per_channel():
    k = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(k[0], (2, 11, 5))
    kernel = jax.random.normal(k[1], (4, 5))
    bias = jax.random.normal(k[2], (5,))
    got = np.asarray(ssd_lib.causal_conv1d(x, kernel, bias))
    for b in range(2):
        for c in range(5):
            # y_t = sum_k kernel[k] x_{t-3+k}: a convolution with the taps
            # reversed, cut to the first S outputs (zero history)
            want = np.convolve(np.asarray(x[b, :, c]),
                               np.asarray(kernel[::-1, c]))[:11] + bias[c]
            np.testing.assert_allclose(got[b, :, c], want, rtol=1e-5,
                                       atol=1e-5)


# -- the conv with its silu as one kernel pair (interpreted here) ----------------


def _conv_silu_body(x, kernel, bias):
    return jax.nn.silu(ssd_lib.causal_conv1d(x, kernel, bias))


def _conv_inputs(S, C, dtype, K=4, b=2):
    k = jax.random.split(jax.random.key(2), 4)
    return ((jax.random.normal(k[0], (b, S, C)).astype(dtype),
             0.5 * jax.random.normal(k[1], (K, C)),
             0.1 * jax.random.normal(k[2], (C,))),
            jax.random.normal(k[3], (b, S, C)))


def _value_and_cotangents(fn, args, w):
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
        argnums=tuple(range(len(args))))(*args)


def _close(got, want, tol, what):
    for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))):
        assert g.shape == w.shape and g.dtype == w.dtype, (what, i)
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        gap, scale = float(jnp.max(jnp.abs(g - w))), float(jnp.max(jnp.abs(w)))
        assert gap <= tol * scale, (what, i, gap, scale)


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of [32, 128] for the conv and of 32 rows of whole groups (at most
    256 lanes, or one group) for the norm: the interpreter's size."""
    monkeypatch.setattr(ssd_lib, "STAGE_TILE", 32 * 128)
    monkeypatch.setattr(ssd_lib, "STAGE_COLS", 128)


@pytest.mark.parametrize("S", [64, 80], ids=["tiles", "ragged"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["fp32", "bf16"])
def test_conv_silu_kernels_are_the_jax_numpy_body(small_tiles, dtype, tol, S):
    """Values, ``dx``, ``d kernel`` and ``d bias`` of the kernel pair at two
    sequences, two column blocks and two or three row tiles (the last one
    part-filled where S is 80) against ``silu(causal_conv1d)`` differentiated
    by XLA. bf16: the body rounds the conv before its silu and the kernel does
    not, so they differ by a rounding."""
    args, w = _conv_inputs(S, 256, dtype)
    assert ssd_lib._stage_plan("conv_silu", S, 256, 1, dtype, 4) == (32, 128)
    got = _value_and_cotangents(ssd_lib.conv_silu, args, w)
    want = _value_and_cotangents(_conv_silu_body, args, w)
    _close(got, want, tol, "conv_silu")


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["fp32", "bf16"])
def test_conv_silu_halo_rows_by_themselves(small_tiles, dtype, tol):
    """The first K-1 rows of a tile read the rows before it and the last K-1
    feed the tile that follows: those rows of the result and of ``dx`` alone,
    at both tile borders of a 96-row sequence."""
    args, w = _conv_inputs(96, 128, dtype)
    out = ssd_lib.conv_silu(*args)
    dx = jax.grad(lambda x: jnp.sum(ssd_lib.conv_silu(x, *args[1:]) * w))(
        args[0])
    want = _conv_silu_body(*args)
    want_dx = jax.grad(lambda x: jnp.sum(_conv_silu_body(x, *args[1:]) * w))(
        args[0])
    for border in (32, 64):
        _close(out[:, border:border + 3], want[:, border:border + 3], tol,
               ("first rows", border))
        _close(dx[:, border - 3:border], want_dx[:, border - 3:border], tol,
               ("last rows", border))


def test_conv_silu_carries_nothing_from_one_sequence_into_the_next(
        small_tiles):
    """A batch of two is each sequence by itself, forward and backward: the
    history and the cotangent a tile hands on are zeroed at a sequence's
    start and end."""
    (x, kernel, bias), w = _conv_inputs(64, 128, jnp.float32)
    both = _value_and_cotangents(ssd_lib.conv_silu, (x, kernel, bias), w)
    for i in range(2):
        alone = _value_and_cotangents(
            ssd_lib.conv_silu, (x[i:i + 1], kernel, bias), w[i:i + 1])
        np.testing.assert_array_equal(both[1][0][i], alone[1][0][0])
    # and the parameters' gradients are the two sequences' summed
    one, two = (_value_and_cotangents(
        ssd_lib.conv_silu, (x[i:i + 1], kernel, bias), w[i:i + 1])[1]
        for i in range(2))
    _close(both[1][1:], jax.tree.map(jnp.add, one[1:], two[1:]), 1e-6, "sum")


@pytest.mark.parametrize("offset", [128, 64], ids=["block_edge", "off_it"])
def test_conv_silu_reads_its_slice_out_of_the_wider_array(small_tiles,
                                                          offset):
    """Handed the array whose lanes ``offset : offset + C`` are ``x``, the
    kernels read them there (an offset on a column block's edge; off it they
    read ``x``), and the wider array's cotangent is the slice's, through the
    slice alone: values and every gradient are the body's."""
    (x, kernel, bias), w = _conv_inputs(64, 256, jnp.float32)
    k = jax.random.split(jax.random.key(7), 2)
    wide = jnp.concatenate([jax.random.normal(k[0], (2, 64, offset)), x,
                            jax.random.normal(k[1], (2, 64, 64))], -1)
    cut = lambda a: a[..., offset:offset + 256]
    got = _value_and_cotangents(
        lambda a, *rest: ssd_lib.conv_silu(cut(a), *rest, source=a,
                                           offset=offset),
        (wide, kernel, bias), w)
    want = _value_and_cotangents(lambda a, *rest: _conv_silu_body(cut(a), *rest),
                                 (wide, kernel, bias), w)
    _close(got, want, 1e-5, "source")
    # on a block's edge nothing reads the slice: the call's operand is wide
    read = _pallas_operands(lambda a: ssd_lib.conv_silu(
        cut(a), kernel, bias, source=a, offset=offset), wide)
    assert [shapes[0] for shapes in read] == [
        wide.shape if offset == 128 else x.shape]


def _pallas_operands(fn, *args):
    """The operands' shapes of every ``pallas_call`` in ``fn``'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append([v.aval.shape for v in eqn.invars])
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) else [
                        param]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("S,C,dtype,K,why", [
    (64, 256, jnp.float16, 4, "fp16"),
    (64, 192, jnp.float32, 4, "channels off the lane tiling"),
    (16, 256, jnp.float32, 4, "a sequence shorter than a tile"),
    (64, 256, jnp.float32, 8, "more taps than the history holds"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_conv_silu_plan_refuses_and_the_body_runs(small_tiles, S, C, dtype, K,
                                                  why):
    assert ssd_lib._stage_plan("conv_silu", S, C, 1, dtype, K) is None, why
    args, w = _conv_inputs(S, C, dtype, K)
    got = _value_and_cotangents(ssd_lib.conv_silu, args, w)
    want = _value_and_cotangents(_conv_silu_body, args, w)
    for g, t in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, t)
    assert "pallas_call" not in str(jax.make_jaxpr(ssd_lib.conv_silu)(*args))


# -- the model against the plain reference -------------------------------------

#: the reference reads the published config's keys; these are the toy's
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "shared_intermediate_size": 128, "mamba_n_heads": 4,
        "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
        "mamba_d_conv": 4, "mamba_chunk_size": 8, "num_hidden_layers": 10,
        "layer_types": list(granite_hybrid.PERIOD) * 4, "vocab_size": 96,
        "attention_multiplier": 0.0625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "rms_norm_eps": 1e-5}
RULES = [["(scale|/D)$", "const", 1.0], ["dt_bias$", "const", -1.0],
         ["bias$", "normal", 0.1], ["A_log$", "normal", 1.0],
         ["conv_kernel$", "normal", 0.3], ["", "normal", 0.05]]


def _tiny(**kw):
    # attention_multiplier 1/16 at head_dim 16: the fold into q is 1/4
    return granite_hybrid.granite_hybrid_tiny(attention_multiplier=0.0625,
                                              **kw)


def _seeded(module, S, seed=3):
    tokens = jax.random.randint(jax.random.key(seed), (2, S + 1), 0,
                                module.vocab_size)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens[:, :-1]))["params"]
    params = weights.make_like(shapes, RULES, weights.seed_key(seed))
    return params, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


@pytest.mark.parametrize("S", [16, 19], ids=["chunks", "ragged"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_the_plain_reference(S, remat):
    """Logits, loss and every leaf's gradient, in float32."""
    module = _tiny(remat=remat)
    params, batch = _seeded(module, S)
    task = train_loop.get_task("lm")

    def program(p):
        logits = module.apply({"params": p}, batch["tokens"])
        return task.loss(logits, batch), logits

    with HIGHEST:
        (loss, logits), grads = jax.jit(
            jax.value_and_grad(program, has_aux=True))(params)
        flat = weights.flatten(params)
        want_logits = jax.jit(lambda p: reference.logits_fn(
            p, batch["tokens"], TINY))(flat)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, TINY)))(flat)
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    grads = weights.flatten(grads)
    assert set(grads) == set(want) and len(want) == 128
    for path, g in grads.items():
        scale = float(jnp.max(jnp.abs(want[path])))
        assert scale > 0, path  # every leaf is alive at this init
        np.testing.assert_allclose(g, want[path], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=path)


def test_forward_flops_agree_with_the_benchmarks_count():
    module = granite_hybrid.chip_share(granite_hybrid.granite4_h_micro())
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite4_h_micro.json")) as fh:
        import json

        model = json.load(fh)["model"]
    program = 4096 * granite_hybrid.forward_flops_per_token(module, 4096)
    assert program == reference.forward_flops(model, {"seq_len": 4096})
    assert 1.55e9 < program / 4096 < 1.65e9


# -- the published entry and the chip's share of it ------------------------------


def _count(module):
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def test_published_entry_and_its_share():
    full = registry.create_model("granite4_h_micro", seq_len=4096).module
    assert full.num_layers == 40 and full.vocab_size == 100352
    assert [i for i, k in enumerate(full.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    share = registry.create_model("granite4_h_micro_share",
                                  seq_len=4096).module
    assert share.layer_types == full.layer_types[:10]
    assert share.vocab_size == 12544
    for field in ("d_model", "ffn_dim", "num_heads", "num_kv_heads",
                  "head_dim", "mamba_heads", "mamba_head_dim", "mamba_state",
                  "mamba_conv", "mamba_chunk"):  # no width is cut
        assert getattr(share, field) == getattr(full, field)
    # ISSUE 28's arithmetic: a Mamba layer 76,182,976, an attention layer
    # 60,821,504, a period 746,468,288, the share 772,160,448
    period = 9 * 76_182_976 + 60_821_504
    assert period == 746_468_288
    assert _count(share) == granite_hybrid.num_params(share) \
        == period + 12544 * 2048 + 2048 == 772_160_448
    assert _count(full) == granite_hybrid.num_params(full) \
        == 4 * period + 100352 * 2048 + 2048


def test_share_logits_are_the_slice_of_the_full_vocabularys():
    """Ids from the slice, the same layers: the share's logits are the first
    columns of the logits under the whole embedding."""
    full = _tiny(vocab_size=96 * 8, layer_types=granite_hybrid.PERIOD * 2)
    share = granite_hybrid.chip_share(full)
    assert share.vocab_size == 96 and share.num_layers == 10
    tokens = jax.random.randint(jax.random.key(5), (2, 16), 0, 96)
    whole = full.init(jax.random.key(0), tokens)["params"]
    part = {k: v for k, v in whole.items()
            if k in share.init(jax.random.key(0), tokens)["params"]}
    part["embed"] = {"embedding": whole["embed"]["embedding"][:96]}
    ten = full.clone(layer_types=full.layer_types[:10])
    with HIGHEST:
        np.testing.assert_allclose(
            jax.jit(share.apply)({"params": part}, tokens),
            jax.jit(ten.apply)({"params": {**part, "embed": whole["embed"]}},
                               tokens)[..., :96], rtol=1e-6, atol=1e-6)


# -- the normal path: strategies, optimizer, trainer -----------------------------


def _two_steps(mesh, strategy):
    bundle = registry.create_model("granite_hybrid_tiny", seq_len=32,
                                   dtype=jnp.float32)
    # wide enough that AUTO_FSDP shards the projections (>= 16384 elements)
    module = bundle.module.clone(d_model=128, ffn_dim=256)
    cfg = Config(lr=1e-2, warmup_epochs=0.0, optimizer="sgd", grad_clip=0.0,
                 weight_decay=0.0)
    tx, _ = optim.build_optimizer(cfg, steps_per_epoch=100)
    rules = sharding_lib.strategy_rules(strategy, bundle.rules)
    state = train_loop.create_train_state(
        module, tx, bundle.input_template, mesh, rules, seed=0)
    step = jax.jit(train_loop.make_train_step(train_loop.get_task("lm")),
                   donate_argnums=0)
    sharded = [p for p in jax.tree.leaves(state.params)
               if not p.sharding.is_fully_replicated]
    with mesh_lib.use_mesh(mesh):
        for i in range(2):
            toks = np.random.RandomState(i).randint(
                0, 96, (8, 33)).astype(np.int32)
            batch = prefetch.shard_batch(
                {"tokens": toks[:, :-1], "targets": toks[:, 1:]},
                mesh_lib.batch_sharding(mesh))
            state, metrics = step(state, batch)
        return (jax.device_get(state.params), float(metrics["loss"]),
                len(sharded))


def test_fsdp_over_four_devices_matches_one(devices):
    ref_params, ref_loss, _ = _two_steps(mesh_lib.single_device_mesh(), "dp")
    mesh = mesh_lib.build_mesh({"data": 1, "fsdp": 4}, devices=devices[:4])
    params, loss, sharded = _two_steps(mesh, "fsdp")
    assert sharded >= 30  # the projections and the MLP of every layer
    assert np.isclose(loss, ref_loss, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(params)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


def test_what_the_family_does_not_do_fails_loudly():
    bundle = registry.create_model("granite_hybrid_tiny", seq_len=16)
    for strategy in ("tp", "fsdp_tp"):  # no rule table: never replicated
        with pytest.raises(ValueError, match="unknown strategy"):
            sharding_lib.strategy_rules(strategy, bundle.rules)
    with pytest.raises(ValueError, match="parallel rules"):
        registry.create_model("granite_hybrid_tiny", seq_len=16, sp=True)
    module = bundle.module
    tokens = jnp.zeros((1, 16), jnp.int32)
    variables = module.init(jax.random.key(0), tokens)
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        module.apply(variables, tokens, decode_ctx={})


@pytest.mark.parametrize("model,b2", [
    ("gpt2_tiny", 0.95), ("llama_tiny", 0.95), ("granite_hybrid_tiny", 0.95),
    ("vit_tiny", 0.999)])
def test_adamw_b2_follows_the_task(model, b2):
    """Read off the optimizer itself: after one step on a gradient of ones
    the second moment is ``1 - b2``."""
    tx, _ = optim.build_optimizer(
        Config(model=model, optimizer="adamw", lr=1e-3, warmup_epochs=0.0),
        steps_per_epoch=10)
    params = {"w": jnp.ones((2, 2))}
    _, state = tx.update({"w": jnp.ones((2, 2))}, tx.init(params), params)
    nu = next(s.nu for s in jax.tree.leaves(
        state, is_leaf=lambda s: hasattr(s, "nu")) if hasattr(s, "nu"))
    np.testing.assert_allclose(nu["w"], 1 - b2, rtol=1e-5)
    assert isinstance(tx, optax.GradientTransformation)


def test_preset_trains_through_the_trainer_with_named_regions(devices):
    """The preset at toy size through ``Trainer`` (what ``main.py --preset``
    builds): it steps, the loss is sane, and the step's text carries the mixer's scopes."""
    cfg = from_preset("granite4_h_micro_share", model="granite_hybrid_tiny",
                      seq_len=32, global_batch_size=8, precision="fp32",
                      lr=3e-3, lr_schedule="constant", warmup_epochs=0.0,
                      workers=0, steps_per_epoch=12, log_every=1000,
                      checkpoint_dir=None, mesh_fsdp=4, mesh_data=2)
    trainer = Trainer(cfg)
    assert trainer.bundle.task == "lm" and cfg.remat
    for epoch in range(2):
        trainer.train_epoch(epoch)
    assert int(trainer.state.step) == 24
    # uniform random tokens cannot be learned: the loss stays at ln(vocab)
    assert abs(trainer.evaluate(1)["loss"] - np.log(96)) < 0.1
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                     sharding=trainer.batch_sharding)
             for k in ("tokens", "targets")}
    with mesh_lib.use_mesh(trainer.mesh):
        text = trainer.train_step.lower(trainer.state, batch).as_text(
            debug_info=True)
    for scope in ("mamba", "conv1d", "ssd", "gated_norm", "attn", "mlp",
                  "head_loss", "optimizer"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope

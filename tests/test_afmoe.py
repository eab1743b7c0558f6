"""The ``afmoe`` family (``models/afmoe.py``, ``parallel/moe.SharedExpertMoE``,
the window path of ``ops/attention.py`` and ``ops/flash_attention.py``): the
window mask against the plain mask, the model against the benchmark's plain
reference, the published entry's shape and the chip's share of it, the
shares adding up to the uncut layer, the router's bias, and the preset
through the ``Trainer``. Float32 on the CPU at toy widths."""

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
from chipbench.references import trinity_mini as reference  # noqa: E402
from pytorch_distributed_training_example_tpu.core import (  # noqa: E402
    mesh as mesh_lib, train_loop)
from pytorch_distributed_training_example_tpu.core.trainer import Trainer  # noqa: E402
from pytorch_distributed_training_example_tpu.models import (  # noqa: E402
    afmoe, registry)
from pytorch_distributed_training_example_tpu.ops import (  # noqa: E402
    attention as attn_lib, flash_attention as flash_lib,
    grouped_matmul as gmm_lib)
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.utils.config import from_preset  # noqa: E402

HIGHEST = jax.default_matmul_precision("highest")
RULES = [["scale$", "const", 1.0], [".*", "normal", 0.02]]


# -- the window ------------------------------------------------------------------


def _qkv(S, H=4, Hkv=2, D=32, b=2):
    k = jax.random.split(jax.random.key(0), 4)
    return (jax.random.normal(k[0], (b, S, H, D)),
            jax.random.normal(k[1], (b, S, Hkv, D)),
            jax.random.normal(k[2], (b, S, Hkv, D)),
            jax.random.normal(k[3], (b, S, H, D)))


def _masked_softmax_attention(q, k, v, window):
    """The definition, written out: row i sees 0 <= i - j < window."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    gap = jnp.arange(q.shape[1])[:, None] - jnp.arange(q.shape[1])[None, :]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where((gap >= 0) & (gap < window), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("window", [1, 16, 37, 64, 200])
def test_attention_window_matches_the_written_out_mask(window):
    """``attention(window=)`` (the XLA path off the chip) at a sequence of
    several windows: forward and the three gradients."""
    q, k, v, g = _qkv(128)
    want = lambda q, k, v: jnp.sum(_masked_softmax_attention(q, k, v, window) * g)
    got = lambda q, k, v: jnp.sum(attn_lib.attention(
        q, k, v, causal=True, window=window) * g)
    with HIGHEST:
        np.testing.assert_allclose(
            attn_lib.attention(q, k, v, causal=True, window=window),
            _masked_softmax_attention(q, k, v, window), atol=2e-6)
        for a, b in zip(jax.grad(got, (0, 1, 2))(q, k, v),
                        jax.grad(want, (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.fixture
def sub_offsets_max(monkeypatch):
    """Set the cap on a crossed block's offsets for one test: the jitted
    launchers read it as they trace, so their traces go with it."""
    def set_cap(cap):
        monkeypatch.setattr(flash_lib, "SUB_OFFSETS_MAX", cap)
        flash_lib._flash_fwd.clear_cache()
        flash_lib._flash_bwd.clear_cache()
    yield set_cap
    flash_lib._flash_fwd.clear_cache()
    flash_lib._flash_bwd.clear_cache()


@pytest.mark.parametrize("S,window,block_q,block_kv,cap,sub", [
    (256, 100, 64, 64, 2, 0),      # no multiple of the block: both edge blocks masked
    (256, 128, 64, 64, 2, 0),      # whole blocks: the blocks inside take the unmasked body
    (512, 129, 128, 128, 2, 0),    # one key past a block's edge
    (256, 1, 64, 64, 2, 0),        # a row sees itself alone
    (256, 64, 64, 64, 2, 0),       # the window is one block
    (256, 300, 64, 64, 2, 0),      # covers the sequence: plain causal, no window
    (1024, 512, 256, 256, 2, 128),     # sub-tiles on both edges, a block inside between
    (1024, 256, 256, 256, 2, 128),     # the far edge in the diagonal block's neighbour
    (1024, 128, 256, 256, 2, 128),     # narrower than a block: both edges in one block
    (1024, 512, 256, 512, 2, 0),       # unequal blocks: four crossed offsets, whole blocks
    (1024, 512, 256, 512, 4, 256),     # the same in sub-tiles, under a cap that admits them
    (1024, 512, 512, 256, 4, 256)])
def test_window_kernels_match_the_reference_interpret(sub_offsets_max, S, window,
                                                      block_q, block_kv, cap, sub):
    """The online kernels under a window in interpret mode against
    ``dot_product_attention``, forward and gradients, grouped queries."""
    sub_offsets_max(cap)
    if window < S:
        assert [flash_lib.online_schedule(k, True, S, S, block_q, block_kv,
                                          window=window).sub
                for k in flash_lib.ONLINE_KERNELS] == [sub] * 3
    q, k, v, g = _qkv(S)
    flash = lambda q, k, v: flash_lib.flash_attention(
        q, k, v, True, block_q, block_kv, "auto", None, window)
    plain = lambda q, k, v: attn_lib.dot_product_attention(
        q, k, v, causal=True, window=window)
    total = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * g)
    with pltpu.force_tpu_interpret_mode():
        out = flash(q, k, v)
        grads = jax.grad(total(flash), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(out, plain(q, k, v), atol=2e-6)
    for a, b in zip(grads, jax.grad(total(plain), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("S,window,block,dtype", [
    (1024, 512, 256, jnp.float32), (1024, 256, 256, jnp.float32),
    (1024, 128, 256, jnp.float32), (1536, 768, 384, jnp.float32),
    (1024, 512, 256, jnp.bfloat16), (1024, 128, 256, jnp.bfloat16)],
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_window_sub_tiles_are_the_masked_blocks_bitwise(S, window, block, dtype):
    """As for the diagonal (tests/test_attention.py): the sub-tiled form of
    the blocks both edges cross gives what the same blocks give computed whole
    under the positional mask, bit for bit in o, lse and dq; dk and dv, whose
    stripes drop a contraction's leading zeros, to a rounding of the sum on
    XLA's CPU dot (``benchmarks/flash_micro.py --schedule-parts --window``
    compares all five on the chip)."""
    r = np.random.RandomState(7)
    q, k, v, g = (jnp.asarray(r.randn(1, S, 2, 64), dtype) for _ in range(4))
    call = dict(causal=True, block_q=block, block_kv=block, window=window)
    assert flash_lib.online_schedule("flash_fwd_online", True, S, S, block,
                                     block, window=window).sub == 128
    with pltpu.force_tpu_interpret_mode():
        o0, l0 = flash_lib._flash_fwd(q, k, v, **call, sub=0)
        o1, l1 = flash_lib._flash_fwd(q, k, v, **call)
        g0 = flash_lib._flash_bwd(q, k, v, o0, l0, g, **call, sub=0)
        g1 = flash_lib._flash_bwd(q, k, v, o0, l0, g, **call)
    for name, a, b in zip(("o", "lse", "dq"), (o1, l1, g1[0]), (o0, l0, g0[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    eps = float(jnp.finfo(dtype).eps)
    for name, a, b in zip(("dk", "dv"), g1[1:], g0[1:]):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        np.testing.assert_allclose(a, b, rtol=2 * eps,
                                   atol=eps * np.abs(b).max(), err_msg=name)


def test_window_kernels_carry_their_own_names():
    q, k, v, _ = _qkv(256)
    with pltpu.force_tpu_interpret_mode():
        text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
            flash_lib.flash_attention(q, k, v, True, 64, 64, "auto", None,
                                      100))))(q))
    for name in ("flash_fwd_window", "flash_bwd_window_dq",
                 "flash_bwd_window_dkv"):
        assert name in text, name
    assert "flash_fwd_online" not in text


def test_a_window_needs_causal_self_attention():
    q, k, v, _ = _qkv(64)
    with pytest.raises(ValueError, match="causal"):
        attn_lib.attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="context-parallel"):
        attn_lib.attention(q, k, v, causal=True, window=8, impl="ring")


# -- the model against the plain reference --------------------------------------


def _model_dict(module: afmoe.Afmoe, held_layers=None) -> dict:
    """The reference's ``model`` group for a program module."""
    held, first = module.held_experts or (module.num_experts, 0)
    return {
        "hidden_size": module.d_model, "head_dim": module.head_dim,
        "num_attention_heads": module.num_heads,
        "num_key_value_heads": module.num_kv_heads,
        "intermediate_size": module.dense_ffn_dim,
        "moe_intermediate_size": module.expert_ffn_dim,
        "num_experts": held, "held_experts_start": first,
        "routed_experts": module.num_experts,
        "num_experts_per_tok": module.top_k,
        "num_shared_experts": module.shared_experts,
        "num_dense_layers": module.num_dense_layers,
        "num_hidden_layers": module.num_layers,
        "layer_types": list(module.layer_types),
        "held_layers": held_layers or list(range(module.num_layers)),
        "sliding_window": module.window, "rope_theta": module.rope_theta,
        "rms_norm_eps": module.epsilon, "route_scale": module.route_scale,
        "load_balance_coeff": module.balance_coeff,
        "mup_enabled": module.mup, "vocab_size": module.vocab_size}


def _seeded(module, S, seed=3, batch=2):
    tokens = jax.random.randint(jax.random.key(seed), (batch, S + 1), 0,
                                module.vocab_size)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens[:, :-1]))
    params = weights.make_like(shapes["params"], RULES, weights.seed_key(seed))
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
    """Loss, every leaf's gradient and the bias after the step, in float32,
    at a sequence of three windows. The tolerances are float32 rounding
    through five layers of four norms (the Granite test's, which the same
    arithmetic met): 1e-5 on the loss, 2e-3 of a leaf's largest entry on a
    gradient."""
    module = afmoe.afmoe_tiny(remat=remat, held_experts=held)
    params, stats, batch = _seeded(module, 48)
    # a bias that changes who is chosen, so that the test sees it in the choice
    stats = jax.tree.map(lambda b: 0.3 * jnp.cos(jnp.arange(b.size) * 1.7),
                         stats)
    task = train_loop.get_task("lm")
    model = _model_dict(module)

    def program(p):
        logits, new = module.apply({"params": p, "batch_stats": stats},
                                   batch["tokens"], train=True,
                                   mutable=["batch_stats"])
        return task.loss(logits, batch), new["batch_stats"]

    with HIGHEST:
        (loss, new_stats), grads = jax.jit(
            jax.value_and_grad(program, has_aux=True))(params)
        flat = weights.flatten(params)
        (want_loss, counts), want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, _biases(stats, module), batch,
                                        model), has_aux=True))(flat)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
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
    assert float(jnp.sum(counts)) == 4 * 2 * 48 * module.top_k


@pytest.mark.parametrize("leave_out", ["shared", "bias", "window"])
def test_reference_sees_what_a_step_leaves_out(leave_out):
    """The comparison's other side: a program without the shared expert,
    without the bias in the choice, or without the window is not the
    reference's model (the rehearsal twin holds the whole command to the
    same three)."""
    module = afmoe.afmoe_tiny()
    params, stats, batch = _seeded(module, 48)
    stats = jax.tree.map(lambda b: 0.3 * jnp.cos(jnp.arange(b.size) * 1.7),
                         stats)
    broken = {"shared": module.clone(shared_experts=0),
              "bias": module, "window": module.clone(window=4096)}[leave_out]
    if leave_out == "shared":
        params = jax.tree.map(lambda x: x, params)
        for i in range(1, 5):
            params[f"block_{i}"]["moe"] = {
                k: v for k, v in params[f"block_{i}"]["moe"].items()
                if k != "shared"}
    used = jax.tree.map(jnp.zeros_like, stats) if leave_out == "bias" else stats
    task = train_loop.get_task("lm")
    with HIGHEST:
        loss = task.loss(broken.apply({"params": params, "batch_stats": used},
                                      batch["tokens"], train=False), batch)
        want, _ = reference.loss_fn(
            weights.flatten(_seeded(module, 48)[0]), _biases(stats, module),
            batch, _model_dict(module))
    assert abs(float(loss) - float(want)) > 1e-4 * float(want)


# -- the published entry and the chip's share ------------------------------------


def test_published_entry_and_its_share():
    full = afmoe.trinity_mini()
    kinds = full.layer_types
    assert len(kinds) == 32 and kinds[:4] == afmoe.PERIOD
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == list(
        range(3, 32, 4))
    assert (full.num_dense_layers, full.num_experts, full.top_k) == (2, 128, 8)
    assert afmoe.num_params(full) == 26_123_970_560
    share = afmoe.chip_share(full)
    assert share.layer_types == ("sliding_attention",) * 4 + ("full_attention",)
    assert (share.num_dense_layers, share.held_experts) == (1, (16, 0))
    assert share.vocab_size * 8 == full.vocab_size == 200192
    assert afmoe.chip_share(full, chip=3).held_experts == (16, 48)
    # no width differs
    for field in ("d_model", "num_heads", "num_kv_heads", "head_dim",
                  "dense_ffn_dim", "expert_ffn_dim", "num_experts", "top_k",
                  "window", "route_scale"):
        assert getattr(share, field) == getattr(full, field), field
    assert afmoe.num_params(share) == 705_473_792
    shapes = jax.eval_shape(
        lambda: share.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(shapes["params"])) == 705_473_792
    assert shapes["params"]["block_1"]["moe"]["router"].shape == (2048, 128)
    assert shapes["params"]["block_1"]["moe"]["w_gate"].shape == (16, 2048, 1024)
    assert shapes["batch_stats"]["block_4"]["moe"]["expert_bias"].shape == (128,)
    assert "moe" not in shapes["params"]["block_0"]


def test_forward_flops_agree_with_the_benchmarks_count():
    import json

    share = afmoe.chip_share(afmoe.trinity_mini())
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "trinity_mini.json")) as fh:
        model = json.load(fh)["model"]
    want = reference.forward_flops(model, {"seq_len": 8192})
    assert want == pytest.approx(
        8192 * afmoe.forward_flops_per_token(share, 8192), rel=1e-12)
    assert 3 * want == pytest.approx(18.14e12, rel=1e-3)
    bundle = registry.create_model(
        "trinity_mini_share", num_classes=0, image_size=0, seq_len=8192,
        dtype=jnp.bfloat16, param_dtype=jnp.float32,
        logits_dtype=jnp.float32, remat=True)
    assert bundle.fwd_flops_per_example == pytest.approx(want, rel=1e-12)


def _layer(held, **kw):
    return moe_lib.SharedExpertMoE(
        num_experts=8, ffn_dim=32, top_k=2, held_experts=held,
        shared_ffn_dim=32, route_scale=2.826, balance_coeff=0.001, **kw)


def _layer_inputs(T=64, d=64, seed=5):
    x = jax.random.normal(jax.random.key(seed), (2, T // 2, d))
    whole = _layer(None)
    variables = whole.init(jax.random.key(1), x, train=False)
    params = weights.make_like(
        jax.eval_shape(lambda: variables["params"]), [[".*", "normal", 0.3]],
        weights.seed_key(seed))
    return x, whole, params


def _part(params, first, held):
    """The share's parameters: its slice of the stacked experts, and the
    router and the shared expert whole."""
    cut = lambda w: w[first:first + held]
    return {**params, **{k: cut(params[k]) for k in ("w_gate", "w_up",
                                                     "w_down")}}


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips hold an expert each of the eight: the routed parts that
    the shares give, with the shared expert counted once, are the uncut
    layer's output; and each share's gradient of its own experts is the uncut
    layer's gradient of them."""
    x, whole, params = _layer_inputs()
    bias = {"expert_bias": 0.2 * jnp.sin(jnp.arange(8.0))}
    run = lambda layer, p: layer.apply(
        {"params": p, "batch_stats": bias}, x, train=False)
    with HIGHEST:
        want = run(whole, params)
        no_shared = {k: v for k, v in params.items() if k != "shared"}
        shared = want - run(_layer(None).clone(shared_ffn_dim=0), no_shared)
        parts = [run(_layer((1, e)), _part(params, e, 1)) - shared
                 for e in range(8)]
        np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5, atol=2e-4)  # float32 sums at |y| ~ 20
        # two chips of four experts, and the gradients of the held experts
        halves = [run(_layer((4, s)), _part(params, s, 4)) - shared
                  for s in (0, 4)]
        np.testing.assert_allclose(sum(halves) + shared, want, rtol=2e-5, atol=2e-4)
        g = jax.grad(lambda p: jnp.sum(jnp.sin(run(whole, p))))(params)
        part = jax.grad(lambda p: jnp.sum(jnp.sin(
            run(_layer((4, 4)), p) + halves[0])))(_part(params, 4, 4))
    for name in ("w_gate", "w_up", "w_down"):
        scale = float(jnp.max(jnp.abs(g[name])))   # float32 sums, as above
        np.testing.assert_allclose(part[name], g[name][4:], atol=1e-5 * scale)
    assert float(jnp.max(jnp.abs(parts[3]))) > 0.1   # a share does something


def test_collapsed_routing_takes_the_parts_and_drops_nothing():
    """Every token on the two held experts of sixteen: four times the rows a
    balanced router sends, past the whole-layout's bound, so the layer takes
    the tokens in parts; the result and the gradients are the dense sum's."""
    d, T, E = 32, 64, 16
    x = jax.random.normal(jax.random.key(2), (1, T, d))
    layer = moe_lib.SharedExpertMoE(
        num_experts=E, ffn_dim=16, top_k=2, held_experts=(2, 6),
        route_scale=1.0)
    params = weights.make_like(jax.eval_shape(
        lambda: layer.init(jax.random.key(0), x, train=False)["params"]),
        [[".*", "normal", 0.3]], weights.seed_key(1))
    collapsed = {"expert_bias": jnp.zeros((E,)).at[6:8].set(5.0)}
    spread = {"expert_bias": jnp.zeros((E,))}

    def dense(p, bias):
        scores = jax.nn.sigmoid(x[0] @ p["router"])
        _, chosen = jax.lax.top_k(scores + bias, 2)
        picked = jnp.take_along_axis(scores, chosen, -1)
        weight = picked / (picked.sum(-1, keepdims=True) + 1e-20)
        out = 0.0
        for e in range(2):
            mine = jnp.sum(jnp.where(chosen == 6 + e, weight, 0.0), -1)
            h = jax.nn.silu(x[0] @ p["w_gate"][e]) * (x[0] @ p["w_up"][e])
            out = out + mine[:, None] * (h @ p["w_down"][e])
        return out[None]

    with HIGHEST:
        for bias in (collapsed, spread):
            run = lambda p: layer.apply({"params": p, "batch_stats": bias}, x,
                                        train=False)
            np.testing.assert_allclose(run(params),
                                       dense(params, bias["expert_bias"]),
                                       atol=2e-5)
            got = jax.grad(lambda p: jnp.sum(jnp.sin(run(p))))(params)
            want = jax.grad(lambda p: jnp.sum(jnp.sin(
                dense(p, bias["expert_bias"]))))(params)
            for name in want:
                np.testing.assert_allclose(got[name], want[name], atol=2e-5,
                                           err_msg=name)
    text = str(jax.make_jaxpr(lambda p: layer.apply(
        {"params": p, "batch_stats": spread}, x, train=False))(params))
    assert "cond" in text     # whole where it fits, in parts where not
    for bias, whole in ((collapsed, 0.0), (spread, 1.0)):
        _, sown = layer.apply({"params": params, "batch_stats": bias}, x,
                              train=False, mutable=["telemetry"])
        assert float(sown["telemetry"]["moe_whole"][0]) == whole


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("routing", ["level", "collapsed"])
def test_bounded_backward_is_plain_ad_of_the_routine(routing, remat):
    """The hand-written backward of ``_routed_bounded`` against plain AD of
    ``_routed`` on the same inputs, in tokens, weights and the three stacked
    weights: the whole layout's side strings the same products together from
    what the forward kept (bit for bit), the parts' side sums the weights'
    gradients part by part (to 1e-6 of the leaf's scale); with the block's
    remat around it the forward rule is what runs again."""
    T, d, f, E, k, held, first = 64, 32, 16, 16, 2, 2, 6
    keys = jax.random.split(jax.random.key(3), 6)
    tokens = jax.random.normal(keys[0], (T, d))
    scores = jax.random.uniform(keys[1], (T, E))
    if routing == "collapsed":
        scores = scores.at[:, first:first + held].add(5.0)
    _, chosen = jax.lax.top_k(scores, k)
    weights_ = jax.random.uniform(keys[2], (T, k), minval=0.2)
    experts = tuple(0.3 * jax.random.normal(key, shape) for key, shape in zip(
        keys[3:], [(held, d, f), (held, d, f), (held, f, d)]))
    counts = jnp.bincount(chosen.reshape(-1), length=E)[
        first:first + held].astype(jnp.int32)
    bt, chunks = 8, E // (2 * held)
    whole = bool(moe_lib._fits(counts, bt, moe_lib._bounded_tiles(
        chosen, experts, bt, chunks)))
    assert whole == (routing == "level")

    def bounded(tokens, weights_, experts):
        return moe_lib._routed_bounded(tokens, chosen, weights_, experts,
                                       counts, first, bt, chunks)

    def plain(tokens, weights_, experts):
        return moe_lib._routed(tokens, chosen, weights_, experts, first, bt)

    grads = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2)))(
            tokens, weights_, experts)
    with HIGHEST:
        want_out, want = grads(plain)
        got_out, got = grads(jax.checkpoint(bounded) if remat else bounded)
    assert float(jnp.max(jnp.abs(want[0]))) > 1e-3
    np.testing.assert_allclose(got_out, want_out, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if whole:
            np.testing.assert_array_equal(g, w)
        else:
            np.testing.assert_allclose(
                g, w, rtol=0, atol=1e-6 * float(jnp.max(jnp.abs(w))))


# -- the router's and the plan's indexing (PR 49): dense passes over [T, k, E]
# -- and a sort's payload, against the forms they replaced (a gather, a
# -- scatter-add and a gather of one scalar a pair), kept here as the yardstick

#: ``(k, E, held)`` of the five expert cells: Trinity, SmallThinker, GLM,
#: Nemotron, LFM2
CELLS = pytest.mark.parametrize("k,E,held", [
    (8, 128, 16), (6, 64, 16), (4, 64, 8), (6, 128, 8), (4, 32, 8)])
ROUTINGS = pytest.mark.parametrize("routing", ["random", "collapsed", "ties"])


def _router_inputs(routing, E, T=256, d=32, seed=7):
    """``(tokens, kernel, bias)``: a random router; one whose bias puts every
    token on the first held expert; one with exact ties in ``scores + bias``
    (every second column of the kernel a copy of the one before it, one bias
    for all, and a sixteenth of the tokens zero: all their scores 0.5)."""
    keys = jax.random.split(jax.random.key(seed), 3)
    tokens = jax.random.normal(keys[0], (T, d))
    kernel = 0.3 * jax.random.normal(keys[1], (d, E))
    bias = 0.05 * jax.random.normal(keys[2], (E,))
    if routing == "collapsed":
        bias = bias.at[0].add(5.0)
    elif routing == "ties":
        kernel = kernel.at[:, 1::2].set(kernel[:, 0::2])
        bias = jnp.full((E,), 0.01)
        tokens = tokens.at[::16].set(0.0)
    return tokens, kernel, bias


def _gathered_route_sigmoid_bias(tokens, kernel, bias, k, route_scale,
                                 norm_eps=1e-20):
    """``route_sigmoid_bias`` as it was before PR 49."""
    scores = jax.nn.sigmoid(moe_lib._scores(tokens, kernel))
    _, chosen = jax.lax.top_k(scores + bias, k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = route_scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + norm_eps)
    return moe_lib.Route(chosen, weights, jnp.bincount(
        chosen.reshape(-1), length=kernel.shape[1]))


def _gathered_plan(chosen, first, held, bt, max_tiles, counts):
    """``_plan`` as it was before PR 49: a second sort for every pair's rank,
    a gather of ``dst`` by it, ``bincount`` for the counts."""
    n, k = chosen.shape
    key = moe_lib._held_keys(chosen, first, held)
    pairs = jnp.arange(n * k, dtype=jnp.int32)
    _, order = jax.lax.sort((key, pairs), num_keys=1)
    _, rank = jax.lax.sort((order, pairs), num_keys=1)
    if counts is None:
        counts = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    tiles, src, dst = gmm_lib._padded_layout(
        starts, counts, n * k, held, bt, max_tiles)
    row_pair = moe_lib._rows(order, src) + (src >= n * k) * (n * k)
    return tiles, dst[rank].reshape(n, k).T, row_pair


@ROUTINGS
@CELLS
def test_sigmoid_router_selects_and_counts_what_it_gathered(k, E, held,
                                                            routing):
    """``chosen``, ``load`` and the picked scores are the gather's and the
    ``bincount``'s exactly, the weights with them, op by op and under one
    ``jit``; the gradients to the tokens and the router's kernel, whose
    transpose was a scatter of a scalar a pair, are within 1e-6 of theirs."""
    tokens, kernel, bias = _router_inputs(routing, E)
    with HIGHEST:
        want = _gathered_route_sigmoid_bias(tokens, kernel, bias, k, 2.826)
        got = moe_lib.route_sigmoid_bias(tokens, kernel, bias, k, 2.826)
        scores = jax.nn.sigmoid(moe_lib._scores(tokens, kernel))
        picked = jnp.take_along_axis(scores, want.chosen, axis=-1)
        for pick in (moe_lib._pick, jax.jit(moe_lib._pick)):
            np.testing.assert_array_equal(pick(scores, want.chosen), picked)
        jitted = jax.jit(moe_lib.route_sigmoid_bias, static_argnums=(3, 4))(
            tokens, kernel, bias, k, 2.826)
    for route in (got, jitted):
        np.testing.assert_array_equal(route.chosen, want.chosen)
        np.testing.assert_array_equal(route.load, want.load)
        assert route.load.dtype == want.load.dtype == jnp.int32
    np.testing.assert_array_equal(got.weights, want.weights)
    np.testing.assert_allclose(jitted.weights, want.weights, rtol=3e-7)
    assert int(want.load.sum()) == tokens.shape[0] * k
    if routing == "collapsed":
        assert int(want.load[0]) == tokens.shape[0]
    if routing == "ties":       # ties at the boundary of the choice exist
        ranked = np.sort(np.asarray(scores + bias), -1)[:, ::-1]
        assert np.sum(ranked[:, k - 1] == ranked[:, k]) >= tokens.shape[0] // 16

    mix = jax.random.normal(jax.random.key(9), (tokens.shape[0], k))
    grads = lambda route: jax.grad(lambda t, w: jnp.sum(jnp.sin(
        route(t, w, bias, k, 2.826).weights * mix)), (0, 1))(tokens, kernel)
    with HIGHEST:
        want_g = grads(_gathered_route_sigmoid_bias)
        got_g = grads(moe_lib.route_sigmoid_bias)
    for g, w in zip(got_g, want_g):
        assert float(jnp.max(jnp.abs(w))) > 1e-4
        np.testing.assert_allclose(
            g, w, rtol=0, atol=1e-6 * float(jnp.max(jnp.abs(w))))


@ROUTINGS
@CELLS
def test_plan_carries_the_padded_rows_through_its_second_sort(k, E, held,
                                                              routing):
    """``tiles``, ``pair_row`` and ``row_pair`` are exactly what the plan made
    with a rank a pair and ``dst[rank]``, with the router's counts and with
    its own, in the bounded layout (the unbounded one where routing
    collapses) at the cell's ``(k, E, held)``."""
    tokens, kernel, bias = _router_inputs(routing, E)
    with HIGHEST:
        route = moe_lib.route_sigmoid_bias(tokens, kernel, bias, k, 1.0)
    bt, first = 8, 0
    cap = None if routing == "collapsed" else (
        -(-(tokens.shape[0] // (E // (2 * held))) * k // bt) + held)
    plan = lambda fn, counts: jax.jit(fn, static_argnums=(1, 2, 3, 4))(
        route.chosen, first, held, bt, cap, counts)
    for counts in (None, route.load[first:first + held].astype(jnp.int32)):
        want, got = plan(_gathered_plan, counts), plan(moe_lib._plan, counts)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
    held_pairs = int(np.sum(np.asarray(got[1]) < got[2].shape[0]))
    assert held_pairs == int(route.load[:held].sum()) > 0


def test_bias_moves_as_the_rule_says_and_no_optimizer_sees_it():
    x, whole, params = _layer_inputs()
    bias = 0.01 * jnp.arange(8.0)
    _, new = whole.apply({"params": params,
                          "batch_stats": {"expert_bias": bias}}, x,
                         train=True, mutable=["batch_stats", "telemetry"])
    with HIGHEST:
        scores = jax.nn.sigmoid(x.reshape(-1, 64) @ params["router"])
    _, chosen = jax.lax.top_k(scores + bias, 2)
    counts = jnp.bincount(chosen.reshape(-1), length=8)
    delta = 0.001 * jnp.sign(counts.mean() - counts)
    np.testing.assert_allclose(new["batch_stats"]["expert_bias"],
                               bias + delta - delta.mean(), atol=1e-7)
    assert float(jnp.sum(new["batch_stats"]["expert_bias"] - bias)) == \
        pytest.approx(0.0, abs=1e-6)
    sown = {k: float(v[0]) for k, v in new["telemetry"].items()}
    assert sown["moe_held_rows"] == 64 * 2
    assert sown["moe_whole"] == 1.0      # every expert held: no bound to pass
    assert sown["moe_held_peak"] == pytest.approx(
        float(counts.max()) / float(counts.mean()))
    # evaluation leaves the bias alone
    _, kept = whole.apply({"params": params,
                           "batch_stats": {"expert_bias": bias}}, x,
                          train=False, mutable=["batch_stats"])
    np.testing.assert_array_equal(kept["batch_stats"]["expert_bias"], bias)


def test_preset_trains_through_the_trainer_with_named_regions(devices):
    """The preset at toy size through ``Trainer`` (what ``main.py --preset``
    builds): it steps, the bias moves and sits in ``batch_stats`` where no
    optimizer state mirrors it, and the step's text carries the scopes that
    the benchmark's readers look for."""
    cfg = from_preset("trinity_mini_share", model="afmoe_tiny", seq_len=32,
                      global_batch_size=8, precision="fp32", lr=3e-3,
                      lr_schedule="constant", warmup_epochs=0.0, workers=0,
                      steps_per_epoch=4, log_every=1000, checkpoint_dir=None,
                      mesh_fsdp=4, mesh_data=2)
    trainer = Trainer(cfg)
    assert trainer.bundle.task == "lm" and cfg.remat
    trainer.train_epoch(0)
    assert int(trainer.state.step) == 4
    bias = trainer.state.batch_stats["block_1"]["moe"]["expert_bias"]
    assert 0 < float(jnp.max(jnp.abs(bias))) <= 4 * 0.05 * 2
    moments = weights.flatten(trainer.state.opt_state)
    assert not any("expert_bias" in path for path in moments)
    assert not any("expert_bias" in path
                   for path in weights.flatten(trainer.state.params))
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                     sharding=trainer.batch_sharding)
             for k in ("tokens", "targets")}
    with mesh_lib.use_mesh(trainer.mesh):
        text = trainer.train_step.lower(trainer.state, batch).as_text(
            debug_info=True)
    for scope in ("attn", "mlp", "moe", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "moe_shared", "head_loss",
                  "optimizer", "grouped_matmul"):
        assert f"/{scope}/" in text or f"({scope})" in text \
            or f"{scope}" in text, scope


def test_what_the_family_does_not_do_fails_loudly():
    module = afmoe.afmoe_tiny()
    tokens = jnp.zeros((1, 8), jnp.int32)
    variables = module.init(jax.random.key(0), tokens, train=False)
    with pytest.raises(NotImplementedError, match="trains only"):
        module.apply(variables, tokens, train=False, decode_ctx={})
    with pytest.raises(ValueError, match="sequence-parallel"):
        registry.create_model(
            "afmoe_tiny", num_classes=0, image_size=0, seq_len=8,
            dtype=jnp.float32, param_dtype=jnp.float32,
            logits_dtype=jnp.float32, remat=False, sp=True)
    with pytest.raises(ValueError, match="held_experts"):
        _layer((4, 6)).init(jax.random.key(0), jnp.zeros((1, 4, 8)))

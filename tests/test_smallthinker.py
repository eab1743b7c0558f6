"""The ``smallthinker`` family (``models/smallthinker.py``,
``parallel/moe.TopKSoftmaxRouter`` and ``HeldExperts``, the gate of
``ops/grouped_matmul.py``'s gated FFN as an argument): the model against the
benchmark's plain reference, the router on planted near-ties, rotary positions
and the window on the layers the layouts name and on no others, the shares
adding up to the uncut layer, zero for a token with no held choice, collapsed
routing through the bounded layout's parts, the hand-written backward against
plain AD for both gates, the published entry and the chip's share of it, and
the preset through the ``Trainer``. Float32 on the CPU at toy widths."""

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
from chipbench.references import smallthinker_21b as reference  # noqa: E402
from pytorch_distributed_training_example_tpu.core import (  # noqa: E402
    mesh as mesh_lib, train_loop)
from pytorch_distributed_training_example_tpu.core.trainer import Trainer  # noqa: E402
from pytorch_distributed_training_example_tpu.models import (  # noqa: E402
    registry, smallthinker)
from pytorch_distributed_training_example_tpu.ops import (  # noqa: E402
    grouped_matmul as gmm_lib)
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.utils.config import from_preset  # noqa: E402

HIGHEST = jax.default_matmul_precision("highest")
RULES = [["scale$", "const", 1.0], [".*", "normal", 0.02]]


def _model_dict(module: smallthinker.SmallThinker) -> dict:
    """The reference's ``model`` group for a program module."""
    held, first = module.held_experts or (module.num_experts, 0)
    return {
        "hidden_size": module.d_model, "head_dim": module.head_dim,
        "num_attention_heads": module.num_heads,
        "num_key_value_heads": module.num_kv_heads,
        "moe_ffn_hidden_size": module.expert_ffn_dim,
        "moe_num_primary_experts": held, "held_experts_start": first,
        "routed_experts": module.num_experts,
        "moe_num_active_primary_experts": module.top_k,
        "num_hidden_layers": module.num_layers,
        "sliding_window_layout": list(module.window_layout),
        "rope_layout": list(module.rope_layout),
        "held_layers": list(range(module.num_layers)),
        "sliding_window_size": module.window, "rope_theta": module.rope_theta,
        "rms_norm_eps": module.epsilon, "vocab_size": module.vocab_size}


def _seeded(module, S, seed=3, batch=2):
    tokens = jax.random.randint(jax.random.key(seed), (batch, S + 1), 0,
                                module.vocab_size)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens[:, :-1]))
    assert set(shapes) == {"params", "telemetry"}    # no buffer: no batch_stats
    params = weights.make_like(shapes["params"], RULES, weights.seed_key(seed))
    return params, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def _program_loss(module, batch):
    task = train_loop.get_task("lm")
    return lambda p: task.loss(
        module.apply({"params": p}, batch["tokens"], train=True), batch)


# -- the model against the plain reference ---------------------------------------


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "share"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_the_plain_reference(held, remat):
    """Loss and every leaf's gradient, in float32, at a sequence of three
    windows. The tolerances are float32 rounding through four layers (the
    Trinity test's, which the same kind of arithmetic met): 1e-5 on the loss,
    2e-3 of an entry and 2e-4 of a leaf's largest entry on a gradient."""
    module = smallthinker.smallthinker_tiny(remat=remat, held_experts=held)
    params, batch = _seeded(module, 48)
    model = _model_dict(module)
    with HIGHEST:
        loss, grads = jax.jit(jax.value_and_grad(
            _program_loss(module, batch)))(params)
        (want_loss, counts), want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, model), has_aux=True))(
                weights.flatten(params))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    grads = weights.flatten(grads)
    assert set(grads) == set(want)
    for path, g in grads.items():
        scale = float(jnp.max(jnp.abs(want[path])))
        assert scale > 0, path  # every leaf is alive at this init
        np.testing.assert_allclose(g, want[path], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=path)
    assert float(jnp.sum(counts)) == 4 * 2 * 48 * module.top_k


@pytest.mark.parametrize("left_out,change", [
    ("rotary positions on the window layers", {"rope_layout": [0, 0, 0, 0]}),
    ("the window", {"sliding_window_size": 10 ** 6}),
    ("a position-free full layer (rotary there too)",
     {"rope_layout": [1, 1, 1, 1]}),
    ("a full first layer (a window there too)",
     {"sliding_window_layout": [1, 1, 1, 1]}),
    ("the router ahead of attention (another layer's router)",
     "swap_routers"),
    ("the softmax over the chosen (uniform weights)", "uniform")])
def test_reference_sees_what_a_step_leaves_out(left_out, change, monkeypatch):
    """Rotary positions and the window mask are on the layers the layouts
    name and on no others, and the router is where the model says: the
    reference with the piece moved or left out is farther from the program
    than the tolerance of the test above, on the loss or on a gradient."""
    module = smallthinker.smallthinker_tiny()
    params, batch = _seeded(module, 48)
    model, flat = _model_dict(module), weights.flatten(params)
    if change == "swap_routers":
        flat = dict(flat, **{
            "block_0/moe_router/kernel": flat["block_1/moe_router/kernel"],
            "block_1/moe_router/kernel": flat["block_0/moe_router/kernel"]})
    elif change == "uniform":
        real = reference.route
        monkeypatch.setattr(reference, "route", lambda r, kernel, k: (
            real(r, kernel, k)[0], jnp.full(r.shape[:-1] + (k,), 1.0 / k)))
    else:
        model = dict(model, **change)
    with HIGHEST:
        loss, grads = jax.jit(jax.value_and_grad(
            _program_loss(module, batch)))(params)
        (want_loss, _), want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, model), has_aux=True))(flat)
    grads = weights.flatten(grads)
    if change == "swap_routers":    # compare like with like
        want = dict(want, **{
            "block_0/moe_router/kernel": want["block_1/moe_router/kernel"],
            "block_1/moe_router/kernel": want["block_0/moe_router/kernel"]})
    worst = max(float(jnp.max(jnp.abs(g - want[path]))
                      / jnp.max(jnp.abs(want[path])))
                for path, g in grads.items())
    assert abs(float(loss) - float(want_loss)) > 1e-4 * float(want_loss) \
        or worst > 2e-2, (left_out, float(loss), float(want_loss), worst)


# -- the router ------------------------------------------------------------------


def test_router_on_planted_near_ties_is_the_references():
    """The top-k of the logits and the softmax over the chosen, float32: on
    inputs where two experts' logits differ by a few parts in 1e7 for every
    token (a column of the kernel all but copied), so that the choice at the
    boundary is a near-tie for a part of the tokens, the module chooses what
    the reference chooses and weighs as it weighs; the weights are the
    softmax over the chosen logits alone."""
    T, d, E, k = 256, 64, 8, 3
    x = jax.random.normal(jax.random.key(1), (2, T // 2, d))
    kernel = 0.3 * jax.random.normal(jax.random.key(2), (d, E))
    kernel = kernel.at[:, 5].set(kernel[:, 2] * (1 + 3e-7))
    router = moe_lib.TopKSoftmaxRouter(num_experts=E, top_k=k)
    with HIGHEST:
        route = router.apply({"params": {"kernel": kernel}}, x)
        chosen, weight = reference.route(x.reshape(T, d), kernel, k)
        logits = np.asarray(x.reshape(T, d) @ kernel, np.float64)
    np.testing.assert_array_equal(route.chosen, chosen)
    np.testing.assert_allclose(route.weights, weight, rtol=0, atol=1e-7)
    assert route.weights.dtype == jnp.float32
    # near-ties at the boundary exist: the third and fourth largest logits of
    # a token are the planted pair
    order = np.sort(logits, -1)[:, ::-1]
    gaps = order[:, k - 1] - order[:, k]
    assert np.sum(gaps < 1e-5) >= T // 16, np.sum(gaps < 1e-5)
    picked = np.take_along_axis(logits, np.asarray(chosen), -1)
    want = np.exp(picked - picked.max(-1, keepdims=True))
    np.testing.assert_allclose(route.weights,
                               want / want.sum(-1, keepdims=True), atol=1e-6)
    np.testing.assert_array_equal(
        route.load, np.bincount(np.asarray(chosen).reshape(-1), minlength=E))


#: ``(k, E, held)`` of the five expert cells: Trinity, SmallThinker, GLM,
#: Nemotron, LFM2
CELLS = pytest.mark.parametrize("k,E,held", [
    (8, 128, 16), (6, 64, 16), (4, 64, 8), (6, 128, 8), (4, 32, 8)])


def _router_inputs(routing, E, T=256, d=32, seed=7):
    """``(tokens, kernel)``: a random router; one whose logits put every token
    on expert 0; one with exact ties (every second column of the kernel a copy
    of the one before it, a sixteenth of the tokens zero: all logits 0)."""
    keys = jax.random.split(jax.random.key(seed), 2)
    tokens = jax.random.normal(keys[0], (T, d))
    kernel = 0.3 * jax.random.normal(keys[1], (d, E))
    if routing == "collapsed":
        tokens = tokens.at[:, 0].set(9.0)
        kernel = kernel.at[0].set(0.0).at[0, 0].set(9.0)
    elif routing == "ties":
        kernel = kernel.at[:, 1::2].set(kernel[:, 0::2])
        tokens = tokens.at[::16].set(0.0)
    return tokens, kernel


@pytest.mark.parametrize("routing", ["random", "collapsed", "ties"])
@CELLS
def test_softmax_router_counts_what_bincount_counted(k, E, held, routing):
    """The router's ``load`` is ``bincount``'s integers (a one-hot summed over
    tokens and choices since PR 49, where a scatter-add of a scalar a pair
    was), ``chosen`` and the weights what they were, op by op and under one
    ``jit``; the kernel's and the tokens' gradients within 1e-6."""
    tokens, kernel = _router_inputs(routing, E)

    def counted(tokens, kernel, k):          # the router before PR 49
        top, chosen = jax.lax.top_k(moe_lib._scores(tokens, kernel), k)
        weights = jax.nn.softmax(top, axis=-1)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return moe_lib.Route(chosen, weights, jnp.bincount(
            chosen.reshape(-1), length=kernel.shape[1]))

    with HIGHEST:
        want = counted(tokens, kernel, k)
        got = moe_lib.route_softmax_chosen(tokens, kernel, k)
        jitted = jax.jit(moe_lib.route_softmax_chosen, static_argnums=2)(
            tokens, kernel, k)
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
        with HIGHEST:
            ranked = np.sort(np.asarray(moe_lib._scores(tokens, kernel)),
                             -1)[:, ::-1]
        assert np.sum(ranked[:, k - 1] == ranked[:, k]) >= tokens.shape[0] // 16

    mix = jax.random.normal(jax.random.key(9), (tokens.shape[0], k))
    grads = lambda route: jax.grad(lambda t, w: jnp.sum(jnp.sin(
        route(t, w, k).weights * mix)), (0, 1))(tokens, kernel)
    with HIGHEST:
        want_g, got_g = grads(counted), grads(moe_lib.route_softmax_chosen)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=1e-6 * float(jnp.max(jnp.abs(w))) + 1e-30)


@pytest.mark.parametrize("first", [0, 8])
@CELLS
def test_held_counts_of_a_part_are_bincounts(k, E, held, first):
    """``_held_counts`` (what a part of the bounded layout's second way counts
    of its own rows, ``_plan`` without the router's counts) over keys that
    hold ``held`` for another chip's expert: ``bincount``'s integers less its
    last bin, for a level router's part and for one that sends every token to
    the held experts."""
    scores = np.random.RandomState(3).rand(128, E)
    for lift in (0.0, 1.0):
        scores[:, first:first + held] += lift
        chosen = jnp.asarray(np.argsort(-scores, -1)[:, :k].astype(np.int32))
        key = moe_lib._held_keys(chosen, first, held)
        want = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        for count in (moe_lib._held_counts, jax.jit(moe_lib._held_counts,
                                                    static_argnums=1)):
            got = count(key, held)
            np.testing.assert_array_equal(got, want)
            assert got.dtype == jnp.int32 and got.shape == (held,)
    assert int(want.sum()) == 128 * k          # collapsed: every pair is held


# -- the held experts' layer -------------------------------------------------------


def _held(held, **kw):
    return moe_lib.HeldExperts(ffn_dim=32, held_experts=held, act="relu", **kw)


def _layer_inputs(T=64, d=64, E=8, k=3, seed=5):
    """``(r, y, route, params)``: the router's input and the experts' (two
    different tensors, as in the model), the plan, all eight experts."""
    keys = jax.random.split(jax.random.key(seed), 3)
    r = jax.random.normal(keys[0], (2, T // 2, d))
    y = jax.random.normal(keys[1], (2, T // 2, d))
    kernel = 0.3 * jax.random.normal(keys[2], (d, E))
    with HIGHEST:
        route = moe_lib.route_softmax_chosen(r.reshape(T, d), kernel, k)
    whole = _held(None)
    params = weights.make_like(jax.eval_shape(
        lambda: whole.init(jax.random.key(0), y, route)["params"]),
        [[".*", "normal", 0.3]], weights.seed_key(seed))
    return r, y, kernel, route, params


def _part(params, first, held):
    return {k: v[first:first + held] for k, v in params.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold two experts each of the eight: the parts that the
    shares give are the uncut layer's output, which is the uncut reference's;
    and each share's gradient of its own experts is the uncut layer's
    gradient of them."""
    r, y, kernel, route, params = _layer_inputs()
    run = lambda layer, p: layer.apply({"params": p}, y, route)
    flat = {"moe_router/kernel": kernel,
            **{f"moe/{k}": v for k, v in params.items()}}
    z = {"k": 3, "routed": 8, "first": 0, "held": 8}
    with HIGHEST:
        want = run(_held(None), params)
        uncut, counts = reference.experts(r, y, flat, z, lambda a: a)
        np.testing.assert_allclose(want, uncut, rtol=2e-5, atol=2e-4)  # float32 sums at |y| ~ 20
        parts = [run(_held((2, s)), _part(params, s, 2)) for s in (0, 2, 4, 6)]
        np.testing.assert_allclose(sum(parts), want, rtol=2e-5, atol=2e-4)
        # the reference's own shares add up too
        shares = [reference.experts(
            r, y, {"moe_router/kernel": kernel,
                   **{f"moe/{k}": v for k, v in _part(params, s, 2).items()}},
            dict(z, first=s, held=2), lambda a: a)[0] for s in (0, 2, 4, 6)]
        np.testing.assert_allclose(sum(shares), uncut, rtol=2e-5, atol=2e-4)
        g = jax.grad(lambda p: jnp.sum(jnp.sin(run(_held(None), p))))(params)
        others = sum(parts) - parts[1]
        part = jax.grad(lambda p: jnp.sum(jnp.sin(
            run(_held((2, 2)), p) + others)))(_part(params, 2, 2))
    for name in ("w_gate", "w_up", "w_down"):
        scale = float(jnp.max(jnp.abs(g[name])))   # float32 sums, as above
        np.testing.assert_allclose(part[name], g[name][2:4],
                                   atol=1e-5 * scale)
    np.testing.assert_array_equal(counts, route.load)
    assert float(jnp.max(jnp.abs(parts[3]))) > 0.1   # a share does something


def test_a_token_with_no_held_choice_gets_exactly_zero():
    """No shared expert: a token none of whose choices is held gets zero from
    the layer, not a small number, in the program and in the reference; the
    others get something."""
    r, y, kernel, route, params = _layer_inputs()
    out = _held((2, 0)).apply({"params": _part(params, 0, 2)}, y, route)
    lands = np.asarray(jnp.any(route.chosen < 2, axis=-1))
    assert 0.15 < 1 - lands.mean() < 0.6      # such tokens exist, and others
    rows = np.asarray(out).reshape(-1, y.shape[-1])
    np.testing.assert_array_equal(rows[~lands], 0.0)
    assert np.all(np.abs(rows[lands]).max(-1) > 0)
    z = {"k": 3, "routed": 8, "first": 0, "held": 2}
    ref, _ = reference.experts(
        r, y, {"moe_router/kernel": kernel,
               **{f"moe/{k}": v for k, v in _part(params, 0, 2).items()}},
        z, lambda a: a)
    np.testing.assert_array_equal(
        np.asarray(ref).reshape(rows.shape)[~lands], 0.0)


def test_collapsed_routing_takes_the_parts_and_drops_nothing(monkeypatch):
    """Every token on the two held experts of eight (and a third elsewhere):
    four times the rows a level router sends, past the whole layout's bound
    (``chunks`` is 2 here, as at the published sizes), so the layer takes the
    tokens in parts; the result and the gradients are the dense sum's."""
    T, d, E, k = 64, 32, 8, 3
    keys = jax.random.split(jax.random.key(2), 3)
    y = jax.random.normal(keys[0], (1, T, d))
    layer = moe_lib.HeldExperts(ffn_dim=16, held_experts=(2, 2), act="relu")
    third = jax.random.randint(keys[1], (T, 1), 4, 8)
    collapsed = jnp.concatenate([jnp.full((T, 1), 2), jnp.full((T, 1), 3),
                                 third], -1).astype(jnp.int32)
    spread = jnp.stack([jnp.arange(T) % 8, (jnp.arange(T) + 3) % 8,
                        (jnp.arange(T) + 5) % 8], -1).astype(jnp.int32)
    weight = jax.random.uniform(keys[2], (T, k), minval=0.2)
    plan = lambda chosen: moe_lib.Route(
        chosen, weight, jnp.bincount(chosen.reshape(-1), length=E))
    params = weights.make_like(jax.eval_shape(
        lambda: layer.init(jax.random.key(0), y, plan(spread))["params"]),
        [[".*", "normal", 0.3]], weights.seed_key(1))

    def dense(p, chosen):
        out = 0.0
        for e in range(2):
            mine = jnp.sum(jnp.where(chosen == 2 + e, weight, 0.0), -1)
            h = jax.nn.relu(y[0] @ p["w_gate"][e]) * (y[0] @ p["w_up"][e])
            out = out + mine[:, None] * (h @ p["w_down"][e])
        return out[None]

    with HIGHEST:
        for chosen, whole in ((collapsed, 0.0), (spread, 1.0)):
            run = lambda p: layer.apply({"params": p}, y, plan(chosen))
            np.testing.assert_allclose(run(params), dense(params, chosen),
                                       atol=2e-5)
            got = jax.grad(lambda p: jnp.sum(jnp.sin(run(p))))(params)
            want = jax.grad(lambda p: jnp.sum(jnp.sin(dense(p, chosen))))(
                params)
            for name in want:
                np.testing.assert_allclose(got[name], want[name], atol=2e-5,
                                           err_msg=name)
            _, sown = layer.apply({"params": params}, y, plan(chosen),
                                  mutable=["telemetry"])
            sown = {k: float(v[0]) for k, v in sown["telemetry"].items()}
            assert sown["moe_whole"] == whole
            assert sown["moe_source_parts"] == 1.0     # kilobytes
            assert sown["moe_held_rows"] == float(jnp.sum(
                (chosen >= 2) & (chosen < 4)))
            if whole:     # the gate's zeros are counted on the whole layout
                rows = jnp.concatenate([y[0][jnp.any(chosen == 2 + e, -1)]
                                        @ params["w_gate"][e]
                                        for e in range(2)])
                assert sown["moe_gate_zero"] == pytest.approx(
                    float(jnp.mean(rows <= 0)), abs=1e-6)
                assert 0.3 < sown["moe_gate_zero"] < 0.7
            else:
                assert np.isnan(sown["moe_gate_zero"])
    text = str(jax.make_jaxpr(lambda p: layer.apply(
        {"params": p}, y, plan(spread)))(params))
    assert "cond" in text     # whole where it fits, in parts where not
    # the counter says what the rule said, and the parts change no bit
    for chosen in (collapsed, spread):
        run = lambda: layer.apply({"params": params}, y, plan(chosen),
                                  mutable=["telemetry"])
        with monkeypatch.context() as patch:
            patch.setattr(moe_lib, "_source_parts", lambda *shape: 2)
            out, sown = run()
        assert float(sown["telemetry"]["moe_source_parts"][0]) == 2.0
        np.testing.assert_array_equal(out, run()[0])


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("routing", ["level", "collapsed"])
@pytest.mark.parametrize("act", sorted(gmm_lib.GATES))
def test_bounded_backward_is_plain_ad_of_the_routine(act, routing, remat):
    """The hand-written backward of ``_routed_bounded`` against plain AD of
    ``_routed`` on the same inputs, for either gate, at the split the
    published sizes give (a quarter of the experts held: ``chunks`` 2): the
    whole layout's side strings the same products together from what the
    forward kept (bit for bit), the parts' side sums the weights' gradients
    part by part (to 1e-6 of the leaf's scale); with the block's remat around
    it the forward rule is what runs again."""
    T, d, f, E, k, held, first = 64, 32, 16, 8, 3, 2, 4
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
    assert chunks == 2
    whole = bool(moe_lib._fits(counts, bt, moe_lib._bounded_tiles(
        chosen, experts, bt, chunks)))
    assert whole == (routing == "level")

    def bounded(tokens, weights_, experts):
        return moe_lib._routed_bounded(tokens, chosen, weights_, experts,
                                       counts, first, bt, chunks, act)

    def plain(tokens, weights_, experts):
        return moe_lib._routed(tokens, chosen, weights_, experts, first, bt,
                               act=act)

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


@pytest.mark.parametrize("source_parts", [1, 2],
                         ids=["whole_source", "two_parts"])
@pytest.mark.parametrize("k", [6, 8])
@pytest.mark.parametrize("routing", ["ragged", "absent", "collapsed"])
def test_moves_of_rows_transpose_as_their_dense_sums(routing, k, source_parts,
                                                     monkeypatch):
    """The three token-side moves and their hand-written transposes against
    ``jax.vjp`` of the same sums stated as one-hot float32 matrices (no
    ``take`` in them), over the choice-major plan ``_plan`` hands out: with
    every expert held (ragged tiles, no pair absent), with a quarter held and
    every token's first choice on another chip (an absent pair in every
    token), and with routing collapsed onto the held half, where ``_in_parts``
    repeats the moves over parts of the tokens; each with the padded rows
    gathered back whole and in two column parts (as the rule asks of a source
    wider than VMEM: kilobytes here, so the rule is stood in for)."""
    monkeypatch.setattr(moe_lib, "_source_parts",
                        lambda *shape: source_parts)
    T, d, f, E, bt = 48, 32, 16, 16, 8
    held, first = {"ragged": (E, 0), "absent": (4, 4),
                   "collapsed": (8, 4)}[routing]
    keys = jax.random.split(jax.random.key(11), 9)
    scores = jax.random.uniform(keys[0], (T, E))
    if routing == "absent":
        scores = scores.at[:, 0].add(5.0)
    if routing == "collapsed":
        scores = scores.at[:, first:first + held].add(5.0)
    _, chosen = jax.lax.top_k(scores, k)
    weights_ = jax.random.uniform(keys[1], (T, k), minval=0.2)
    tokens, d_out = (jax.random.normal(key, (T, d)) for key in keys[2:4])

    def agree(got, want):
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        strict=True):
            scale = float(jnp.max(jnp.abs(w)))
            assert scale > 1e-3
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6 * scale)

    if routing == "collapsed":
        experts = tuple(0.3 * jax.random.normal(key, shape) for key, shape in
                        zip(keys[4:], [(held, d, f), (held, d, f),
                                       (held, f, d)]))
        cap = moe_lib._bounded_tiles(chosen, experts, bt, 2)
        assert not bool(moe_lib._fits(moe_lib._held_counts(
            moe_lib._held_keys(chosen, first, held), held), bt, cap))

        def dense(tokens, weights_, experts):
            out = 0.0
            for e, (w_gate, w_up, w_down) in enumerate(zip(*experts)):
                mine = jnp.sum(jnp.where(chosen == first + e, weights_, 0.0),
                               -1)
                h = jax.nn.relu(tokens @ w_gate) * (tokens @ w_up)
                out = out + mine[:, None] * (h @ w_down)
            return out

        parts = lambda *a: moe_lib._in_parts(
            a[0], chosen, *a[1:], first, bt, 2, cap, "relu")
        with HIGHEST:
            want_out, want = jax.vjp(dense, tokens, weights_, experts)
            got_out, got = jax.vjp(parts, tokens, weights_, experts)
            agree(got_out, want_out)
            agree(got(d_out), want(d_out))
        return

    _, pair_row, row_pair = moe_lib._plan(chosen, first, held, bt, None, None)
    P, row_token = row_pair.shape[0], row_pair // k
    assert pair_row.shape == (k, T)
    absent = np.asarray(pair_row == P)
    if routing == "absent":
        assert absent.any(axis=0).all() and not absent.all()
    else:
        counts = np.bincount(np.asarray(chosen).ravel(), minlength=E)
        assert not absent.any() and (counts % bt).any()
    y_pad, d_pad = (jax.random.normal(key, (P, d)) for key in keys[4:6])
    into = jax.nn.one_hot(row_token, T)                  # [P, T]
    back = jax.nn.one_hot(pair_row, P)                   # [k, T, P]
    with HIGHEST:
        want_pad, want = jax.vjp(lambda t: into @ t, tokens)
        got_pad, got = jax.vjp(
            lambda t: moe_lib._dispatch_rows(t, row_token, pair_row), tokens)
        agree(got_pad, want_pad)
        agree(got(d_pad), want(d_pad))
        want_out, want = jax.vjp(
            lambda y, w: jnp.einsum("tc,ctp,pd->td", w, back, y), y_pad,
            weights_)
        got_out, got = jax.vjp(
            lambda y, w: moe_lib._combine_rows(y, w, pair_row, row_pair),
            y_pad, weights_)
        agree(got_out, want_out)
        agree(got(d_out), want(d_out))


@pytest.mark.parametrize("rows,d,itemsize,parts", [
    pytest.param(18432, 2048, 2, 1, id="trinity_y_pad_75MB"),
    pytest.param(8192, 2560, 4, 1, id="float32_d_out_84MB"),
    pytest.param(8192, 2560, 2, 1, id="tokens_42MB"),
    pytest.param(22936, 2560, 2, 1, id="the_edge_112MiB"),
    pytest.param(22944, 2560, 2, 2, id="a_tile_past_the_edge"),
    pytest.param(26624, 2560, 2, 2, id="smallthinker_y_pad_136MB"),
    pytest.param(53248, 2560, 2, 4, id="twice_that_no_third_of_2560"),
    pytest.param(1 << 20, 200, 2, 1, id="no_128_lane_part"),
    pytest.param(1 << 22, 256, 2, 1, id="no_part_small_enough")])
def test_source_parts_rule(rows, d, itemsize, parts):
    """The fewest column parts of whole 128-lane columns whose bytes XLA places
    in VMEM, from the source's shape and dtype alone; 1 where no such part
    exists."""
    assert moe_lib._source_parts(rows, d, itemsize) == parts


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "plain"])
def test_choice_sum_in_parts_is_the_whole_sum_to_the_bit(weighted, n):
    """``_choice_sum_in`` over 2 and 4 column parts against one part, on a
    ragged plan with an absent pair in every token: every element is the same
    sum of the same terms in the same order, so not one bit differs, eagerly
    and under ``jit`` (each against its own: a compiler may contract a
    product and a sum, in every part alike)."""
    T, d, E, k, bt = 48, 512, 16, 6, 8
    keys = jax.random.split(jax.random.key(13), 3)
    scores = jax.random.uniform(keys[0], (T, E)).at[:, 0].add(5.0)
    _, chosen = jax.lax.top_k(scores, k)
    _, pair_row, row_pair = moe_lib._plan(chosen, 4, 4, bt, None, None)
    P = row_pair.shape[0]
    absent = np.asarray(pair_row == P)
    assert absent.any(axis=0).all() and not absent.all()
    x_pad = jax.random.normal(keys[1], (P, d)).astype(jnp.bfloat16)
    w = jax.random.uniform(keys[2], (T, k), minval=0.2) if weighted else None
    for form in (moe_lib._choice_sum_in,
                 jax.jit(moe_lib._choice_sum_in, static_argnums=3)):
        whole = form(x_pad, pair_row, w, 1)
        assert whole.shape == (T, d) and whole.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(whole))) > 1.0
        np.testing.assert_array_equal(form(x_pad, pair_row, w, n), whole)
    # the public form asks the rule: kilobytes, one part, no barrier
    text = str(jax.make_jaxpr(moe_lib._choice_sum)(x_pad, pair_row, w))
    assert "optimization_barrier" not in text and "concatenate" not in text
    text = str(jax.make_jaxpr(
        lambda *a: moe_lib._choice_sum_in(*a, n))(x_pad, pair_row, w))
    assert text.count("optimization_barrier") == n - 1


@pytest.mark.parametrize("preset,source,parts", [
    ("smallthinker_21b_share", (26624, 2560, 2), 2),
    ("trinity_mini_share", (18432, 2048, 2), 1)])
def test_source_parts_at_the_cells_widths(preset, source, parts, monkeypatch):
    """What each expert cell's step asks the rule (one sequence of 8,192 in
    bf16, traced on shapes): SmallThinker's share ``bf16[26624, 2560]``, 136
    MB, in 2 parts; Trinity's ``bf16[18432, 2048]``, 75 MB, in 1; four blocks
    each sow ``moe_source_parts``, worked out from the rows that every source
    ``_choice_sum`` sees has."""
    from pytorch_distributed_training_example_tpu.core import (
        trainer as trainer_lib)

    asked, rule = {}, moe_lib._source_parts
    monkeypatch.setattr(moe_lib, "_source_parts", lambda *shape: (
        asked.setdefault(shape, rule(*shape))))
    bundle = trainer_lib.build_model(from_preset(
        preset, global_batch_size=1, seq_len=8192))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    shapes = jax.eval_shape(lambda t: bundle.module.init(
        jax.random.key(0), t, train=False), tokens)
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(shapes["telemetry"])]
    assert sum("moe_source_parts.block_" in name for name in names) == 4
    asked.clear()
    jax.eval_shape(lambda v, t: bundle.module.apply(
        v, t, train=False, mutable=["telemetry"]), shapes, tokens)
    # one [P, d] of bf16 for the counter and for every move of every block
    assert asked == {source: parts}, asked


def _numpy_plan(chosen, first, held, bt, G):
    """The held experts' padded layout written out: for each held expert in
    turn its (token, choice) pairs in flat order, padded to whole tiles of
    ``bt`` rows (an expert with no pair keeps one tile), in ``G`` tiles;
    ``(counts, tile_expert, row_pair, pair_row)`` with ``n*k`` for a padded
    row that holds no pair and ``G*bt`` for a pair that has no row."""
    n, k = chosen.shape
    flat = chosen.reshape(-1)
    counts, tile_expert, row_pair = [], [], []
    pair_row = np.full(n * k, G * bt, np.int64)
    for e in range(held):
        pairs = np.flatnonzero(flat == first + e)
        rows = max(-(-len(pairs) // bt), 1) * bt
        pair_row[pairs] = len(row_pair) + np.arange(len(pairs))
        row_pair += list(pairs) + [n * k] * (rows - len(pairs))
        tile_expert += [e] * (rows // bt)
        counts.append(len(pairs))
    return (np.array(counts), np.array(tile_expert), np.array(row_pair),
            pair_row.reshape(n, k).T)


@pytest.mark.parametrize("routing", ["level", "collapsed", "absent"])
@pytest.mark.parametrize("preset", [
    "trinity_mini_share", "smallthinker_21b_share", "glm47_flash_share",
    "nemotron3_nano_share", "lfm2_8b_a1b_share", "qwen3_next_80b_share"])
def test_plan_at_the_cells_sizes_is_the_written_out_layout(preset, routing):
    """``_held_counts`` and ``_plan`` against a numpy layout at each expert
    cell's ``(experts, k, held, tile rows)`` and 8,192 tokens: level routing
    in the bounded layout the cell runs (it fits), every token on held
    experts in the unbounded one (the bounded one's parts each plan their own
    rows), and no token on them (one empty tile an expert)."""
    module = registry.create_model(preset, seq_len=8192).module
    E, k, (held, first), n = (module.num_experts, module.top_k,
                              module.held_experts, 8192)
    scores = np.random.RandomState(5).rand(n, E)
    if routing == "collapsed":
        scores[:, first:first + held] += 1.0
    elif routing == "absent":
        scores[:, first:first + held] -= 1.0
    chosen = np.argsort(-scores, axis=-1)[:, :k].astype(np.int32)
    bt = min(moe_lib.EXPERT_TILE_ROWS, gmm_lib._block_rows(n * k, held))
    assert bt == 128
    cap = None
    if routing != "collapsed":
        cap = -(-(n // (E // (2 * held))) * k // bt) + held
    G = min(-(-n * k // bt) + held, cap or n * k)
    counts, tile_expert, row_pair, pair_row = _numpy_plan(
        chosen, first, held, bt, G)
    used = len(tile_expert)
    assert used <= G and (counts.sum() == 0) == (routing == "absent")

    got_counts = moe_lib._held_counts(
        moe_lib._held_keys(jnp.asarray(chosen), first, held), held)
    np.testing.assert_array_equal(got_counts, counts)
    assert bool(moe_lib._fits(got_counts, bt, G))
    tiles, got_pair_row, got_row_pair = jax.jit(
        moe_lib._plan, static_argnums=(1, 2, 3, 4))(
            jnp.asarray(chosen), first, held, bt, cap, None)
    assert int(tiles[2][0]) == used and got_row_pair.shape == (G * bt,)
    np.testing.assert_array_equal(tiles[0][:used], tile_expert)
    np.testing.assert_array_equal(
        tiles[1][:used], np.diff(tile_expert, prepend=-1) > 0)
    np.testing.assert_array_equal(got_row_pair[:used * bt], row_pair)
    np.testing.assert_array_equal(got_pair_row, pair_row)


def test_the_gate_is_the_one_asked_for():
    """``gated_ffn_padded`` with ``act`` against the written-out product, and
    the hand-written backward against AD, for both gates on one tile
    layout."""
    T, d, f, E, bt = 32, 16, 8, 2, 8
    keys = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(keys[0], (T, d))
    w = [0.5 * jax.random.normal(key, shape) for key, shape in zip(
        keys[1:4], [(E, d, f), (E, d, f), (E, f, d)])]
    counts = jnp.array([T // 2, T // 2], jnp.int32)
    tiles, src, _ = gmm_lib._padded_layout(
        jnp.array([0, T // 2], jnp.int32), counts, T, E, bt, max_tiles=4)
    x_pad = gmm_lib._pad_rows(x, src)
    dy = jax.random.normal(keys[4], x_pad.shape)
    seg = (jnp.arange(x_pad.shape[0]) // bt >= 2).astype(jnp.int32)
    for act, fn in gmm_lib.GATES.items():
        with HIGHEST:
            got = gmm_lib.gated_ffn_padded(x_pad, *w, tiles, act)
            want = jnp.einsum(
                "tf,tfd->td", fn(jnp.einsum("td,tdf->tf", x_pad, w[0][seg]))
                * jnp.einsum("td,tdf->tf", x_pad, w[1][seg]), w[2][seg])
            np.testing.assert_allclose(got, want, atol=1e-5, err_msg=act)
            _, gate, up = gmm_lib.gated_ffn_padded_kept(x_pad, *w, tiles, act)
            by_hand = gmm_lib.gated_ffn_padded_bwd(x_pad, gate, up, *w, tiles,
                                                   dy, act)
            _, vjp = jax.vjp(lambda x, *w: gmm_lib.gated_ffn_padded(
                x, *w, tiles, act), x_pad, *w)
            for a, b in zip(by_hand, vjp(dy)):
                np.testing.assert_array_equal(a, b)
    silu = gmm_lib.gated_ffn_padded(x_pad, *w, tiles)       # the default
    np.testing.assert_array_equal(
        silu, gmm_lib.gated_ffn_padded(x_pad, *w, tiles, "silu"))
    assert float(jnp.max(jnp.abs(silu - got))) > 1e-2        # relu differs


# -- the published entry, its share, the preset --------------------------------------


def test_published_entry_and_its_share():
    whole = smallthinker.smallthinker_21b()
    assert (whole.num_layers, whole.d_model, whole.num_experts, whole.top_k,
            whole.expert_ffn_dim, whole.window, whole.vocab_size) == (
                52, 2560, 64, 6, 768, 4096, 151936)
    assert whole.window_layout == whole.rope_layout == (0, 1, 1, 1) * 13
    assert smallthinker.num_params(whole) == 21_506_562_560
    share = smallthinker.chip_share(whole)
    assert (share.window_layout, share.rope_layout) == ((0, 1, 1, 1),) * 2
    assert share.held_experts == (16, 0) and share.vocab_size == 37984
    assert smallthinker.chip_share(whole, 3).held_experts == (16, 48)
    # no width changes
    for key in ("d_model", "num_heads", "num_kv_heads", "head_dim",
                "expert_ffn_dim", "num_experts", "top_k", "window",
                "rope_theta", "epsilon"):
        assert getattr(share, key) == getattr(whole, key), key
    assert smallthinker.num_params(share) == 656_529_920
    shapes = jax.eval_shape(lambda: share.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    assert set(shapes) == {"params", "telemetry"}
    leaves = jax.tree.leaves(shapes["params"])
    assert sum(int(np.prod(s.shape)) for s in leaves) == 656_529_920
    block = shapes["params"]["block_2"]
    assert block["moe"]["w_gate"].shape == (16, 2560, 768)
    assert block["moe_router"]["kernel"].shape == (2560, 64)
    assert block["attn"]["query"]["kernel"].shape == (2560, 28, 128)
    assert block["attn"]["key"]["kernel"].shape == (2560, 4, 128)
    tiny = smallthinker.smallthinker_tiny()
    shapes = jax.eval_shape(lambda: tiny.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        shapes["params"])) == smallthinker.num_params(tiny)


def test_forward_flops_agree_with_the_benchmarks_count():
    for module, S in ((smallthinker.chip_share(
            smallthinker.smallthinker_21b()), 8192),
            (smallthinker.smallthinker_tiny(), 48),
            (smallthinker.smallthinker_tiny(), 8)):
        ours = S * smallthinker.forward_flops_per_token(module, S)
        theirs = reference.forward_flops(_model_dict(module), {"seq_len": S})
        assert ours == pytest.approx(theirs, rel=1e-12), S
    assert theirs > 0
    share = smallthinker.chip_share(smallthinker.smallthinker_21b())
    assert 8192 * smallthinker.forward_flops_per_token(share, 8192) \
        == pytest.approx(5.12e12, rel=1e-2)


def test_preset_trains_through_the_trainer_with_named_regions(devices):
    """The preset at toy size through ``Trainer`` (what ``main.py --preset``
    builds): it steps, there is no ``batch_stats``, the telemetry carries the
    expert layers' sows, and the step's text carries the scopes that the
    benchmark's readers look for, the router's outside the experts'."""
    cfg = from_preset("smallthinker_21b_share", model="smallthinker_tiny",
                      seq_len=32, global_batch_size=8, precision="fp32",
                      lr=3e-3, lr_schedule="constant", warmup_epochs=0.0,
                      workers=0, steps_per_epoch=4, log_every=1000,
                      checkpoint_dir=None, mesh_fsdp=4, mesh_data=2)
    trainer = Trainer(cfg)
    assert trainer.bundle.task == "lm" and cfg.remat
    before = jax.device_get(trainer.state.params["block_1"]["moe"]["w_up"])
    trainer.train_epoch(0)
    assert int(trainer.state.step) == 4
    assert not jax.tree.leaves(trainer.state.batch_stats)
    after = jax.device_get(trainer.state.params["block_1"]["moe"]["w_up"])
    assert np.abs(after - before).max() > 0
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                     sharding=trainer.batch_sharding)
             for k in ("tokens", "targets")}
    with mesh_lib.use_mesh(trainer.mesh):
        text = trainer.train_step.lower(trainer.state, batch).as_text(
            debug_info=True)
    for scope in ("embed", "attn", "mlp", "moe", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "norm", "head_loss",
                  "optimizer"):
        assert f"/{scope}/" in text, scope
    assert "grouped_matmul" in text
    assert "/mlp/moe/" in text and "/moe/moe_router" not in text
    assert "/mlp/moe_router" not in text and "/attn/moe_router" not in text
    assert "moe_shared" not in text


def test_what_the_family_does_not_do_fails_loudly():
    module = smallthinker.smallthinker_tiny()
    tokens = jnp.zeros((1, 8), jnp.int32)
    variables = module.init(jax.random.key(0), tokens, train=False)
    with pytest.raises(NotImplementedError, match="trains only"):
        module.apply(variables, tokens, train=False, decode_ctx={})
    with pytest.raises(ValueError, match="sequence-parallel"):
        registry.create_model(
            "smallthinker_tiny", num_classes=0, image_size=0, seq_len=8,
            dtype=jnp.float32, param_dtype=jnp.float32,
            logits_dtype=jnp.float32, remat=False, sp=True)
    with pytest.raises(ValueError, match="held_experts"):
        route = moe_lib.Route(jnp.zeros((4, 3), jnp.int32),
                              jnp.ones((4, 3)), jnp.zeros((8,), jnp.int32))
        _held((4, 6)).init(jax.random.key(0), jnp.zeros((1, 4, 8)), route)
    with pytest.raises(ValueError, match="differ in length"):
        smallthinker.smallthinker_tiny(rope_layout=(0, 1)).init(
            jax.random.key(0), tokens, train=False)

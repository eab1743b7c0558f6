"""The ``xing4`` family (``models/xing4.py``: ``glm_moe_lite.LatentAttention``
with a value width under its query/key width and YaRN positions, and
``parallel/moe.SharedExpertMoE`` as it is, on a residual path ``hc_mult``
streams wide under manifold-constrained hyper-connections): the model against
the benchmark's plain reference (loss, every leaf's gradient, three AdamW
steps, the biases after them), the Sinkhorn projection's rows and columns,
what the comparison does not let through, YaRN's frequencies and scale against
a hand table, the published entry and the chip's share, the eight shares adding
up to the uncut layer, and the preset through the ``Trainer``. Float32 on the
CPU at toy widths."""

import json
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
from chipbench.references import xing4_29b as reference  # noqa: E402
from pytorch_distributed_training_example_tpu.core import (  # noqa: E402
    mesh as mesh_lib, train_loop)
from pytorch_distributed_training_example_tpu.core.trainer import Trainer  # noqa: E402
from pytorch_distributed_training_example_tpu.models import (  # noqa: E402
    registry, xing4)
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.utils.config import from_preset  # noqa: E402

HIGHEST = jax.default_matmul_precision("highest")
#: The maps as the cell's init sets them, at toy widths: a static residual map
#: far from uniform and from the identity, an input-dependent part of a third
#: of a logit (``0.25 * 0.1 * sqrt(3 * 64)``).
RULES = [["scale$", "const", 1.0], ["alpha_", "const", 0.25],
         ["b_res$", "normal", 2.0], ["b_(pre|post)$", "normal", 0.5],
         ["phi_", "normal", 0.1], [".*", "normal", 0.02]]
#: Livelier, so that the loss itself feels each piece: weights of 0.2, and a
#: residual map whose logits spread by 6 (the projection is then far from
#: converged at 20 iterations, as at the published clamp of 30 it may be).
LIVELY = [["scale$", "const", 1.0], ["alpha_", "const", 0.25],
          ["b_res$", "normal", 6.0], ["b_(pre|post)$", "normal", 0.5],
          ["phi_", "normal", 0.1], [".*", "normal", 0.2]]
#: The first hyper-connection's read and mix see ``hc_mult`` copies of the
#: embedding: rows that sum to one leave them as they are and the branch's
#: norm removes the read's scale, so these leaves have no gradient but what
#: the norm's eps and rounding leave (1e-5 of a live leaf's, or less).
DEAD = tuple(f"block_0/hc_attn/{kind}_{which}" for kind in ("alpha", "phi", "b")
             for which in ("pre", "res"))


def _model_dict(module: xing4.Xing4) -> dict:
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
        "held_layers": list(range(module.num_layers)),
        "hc_mult": module.hc_mult,
        "hc_sinkhorn_iters": module.hc_sinkhorn_iters,
        "hc_eps": module.hc_eps,
        "mhc_h_res_clamp_min": module.hc_res_clamp[0],
        "mhc_h_res_clamp_max": module.hc_res_clamp[1],
        "rope_theta": module.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": module.yarn_factor,
            "beta_fast": module.yarn_beta_fast,
            "beta_slow": module.yarn_beta_slow,
            "mscale": module.yarn_mscale,
            "mscale_all_dim": module.yarn_mscale_all_dim,
            "original_max_position_embeddings":
                module.yarn_original_positions},
        "rms_norm_eps": module.epsilon,
        "routed_scaling_factor": module.route_scale,
        "load_balance_coeff": module.balance_coeff,
        "vocab_size": module.vocab_size}


def _seeded(module, S, seed=3, batch=2, rules=RULES):
    tokens = jax.random.randint(jax.random.key(seed), (batch, S + 1), 0,
                                module.vocab_size)
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), tokens[:, :-1], train=False))
    params = weights.make_like(shapes["params"], rules, weights.seed_key(seed))
    stats = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["batch_stats"])
    return params, stats, {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def _biases(stats, module):
    """The reference's ``[blocks, routed]`` biases from the program's."""
    none = jnp.zeros((module.num_experts,))
    return jnp.stack([
        stats.get(f"block_{i}", {}).get("moe", {}).get("expert_bias", none)
        for i in range(module.num_layers)])


def _program(module, stats, batch):
    """``p -> (loss as the step adds it up, new batch_stats)``."""
    task = train_loop.get_task("lm")

    def program(p):
        logits, new = module.apply({"params": p, "batch_stats": stats},
                                   batch["tokens"], train=True,
                                   mutable=["batch_stats"])
        return task.loss(logits, batch), new["batch_stats"]
    return program


def _moved(stats):
    """A bias that changes who is chosen, so that a test sees it."""
    return jax.tree.map(lambda b: 0.3 * jnp.cos(jnp.arange(b.size) * 1.7),
                        stats)


def _both(module, params, stats, batch, model=None, program=None):
    """``((loss, new stats, grads), (loss, counts, grads))`` of the program
    (or ``program``, its side computed before) and of the reference (given
    ``model``, or the module's own sizes)."""
    model = model or _model_dict(module)
    with HIGHEST:
        if program is None:
            (loss, new_stats), grads = jax.jit(jax.value_and_grad(
                _program(module, stats, batch), has_aux=True))(params)
            program = (loss, new_stats, weights.flatten(grads))
        (want_loss, counts), want = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, _biases(stats, module), batch,
                                        model), has_aux=True))(
                weights.flatten(params))
    return program, (want_loss, counts, want)


def _assert_same(got, want, clamped=()):
    """The comparison: float32 rounding through three blocks (the afmoe
    test's tolerances): 1e-5 on the loss, 2e-3 of a leaf's largest entry on
    a gradient. ``clamped``: leaves behind a clamp that is reached on every
    token, whose gradient is zero on both sides."""
    (loss, _, grads), (want_loss, _, want_grads) = got, want
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert set(grads) == set(want_grads)
    alive = max(float(jnp.max(jnp.abs(g))) for g in want_grads.values())
    for path, g in grads.items():
        scale = float(jnp.max(jnp.abs(want_grads[path])))
        if path in DEAD:
            assert scale < 1e-4 * alive, path
            assert float(jnp.max(jnp.abs(g))) < 1e-4 * alive, path
            continue
        if path in clamped:
            assert scale == 0 and not np.asarray(g).any(), path
            continue
        assert scale > 0, path  # every other leaf is alive at this init
        np.testing.assert_allclose(g, want_grads[path], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=path)


# -- the model against the plain reference --------------------------------------


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "share"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_the_plain_reference(held, remat):
    """The loss, every leaf's gradient (the hyper-connections' nine leaves a
    sub-layer among them) and every bias after the step, in float32."""
    module = xing4.xing4_tiny(remat=remat, held_experts=held)
    params, stats, batch = _seeded(module, 48)
    stats = _moved(stats)
    got, want = _both(module, params, stats, batch)
    _assert_same(got, want)
    counts = want[1]
    np.testing.assert_allclose(
        _biases(got[1], module),
        reference.next_biases(_biases(stats, module), counts,
                              _model_dict(module)), atol=1e-7)
    # two expert blocks, every row of every sequence
    assert float(jnp.sum(counts)) == 2 * 2 * 48 * module.top_k
    assert float(jnp.sum(counts[0])) == 0        # the dense block counts none


def test_three_adamw_steps_and_the_biases_after_them():
    """The step the ``Trainer`` builds (``make_train_step`` with the preset's
    AdamW chain) for three steps against the reference's own three: each
    step's loss, every live leaf's change, every bias."""
    cfg = from_preset("xing4_29b_share", model="xing4_tiny",
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
    moved = weights.flatten(jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
        jax.device_get(state.params), start))
    assert set(moved) == set(want["dparam_norms"])
    for path, norm in moved.items():
        if path in DEAD:    # Adam makes full steps of rounding noise there
            continue
        # Adam's first steps are sign-like: a gradient entry near zero may
        # step either way, so a leaf's change agrees to a few percent
        assert float(norm) == pytest.approx(want["dparam_norms"][path],
                                            rel=5e-2), path
    got = _biases(jax.device_get(state.batch_stats), module)
    np.testing.assert_allclose(got, want["biases"], atol=1e-6)
    assert float(np.abs(want["biases"][1:]).max()) > 0.05   # they moved
    assert not np.abs(want["biases"][0]).any()


# -- the hyper-connection ----------------------------------------------------------


def _maps_of(module, params, stats, batch, name="block_1"):
    """What one block's two hyper-connections sow into ``telemetry``."""
    with HIGHEST:
        _, sown = module.apply({"params": params, "batch_stats": stats},
                               batch["tokens"], train=True,
                               mutable=["telemetry", "batch_stats"])
    return sown["telemetry"][name]


def test_residual_map_rows_sum_to_one_and_columns_nearly():
    """After ``hc_sinkhorn_iters`` = 20 iterations every row of ``H_res``
    sums to one within 1e-5 (the row pass is the last) and every column
    within 1e-3 at this init; the map is far from the identity and from
    uniform; the program's telemetry reads the same."""
    module = xing4.xing4_tiny()
    params, stats, batch = _seeded(module, 48)
    n = module.hc_mult
    X = jax.random.normal(jax.random.key(7), (2, 48, n, module.d_model))
    flat = {k[len("block_1/"):]: v for k, v in
            weights.flatten(params).items() if k.startswith("block_1/")}
    with HIGHEST:
        pre, post, res = reference.maps(X, flat, "hc_ffn/",
                                        _model_dict(module))
        u, mine, mine_post = xing4.HyperConnection(
            sinkhorn_iters=20, sinkhorn_eps=1e-6, clamp=(-30.0, 30.0),
            epsilon=module.epsilon).apply(
                {"params": params["block_1"]["hc_ffn"]}, X)
    assert res.shape == (2, 48, n, n)
    assert float(jnp.max(jnp.abs(jnp.sum(res, -1) - 1))) < 1e-5
    assert float(jnp.max(jnp.abs(jnp.sum(res, -2) - 1))) < 1e-3
    assert float(jnp.min(res)) >= 0
    diag = float(jnp.mean(jnp.trace(res, axis1=-2, axis2=-1))) / n
    assert 0.02 < diag < 0.9 and float(jnp.std(res)) > 0.1
    # the input-dependent part moves every map, token by token
    for m in (pre, post, res):
        assert float(jnp.min(jnp.std(m.reshape(-1, m[0, 0].size), 0))) > 1e-3
    np.testing.assert_allclose(mine, res, atol=2e-6)
    np.testing.assert_allclose(mine_post, post, atol=2e-6)
    np.testing.assert_allclose(u, jnp.einsum("bsn,bsnd->bsd", pre, X),
                               atol=1e-5)
    sown = _maps_of(module, params, stats, batch)
    for which in ("hc_attn", "hc_ffn"):
        assert float(sown[which]["hc_res_row_err.block_1"][0]) < 1e-5
        assert 0.0 < float(sown[which]["hc_res_diag.block_1"][0]) < 1.0
        assert 0.0 < float(sown[which]["hc_pre_mean.block_1"][0]) < 1.0
        assert 0.0 < float(sown[which]["hc_post_mean.block_1"][0]) < 2.0


def test_sinkhorn_is_the_written_out_projection():
    """The program's lane-dense form against twenty column and row passes an
    entry at a time, on logits that reach the clamp."""
    logits = np.asarray(jax.random.normal(jax.random.key(1), (5, 7, 4, 4))
                        * 8.0).clip(-30, 30)
    m = np.exp(logits.astype(np.float64))
    for _ in range(20):
        for j in range(4):
            m[..., :, j] /= m[..., :, j].sum(-1, keepdims=True) + 1e-6
        for i in range(4):
            m[..., i, :] /= m[..., i, :].sum(-1, keepdims=True) + 1e-6
    got = xing4.sinkhorn(jnp.asarray(logits), 20, 1e-6)
    np.testing.assert_allclose(got, m, rtol=2e-5, atol=1e-7)
    model = {"mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
             "hc_sinkhorn_iters": 20, "hc_eps": 1e-6}
    np.testing.assert_allclose(reference.sinkhorn(jnp.asarray(logits), model),
                               m, rtol=2e-5, atol=1e-7)


@pytest.fixture(scope="module")
def lively():
    """The program's side of the comparison below, once for its nine cases."""
    module = xing4.xing4_tiny(held_experts=(2, 4))
    params, stats, batch = _seeded(module, 48, rules=LIVELY)
    past = params["block_2"]["hc_attn"]["b_res"]
    params["block_2"]["hc_attn"]["b_res"] = 37.0 + 3.0 * jnp.sin(
        jnp.arange(past.size) * 2.3)
    stats = _moved(stats)
    with HIGHEST:
        (loss, new_stats), grads = jax.jit(jax.value_and_grad(
            _program(module, stats, batch), has_aux=True))(params)
    return module, params, stats, batch, (loss, new_stats,
                                          weights.flatten(grads))


def _bf16_maps(monkeypatch):
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    plain = reference.maps
    monkeypatch.setattr(reference, "maps", lambda X, w, prefix, model: plain(
        low(X), {k: low(v) for k, v in w.items()}, prefix, model))


@pytest.mark.parametrize("left_out", [
    "nothing", "the twentieth iteration", "the clamp", "float32 maps",
    "the sum over the streams", "YaRN", "the scaling factor",
    "the shared expert", "the bias in the choice"])
def test_reference_sees_what_a_step_leaves_out(left_out, monkeypatch, lively):
    """The comparison's other side: the program against a reference without
    the piece fails the comparison that the sound pair passes (at a livelier
    init, with one residual map's bias past the clamp: 34..40, so that the
    clamp levels what ``exp`` would part)."""
    module, params, stats, batch, program = lively
    model = _model_dict(module)
    if left_out == "the twentieth iteration":
        model["hc_sinkhorn_iters"] = 19
    elif left_out == "the clamp":
        model["mhc_h_res_clamp_min"], model["mhc_h_res_clamp_max"] = -1e9, 1e9
    elif left_out == "float32 maps":
        _bf16_maps(monkeypatch)
    elif left_out == "the sum over the streams":
        monkeypatch.setattr(reference, "collapse", lambda X: X[..., 0, :])
    elif left_out == "YaRN":
        model["rope_scaling"] = None
    elif left_out == "the scaling factor":
        model["routed_scaling_factor"] = 1.0
    elif left_out == "the shared expert":
        model["n_shared_experts"] = 0
    elif left_out == "the bias in the choice":
        stats_ref = jax.tree.map(jnp.zeros_like, stats)
    got, want = _both(module, params, stats, batch, model, program)
    if left_out == "the bias in the choice":
        with HIGHEST:
            loss, _ = jax.jit(lambda p: reference.loss_fn(
                p, _biases(stats_ref, module), batch, model))(
                    weights.flatten(params))
        want = (loss, want[1], want[2])
    clamped = [f"block_2/hc_attn/{leaf}_res" for leaf in ("alpha", "phi", "b")]
    if left_out == "nothing":
        return _assert_same(got, want, clamped)
    with pytest.raises(AssertionError):
        _assert_same(got, want, clamped)


# -- YaRN -----------------------------------------------------------------------------


def test_yarn_frequencies_and_scale_against_a_hand_table():
    """The published ``rope_scaling`` (factor 64 over 4,096 positions, theta
    1e4, 64 rotary columns): ``low`` 10 and ``high`` 23, so pairs 0..10 turn
    as a plain rope's, pairs 23..31 sixty-four times slower, and the thirteen
    between blend; the scores' factor is ``192^-1/2 (0.1 ln 64 + 1)^2``."""
    sizes = xing4.xing4_29b().attention_sizes()
    got = np.asarray(sizes["rope_inv_freq"])
    plain = 1e4 ** (-np.arange(32) / 32.0)
    assert got.shape == (32,)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-12)
    # by hand: pair 16 keeps 1 - 6/13 of itself, f = 1e4^-0.5 = 0.01
    assert got[16] == pytest.approx(0.01 * (7 / 13) + 0.01 / 64 * (6 / 13),
                                    rel=1e-12)
    assert got[11] == pytest.approx(
        plain[11] * (12 / 13) + plain[11] / 64 / 13, rel=1e-12)
    assert got[22] == pytest.approx(
        plain[22] / 13 + plain[22] / 64 * (12 / 13), rel=1e-12)
    assert (got[8], got[24]) == (pytest.approx(0.1), pytest.approx(1e-3 / 64))
    assert np.all(np.diff(got) < 0)
    assert xing4.yarn_mscale(64, 1) ** 2 == pytest.approx(2.0047, abs=5e-5)
    assert sizes["softmax_scale"] == pytest.approx(
        2.0047 / np.sqrt(192), rel=3e-5)
    # the reference computes its own, from the configuration's group
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "xing4_29b.json")) as fh:
        model = json.load(fh)["model"]
    inv_freq, trig, scale = reference.yarn(model)
    np.testing.assert_allclose(inv_freq, got, rtol=1e-12)
    assert trig == 1.0 and scale == pytest.approx(sizes["softmax_scale"],
                                                  rel=1e-12)
    # nothing scaled: a plain rope and 1 / sqrt(D), as LatentAttention's own
    plain_sizes = xing4.xing4_29b(yarn_factor=1.0).attention_sizes()
    assert plain_sizes["rope_inv_freq"] is None
    assert plain_sizes["softmax_scale"] is None
    with pytest.raises(NotImplementedError, match="mscale"):
        xing4.xing4_29b(yarn_mscale=0.7).attention_sizes()


def test_rope_takes_the_frequencies_it_is_given():
    from pytorch_distributed_training_example_tpu.models import llama

    x = jax.random.normal(jax.random.key(0), (1, 6, 2, 8))
    positions = jnp.arange(6)[None, :]
    own = 100.0 ** (-np.arange(4) / 4.0)
    np.testing.assert_array_equal(
        llama.rope(x, positions, 100.0),
        llama.rope(x, positions, 100.0, inv_freq=tuple(own)))
    # a quarter of the frequencies: position 4 turns as position 1 did
    slow = llama.rope(x, positions, 100.0, inv_freq=tuple(own / 4))
    np.testing.assert_allclose(
        slow[:, 4], llama.rope(x[:, 4:5], positions[:, 1:2], 100.0)[:, 0],
        atol=1e-6)


# -- the published entry, its share, its count -------------------------------------


def test_published_entry_and_its_share():
    full = xing4.xing4_29b()
    assert (full.num_layers, full.num_dense_layers, full.mtp_layers,
            full.num_experts, full.top_k, full.vocab_size, full.hc_mult,
            full.hc_sinkhorn_iters) == (40, 2, 1, 64, 4, 131072, 4, 20)
    assert xing4.num_params(full) == 30_276_192_678
    assert xing4.num_params(full.clone(mtp_layers=0)) == 29_505_502_832
    share = xing4.chip_share(full)
    assert (share.num_layers, share.num_dense_layers, share.mtp_layers,
            share.held_experts, share.vocab_size) == (5, 1, 0, (8, 0), 16384)
    assert xing4.chip_share(full, chip=3).held_experts == (8, 24)
    # no width differs
    for field in ("d_model", "num_heads", "q_rank", "kv_rank", "nope_dim",
                  "rope_dim", "v_dim", "dense_ffn_dim", "expert_ffn_dim",
                  "num_experts", "top_k", "route_scale", "rope_theta",
                  "epsilon", "shared_experts", "hc_mult", "hc_sinkhorn_iters",
                  "hc_eps", "hc_res_clamp", "yarn_factor"):
        assert getattr(share, field) == getattr(full, field), field
    assert (share.d_model, share.num_heads, share.q_rank, share.kv_rank,
            share.nope_dim, share.rope_dim, share.v_dim, share.dense_ffn_dim,
            share.expert_ffn_dim, share.route_scale) == (
                3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 2.0)
    assert xing4.num_params(share) == 759_346_190
    shapes = jax.eval_shape(lambda: share.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = lambda tree: sum(int(np.prod(x.shape))
                              for x in jax.tree.leaves(tree))
    params = shapes["params"]
    assert leaves(params) == 759_346_190
    assert leaves(params["block_0"]) == 128_196_918
    assert leaves(params["block_3"]) == 128_426_294
    assert leaves(params["block_3"]["attn"]) == 28_411_136
    assert leaves(params["block_3"]["hc_ffn"]) == 344_091
    assert leaves(params["embed"]) + leaves(params["lm_head"]) == 117_440_512
    attn = params["block_1"]["attn"]
    assert attn["q_a"]["kernel"].shape == (3584, 768)
    assert attn["q_b"]["kernel"].shape == (768, 32, 192)
    assert attn["kv_a"]["kernel"].shape == (3584, 576)
    assert attn["kv_b"]["kernel"].shape == (512, 32, 256)
    assert attn["out"]["kernel"].shape == (32, 128, 3584)
    hc = params["block_1"]["hc_attn"]
    assert hc["phi_res"].shape == (14336, 16) and hc["b_res"].shape == (16,)
    assert hc["phi_pre"].shape == hc["phi_post"].shape == (14336, 4)
    assert params["block_1"]["moe"]["router"].shape == (3584, 64)
    assert params["block_1"]["moe"]["w_gate"].shape == (8, 3584, 1024)
    assert "moe" not in params["block_0"]
    assert shapes["batch_stats"]["block_4"]["moe"]["expert_bias"].shape == (64,)
    tiny = xing4.xing4_tiny()
    shapes = jax.eval_shape(lambda: tiny.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    assert leaves(shapes["params"]) == xing4.num_params(tiny)


def test_forward_flops_agree_with_the_benchmarks_count():
    share = xing4.chip_share(xing4.xing4_29b())
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "xing4_29b.json")) as fh:
        model = json.load(fh)["model"]
    want = reference.forward_flops(model, {"seq_len": 2048})
    assert want == pytest.approx(
        2048 * xing4.forward_flops_per_token(share, 2048), rel=1e-12)
    assert 3 * want == pytest.approx(5.195e12, rel=1e-3)
    module = xing4.xing4_tiny()
    assert 48 * xing4.forward_flops_per_token(module, 48) == pytest.approx(
        reference.forward_flops(_model_dict(module), {"seq_len": 48}),
        rel=1e-12)
    bundle = registry.create_model(
        "xing4_29b_share", num_classes=0, image_size=0, seq_len=2048,
        dtype=jnp.bfloat16, param_dtype=jnp.float32,
        logits_dtype=jnp.float32, remat=True)
    assert bundle.fwd_flops_per_example == pytest.approx(want, rel=1e-12)
    assert registry.create_model(
        "xing4_29b", num_classes=0, image_size=0, seq_len=2048,
        dtype=jnp.bfloat16, param_dtype=jnp.float32,
        logits_dtype=jnp.float32, remat=True).module.mtp_layers == 0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The expert layer at this family's routing (4 of 64, scale 2) against
    the reference's uncut layer: eight chips hold 8 experts each; the routed
    parts that the shares give, with the shared expert counted once, are the
    uncut layer's output as the plain reference computes it."""
    d, f, E, k = 32, 16, 64, 4
    layer = lambda held: moe_lib.SharedExpertMoE(
        num_experts=E, ffn_dim=f, top_k=k, held_experts=held,
        shared_ffn_dim=f, route_scale=2.0, balance_coeff=0.001)
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
    model = {"routed_scaling_factor": 2.0, "n_shared_experts": 1}
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
    builds): it steps, the biases move and sit in ``batch_stats``, the
    hyper-connections' leaves move, and the step's text carries the scopes
    that the benchmark's readers look for."""
    cfg = from_preset("xing4_29b_share", model="xing4_tiny",
                      seq_len=32, global_batch_size=8, precision="fp32",
                      lr=3e-3, lr_schedule="constant", warmup_epochs=0.0,
                      workers=0, steps_per_epoch=4, log_every=1000,
                      checkpoint_dir=None, mesh_fsdp=4, mesh_data=2,
                      telemetry=True)
    trainer = Trainer(cfg)
    assert trainer.bundle.task == "lm" and cfg.remat and cfg.seq_len == 32
    before = jax.device_get(trainer.state.params["block_2"]["hc_ffn"])
    trainer.train_epoch(0)
    assert int(trainer.state.step) == 4
    bias = trainer.state.batch_stats["block_1"]["moe"]["expert_bias"]
    assert 0 < float(jnp.max(jnp.abs(bias))) <= 4 * 0.05 * 2
    after = jax.device_get(trainer.state.params["block_2"]["hc_ffn"])
    for leaf in ("phi_post", "b_post", "alpha_post", "b_res"):
        assert np.abs(after[leaf] - before[leaf]).max() > 0, leaf
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
                  "head_loss", "hc", "hc_maps", "hc_sinkhorn", "hc_read",
                  "hc_write", "optimizer"):
        assert f"/{scope}/" in text, scope
    assert "/hc_attn/hc/hc_maps/" in text and "/hc_ffn/hc/hc_sinkhorn/" in text
    assert "/block_1/hc/hc_write/" in text and "/attn/mla/mla_q/" in text
    for name in ("hc_res_row_err.block_0", "hc_res_diag.block_2",
                 "hc_pre_mean.block_1", "hc_post_mean.block_1",
                 "moe_held_rows.block_1", "moe_whole.block_2"):
        assert name in metrics, name


def test_what_the_family_does_not_do_fails_loudly():
    module = xing4.xing4_tiny()
    tokens = jnp.zeros((1, 8), jnp.int32)
    variables = module.init(jax.random.key(0), tokens, train=False)
    with pytest.raises(NotImplementedError, match="hc_mult streams"):
        module.apply(variables, tokens, train=False, decode_ctx={})
    # the published prediction layer: the row does not say how it joins
    for mtp in (1, 2):
        with pytest.raises(NotImplementedError, match="prediction module"):
            xing4.xing4_tiny(mtp_layers=mtp).init(
                jax.random.key(0), tokens, train=False)
    with pytest.raises(NotImplementedError, match="prediction module"):
        xing4.xing4_29b().init(jax.random.key(0), tokens, train=False)
    with pytest.raises(ValueError, match="prediction module"):
        reference._sizes(dict(_model_dict(module),
                              num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="sequence-parallel"):
        registry.create_model(
            "xing4_tiny", num_classes=0, image_size=0, seq_len=8,
            dtype=jnp.float32, param_dtype=jnp.float32,
            logits_dtype=jnp.float32, remat=False, sp=True)
    for strategy in ("tp", "fsdp_tp"):
        with pytest.raises(ValueError, match="unknown strategy"):
            Trainer(from_preset(
                "xing4_29b_share", model="xing4_tiny", seq_len=16,
                global_batch_size=8, precision="fp32", workers=0,
                checkpoint_dir=None, strategy=strategy, mesh_fsdp=1,
                mesh_data=4, mesh_model=2))
    with pytest.raises(ValueError, match="remat_policy"):
        xing4.xing4_tiny(remat=True, remat_policy="?").init(
            jax.random.key(0), tokens, train=False)

"""Dropless MoE: the ragged grouped-matmul kernel and its wiring.

Three layers of guarantee, mirroring how the sort-dispatch suite is built:

1. Kernel parity (interpret mode off-TPU, so the REAL Pallas kernel
   bodies run): ``gmm`` / ``grouped_ffn`` forward and custom_vjp grads
   against a dense segment-einsum reference, across uneven / empty /
   single-expert-takes-all segments, E in {2, 8}, fp32 and bf16.
2. Module oracle: ``dispatch_impl="dropless"`` equals the einsum path at
   a never-drop capacity factor — the routing decisions are bitwise the
   same (shared fp32 router), so outputs, aux/z losses and parameter
   grads must match to accumulation tolerance, and the drop-fraction
   telemetry must be the exact constant 0.0.
3. Wiring: a full train step on the GQA llama_moe_tiny trunk under an
   fsdp x ep mesh matches the einsum oracle loss/params, an EP-mesh leg
   guards the sharded-operand gather miscompile workaround,
   and the capacity-clamp warning fires (once) for the non-dropless
   paths it protects.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.ops import (
    grouped_matmul as gmm_lib)
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib
from pytorch_distributed_training_example_tpu.parallel import (
    sharding as sharding_lib)

D = 16

# Never-drop capacity factor for the einsum oracle: capacity >= k*T for
# every test shape here, so within_cap keeps every routed token.
NEVER_DROP_CF = 100.0


def _segments(rng, E, Tk, *, empty=None, takes_all=None):
    """Random ragged segment sizes; optionally force expert ``empty`` to
    zero rows or expert ``takes_all`` to own every row."""
    if takes_all is not None:
        counts = np.zeros(E, np.int64)
        counts[takes_all] = Tk
    else:
        counts = rng.multinomial(Tk, np.ones(E) / E)
        if empty is not None:
            nxt = (empty + 1) % E
            counts[nxt] += counts[empty]
            counts[empty] = 0
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    return starts.astype(np.int32), counts.astype(np.int32)


def _ref_gmm(x, w, starts, counts):
    seg = np.zeros(x.shape[0], np.int32)
    for e in range(w.shape[0]):
        seg[int(starts[e]):int(starts[e]) + int(counts[e])] = e
    return jnp.einsum("td,tdf->tf", x, w[seg],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _ref_ffn(x, w_up, w_down, starts, counts):
    h = jax.nn.gelu(_ref_gmm(x, w_up, starts, counts))
    return _ref_gmm(h, w_down, starts, counts)


_TOLS = {  # dtype -> (fwd rtol, fwd atol, grad rtol, grad atol)
    "float32": (1e-5, 1e-6, 1e-4, 1e-5),
    # bf16 grad atol: dw sums bf16 products over a whole segment in a
    # different association order than XLA's transpose, so the noise
    # floor is ~eps_bf16 * sum_t |x_t * g_t| — with ~32-row segments and
    # O(1) entries that is a few tenths absolute on near-cancelling
    # elements (fp32 runs of the same cases agree to 1e-4: the math,
    # not the kernel, is the noise source).
    "bfloat16": (3e-2, 3e-2, 6e-2, 3e-1),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,Tk,segs", [
    (2, 24, {}),                 # uneven random segments
    (2, 24, {"empty": 0}),       # an empty expert (still gets a dw block)
    (8, 256, {}),                # many experts
    (8, 256, {"empty": 3}),      # empty expert mid-pack
    (4, 64, {"takes_all": 2}),   # one expert owns every token
])
def test_gmm_matches_dense_reference(E, Tk, segs, dtype):
    """Kernel forward + custom_vjp grads == dense einsum over the same
    segment map, in interpret mode (the actual kernel bodies execute)."""
    rng = np.random.default_rng(0)
    rt, at, grt, gat = _TOLS[np.dtype(dtype).name]
    starts, counts = _segments(rng, E, Tk, **segs)
    x = jnp.asarray(rng.standard_normal((Tk, D)), dtype)
    w = jnp.asarray(rng.standard_normal((E, D, 2 * D)) * 0.1, dtype)
    sj, cj = jnp.asarray(starts), jnp.asarray(counts)

    out = gmm_lib.gmm(x, w, sj, cj)
    ref = _ref_gmm(x, w, starts, counts)
    assert out.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=rt, atol=at)

    def loss_k(x, w):
        return jnp.sum(jnp.sin(gmm_lib.gmm(x, w, sj, cj)
                               .astype(jnp.float32)))

    def loss_r(x, w):
        return jnp.sum(jnp.sin(_ref_gmm(x, w, starts, counts)
                               .astype(jnp.float32)))

    gk = jax.grad(loss_k, argnums=(0, 1))(x, w)
    gr = jax.grad(loss_r, argnums=(0, 1))(x, w)
    for a, b in zip(gk, gr):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=grt, atol=gat)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_ffn_matches_dense_reference(dtype):
    """The padded-layout FFN composition (one relayout round trip across
    up-proj -> gelu -> down-proj) == the dense per-segment MLP."""
    rng = np.random.default_rng(1)
    rt, at, grt, gat = _TOLS[np.dtype(dtype).name]
    E, Tk = 8, 192
    starts, counts = _segments(rng, E, Tk, empty=5)
    x = jnp.asarray(rng.standard_normal((Tk, D)), dtype)
    w_up = jnp.asarray(rng.standard_normal((E, D, 32)) * 0.1, dtype)
    w_down = jnp.asarray(rng.standard_normal((E, 32, D)) * 0.1, dtype)
    sj, cj = jnp.asarray(starts), jnp.asarray(counts)

    out = gmm_lib.grouped_ffn(x, w_up, w_down, sj, cj)
    ref = _ref_ffn(x, w_up, w_down, starts, counts)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=rt, atol=at)

    def loss(fn):
        def f(x, wu, wd):
            return jnp.sum(jnp.sin(fn(x, wu, wd).astype(jnp.float32)))
        return jax.grad(f, argnums=(0, 1, 2))(x, w_up, w_down)

    gk = loss(lambda x, wu, wd: gmm_lib.grouped_ffn(x, wu, wd, sj, cj))
    gr = loss(lambda x, wu, wd: _ref_ffn(x, wu, wd, starts, counts))
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=grt, atol=gat)


def _blocks(E, k, **kw):
    mk = lambda impl, cf: moe_lib.MoEBlock(  # noqa: E731
        num_experts=E, ffn_dim=32, top_k=k, capacity_factor=cf,
        dispatch_impl=impl, **kw)
    return mk("dropless", 1.0), mk("einsum", NEVER_DROP_CF)


def _x(seed=7, b=2, t=32):
    return jnp.asarray(np.random.RandomState(seed).randn(b, t, D),
                       jnp.float32)


def _drop_leaves(tel):
    return [leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(tel)
            if "drop" in jax.tree_util.keystr(path)]


@pytest.mark.parametrize("E,k", [(4, 2), (4, 1), (8, 2)])
def test_dropless_matches_einsum_oracle(E, k):
    """dropless == einsum at a never-drop capacity factor: same forward,
    same aux/z losses, same param/input grads; drop fraction is the
    constant 0.0 (the sow short-circuits — no mask work to DCE)."""
    d_blk, e_blk = _blocks(E, k)
    x = _x()
    params = d_blk.init(jax.random.PRNGKey(0), x)["params"]

    out_d, var_d = d_blk.apply({"params": params}, x,
                               mutable=["telemetry", "losses"])
    out_e, var_e = e_blk.apply({"params": params}, x,
                               mutable=["telemetry", "losses"])
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_e),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(var_d["losses"]),
                    jax.tree.leaves(var_e["losses"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    drops = _drop_leaves(var_d["telemetry"])
    assert drops, "dropless must still sow moe_drop_fraction"
    for leaf in drops:
        assert leaf.dtype == jnp.float32
        assert np.asarray(leaf) == 0.0

    def loss(blk):
        def f(p, xx):
            out, _ = blk.apply({"params": p}, xx,
                               mutable=["telemetry", "losses"])
            return jnp.sum(out ** 2)
        return jax.grad(f, argnums=(0, 1))(params, x)

    for a, b in zip(jax.tree.leaves(loss(d_blk)),
                    jax.tree.leaves(loss(e_blk))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def test_dropless_bf16_tracks_fp32():
    """bf16 compute dtype: routing stays fp32 (same decisions), output
    tracks the fp32 block to bf16 resolution."""
    ref = moe_lib.MoEBlock(num_experts=4, ffn_dim=32, top_k=2,
                           capacity_factor=1.0, dispatch_impl="dropless")
    b16 = moe_lib.MoEBlock(num_experts=4, ffn_dim=32, top_k=2,
                           capacity_factor=1.0, dispatch_impl="dropless",
                           dtype=jnp.bfloat16)
    x = _x(seed=11)
    params = ref.init(jax.random.PRNGKey(0), x)["params"]
    a = np.asarray(ref.apply({"params": params}, x,
                             mutable=["telemetry", "losses"])[0])
    b = np.asarray(b16.apply({"params": params}, x,
                             mutable=["telemetry", "losses"])[0],
                   np.float32)
    np.testing.assert_allclose(a, b, rtol=5e-2, atol=5e-2)


def test_dropless_expert_parallel_matches_replicated(devices):
    """Dropless under an expert x data mesh == unsharded oracle, forward
    AND grads — the sharded-operand gather miscompile guard for the
    dropless sort/combine gathers (see test_moe_sort_dispatch)."""
    block = moe_lib.MoEBlock(num_experts=4, ffn_dim=32, top_k=2,
                             capacity_factor=1.0, dispatch_impl="dropless")
    x = _x(seed=0, b=4, t=8)
    params = block.init(jax.random.PRNGKey(0), x)["params"]

    def apply(p, xx):
        out, _ = block.apply({"params": p}, xx,
                             mutable=["telemetry", "losses"])
        return out

    def loss(p, xx):
        return jnp.sum(apply(p, xx) ** 2)

    ref = apply(params, x)
    g_ref = jax.grad(loss)(params, x)

    mesh = mesh_lib.build_mesh({"expert": 4, "data": 2})
    shardings = sharding_lib.make_shardings(params, mesh, moe_lib.EP_RULES)
    params_sharded = jax.tree.map(jax.device_put, params, shardings)
    assert "expert" in str(params_sharded["experts"]["w_up"].sharding.spec)
    with mesh_lib.use_mesh(mesh):
        out = jax.jit(apply)(params_sharded, x)
        g_out = jax.jit(jax.grad(loss))(params_sharded, x)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_dropless_llama_gqa_fsdp_ep(devices):
    """Full MoE-Llama (GQA trunk) one train step under fsdp x ep: the
    dropless program matches the einsum never-drop oracle loss and
    updated params through the registry -> config plumbing."""
    from pytorch_distributed_training_example_tpu.core import (
        optim, train_loop)
    from pytorch_distributed_training_example_tpu.data import prefetch
    from pytorch_distributed_training_example_tpu.models import registry
    from pytorch_distributed_training_example_tpu.utils.config import Config

    mesh = mesh_lib.build_mesh({"data": 2, "fsdp": 2, "expert": 2})
    r = np.random.RandomState(0)
    toks = r.randint(0, 512, (8, 33)).astype(np.int32)
    results = {}
    for impl, cf in (("einsum", NEVER_DROP_CF), ("dropless", 1.0)):
        bundle = registry.create_model("llama_moe_tiny", seq_len=32,
                                       dtype=jnp.float32,
                                       param_dtype=jnp.float32,
                                       moe_dispatch_impl=impl,
                                       moe_capacity_factor=cf)
        tx, _ = optim.build_optimizer(
            Config(lr=1e-2, warmup_epochs=0.0, optimizer="sgd",
                   weight_decay=0.0), steps_per_epoch=10)
        rules = sharding_lib.strategy_rules("fsdp_tp", bundle.rules)
        state = train_loop.create_train_state(bundle.module, tx,
                                              bundle.input_template, mesh,
                                              rules, seed=0)
        step = jax.jit(train_loop.make_train_step(
            train_loop.get_task("lm")), donate_argnums=0)
        with mesh_lib.use_mesh(mesh):
            b = prefetch.shard_batch(
                {"tokens": toks[:, :-1], "targets": toks[:, 1:]},
                mesh_lib.batch_sharding(mesh))
            state, m = step(state, b)
        results[impl] = (float(m["loss"]),
                         np.asarray(state.params["block_0"]["moe"]
                                    ["experts"]["w_up"]))
    assert np.isfinite(results["dropless"][0])
    np.testing.assert_allclose(results["dropless"][0],
                               results["einsum"][0], rtol=1e-5)
    np.testing.assert_allclose(results["dropless"][1],
                               results["einsum"][1], rtol=1e-4, atol=1e-5)


def test_dropless_telemetry_drop_fraction_in_train(devices):
    """Through the real model stack the dropless drop-fraction telemetry
    is the exact fp32 constant 0.0 for every layer."""
    from pytorch_distributed_training_example_tpu.models import registry

    bundle = registry.create_model("llama_moe_tiny", seq_len=32,
                                   dtype=jnp.float32,
                                   param_dtype=jnp.float32,
                                   moe_dispatch_impl="dropless")
    toks = np.random.RandomState(3).randint(0, 512, (2, 32)).astype(np.int32)
    variables = bundle.module.init(jax.random.PRNGKey(0), toks)
    _, var = bundle.module.apply({"params": variables["params"]}, toks,
                                 mutable=["telemetry", "losses"])
    drops = _drop_leaves(var["telemetry"])
    assert drops
    for leaf in drops:
        assert np.asarray(leaf) == 0.0


def test_capacity_clamp_warns_once():
    """int(cf*T*k/E) == 0 silently became capacity=1 before r14; now the
    clamp warns (once per process) for the capacity-bound impls. The
    dropless path never clamps — capacity is T*k by construction."""
    x = _x(seed=5, b=1, t=4)  # T=4, k=2, E=8, cf=0.1 -> int(0.1) == 0
    blk = moe_lib.MoEBlock(num_experts=8, ffn_dim=32, top_k=2,
                           capacity_factor=0.1, dispatch_impl="gather")
    moe_lib._capacity_clamp_warned = False
    with pytest.warns(RuntimeWarning, match="capacity clamped to 1"):
        blk.init(jax.random.PRNGKey(0), x)
    # once per process: a second trace stays silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        blk = moe_lib.MoEBlock(num_experts=8, ffn_dim=64, top_k=2,
                               capacity_factor=0.1, dispatch_impl="gather")
        blk.init(jax.random.PRNGKey(0), x)

    # dropless never routes through the clamp
    moe_lib._capacity_clamp_warned = False
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        blk = moe_lib.MoEBlock(num_experts=8, ffn_dim=32, top_k=2,
                               capacity_factor=0.1,
                               dispatch_impl="dropless")
        blk.init(jax.random.PRNGKey(0), x)


# ---- r17: expert-parallel dropless dispatch (ep_dispatch) ----------------


def _a2a_blocks_run(mesh, x, impl):
    from pytorch_distributed_training_example_tpu.ops import collectives
    from jax.sharding import PartitionSpec as P

    def body(xl):
        return collectives.all_to_all_blocks(xl, "expert", impl=impl)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("expert"),),
                       out_specs=P("expert"), check_vma=False)
    with mesh_lib.use_mesh(mesh):
        val = jax.jit(fn)(x)
        grad = jax.jit(jax.grad(
            lambda a: jnp.sum(jnp.sin(fn(a).astype(jnp.float32)))))(x)
    return np.asarray(val), np.asarray(grad)


def test_a2a_blocks_native_vs_ppermute(devices):
    """The ppermute fallback (gloo gangs without a real all-to-all) is
    value-bitwise and grad-close to lax.all_to_all, and both match the
    block-transpose semantics: out[dst-major] = in[src-major].T."""
    ep = 4
    mesh = mesh_lib.build_mesh({"expert": ep, "data": 2})
    x = jnp.asarray(np.random.default_rng(7).standard_normal((ep * ep, 6, 8)),
                    jnp.float32)
    v_nat, g_nat = _a2a_blocks_run(mesh, x, "native")
    v_pp, g_pp = _a2a_blocks_run(mesh, x, "ppermute")
    np.testing.assert_array_equal(v_nat, v_pp)
    np.testing.assert_allclose(g_nat, g_pp, rtol=1e-6, atol=1e-7)
    # semantics: device p's block q lands on device q as its block p
    blocks = np.asarray(x).reshape(ep, ep, 6, 8)
    np.testing.assert_array_equal(
        v_nat, np.swapaxes(blocks, 0, 1).reshape(ep * ep, 6, 8))
    # grad of sum-of-sin is elementwise through a permutation: positions
    # only move, so the cotangent must ride the inverse route exactly
    np.testing.assert_allclose(g_nat, np.cos(np.asarray(x)), rtol=1e-6,
                               atol=1e-7)


@pytest.mark.parametrize("ep_dispatch,chunks", [
    ("a2a", 2),
    ("a2a_overlap", 2),     # even split: R=16 -> [8, 8]
    ("a2a_overlap", 3),     # torn last window: R=16 -> [6, 6, 4]
    ("a2a_overlap", 16),    # chunk == single row (degenerate geometry)
])
def test_dropless_ep_dispatch_matches_replicated(devices, ep_dispatch,
                                                 chunks):
    """Sharded EP execution (a2a tokens to weight shards, local gmm) ==
    the replicated r14 block, forward and grads, including the torn
    ragged-last-chunk double-buffer geometries. Tolerance is the
    block-level contract (PROFILE_MOE.md r17): the gmm itself is bitwise,
    the surrounding router matmul fuses differently once the mesh is
    live, giving 1-ulp-scale wobble."""
    blk_kw = dict(num_experts=4, ffn_dim=32, top_k=2, capacity_factor=1.0,
                  dispatch_impl="dropless")
    ref_blk = moe_lib.MoEBlock(**blk_kw)
    ep_blk = moe_lib.MoEBlock(**blk_kw, ep_dispatch=ep_dispatch,
                              ep_overlap_chunks=chunks)
    x = _x(seed=3, b=2, t=16)  # kT=64, ep=4 -> R=16 rows per device
    params = ref_blk.init(jax.random.PRNGKey(0), x)["params"]

    def apply(blk, p, xx):
        out, _ = blk.apply({"params": p}, xx,
                           mutable=["telemetry", "losses"])
        return out

    ref = apply(ref_blk, params, x)
    g_ref = jax.grad(lambda p: jnp.sum(apply(ref_blk, p, x) ** 2))(params)

    mesh = mesh_lib.build_mesh({"expert": 4, "data": 2})
    shardings = sharding_lib.make_shardings(params, mesh, moe_lib.EP_RULES)
    p_sh = jax.tree.map(jax.device_put, params, shardings)
    with mesh_lib.use_mesh(mesh):
        out = jax.jit(lambda p: apply(ep_blk, p, x))(p_sh)
        g_out = jax.jit(jax.grad(
            lambda p: jnp.sum(apply(ep_blk, p, x) ** 2)))(p_sh)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ep_a2a_impl_env_ppermute_end_to_end(devices, monkeypatch):
    """PDTX_EP_A2A_IMPL=ppermute swaps the transport under the whole
    block: outputs must match the native-a2a run bitwise (same floats,
    different collective)."""
    blk = moe_lib.MoEBlock(num_experts=4, ffn_dim=32, top_k=2,
                           capacity_factor=1.0, dispatch_impl="dropless",
                           ep_dispatch="a2a")
    x = _x(seed=9, b=2, t=16)
    params = blk.init(jax.random.PRNGKey(1), x)["params"]
    mesh = mesh_lib.build_mesh({"expert": 4, "data": 2})
    shardings = sharding_lib.make_shardings(params, mesh, moe_lib.EP_RULES)
    p_sh = jax.tree.map(jax.device_put, params, shardings)

    def run():
        with mesh_lib.use_mesh(mesh):
            out, _ = jax.jit(lambda p: blk.apply(
                {"params": p}, x, mutable=["telemetry", "losses"]))(p_sh)
        return np.asarray(out)

    monkeypatch.setenv(moe_lib.EP_A2A_IMPL_ENV, "native")
    a = run()
    monkeypatch.setenv(moe_lib.EP_A2A_IMPL_ENV, "ppermute")
    jax.clear_caches()  # env is read at trace time
    b = run()
    np.testing.assert_array_equal(a, b)


def test_ep_chunk_log_static_and_deterministic(devices, tmp_path,
                                               monkeypatch):
    """The a2a chunk log captures the static transfer geometry (torn last
    chunk included) and is byte-identical across traces — the dryrun
    gang's determinism contract."""
    log = tmp_path / "chunks.jsonl"
    monkeypatch.setenv(moe_lib.A2A_CHUNK_LOG_ENV, str(log))
    blk = moe_lib.MoEBlock(num_experts=4, ffn_dim=32, top_k=2,
                           capacity_factor=1.0, dispatch_impl="dropless",
                           ep_dispatch="a2a_overlap", ep_overlap_chunks=3)
    x = _x(seed=4, b=2, t=16)  # R=16 -> chunk_rows [6, 6, 4]
    params = blk.init(jax.random.PRNGKey(0), x)["params"]
    mesh = mesh_lib.build_mesh({"expert": 4, "data": 2})

    def trace():
        with mesh_lib.use_mesh(mesh):
            jax.jit(lambda p: blk.apply(
                {"params": p}, x,
                mutable=["telemetry", "losses"])[0]).lower(params)

    trace()
    first = log.read_text()
    trace()
    lines = log.read_text().splitlines()
    assert len(lines) == 2 and lines[0] == lines[1], lines
    assert first.splitlines()[0] == lines[0]
    import json as _json
    row = _json.loads(lines[0])
    assert row["mode"] == "a2a_overlap" and row["ep"] == 4
    assert row["chunk_rows"] == [6, 6, 4] and row["rows_per_device"] == 16
    assert row["send_bytes_per_chunk"] == [4 * w * D * 4
                                           for w in (6, 6, 4)]


def test_ep_overlap_hlo_interleaves_a2a_with_gmm(devices):
    """Acceptance criterion: the a2a_overlap compiled program actually
    interleaves per-chunk all-to-all transfers with grouped-FFN compute —
    inspected on the optimized HLO. The plain a2a variant moves the same
    tokens in ONE all-to-all; overlap splits it into >= n_chunks of them,
    and at least one moe_experts_gmm computation sits strictly between
    the first and last transfer in program order."""
    import re as _re

    x = _x(seed=2, b=2, t=16)
    mesh = mesh_lib.build_mesh({"expert": 4, "data": 2})

    def hlo(ep_dispatch, chunks=3):
        blk = moe_lib.MoEBlock(num_experts=4, ffn_dim=32, top_k=2,
                               capacity_factor=1.0,
                               dispatch_impl="dropless",
                               ep_dispatch=ep_dispatch,
                               ep_overlap_chunks=chunks)
        params = blk.init(jax.random.PRNGKey(0), x)["params"]
        shardings = sharding_lib.make_shardings(params, mesh,
                                                moe_lib.EP_RULES)
        p_sh = jax.tree.map(jax.device_put, params, shardings)
        with mesh_lib.use_mesh(mesh):
            return jax.jit(lambda p: blk.apply(
                {"params": p}, x, mutable=["telemetry", "losses"]
            )[0]).lower(p_sh).compile().as_text()

    a2a_re = _re.compile(r"= (?:\([^)]*\)|\S+) all-to-all(?:-start)?\(")
    n_plain = len(a2a_re.findall(hlo("a2a")))
    text = hlo("a2a_overlap", chunks=3)
    lines = text.splitlines()
    a2a_at = [i for i, ln in enumerate(lines) if a2a_re.search(ln)]
    gmm_at = [i for i, ln in enumerate(lines)
              if "moe_experts_gmm" in ln and "fusion" in ln]
    assert n_plain >= 1 and len(a2a_at) >= 3 * n_plain, (n_plain, len(a2a_at))
    assert gmm_at, "grouped-FFN fusions must be scope-tagged in the HLO"
    assert any(a2a_at[0] < g < a2a_at[-1] for g in gmm_at), (
        "no gmm compute between the first and last a2a chunk",
        a2a_at[:4], gmm_at[:4])

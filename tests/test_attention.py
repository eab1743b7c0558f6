"""Ring/Ulysses/flash attention vs the XLA oracle (SURVEY.md §4.2, §7(c))."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.ops import attention as A
from pytorch_distributed_training_example_tpu.ops import flash_attention as F


def _qkv(B=2, S=64, H=4, Hkv=None, D=16, seed=0):
    r = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(r.randn(B, S, h, D), jnp.float32)
    return mk(H), mk(Hkv or H), mk(Hkv or H)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_oracle(devices, causal):
    mesh = mesh_lib.build_mesh({"context": 8})
    q, k, v = _qkv()
    ref = A.dot_product_attention(q, k, v, causal=causal)
    out = A.ring_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_ring_gqa_and_grads(devices):
    mesh = mesh_lib.build_mesh({"context": 4, "data": 2})
    q, k, v = _qkv(H=4, Hkv=2)
    ref = A.dot_product_attention(q, k, v, causal=True)
    out = A.ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(lambda *a: A.ring_attention(*a, mesh=mesh, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# Documented tolerances for the ring family vs the XLA oracle: the online
# softmax reorders the reduction, so fwd agrees to rtol/atol 1e-5 in fp32
# and grads (one extra rounding through the recomputed blocks) to
# rtol 1e-4 / atol 1e-5 — same bars as the flash kernels.
RING_FWD_TOL = dict(rtol=1e-5, atol=1e-5)
RING_GRAD_TOL = dict(rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("ring", [2, 4])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_torn_last_block(devices, ring, causal):
    """S=50 does not divide ring degrees 2/4: the torn last block is padded
    and key-masked; fwd + grads stay at the documented tolerances."""
    mesh = mesh_lib.build_mesh({"context": ring, "data": 8 // ring})
    q, k, v = _qkv(B=8, S=50)
    ref = A.dot_product_attention(q, k, v, causal=causal)
    out = A.ring_attention(q, k, v, mesh=mesh, causal=causal)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               **RING_FWD_TOL)
    g_ref = jax.grad(
        lambda *a: A.dot_product_attention(*a, causal=causal).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(
        lambda *a: A.ring_attention(*a, mesh=mesh, causal=causal).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **RING_GRAD_TOL)


@pytest.mark.parametrize("ring", [2, 4])
def test_ring_torn_gqa_grads(devices, ring):
    """Torn last block + GQA 4:1 together, fwd and grads."""
    mesh = mesh_lib.build_mesh({"context": ring, "data": 8 // ring})
    q, k, v = _qkv(B=8, S=42, H=4, Hkv=1)
    ref = A.dot_product_attention(q, k, v, causal=True)
    out = A.ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               **RING_FWD_TOL)
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(
        lambda *a: A.ring_attention(*a, mesh=mesh, causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **RING_GRAD_TOL)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_allgather_matches_oracle_and_flash(devices, causal):
    """The all-gather-KV fallback vs both oracles: the XLA reference and
    the contiguous ppermute ring (same mesh, same inputs)."""
    mesh = mesh_lib.build_mesh({"context": 4, "data": 2})
    q, k, v = _qkv()
    ref = A.dot_product_attention(q, k, v, causal=causal)
    out = A.ring_attention(q, k, v, mesh=mesh, causal=causal,
                           ring_impl="allgather")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               **RING_FWD_TOL)
    ring = A.ring_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(out),
                               **RING_FWD_TOL)


def test_ring_allgather_torn_gqa_grads(devices):
    """allgather fallback with a torn last block + GQA, fwd + grads."""
    mesh = mesh_lib.build_mesh({"context": 4, "data": 2})
    q, k, v = _qkv(S=50, H=4, Hkv=1)
    ref = A.dot_product_attention(q, k, v, causal=True)
    out = A.ring_attention(q, k, v, mesh=mesh, causal=True,
                           ring_impl="allgather")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               **RING_FWD_TOL)
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(
        lambda *a: A.ring_attention(*a, mesh=mesh, causal=True,
                                    ring_impl="allgather").sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **RING_GRAD_TOL)


def test_ring_allgather_dispatch(devices):
    """attn_impl='ring_allgather' reaches the fallback through the
    dispatcher and collapses to XLA at ctx=1."""
    mesh = mesh_lib.build_mesh({"context": 2, "data": 4})
    q, k, v = _qkv(B=8, S=32)
    ref = A.dot_product_attention(q, k, v, causal=True)
    with mesh_lib.use_mesh(mesh):
        out = A.attention(q, k, v, causal=True, impl="ring_allgather")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               **RING_FWD_TOL)
    m1 = mesh_lib.build_mesh({"data": 8})
    with mesh_lib.use_mesh(m1):
        out1 = A.attention(q, k, v, causal=True, impl="ring_allgather")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out1),
                               **RING_FWD_TOL)


def test_ring_bad_impl_rejected(devices):
    mesh = mesh_lib.build_mesh({"context": 2, "data": 4})
    q, k, v = _qkv(S=32)
    with pytest.raises(ValueError, match="ring_impl"):
        A.ring_attention(q, k, v, mesh=mesh, ring_impl="bogus")


@pytest.mark.parametrize("ctx", [2, 4, 8])
def test_zigzag_ring_matches_oracle(devices, ctx):
    mesh = mesh_lib.build_mesh({"context": ctx, "data": 8 // ctx})
    q, k, v = _qkv(B=8)
    ref = A.dot_product_attention(q, k, v, causal=True)
    out = A.zigzag_ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # ~40-105s compile on the 1-core CI host (r4 suite-budget pass)
def test_zigzag_ring_gqa_tp_and_grads(devices):
    mesh = mesh_lib.build_mesh({"context": 4, "model": 2})
    q, k, v = _qkv(H=4, Hkv=2)
    ref = A.dot_product_attention(q, k, v, causal=True)
    out = A.zigzag_ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(
        lambda *a: A.zigzag_ring_attention(*a, mesh=mesh, causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_zigzag_falls_back_when_indivisible(devices):
    """Sequence not divisible into 2c chunks -> contiguous ring, same result."""
    mesh = mesh_lib.build_mesh({"context": 8})
    q, k, v = _qkv(S=24)  # 24 % 16 != 0
    ref = A.dot_product_attention(q, k, v, causal=True)
    out = A.zigzag_ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_oracle(devices, causal):
    mesh = mesh_lib.build_mesh({"context": 4, "data": 2})
    q, k, v = _qkv(H=8)
    ref = A.dot_product_attention(q, k, v, causal=causal)
    out = A.ulysses_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_head_padding(devices):
    """Heads not divisible by the context shards are zero-padded (r3
    hard-errored here): values AND grads must match the oracle exactly —
    the slice vjp drops the padded heads' contributions."""
    mesh = mesh_lib.build_mesh({"context": 8})
    q, k, v = _qkv(H=4)  # 4 % 8 != 0 -> padded to 8
    ref = A.dot_product_attention(q, k, v, causal=True)
    out = A.ulysses_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(
        lambda *a: A.ulysses_attention(*a, mesh=mesh, causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ulysses_head_padding_with_tp_pads_once(devices, caplog):
    """H indivisible by BOTH tp and context: the pad target must be a
    multiple of tp*c so the recursive call doesn't pad a second time
    (r4 review finding: conditioning the pad group on the pre-pad h_ax
    double-padded 6 heads to 16). One pad == one warning."""
    import logging

    mesh = mesh_lib.build_mesh({"model": 2, "context": 4})
    q, k, v = _qkv(H=3)
    ref = A.dot_product_attention(q, k, v, causal=True)
    with caplog.at_level(logging.WARNING,
                         logger="pytorch_distributed_training_example_tpu.ops.attention"):
        out = A.ulysses_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    pads = [r for r in caplog.records if "zero-padding" in r.message]
    assert len(pads) == 1, [r.message for r in pads]


def test_ulysses_head_padding_gqa(devices):
    """GQA with indivisible Q heads: KV expands to full heads before the
    pad so q-to-kv head grouping stays aligned."""
    mesh = mesh_lib.build_mesh({"context": 4, "data": 2})
    q, k, v = _qkv(H=6, Hkv=2)  # 6 % 4 != 0 -> padded to 8
    ref = A.dot_product_attention(q, k, v, causal=False)
    out = A.ulysses_attention(q, k, v, mesh=mesh, causal=False)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["oneshot", "online"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_interpret(causal, impl):
    q, k, v = _qkv(S=128)
    ref = A.dot_product_attention(q, k, v, causal=causal)
    with pltpu.force_tpu_interpret_mode():
        out = F.flash_attention(q, k, v, causal, 32, 32, impl)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["oneshot", "online"])
def test_flash_grads_interpret(impl):
    q, k, v = _qkv(S=64)
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        g_out = jax.grad(
            lambda *a: F.flash_attention(*a, True, 32, 32, impl).sum(),
            argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_fit_block_falls_to_largest_divisor():
    """Blocks must tile S exactly — flooring the grid drops rows (r3 advisor
    high: S=2560 under the 1024 defaults silently lost the last 512 query
    rows' gradients in _flash_bwd)."""
    assert F._fit_block(2560, 1024) == 640
    assert F._fit_block(3584, 1024) == 896
    assert F._fit_block(1536, 1024) == 768
    assert F._fit_block(1024, 1024) == 1024
    assert F._fit_block(512, 1024) == 512
    assert F._fit_block(96, 64) == 48


def test_flash_indivisible_block_grads_interpret():
    """S not divisible by the requested block: the online kernels must fall
    to a fitting block and produce exact grads (every row written)."""
    q, k, v = _qkv(B=1, S=96, H=2)
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        g_out = jax.grad(
            lambda *a: F.flash_attention(*a, True, 64, 64, "online").sum(),
            argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("S,D", [(2560, 128), (3584, 128), (1536, 64)])
def test_flash_eligible_shapes_trace(S, D):
    """Every shape _flash_eligible admits (S % 512 == 0) must trace through
    auto dispatch fwd+bwd with the default 1024 blocks — the r3 advisor found
    S=3584/D=128 crashing at trace time and S=2560/D=128 tracing into a
    row-dropping bwd grid. eval_shape runs the wrapper Python (plan choice,
    block fitting, grid math, asserts) without compiling."""
    q = jax.ShapeDtypeStruct((1, S, 4, D), jnp.bfloat16)
    jax.eval_shape(
        jax.grad(lambda a, b, c: F.flash_attention(a, b, c, True).sum()
                 .astype(jnp.float32)),
        q, q, q)


def test_oneshot_chunked_bwd_grads_interpret():
    """The chunked causal-skip backward (forced one-shot, and auto outside
    the causal kernels' measured shapes) must match the oracle exactly —
    invisible chunks skipped, visible diagonal chunks masked per-chunk."""
    assert F._oneshot_num_chunks(True, None, 1024) == 2
    assert F._oneshot_num_chunks(True, 197, 1024) == 1
    q, k, v = _qkv(B=1, S=1024, H=2, D=16)
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        g_out = jax.grad(
            lambda *a: F.flash_attention(*a, True, 1024, 1024, "oneshot").sum(),
            argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def _lse_oracle(q, k):
    """logsumexp of the causal scores, [B, H, S], as the kernels define it."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, A._repeat_kv(k, q.shape[2]))
    s = s / np.sqrt(q.shape[-1])
    visible = np.tril(np.ones((q.shape[1], q.shape[1]), bool))
    return jax.nn.logsumexp(jnp.where(visible, s, -np.inf), axis=-1)


def _check_causal_lse(lse, q, k):
    """The causal forward's lse: [B, H*D/lanes, S, lanes] float32, each
    head's value across its own D lanes (the backward reads any of them)."""
    B, S, H, D = q.shape
    lanes = F._causal_lanes(H, D)
    lse = np.asarray(lse)
    assert lse.shape == (B, H * D // lanes, S, lanes) and lse.dtype == np.float32
    assert lse.size * 2 <= B * H * S * 128 or D >= 128  # half the padded rows
    per_head = lse.reshape(B, -1, S, lanes // D, D)
    np.testing.assert_array_equal(per_head, per_head[..., :1].repeat(D, -1))
    rows = np.asarray(F._causal_lse_rows(jnp.asarray(lse), H))
    assert rows.shape == (B, H, S, F.LSE_LANES)
    np.testing.assert_array_equal(rows, rows[..., :1].repeat(F.LSE_LANES, -1))
    np.testing.assert_allclose(rows[..., 0], np.asarray(_lse_oracle(q, k)),
                               rtol=1e-5, atol=1e-5)


def _causal_parity(monkeypatch, q, k, v, plans, kernels):
    """Output, lse and all three grads of flash_attention against the XLA
    oracle with auto dispatch pinned to ``plans`` = (forward, backward),
    and the kernel entry points that ran against ``kernels``."""
    # float32 operands pin the comparison; auto plans only bf16 ones
    monkeypatch.setattr(F, "_auto_causal_plan",
                        lambda *a, bwd=False, **k: plans[bwd])
    ran = []
    for name in ("_causal_fwd", "_causal_bwd", "_oneshot_bwd"):
        monkeypatch.setattr(F, name, lambda *a, _f=getattr(F, name), _n=name,
                            **k: (ran.append(_n), _f(*a, **k))[1])
    H = q.shape[2]
    g = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)
    ref, vjp = jax.vjp(
        lambda *a: A.dot_product_attention(*a, causal=True), q, k, v)
    with pltpu.force_tpu_interpret_mode():
        out, vjp_flash = jax.vjp(
            lambda *a: F.flash_attention(*a, True), q, k, v)
        grads = vjp_flash(g)
        _, lse = F._fwd_dispatch(q, A._repeat_kv(k, H), A._repeat_kv(v, H),
                                 True, 1024, 1024, "auto", None)
    assert ran == [*kernels, "_causal_fwd"]
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(vjp(g), grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    _check_causal_lse(lse, q, k)


@pytest.mark.parametrize("Hkv", [4, 2])
@pytest.mark.parametrize("tile", [128, 256])
def test_causal_kernels_parity_interpret(monkeypatch, tile, Hkv):
    """The causal kernels (static triangular sub-tiles) through
    flash_attention's own dispatch, at each sub-tile size the planner
    chooses at some width (both directions at both here), with and without
    GQA, all four 16-wide heads stacked in one 64-lane block: output, lse and
    all three grads."""
    q, k, v = _qkv(B=1, S=1024, H=4, Hkv=Hkv, D=16)
    _causal_parity(monkeypatch, q, k, v, [(4, tile)] * 2,
                   ["_causal_fwd", "_causal_bwd"])


@pytest.mark.parametrize("H,Hkv,D,G,bwd", [
    (4, 4, 64, 2, "causal"),    # GPT-2's blocks: two 64-wide heads, 128 lanes
    (4, 2, 64, 4, "causal"),    # four heads a block (two chunks), GQA
    (2, 1, 128, 1, "causal"),   # one 128-wide head a block: no lane mask; GQA
    (2, 2, 128, 2, "causal"),   # two of them a block
    (2, 2, 128, 1, "oneshot"),  # S=2048/D=128's pairing: the one-shot backward
    (2, 1, 64, 2, "oneshot"),   # the same from a two-head chunk, GQA
])
def test_causal_kernels_lane_dense_layout_interpret(monkeypatch, H, Hkv, D, G,
                                                    bwd):
    """The [B, S, H*D] blocks at the widths the chip runs: heads that share
    a 128-lane chunk are stacked along the rows and told apart by lane masks,
    delta is computed in the backward kernel, and where the backward is
    another family's the forward's lse reaches it as [B,H,S,LSE_LANES]
    rows."""
    q, k, v = _qkv(B=1, S=512, H=H, Hkv=Hkv, D=D)
    tile = F._causal_plan(H, 1024, D)[1]  # 256 rows: a chunk's heads by T
    plans = [(G, tile), (G, 128) if bwd == "causal" else None]
    _causal_parity(monkeypatch, q, k, v, plans,
                   ["_causal_fwd", f"_{bwd}_bwd"])


def test_gpt2_attention_is_dense_generals(devices):
    """GPT-2's SelfAttention computes its projections as flat matmuls: the
    parameter tree (paths, shapes, initial values), the output and every
    gradient are those of the DenseGeneral form, on one device and with the
    heads sharded over a tp=2 mesh."""
    import flax.linen as nn

    from pytorch_distributed_training_example_tpu.models import gpt2
    from pytorch_distributed_training_example_tpu.parallel import sharding

    class DenseGenerals(nn.Module):
        @nn.compact
        def __call__(self, x):
            dg = lambda name: nn.DenseGeneral((4, 16), axis=-1, name=name)
            out = A.attention(dg("query")(x), dg("key")(x), dg("value")(x),
                              causal=True, impl="xla")
            return nn.DenseGeneral(64, axis=(-2, -1), name="out")(out)

    rs = np.random.RandomState(0)
    x, g = (jnp.asarray(rs.randn(4, 32, 64), jnp.float32) for _ in range(2))
    new = gpt2.SelfAttention(4, jnp.float32, jnp.float32, attn_impl="xla")
    old = DenseGenerals()
    params = new.init(jax.random.PRNGKey(1), x, False)
    ref_params = old.init(jax.random.PRNGKey(1), x)
    assert (jax.tree.structure(params) == jax.tree.structure(ref_params))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the benchmark's weights: no zero bias, so that every leaf's gradient counts
    params = jax.tree.map(
        lambda a: jnp.asarray(rs.randn(*a.shape) * 0.1, jnp.float32), params)
    ref_out, ref_vjp = jax.vjp(old.apply, params, x)
    ref_grads = ref_vjp(g)

    def check(out, grads):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    fwd_bwd = lambda p, x, g: (lambda out, vjp: (out, vjp(g)))(
        *jax.vjp(lambda p, x: new.apply(p, x, False), p, x))
    check(*jax.jit(fwd_bwd)(params, x, g))
    mesh = mesh_lib.build_mesh({"data": 4, "model": 2})
    with mesh_lib.use_mesh(mesh):
        # the module stands alone here: its paths lack the block's "attn/"
        rules = [(pattern.replace("attn/", "params/"), spec)
                 for pattern, spec in gpt2.TP_RULES]
        sharded = sharding.shard_params(params, mesh, rules)
        kernel = sharded["params"]["query"]["kernel"]
        assert kernel.sharding.shard_shape(kernel.shape) == (64, 2, 16)
        check(*jax.jit(fwd_bwd)(sharded, x, g))


def _stub_flash_kernels(monkeypatch):
    """Every kernel entry point replaced by a recorder: routing, not math."""
    calls = []
    for name, out in (("_flash_fwd", ("o", "l")), ("_oneshot_fwd", ("o", "l")),
                      ("_causal_fwd", ("o", "l")),
                      ("_flash_bwd", ("q", "k", "v")),
                      ("_oneshot_bwd", ("q", "k", "v")),
                      ("_stream_bwd", ("q", "k", "v")),
                      ("_causal_bwd", ("q", "k", "v"))):
        monkeypatch.setattr(
            F, name, lambda *a, _n=name, _o=out, **k: (calls.append(_n), _o)[1])
    return calls


def test_causal_kernels_dispatch(monkeypatch):
    """GPT-2's shape (H=12, S=1024, D=64) takes the causal kernels in both
    directions under auto; non-causal, kv_len, Sq != Skv, forced impls and
    shapes nobody measured never do."""
    assert F._causal_plan(12, 1024, 64) == (2, 128)
    assert F._causal_plan(12, 1024, 64, bwd=True) == (4, 128)
    # a program's heads are whole 128-lane chunks of [B, S, H*D]: tp=2 and 4
    # leave 6 and 3 heads a device, and three 64-wide heads do not pair up
    assert F._causal_plan(6, 1024, 64, bwd=True) == (2, 128)
    assert F._causal_plan(3, 1024, 64) is None
    assert F._causal_plan(16, 1024, 128) == (2, 256)
    assert F._causal_plan(16, 1024, 128, bwd=True) == (2, 128)
    # S=2048/D=64: two heads' forward is over the budget (and the v5e
    # compiler's 16 MB), one head's 64 lanes are no block
    assert F._causal_plan(12, 2048, 64) is None
    assert F._causal_plan(12, 2048, 64, bwd=True) is None
    calls = _stub_flash_kernels(monkeypatch)
    q = jnp.zeros((1, 1024, 12, 64), jnp.bfloat16)
    res = (q, q, q, "o", "l")
    F._fwd_dispatch(q, q, q, True, 1024, 1024, "auto", None)
    F._vjp_bwd(True, 1024, 1024, "auto", None, None, res, q)
    assert calls == ["_causal_fwd", "_causal_bwd"]
    del calls[:]
    half = q[:, :512]
    p = jnp.zeros((1, 256, 12, 64), jnp.bfloat16)  # ViT's padded 197
    for fwd_args, bwd_args in (
            ((q, q, q, False, 1024, 1024, "auto", None),
             (False, 1024, 1024, "auto", None, None, res, q)),
            ((p, p, p, False, 1024, 1024, "auto", 197),
             (False, 1024, 1024, "auto", 197, None, (p, p, p, "o", "l"), p)),
            ((p, p, p, True, 1024, 1024, "auto", 197),
             (True, 1024, 1024, "auto", 197, None, (p, p, p, "o", "l"), p)),
            ((half, q, q, True, 1024, 1024, "auto", None),
             (True, 1024, 1024, "auto", None, None, (half, q, q, "o", "l"),
              half)),
            ((q, q, q, True, 1024, 1024, "online", None),
             (True, 1024, 1024, "online", None, None, res, q)),
            ((q, q, q, True, 1024, 1024, "oneshot", None),
             (True, 1024, 1024, "oneshot", None, None, res, q))):
        F._fwd_dispatch(*fwd_args)
        F._vjp_bwd(*bwd_args)
    odd = jnp.zeros((1, 1536, 12, 64), jnp.bfloat16)  # not in CAUSAL_MEASURED
    # measured, and its bytes counted, in bf16 (_mxu widens fp16 in VMEM)
    for x in (odd, q.astype(jnp.float32), q.astype(jnp.float16)):
        F._fwd_dispatch(x, x, x, True, 1024, 1024, "auto", None)
        F._vjp_bwd(True, 1024, 1024, "auto", None, None, (x, x, x, "o", "l"), x)
    assert len(calls) == 18 and not any("causal" in c for c in calls), calls
    # S=2048/D=128: measured ahead in both directions, but the backward's
    # whole-head blocks are over the planner's budget -> chunked one-shot
    del calls[:]
    big = jnp.zeros((1, 2048, 16, 128), jnp.bfloat16)
    assert F._causal_plan(16, 2048, 128) == (1, 256)
    assert F._causal_plan(16, 2048, 128, bwd=True) is None
    assert F._causal_plan(16, 4096, 128) is None
    F._fwd_dispatch(big, big, big, True, 1024, 1024, "auto", None)
    F._vjp_bwd(True, 1024, 1024, "auto", None, None, (big, big, big, "o", "l"), big)
    assert calls == ["_causal_fwd", "_oneshot_bwd"]


def test_gqa_repeat():
    q, k, v = _qkv(H=8, Hkv=2)
    ref = A.dot_product_attention(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2))
    out = A.dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-6)


def test_ring_and_ulysses_with_tp_heads(devices):
    """CP composes with TP: heads stay sharded on 'model' inside the ring."""
    mesh = mesh_lib.build_mesh({"context": 2, "model": 2, "data": 2})
    q, k, v = _qkv(S=32)
    ref = A.dot_product_attention(q, k, v, causal=True)
    ring = A.ring_attention(q, k, v, mesh=mesh, causal=True)
    ul = A.ulysses_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ring),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ul),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["oneshot", "online"])
def test_flash_gqa_grads_interpret(impl):
    from jax.experimental.pallas import tpu as pltpu

    q, k, v = _qkv(S=64, H=4, Hkv=2)
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        g_out = jax.grad(
            lambda *a: F.flash_attention(*a, True, 32, 32, impl).sum(),
            argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_padded_flash_matches_oracle(causal):
    """Non-tile-aligned S (ViT's 197-token shape, scaled down) through the
    pad + kv_len-mask path must match the oracle exactly — padded keys are
    masked out of the softmax, padded query rows are sliced away."""
    q, k, v = _qkv(S=50)  # 50 % 64 != 0 -> pads to 64
    ref = A.dot_product_attention(q, k, v, causal=causal)
    with pltpu.force_tpu_interpret_mode():
        out = A.padded_flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_padded_flash_grads(causal):
    q, k, v = _qkv(S=50, H=4, Hkv=2)  # GQA + padding together
    g_ref = jax.grad(
        lambda *a: A.dot_product_attention(*a, causal=causal).sum(),
        argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        g_out = jax.grad(
            lambda *a: A.padded_flash_attention(*a, causal=causal).sum(),
            argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_oneshot_plan_dispatch_thresholds():
    """Lock in the measured auto-dispatch map (BENCH_FLASH_MICRO.json r4):
    causal forwards stream (online), backwards go one-shot whenever the
    plan fits VMEM; long-context backwards leave the dense plan (the
    fallback is streaming at D=128, online elsewhere — see
    test_auto_dispatch_is_per_direction)."""
    # GPT-2 / Llama-class shapes: the one-shot backward plan exists
    assert F._oneshot_plan(12, 1024, 1024, 64, bwd=True) is not None
    assert F._oneshot_plan(16, 2048, 2048, 128, bwd=True) is not None
    # r5 budget policy (ADVICE r4): the 16.8 MB GPT-2 backward plan is
    # admitted via the measured allowlist, not a >VMEM global cap...
    assert F._oneshot_plan(12, 1024, 1024, 64, bwd=True) == (2, 512)
    # (measured in bf16: float32 operands keep to the budget)
    assert F._oneshot_plan(12, 1024, 1024, 64, bwd=True,
                           dtype=jnp.float32) == (2, 256)
    # ...so an unmeasured same-band plan (S=2048/D=64 (1,512) = 16.7 MB)
    # is no longer auto-admitted; the under-budget (1,256) is picked.
    assert F._oneshot_plan(16, 2048, 2048, 64, bwd=True) == (1, 256)
    # S=4096: fwd plan exists at the r4 budget but bwd does not ->
    # backward streams online (the measured faster choice)
    assert F._oneshot_plan(16, 4096, 4096, 128) is not None
    assert F._oneshot_plan(16, 4096, 4096, 128, bwd=True) is None
    assert F._oneshot_plan(16, 4096, 4096, 64, bwd=True) is None
    # ...but impl="oneshot" (forced) still finds a feasible fwd tiling
    assert F._oneshot_plan(16, 4096, 4096, 128, forced=True) is not None
    # tiny sequences are exempt from the fatness threshold (tests use them)
    assert F._oneshot_plan(4, 64, 64, 16) is not None
    # beyond any VMEM-feasible dense tile: no plan even forced
    assert F._oneshot_plan(16, 32768, 32768, 128, forced=True) is None


def test_auto_dispatch_is_per_direction(monkeypatch):
    """The measured r4 dispatch map must hold structurally outside the
    causal kernels' shapes (test_causal_kernels_dispatch): causal auto
    forwards stream (online), non-causal auto forwards take one-shot when
    a plan exists, and auto backwards take one-shot whenever the bwd plan
    fits. Long-context backwards fall back to the streaming one-pass
    backward at D=128 where the v5e compiler admits it (S<=4096) and to
    the online kernel pair elsewhere (S=8192: refused on the chip).
    Kernels are stubbed so this asserts the routing, not the math
    (covered elsewhere)."""
    calls = _stub_flash_kernels(monkeypatch)
    q = jnp.zeros((1, 1536, 12, 64), jnp.bfloat16)  # not in CAUSAL_MEASURED
    F._fwd_dispatch(q, q, q, True, 1024, 1024, "auto", None)
    F._fwd_dispatch(q, q, q, False, 1024, 1024, "auto", None)
    res = (q, q, q, "o", "l")
    F._vjp_bwd(True, 1024, 1024, "auto", None, None, res, jnp.zeros_like(q))
    q4 = jnp.zeros((1, 4096, 16, 64), jnp.bfloat16)  # bwd plan infeasible, D=64
    F._vjp_bwd(True, 1024, 1024, "auto", None, None, (q4, q4, q4, "o", "l"),
               jnp.zeros_like(q4))
    q4k = jnp.zeros((1, 4096, 16, 128), jnp.bfloat16)  # D=128 long context
    F._vjp_bwd(True, 1024, 1024, "auto", None, None, (q4k, q4k, q4k, "o", "l"),
               jnp.zeros_like(q4k))
    # forced online must never take the streaming path
    F._vjp_bwd(True, 1024, 1024, "online", None, None, (q4k, q4k, q4k, "o", "l"),
               jnp.zeros_like(q4k))
    # S=8192/D=128: the streaming plan is over the chip's scoped VMEM
    q8 = jnp.zeros((1, 8192, 16, 128), jnp.bfloat16)
    F._vjp_bwd(True, 1024, 1024, "auto", None, None, (q8, q8, q8, "o", "l"),
               jnp.zeros_like(q8))
    assert calls == ["_flash_fwd", "_oneshot_fwd", "_oneshot_bwd",
                     "_flash_bwd", "_stream_bwd", "_flash_bwd",
                     "_flash_bwd"], calls


def test_stream_bwd_plan_thresholds():
    """Lock the streaming-backward admission map (r6): engages only where
    the dense one-shot bwd plan is infeasible AND D=128 (the dedicated
    long-context round; PDTX_STREAM_BWD="all" widens, "0" kills)."""
    # S=8192: the byte model admitted (1, 256, 512), which the v5e compiler
    # counts at 16.75 MB and more against 16 MB of scoped VMEM — refused on
    # the chip, so not admitted (tests/test_chip_compile.py compiles it).
    assert F._stream_bwd_plan(16, 8192, 8192, 128) is None
    # S=4096/D=128 (bwd one-shot infeasible there too): the largest admitted
    assert F._stream_bwd_plan(16, 4096, 4096, 128) == (1, 512, 512)
    assert F._stream_bwd_plan(8, 2048, 2048, 128) == (2, 256, 512)
    # D=64 keeps the measured online fallback unless widened explicitly
    assert F._stream_bwd_plan(16, 4096, 4096, 64) is None
    assert F._stream_bwd_plan(16, 4096, 4096, 64, mode="all") == (1, 512, 512)
    assert F._stream_bwd_plan(16, 8192, 8192, 64, mode="all") is None
    # kill switch
    assert F._stream_bwd_plan(16, 8192, 8192, 128, mode="0") is None
    # sub-chunk sequences have nothing to stream
    assert F._stream_bwd_plan(16, 512, 512, 128) is None


@pytest.mark.parametrize("causal", [False, True])
def test_stream_bwd_parity_d128_interpret(causal):
    """D=128 streaming one-pass backward vs the oracle VJP at S=2048
    (direct call: at this S auto dispatch still picks the dense one-shot
    bwd, but the kernel must be exact wherever its plan admits).
    Tolerances match the D=64 chunked-bwd assertions."""
    q, k, v = _qkv(B=1, S=2048, H=2, D=128)
    plan = F._stream_bwd_plan(2, 2048, 2048, 128)
    assert plan is not None
    g = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)
    ref, vjp = jax.vjp(
        lambda *a: A.dot_product_attention(*a, causal=causal), q, k, v)
    g_ref = vjp(g)
    with pltpu.force_tpu_interpret_mode():
        out, lse = F._flash_fwd(q, k, v, causal=causal,
                                block_q=512, block_kv=512)
        g_out = F._stream_bwd(q, k, v, out, lse, g, causal=causal, plan=plan)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-4, atol=1e-4)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # interpret-mode S=4096: minutes on the CPU CI host
@pytest.mark.parametrize("causal", [False, True])
def test_stream_bwd_parity_s4096_interpret(causal):
    """The largest shape the chip admits (S=4096, D=128), end to end
    (S=8192's plan is over the v5e's scoped VMEM and no longer admitted)."""
    q, k, v = _qkv(B=1, S=4096, H=1, D=128)
    plan = F._stream_bwd_plan(1, 4096, 4096, 128)
    assert plan == (1, 512, 512)
    g = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)
    ref, vjp = jax.vjp(
        lambda *a: A.dot_product_attention(*a, causal=causal), q, k, v)
    g_ref = vjp(g)
    with pltpu.force_tpu_interpret_mode():
        out, lse = F._flash_fwd(q, k, v, causal=causal,
                                block_q=1024, block_kv=1024)
        g_out = F._stream_bwd(q, k, v, out, lse, g, causal=causal, plan=plan)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_stream_bwd_auto_path_gqa_grads_interpret(monkeypatch):
    """End to end through flash_attention's custom VJP: when the one-shot
    bwd plan is infeasible and the streaming plan admits, auto grads route
    through the streaming backward — including the GQA head fold."""
    monkeypatch.setattr(F, "_oneshot_plan", lambda *a, **k: None)
    monkeypatch.setattr(F, "STREAM_BWD", "all")  # small-D test shape
    q, k, v = _qkv(B=1, S=1024, H=4, Hkv=2, D=16)
    assert F._stream_bwd_plan(4, 1024, 1024, 16) is not None
    g_ref = jax.grad(lambda *a: A.dot_product_attention(*a, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        g_out = jax.grad(
            lambda *a: F.flash_attention(*a, True, 512, 512, "auto").sum(),
            argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_padded_flash_eligibility_gates():
    """auto uses the padded path only at >=1024 padded tokens (ViT's 197
    measured slower through it); explicit use allows any plannable shape."""
    q = jnp.zeros((2, 197, 12, 64), jnp.bfloat16)
    if jax.default_backend() == "cpu":
        assert not A._padded_flash_eligible(q, q, explicit=False)
        assert not A._padded_flash_eligible(q, q)  # CPU: never
    # pure-shape logic (backend-independent pieces)
    assert A._round_up(197, A.PAD_MULTIPLE) == 256
    assert A._round_up(1024, A.PAD_MULTIPLE) == 1024


# ---------------------------------------------------------------------------
# The online kernels' causal schedule (ops/flash_attention.online_schedule):
# the blocks above the diagonal are no step and no copy, the blocks below it
# take no mask, the blocks it crosses are computed in sub-tiles.
# ---------------------------------------------------------------------------

import flash_online_parent as parent_kernels  # noqa: E402  (tests/: the oracle)

# (S, block_q, block_kv) -> per kernel (fwd, dq, dkv) where they differ:
# sub, steps, computed, masked, skipped_subtiles, fetched, pairs_computed
SCHEDULE_CASES = {
    # GLM-4.7-Flash's forward: 28 of the rectangle's 64 are never a step
    (8192, 1024, 1024): dict(sub=512, rectangle=64, steps=36, computed=36,
                             masked=8, skipped_subtiles=8, fetched=35,
                             pairs_computed=34 << 20),
    # its backward, ONLINE_BLOCK_TABLE's row: the wholly masked quarter
    (8192, 512, 1024): dict(sub=512, rectangle=128, steps=72, computed=72,
                            masked=16, skipped_subtiles=8,
                            fetched=(70, 70, 72), pairs_computed=34 << 20),
    (8192, 1024, 512): dict(sub=512, rectangle=128, steps=72, computed=72,
                            masked=16, skipped_subtiles=8,
                            fetched=(72, 72, 70), pairs_computed=34 << 20),
    # Granite's one attention layer
    (4096, 1024, 1024): dict(sub=512, rectangle=16, steps=10, computed=10,
                             masked=4, skipped_subtiles=4, fetched=9,
                             pairs_computed=9 << 20),
    # 640 = 5 x 128 has no sub-tile of whole lanes within four stripes
    (2560, 640, 640): dict(sub=0, rectangle=16, steps=10, computed=10,
                           masked=4, skipped_subtiles=0, fetched=9,
                           pairs_computed=10 * 640 * 640),
    # one block: nothing to skip, the diagonal's sub-tiles alone
    (1024, 1024, 1024): dict(sub=512, rectangle=1, steps=1, computed=1,
                             masked=1, skipped_subtiles=1, fetched=1,
                             pairs_computed=3 << 18),
}


def _coverage(plan):
    """From the schedule's steps and tiles, over cells of 128 x 128 (or the
    sub-tile, where that is narrower): how often each is computed, and
    whether the schedule holds it visible (a tile without a mask says all of
    it is; a masked square says its lower triangle is)."""
    cell = min(128, plan.sub or 128, plan.block_q, plan.block_kv)
    n_r, n_c = plan.sq // cell, plan.skv // cell
    times = np.zeros((n_r, n_c), int)
    whole = np.zeros((n_r, n_c), bool)      # every pair of the cell visible
    some = np.zeros((n_r, n_c), bool)       # a pair of the cell visible
    tri = np.tril(np.ones((n_r, n_c), bool))
    for qi, kvi in plan.steps:
        d = plan.offset(qi, kvi)
        if plan.causal and d <= -plan.block_q:
            continue
        if not plan.causal or d >= plan.block_kv - 1:
            tiles = [((0, plan.block_q), (0, plan.block_kv),
                      None if plan.split or not plan.causal else "whole")]
        else:
            tiles = plan.tiles(d)[0]
        for (r0, r1), (c0, c1), square in tiles:
            rows = slice((qi * plan.block_q + r0) // cell,
                         (qi * plan.block_q + r1) // cell)
            cols = slice((kvi * plan.block_kv + c0) // cell,
                         (kvi * plan.block_kv + c1) // cell)
            times[rows, cols] += 1
            if square is None:
                whole[rows, cols] = some[rows, cols] = True
                continue
            some[rows, cols] |= tri[rows, cols]     # the mask is by position
            if square != "whole":
                # outside its square on the diagonal the tile has no mask
                (r, c), far = square
                assert far is None
                sr = rows.start + r // cell
                sc = cols.start + c // cell
                n = plan.sub // cell
                held = np.ones((rows.stop - rows.start,
                                cols.stop - cols.start), bool)
                held[sr - rows.start:sr - rows.start + n,
                     sc - cols.start:sc - cols.start + n] = False
                whole[rows, cols] |= held
                some[rows, cols] |= held
                # the square itself is the canonical triangle: on the diagonal
                assert sr == sc, (qi, kvi, square)
    return times, whole, some


@pytest.mark.parametrize("kernel", F.ONLINE_KERNELS)
@pytest.mark.parametrize("case", list(SCHEDULE_CASES), ids=lambda c: "S%d-%dx%d" % c)
def test_online_schedule_counts_and_coverage(case, kernel):
    S, bq, bkv = case
    want = dict(SCHEDULE_CASES[case])
    at = F.ONLINE_KERNELS.index(kernel)
    want = {k: v[at] if isinstance(v, tuple) else v for k, v in want.items()}
    plan = F.online_schedule(kernel, True, S, S, bq, bkv)
    got = dict(plan.counts(), sub=plan.sub)
    assert {k: got[k] for k in want} == want
    assert got["pairs_needed"] == S * (S + 1) // 2
    assert plan.walk and got["steps"] == got["computed"]
    times, whole, some = _coverage(plan)
    lower = np.tril(np.ones(times.shape, bool))
    # every pair the mask leaves is computed exactly once, none twice
    assert times.max() == 1 and (times[lower] == 1).all()
    assert (some == lower).all()
    # and no pair is taken for visible that is not: a cell without a mask
    # lies strictly below the diagonal
    assert (whole <= np.tril(lower, -1)).all()


def _pair_coverage(plan):
    """The schedule read pair by pair: how often each (row, key) is computed,
    which pairs its masks leave visible, the computed tiles in the sequence's
    own coordinates as ``(rows, cols, squares)``, and the same for the masked
    ``sub``-squares."""
    times = np.zeros((plan.sq, plan.skv), np.int8)
    seen = np.zeros((plan.sq, plan.skv), bool)
    gap = np.arange(plan.sq)[:, None] - np.arange(plan.skv)[None, :]
    by_position = (gap >= 0) & (gap < (plan.window or plan.sq + plan.skv))
    tiles, masked = [], []
    for qi, kvi in plan.steps:
        inside, crossed = plan.kind(plan.offset(qi, kvi))
        if not plan.causal or inside:
            block = [((0, plan.block_q), (0, plan.block_kv),
                      None if plan.split or not plan.causal else "whole")]
        elif crossed:
            block = plan.tiles(plan.offset(qi, kvi))[0]
        else:
            continue        # a step of the rectangle that computes nothing
        for (r0, r1), (c0, c1), squares in block:
            r0, r1 = qi * plan.block_q + r0, qi * plan.block_q + r1
            c0, c1 = kvi * plan.block_kv + c0, kvi * plan.block_kv + c1
            tiles.append(((r0, r1), (c0, c1), squares))
            times[r0:r1, c0:c1] += 1
            if squares == "whole":
                seen[r0:r1, c0:c1] = by_position[r0:r1, c0:c1]
                continue
            seen[r0:r1, c0:c1] = True
            low = np.tril(np.ones((plan.sub, plan.sub), bool))
            for corner, keeps in zip(squares or (), (low, ~low)):
                if corner:
                    r, c = r0 + corner[0], c0 + corner[1]
                    seen[r:r + plan.sub, c:c + plan.sub] = keeps
                    masked.append((r, c))
    return times, seen, by_position, tiles, masked


# (S, window, block_q, block_kv, SUB_OFFSETS_MAX): what the interpret tests of
# tests/test_afmoe.py run, and the geometry's corners
WINDOW_SCHEDULES = [
    (256, 100, 64, 64, 2),      # no multiple of the block: three offsets, whole
    (512, 129, 128, 128, 2),    # one key past a block's edge
    (256, 1, 64, 64, 2),        # a row sees itself alone
    (256, 64, 64, 64, 2),       # the window is one block
    (1024, 512, 256, 256, 2),   # sub-tiles of 128 on both edges
    (1024, 256, 256, 256, 2),   # the far edge lies in the diagonal's neighbour
    (1024, 128, 256, 256, 2),   # narrower than a block: one block, both edges
    (2048, 1024, 512, 512, 2),  # sub-tiles of 256
    (1024, 384, 256, 256, 2),   # whole sub-tiles, no whole blocks: three offsets
    (1024, 1, 256, 256, 2),     # one offset, and no corner of a sub-tile
    (1024, 512, 256, 512, 2),   # unequal blocks: four offsets, whole
    (1024, 512, 512, 256, 2),
    (1024, 512, 256, 512, 4),   # the same under a cap that admits them
    (1024, 512, 512, 256, 4),
    (1536, 768, 384, 384, 2),   # three stripes a block
    (512, None, 128, 256, 2),   # no window: the one edge, as it was
    (1024, None, 256, 256, 2),
]


@pytest.mark.parametrize("kernel", F.ONLINE_KERNELS)
@pytest.mark.parametrize("S,window,bq,bkv,cap", WINDOW_SCHEDULES, ids=lambda v: str(v))
def test_online_schedule_with_a_window_covers_the_mask(monkeypatch, S, window,
                                                       bq, bkv, cap, kernel):
    """Every visible pair lies in exactly one computed tile, no computed tile
    lies wholly outside the mask, and a ``sub``-square is under a mask exactly
    where an edge passes through it."""
    monkeypatch.setattr(F, "SUB_OFFSETS_MAX", cap)
    plan = F.online_schedule(kernel, True, S, S, bq, bkv, window=window)
    times, seen, visible, tiles, masked = _pair_coverage(plan)
    assert times.max() == 1 and (times[visible] == 1).all()
    assert (seen == visible).all()
    for (r0, r1), (c0, c1), _ in tiles:
        assert visible[r0:r1, c0:c1].any(), ((r0, r1), (c0, c1))
    counts = plan.counts()
    assert counts["pairs_computed"] == int(times.sum())
    assert counts["pairs_needed"] == int(visible.sum())
    assert counts["steps"] == counts["computed"] == len(
        {(r[0] // bq, c[0] // bkv) for r, c, _ in tiles})
    if not plan.sub:
        assert all(squares == "whole" or squares is None
                   for _, _, squares in tiles)
        return
    sub = plan.sub
    crossed_by_an_edge = {
        (r, c) for (r0, r1), (c0, c1), _ in tiles
        for r in range(r0, r1, sub) for c in range(c0, c1, sub)
        if not visible[r:r + sub, c:c + sub].all()}
    assert sorted(masked) == sorted(crossed_by_an_edge)


@pytest.mark.parametrize("S,window,bq,bkv,sub,offsets", [
    (1024, 512, 256, 256, 128, (0, 512)), (1024, 128, 256, 256, 128, (0, 256)),
    (2048, 1024, 512, 512, 256, (0, 1024)), (1024, 1, 256, 256, 0, (0,)),
    (1024, 384, 256, 256, 0, (0, 256, 512)), (256, 100, 64, 64, 0, (0, 64, 128)),
    (1024, 512, 256, 512, 0, (0, 256, 512, 768)),
    (8192, 2048, 512, 1024, 0, (0, 512, 2048, 2560))])
def test_online_schedule_sub_tiles_engage_over_both_edges(S, window, bq, bkv,
                                                          sub, offsets):
    """PR 42's rule read over both edges: ``sub`` divides both blocks, the
    window and every crossed offset, of which there are at most two."""
    for kernel in F.ONLINE_KERNELS:
        plan = F.online_schedule(kernel, True, S, S, bq, bkv, window=window)
        assert (plan.sub, plan.offsets) == (sub, offsets), kernel
        assert plan.name == F.WINDOW_KERNELS[F.ONLINE_KERNELS.index(kernel)]


@pytest.mark.parametrize("kernel", F.ONLINE_KERNELS)
@pytest.mark.parametrize("window,steps,computed,needed", [
    (2048, 21, 18_350_080, 14_681_088),     # Trinity-Mini: 1 + 2 + 6 x 3
    (4096, 30, 28_311_552, 25_167_872)],    # SmallThinker: 1 + 2 + 3 + 4 + 4 x 5
    ids=["trinity_mini", "smallthinker_21b"])
def test_online_schedule_counts_at_the_published_windows(window, steps,
                                                         computed, needed, kernel):
    """B1 S8192 D128 at the blocks dispatch picks (1024 x 1024): three steps a
    1024 rows where the 512-blocks took ten (five where they took eighteen),
    the pairs those computed, and ``W*S - W*(W-1)/2`` needed."""
    bwd = kernel != "flash_fwd_online"
    blocks = F._online_blocks(bwd, 8192, 128, F.DEFAULT_BLOCK_Q,
                              F.DEFAULT_BLOCK_KV, 2, window)
    assert blocks == (1024, 1024)
    plan = F.online_schedule(kernel, True, 8192, 8192, *blocks, window=window)
    assert (plan.sub, plan.offsets) == (512, (0, window))
    counts = plan.counts()
    assert (counts["steps"], counts["pairs_computed"],
            counts["pairs_needed"]) == (steps, computed, needed)
    assert needed == window * 8192 - window * (window - 1) // 2
    record = plan.record(128)
    assert (record["kernel"], record["window"]) == (plan.name, window)
    assert record["fetched"] == steps - 1 and record["rectangle"] == 64


with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "online_schedule_parent.json")) as _f:
    PARENT_SCHEDULES = json.load(_f)


@pytest.mark.parametrize("golden", PARENT_SCHEDULES,
                         ids=lambda g: "%s-%s" % (g["cell"], g["kernel"]))
def test_online_schedule_without_a_window_is_the_parents(golden):
    """With ``window=None`` the schedule of each online call the cells make
    (GLM's, Granite's, Nemotron's, Trinity's and SmallThinker's full layer)
    is what it was before the window became its second edge, field for field
    (tests/fixtures/online_schedule_parent.json, taken from commit 456c3a6)."""
    S, D, kernel = golden["S"], golden["D"], golden["kernel"]
    blocks = F._online_blocks(kernel != "flash_fwd_online", S, D,
                              F.DEFAULT_BLOCK_Q, F.DEFAULT_BLOCK_KV, 2)
    plan = F.online_schedule(kernel, True, S, S, *blocks)
    assert plan.window is None and plan.name == kernel
    got = dict(block_q=plan.block_q, block_kv=plan.block_kv, walk=plan.walk,
               split=plan.split, sub=plan.sub,
               steps=[list(s) for s in plan.steps],
               offsets=list(plan.offsets), counts=plan.counts())
    assert got == {k: golden[k] for k in got}
    for d in plan.offsets:
        tiles, skipped = plan.tiles(d)
        assert all(squares[1] is None for _, _, squares in tiles if squares)
        # the parent named a tile's one square by its corner
        assert [[list(r), list(c), list(squares[0]) if squares else None]
                for r, c, squares in tiles] + [skipped] == golden["tiles"][str(d)]


@pytest.mark.parametrize("kernel", F.ONLINE_KERNELS)
def test_online_schedule_not_causal_is_the_rectangle(kernel):
    plan = F.online_schedule(kernel, False, 4096, 8192, 1024, 512)
    assert (plan.walk, plan.split, plan.sub) == (False, False, 0)
    assert plan.counts() == dict(
        rectangle=64, steps=64, computed=64, masked=0, skipped_subtiles=0,
        fetched=64, pairs_computed=4096 * 8192, pairs_needed=4096 * 8192)
    times, whole, _ = _coverage(plan)
    assert (times == 1).all() and whole.all()


def test_online_schedule_keys_past_the_last_query_keep_a_step():
    """Skv > Sq under the causal mask: the kv blocks no query row sees are
    one empty step each in the dk/dv pass, whose init and finish write the
    zeros; the other passes never visit them."""
    plans = [F.online_schedule(k, True, 256, 512, 128, 128)
             for k in F.ONLINE_KERNELS]
    assert [p.counts()["steps"] for p in plans] == [3, 3, 5]
    assert [p.counts()["computed"] for p in plans] == [3, 3, 3]
    assert plans[2].steps[-2:] == ((1, 2), (1, 3))
    q, k, v = _qkv(B=1, S=256, H=2, D=64)
    k, v = (jnp.concatenate([x, x], axis=1) for x in (k, v))
    g = jnp.ones_like(q)
    with pltpu.force_tpu_interpret_mode():
        o, lse = F._flash_fwd(q, k, v, causal=True, block_q=128, block_kv=128)
        o0, _ = parent_kernels._flash_fwd(q, k, v, causal=True, block_q=128,
                                          block_kv=128)
        grads = F._flash_bwd(q, k, v, o, lse, g, causal=True, block_q=128,
                             block_kv=128)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o0))
    assert not np.asarray(grads[1][:, 256:]).any()
    assert not np.asarray(grads[2][:, 256:]).any()


@pytest.mark.parametrize("kernel", F.ONLINE_KERNELS)
def test_online_schedule_rows_past_the_last_key_see_every_block(kernel):
    """Sq > Skv under the causal mask and no window: nothing lies behind a
    row, however far below the diagonal its block is."""
    plan = F.online_schedule(kernel, True, 1024, 256, 128, 128)
    counts = plan.counts()
    assert (counts["steps"], counts["computed"], counts["masked"]) == (15, 15, 2)
    times, seen, visible, _, _ = _pair_coverage(plan)
    assert times.max() == 1 and (times[visible] == 1).all()
    assert (seen == visible).all()


ONLINE_PARITY = [
    # S, block_q, block_kv, D, H, Hkv, causal
    (512, 128, 256, 64, 4, 1, True),     # two offsets, rows in one stripe
    (512, 128, 256, 128, 2, 2, True),
    (512, 128, 256, 256, 4, 1, True),
    (512, 256, 128, 64, 2, 2, True),     # a stripe of rows sees no key
    (512, 256, 128, 128, 4, 1, True),
    (512, 256, 128, 256, 2, 2, True),
    (1024, 256, 256, 128, 4, 1, True),   # 4 x 4 blocks, sub-tile 128
    (512, 512, 512, 64, 2, 2, True),     # one block: the sub-tiles alone
    (768, 384, 384, 64, 2, 2, True),     # sub-tile 128 in three stripes
    (1024, 512, 512, 64, 2, 2, True),    # sub-tile 256 in two
    (512, 128, 256, 64, 4, 1, False),
    (512, 256, 128, 128, 2, 2, False),
    (512, 256, 256, 256, 4, 1, False),
]


@pytest.mark.parametrize("S,bq,bkv,D,H,Hkv,causal", ONLINE_PARITY)
def test_online_schedule_parity_interpret(S, bq, bkv, D, H, Hkv, causal):
    """Forward and all three gradients through ``flash_attention(...,
    "online")`` against the XLA oracle, at blocks that reach every body:
    the unmasked one, each offset's stripes, a skipped sub-tile."""
    q, k, v = _qkv(B=1, S=S, H=H, Hkv=Hkv, D=D)
    w = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)
    ref = A.dot_product_attention(q, k, v, causal=causal)
    g_ref = jax.grad(lambda *a: (A.dot_product_attention(*a, causal=causal)
                                 * w).sum(), argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        out = F.flash_attention(q, k, v, causal, bq, bkv, "online")
        g_out = jax.grad(lambda *a: (F.flash_attention(
            *a, causal, bq, bkv, "online") * w).sum(),
            argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("qk,dv,Hkv,scale", [
    (192, 128, 2, None),        # latent attention's 128 + 64 over 128
    (192, 128, 1, 0.14468),     # with YaRN's factor on the scores, one KV head
    (64, 128, 2, None),         # a value width over the query/key width
])
def test_online_kernels_at_unequal_widths_match_the_oracle_interpret(
        qk, dv, Hkv, scale):
    """q and k ``qk`` wide, v ``dv`` wide: forward and all three gradients,
    causal, through ``flash_attention`` under ``auto`` (which sends such a call
    to the online kernels: no other family holds two widths) against the XLA
    oracle, at blocks that reach the unmasked body and the sub-tiles."""
    r = np.random.RandomState(3)
    q = jnp.asarray(r.randn(1, 512, 2, qk), jnp.float32)
    k = jnp.asarray(r.randn(1, 512, Hkv, qk), jnp.float32)
    v = jnp.asarray(r.randn(1, 512, Hkv, dv), jnp.float32)
    w = jnp.asarray(r.randn(1, 512, 2, dv), jnp.float32)
    oracle = lambda *a: A.dot_product_attention(*a, causal=True, scale=scale)
    flash = lambda *a: F.flash_attention(*a, True, 256, 256, "auto", None,
                                         None, scale)
    ref = oracle(q, k, v)
    g_ref = jax.grad(lambda *a: (oracle(*a) * w).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    with pltpu.force_tpu_interpret_mode():
        out = flash(q, k, v)
        g_out = jax.grad(lambda *a: (flash(*a) * w).sum(),
                         argnums=(0, 1, 2))(q, k, v)
    assert out.shape == (1, 512, 2, dv)
    assert [g.shape for g in g_out] == [q.shape, k.shape, v.shape]
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_unequal_widths_go_to_the_online_kernels_and_nowhere_else(monkeypatch):
    """Dispatch: a value width that differs, or a scale of the caller's, is
    the online kernels' in both directions whatever ``auto`` would pick at
    equal widths; the families that hold one width refuse it by name; the
    block plan and the recorder key the pair."""
    calls = _stub_flash_kernels(monkeypatch)
    q = jnp.zeros((1, 1024, 12, 64), jnp.bfloat16)     # auto: the causal pair
    v = jnp.zeros((1, 1024, 12, 128), jnp.bfloat16)
    F._fwd_dispatch(q, q, q, True, 1024, 1024, "auto", None)
    assert calls == ["_causal_fwd"]
    del calls[:]
    F._fwd_dispatch(q, q, v, True, 1024, 1024, "auto", None)
    F._vjp_bwd(True, 1024, 1024, "auto", None, None, (q, q, v, "o", "l"), v)
    F._fwd_dispatch(q, q, q, True, 1024, 1024, "auto", None, None, 0.2)
    F._vjp_bwd(True, 1024, 1024, "auto", None, None, (q, q, q, "o", "l"), q,
               scale=0.2)
    assert calls == ["_flash_fwd", "_flash_bwd"] * 2, calls
    for impl, kv_len in (("oneshot", None), ("auto", 197)):
        with pytest.raises(ValueError, match="one head width"):
            F._fwd_dispatch(q, q, v, False, 1024, 1024, impl, kv_len)
    with pytest.raises(ValueError, match="context-parallel"):
        A.attention(q, q, v, causal=True, impl="ring")
    # the plan at the pair: the parent's formulas at equal widths, the pair's
    # own rows where they differ
    for bwd in (False, True):
        for d in (64, 128, 256):
            assert F._online_held(bwd, 512, 1024, (d, d), 2) == \
                F._online_held(bwd, 512, 1024, d, 2)
    assert F._online_held(False, 512, 1024, 256, 2) == 256 * (
        2 * 4 * (512 + 1024) + 4 * 512)
    assert F._online_held(True, 512, 1024, 256, 2) == max(
        256 * (2 * (6 * 512 + 4 * 1024) + 4 * 512),
        256 * (2 * (4 * 512 + 8 * 1024) + 8 * 1024))
    assert F._online_held(False, 1024, 1024, (192, 128), 2) == (
        2 * 2 * 2048 * 320 + 4 * 1024 * 128)
    monkeypatch.setitem(F.ONLINE_BLOCK_TABLE, (True, 2048, (192, 128)),
                        (512, 1024))
    assert F._online_blocks(True, 2048, (192, 128), 1024, 1024, 2) == \
        (512, 1024)
    assert F._online_blocks(True, 2048, 192, 1024, 1024, 2) == (1024, 1024)
    plan = F.online_schedule("flash_bwd_dq", True, 2048, 2048, 1024, 1024)
    assert (plan.record((192, 128))["D"], plan.record((192, 128))["Dv"],
            "Dv" in plan.record(128)) == (192, 128, False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_equal_widths_are_the_parents_kernels_bitwise(dtype):
    """At one width nothing changed: the kernels that now block q, k and v at
    their own widths, with the scale left to them or given as ``1 / sqrt(D)``,
    give the results of the kernels as they stood (``flash_online_parent``)
    bit for bit: o, lse, dq in interpret mode, dk and dv where the
    interpreter's dot adds in the kernel's order."""
    for scale in (None, 1.0 / np.sqrt(64.0)):
        new, old = _both_online(512, 256, 256, dtype=dtype, walk=False,
                                split=False, sub=0, scale=scale)
        for got, want in zip(new, old):
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))


def _both_online(S, bq, bkv, D=64, H=2, causal=True, dtype=jnp.float32,
                 **parts):
    """(o, lse, dq, dk, dv) of the scheduled kernels and of the kernels as
    they stood before the schedule, on the same operands."""
    r = np.random.RandomState(7)
    q, k, v, g = (jnp.asarray(r.randn(1, S, H, D), dtype) for _ in range(4))
    blocks = dict(causal=causal, block_q=bq, block_kv=bkv)
    with pltpu.force_tpu_interpret_mode():
        o0, l0 = parent_kernels._flash_fwd(q, k, v, **blocks)
        o1, l1 = F._flash_fwd(q, k, v, **blocks, **parts)
        g0 = parent_kernels._flash_bwd(q, k, v, o0, l0, g, **blocks)
        g1 = F._flash_bwd(q, k, v, o0, l0, g, **blocks, **parts)
    return (o1, l1, *g1), (o0, l0, *g0)


@pytest.mark.parametrize("S,bq,bkv", [(512, 128, 256), (512, 256, 128),
                                      (512, 256, 256), (768, 384, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_online_schedule_is_the_rectangle_bitwise(S, bq, bkv, dtype):
    """"Only exact zeros went": the scheduled kernels give the results of the
    whole rectangle under a mask everywhere, bit for bit. The forward and dq
    stripes cut the rows they write and drop a contraction's trailing zeros;
    the dk/dv stripes cut the columns they write and drop its LEADING zeros,
    which XLA's CPU dot (the interpreter's) does not add in the order of the
    longer one: there dk and dv are held to a rounding of the sum, and the
    test below holds the dropped terms to zero. On the chip
    ``benchmarks/flash_micro.py --schedule-parts`` compares all five."""
    got, want = _both_online(S, bq, bkv, dtype=dtype)
    for name, a, b in zip(("o", "lse", "dq"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    plan = F.online_schedule("flash_bwd_dkv", True, S, S, bq, bkv)
    assert plan.sub
    whole_rows = all(rows[0] == 0 for d in plan.offsets
                     for rows, _, _ in plan.tiles(d)[0])
    for name, a, b in zip(("dk", "dv"), got[3:], want[3:]):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        if whole_rows:
            np.testing.assert_array_equal(a, b, name)
        else:
            eps = float(jnp.finfo(dtype).eps)
            np.testing.assert_allclose(a, b, rtol=2 * eps,
                                       atol=eps * np.abs(b).max(), err_msg=name)


@pytest.mark.parametrize("parts", [
    dict(walk=False, split=False, sub=0),       # the schedule before
    dict(split=False, sub=0), dict(walk=False, sub=0),
    dict(walk=False, split=False), dict(sub=0)],
    ids=lambda p: "-".join("%s=%s" % kv for kv in p.items()))
def test_online_schedule_parts_alone_bitwise(parts):
    """Each part of the schedule switched on alone (what ``flash_micro.py
    --schedule-parts`` times) gives the rectangle's results bit for bit; at
    (128, 256) the dk/dv stripes keep every row, so dk and dv too."""
    got, want = _both_online(512, 128, 256, **parts)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


def test_online_masked_terms_are_exact_zeros():
    """What the sub-tiles leave out of a crossed block, the rectangle's
    kernels add as exact zeros: a masked probability is exp(NEG_INF - m) =
    0.0 whatever finite m, and dS = P * (dP - delta) * scale is 0.0 with it."""
    r = np.random.RandomState(3)
    q, k, v, do = (jnp.asarray(r.randn(256, 64) * 4, jnp.float32)
                   for _ in range(4))
    scale = 1 / 8
    s = (q @ k.T) * scale
    masked = jnp.where(np.tril(np.ones((256, 256), bool)), s, F.NEG_INF)
    m = masked.max(axis=1, keepdims=True)
    p_fwd = jnp.exp(masked - m)
    lse = m + jnp.log(p_fwd.sum(axis=1, keepdims=True))
    p = jnp.exp(masked - lse)
    delta = jnp.sum(do * (p @ v), axis=1, keepdims=True)
    ds = p * (do @ v.T - delta) * scale
    above = ~np.tril(np.ones((256, 256), bool))
    for name, x in (("forward p", p_fwd), ("backward p", p), ("dS", ds)):
        assert not np.asarray(x)[above].any(), name
    # a running max that another block has raised, or not yet: still 0.0
    for m_prev in (-50.0, 0.0, 80.0):
        assert float(jnp.exp(jnp.float32(F.NEG_INF) - m_prev)) == 0.0


def _online_kernel_jaxprs(mod, causal):
    def calls(q, k, v, g):
        o, lse = mod._flash_fwd(q, k, v, causal=causal, block_q=1024,
                                block_kv=512)
        return o, mod._flash_bwd(q, k, v, o, lse, g, causal=causal,
                                 block_q=512, block_kv=1024)
    x = jax.ShapeDtypeStruct((1, 2048, 4, 128), jnp.bfloat16)
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grid = eqn.params["grid_mapping"]
                found.append((
                    eqn.params["name"], str(eqn.params["jaxpr"]), grid.grid,
                    [(str(b.index_map_jaxpr), str(b.block_shape))
                     for b in grid.block_mappings],
                    str(eqn.params["compiler_params"])))
            elif "jaxpr" in eqn.params:     # the launchers are jitted
                walk(eqn.params["jaxpr"].jaxpr)

    walk(jax.make_jaxpr(calls)(x, x, x, x).jaxpr)
    return found


def test_online_not_causal_lowers_to_the_kernels_before_the_schedule():
    """``causal=False`` skips no block and masks none: the three calls' kernel
    jaxprs, grids, index maps and compiler parameters are the earlier
    kernels', text for text (what Mosaic is handed)."""
    got = _online_kernel_jaxprs(F, False)
    want = _online_kernel_jaxprs(parent_kernels, False)
    assert [c[0] for c in got] == list(F.ONLINE_KERNELS)
    assert got == want
    # and the causal calls do differ: the walked grid is one dimension less
    causal = _online_kernel_jaxprs(F, True)
    assert [len(c[2]) for c in causal] == [3, 3, 3]
    assert [c[0] for c in causal] == list(F.ONLINE_KERNELS)


def test_flash_schedule_record_under_the_span_that_traced():
    """One ``flash_schedule`` record a traced call and kernel, a child of the
    span open on the tracing thread, with the counts the schedule test pins
    at GLM-4.7-Flash's shape."""
    from pytorch_distributed_training_example_tpu.utils import telemetry

    rec = telemetry.recorder()
    mark = len(rec.records())
    x = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.bfloat16)
    with rec.span("trace_here", bucket=None):
        jax.eval_shape(jax.grad(
            lambda q, k, v: F.flash_attention(q, k, v, True).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)), x, x, x)
    new = rec.records()[mark:]
    span = next(r for r in new if r.kind == "span" and r.name == "trace_here")
    said = [r for r in new if r.name == "flash_schedule"]
    assert all(r.kind == "compile" and r.parent == span.id for r in said)
    by_kernel = {r.value["kernel"]: r.value for r in said}
    assert sorted(by_kernel) == sorted(F.ONLINE_KERNELS)
    fwd = by_kernel["flash_fwd_online"]
    assert (fwd["Sq"], fwd["D"], fwd["block_q"], fwd["block_kv"]) == (
        8192, 256, 1024, 1024)
    assert {k: fwd[k] for k in ("rectangle", "steps", "computed", "masked",
                                "skipped_subtiles", "pairs_computed")} == {
        "rectangle": 64, "steps": 36, "computed": 36, "masked": 8,
        "skipped_subtiles": 8, "pairs_computed": 34 << 20}
    for name in F.ONLINE_KERNELS[1:]:
        bwd = by_kernel[name]
        assert (bwd["block_q"], bwd["block_kv"]) == F.ONLINE_BLOCK_TABLE[
            True, 8192, 256]
        assert bwd["steps"] == bwd["computed"] < bwd["rectangle"]
    assert "flash_schedule" in telemetry.COMPILE_RECORDS


@pytest.mark.parametrize("H,window,steps,computed,needed", [
    (32, 2048, 21, 18_350_080, 14_681_088),
    (28, 4096, 30, 28_311_552, 25_167_872)],
    ids=["trinity_mini", "smallthinker_21b"])
def test_flash_schedule_record_of_a_window_call(H, window, steps, computed,
                                                needed):
    """A traced window call at the published shapes says one record a kernel,
    under the names the calls carry, with the window and the counts under it:
    the run's own word that the 1024-blocks and both edges' sub-tiles engaged."""
    from pytorch_distributed_training_example_tpu.utils import telemetry

    rec = telemetry.recorder()
    mark = len(rec.records())
    q = jax.ShapeDtypeStruct((1, 8192, H, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16)
    jax.eval_shape(jax.grad(
        lambda q, k, v: F.flash_attention(q, k, v, True, window=window).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    said = {r.value["kernel"]: r.value for r in rec.records()[mark:]
            if r.name == "flash_schedule"}
    assert sorted(said) == sorted(F.WINDOW_KERNELS)
    for value in said.values():
        assert {k: value[k] for k in (
            "window", "block_q", "block_kv", "sub", "steps", "pairs_computed",
            "pairs_needed")} == dict(
            window=window, block_q=1024, block_kv=1024, sub=512, steps=steps,
            pairs_computed=computed, pairs_needed=needed)

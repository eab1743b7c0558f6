"""The ragged grouped-matmul kernel's sorted-rows entry points.

Kernel parity (interpret mode off-TPU, so the real Pallas kernel bodies
run): ``gmm`` / ``grouped_ffn`` forward and custom_vjp grads against a dense
segment-einsum reference, across uneven / empty / single-expert-takes-all
segments, E in {2, 8}, fp32 and bf16. The expert layer (``parallel/moe.py``)
calls the same kernels through the padded-layout forms (``_gmm_padded``,
``FFN_FORMS``), which the model families' own tests cover.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.ops import (
    grouped_matmul as gmm_lib)

D = 16


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


# -- the gated FFN's own kernels (gated_ffn_up / _down / _dh / _dx / _dw_up /
# -- _dw_down) against the three-call composition they replaced


def _composed_kept(x_pad, w_gate, w_up, w_down, tiles, act):
    """The gated FFN as three ``grouped_matmul`` calls with XLA's ``_gated``
    between them: what ``gated_ffn_padded_kept`` was before its kernels."""
    gate = gmm_lib._gmm_padded(x_pad, w_gate, tiles)
    up = gmm_lib._gmm_padded(x_pad, w_up, tiles)
    return (gmm_lib._gmm_padded(gmm_lib._gated(gate, up, act), w_down, tiles),
            gate, up)


def _gated_case(dtype, f, seed=0):
    """A padded layout of ragged counts with an empty expert, rows that no
    held expert owns behind them and tiles past ``num_tiles`` (``G`` = 16 of
    8 rows, 12 in use), and a gated FFN's weights at width ``f``: ``(x_pad,
    w_gate, w_up, w_down, tiles, live [P] bool, dy_pad)``."""
    rng = np.random.default_rng(seed)
    E, Tk, d, bt = 4, 96, 32, 8
    counts = np.array([40, 0, 30, 10], np.int32)      # 16 rows are not held
    starts = (np.cumsum(counts) - counts).astype(np.int32)
    tiles, src, _ = gmm_lib._padded_layout(
        jnp.asarray(starts), jnp.asarray(counts), Tk, E, bt)
    assert tiles[0].shape == (16,) and int(tiles[2][0]) == 12
    normal = lambda *shape, scale=1.0: jnp.asarray(
        rng.standard_normal(shape) * scale, dtype)
    x_pad = gmm_lib._pad_rows(normal(Tk, d), src)
    live = np.arange(x_pad.shape[0]) // bt < 12
    weights = (normal(E, d, f, scale=0.2), normal(E, d, f, scale=0.2),
               normal(E, f, d, scale=0.2))
    dy_pad = normal(*x_pad.shape) * jnp.asarray(live[:, None], dtype)
    return x_pad, *weights, tiles, live, dy_pad


_GATED_CASES = pytest.mark.parametrize("act,dtype,f", [
    (act, dtype, f) for act in sorted(gmm_lib.GATES)
    for dtype in (jnp.bfloat16, jnp.float32)
    for f in (48, 136)])      # under a lane tile; a whole one and a part


def _f32(a, live=None):
    a = np.asarray(a, np.float32)
    return a if live is None else a[live]


@_GATED_CASES
def test_gated_forward_is_the_three_calls_bit_for_bit(act, dtype, f):
    """``gate``, ``up`` and ``y_pad`` of the fused forward (one pass over a
    row tile for gate and up; ``h`` made in VMEM by the down projection, so
    ``y_pad`` is its witness) are the three-call composition's in every bit,
    in the tiles in use (nothing may read the others)."""
    x_pad, w_gate, w_up, w_down, tiles, live, _ = _gated_case(dtype, f)
    y0, gate0, up0 = _composed_kept(x_pad, w_gate, w_up, w_down, tiles, act)
    y1, gate1, up1 = gmm_lib.gated_ffn_padded_kept(x_pad, w_gate, w_up, w_down,
                                                  tiles, act)
    plain = gmm_lib.gated_ffn_padded(x_pad, w_gate, w_up, w_down, tiles, act)
    again = gmm_lib.gated_down_padded(gate1, up1, w_down, tiles, act)
    for want, got in ((gate0, gate1), (up0, up1), (y0, y1), (y0, plain),
                      (y0, again)):
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(_f32(got, live), _f32(want, live))


@_GATED_CASES
def test_gated_backward_is_the_compositions_vjp(act, dtype, f):
    """The hand-written backward (``dh`` leaves its kernel as ``dgate`` and
    ``dup`` unrounded, ``dx`` is one float32 sum, a weight gradient leaves
    its kernel in the weights' dtype) against ``jax.vjp`` of the three-call
    composition: in float32 every bit; in bf16 ``dgate`` and ``dup`` to the
    last place or one of each element, and ``dx`` and the weight gradients,
    sums of such terms, within two places of their largest element. Plain AD
    of ``gated_ffn_padded`` runs the same kernels."""
    x_pad, w_gate, w_up, w_down, tiles, live, dy_pad = _gated_case(dtype, f)
    weights = (w_gate, w_up, w_down)
    _, gate, up = gmm_lib.gated_ffn_padded_kept(x_pad, *weights, tiles, act)
    _, vjp = jax.vjp(lambda x, *w: _composed_kept(x, *w, tiles, act)[0],
                     x_pad, *weights)
    want = vjp(dy_pad)
    got = gmm_lib.gated_ffn_padded_bwd(x_pad, gate, up, *weights, tiles,
                                       dy_pad, act)
    _, by_ad = jax.vjp(lambda x, *w: gmm_lib.gated_ffn_padded(
        x, *w, tiles, act), x_pad, *weights)
    for a, b in zip(got, by_ad(dy_pad)):
        np.testing.assert_array_equal(_f32(a), _f32(b))
    # a caller that differentiates the kept gate and up too is given theirs
    both = lambda ffn: jax.vjp(lambda x, *w: ffn(x, *w, tiles, act),
                               x_pad, *weights)[1]((dy_pad, up, gate))
    for name, a, b in zip(("dx", "dw_gate", "dw_up", "dw_down"),
                          both(gmm_lib.gated_ffn_padded_kept),
                          both(_composed_kept)):
        a, b = (_f32(v, live if name == "dx" else None) for v in (a, b))
        np.testing.assert_allclose(a, b, rtol=0, err_msg=name, atol=(
            0 if dtype == jnp.float32 else
            2 * float(jnp.finfo(dtype).eps) * np.abs(b).max()))
    # dgate and dup alone: the composition's, from its rounded dh
    dh = gmm_lib._gmm_call(dy_pad, w_down, tiles, 8, dtype, transposed=True)
    _, gated_vjp = jax.vjp(lambda g, u: gmm_lib._gated(g, u, act), gate, up)
    ulp = float(jnp.finfo(dtype).eps)
    exact = dtype == jnp.float32
    for a, b in zip(gmm_lib._gated_call(
            "gated_ffn_dh", tiles,
            (dy_pad, w_down, gate, up), ("rows", "w_rows", "cols", "cols"),
            (("cols", gate.shape, dtype),) * 2, d=32, f=f, cut=f, depth=32,
            itemsize=w_down.dtype.itemsize, act=act), gated_vjp(dh)):
        a, b = _f32(a, live), _f32(b, live)
        np.testing.assert_allclose(a, b, rtol=0 if exact else 2 * ulp,
                                   atol=0 if exact else 1e-30)
    for name, a, b in zip(("dx", "dw_gate", "dw_up", "dw_down"), got, want):
        assert a.dtype == b.dtype == dtype, name
        a, b = (_f32(v, live if name == "dx" else None) for v in (a, b))
        np.testing.assert_allclose(
            a, b, rtol=0, atol=0 if exact else 2 * ulp * np.abs(b).max(),
            err_msg=name)


#: ``(width, depth, itemsize) -> block`` at the five expert cells' widths:
#: gate / up forward, down forward and dx, dh, dw gate / up, dw down.
_CELL_BLOCKS = {
    "trinity": [(1024, 2048, 2, 1024), (2048, 1024, 2, 2048),
                (1024, 2048, 2, 1024), (1024, 2048, 4, 512),
                (2048, 1024, 4, 1024)],                 # as before this rule
    "nemotron": [(1856, 2688, 2, 640), (2688, 1856, 2, 896),
                 (1856, 2688, 2, 640), (1856, 2688, 4, 384),
                 (2688, 1856, 4, 512)],                 # as PR 43 left them
    "smallthinker": [(768, 2560, 2, 768), (2560, 768, 2, 2560),
                     (768, 2560, 2, 768), (768, 2560, 4, 384),
                     (2560, 768, 4, 1280)],             # were 256, 512, 256
    "glm": [(1536, 2048, 2, 768), (2048, 1536, 2, 1024),
            (1536, 2048, 2, 768), (1536, 2048, 4, 512),
            (2048, 1536, 4, 512)],
    "lfm2": [(1792, 2048, 2, 896), (2048, 1792, 2, 1024),
             (1792, 2048, 2, 896), (1792, 2048, 4, 512),
             (2048, 1792, 4, 512)],                     # were 256: 7 blocks
}


@pytest.mark.parametrize("cell,n,depth,itemsize,block", [
    pytest.param(cell, *row, id=f"{cell}-{i}")
    for cell, rows in _CELL_BLOCKS.items() for i, row in enumerate(rows)])
def test_block_cols_at_the_cells_widths(cell, n, depth, itemsize, block):
    """One rule: the fewest blocks of whole lane tiles whose ``[depth,
    block]`` tile fits ``_BLOCK_BYTES``, the narrowest of those."""
    got = gmm_lib._block_cols(n, depth, itemsize)
    assert got == block
    assert got % 128 == 0 and depth * got * itemsize <= gmm_lib._BLOCK_BYTES
    blocks = -(-n // got)
    # no fewer blocks fit, and no narrower block makes as few
    assert blocks == 1 or depth * 128 * -(-(-(-n // 128)) // (blocks - 1)) \
        * itemsize > gmm_lib._BLOCK_BYTES
    assert got == 128 or -(-n // (got - 128)) > blocks


@pytest.mark.parametrize("n,block", [(16, 16), (48, 48), (128, 128),
                                     (136, 128), (192, 128), (256, 256),
                                     (384, 384)])
def test_block_cols_of_small_widths(n, block):
    """A width within one lane tile is one block; over it, whole lane tiles,
    and never the whole of a width that is no whole number of them."""
    assert gmm_lib._block_cols(n, 32, 4) == block

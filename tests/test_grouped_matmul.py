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

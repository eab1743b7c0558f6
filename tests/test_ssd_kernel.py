"""The Mamba-2 scan's Pallas kernels (``ops/ssd.py``), interpreted on the CPU:
against the ``jax.numpy`` scan they replace and against the token-by-token
recurrence, values and all six cotangents; what the plan admits and what it
leaves to the ``jax.numpy`` body; a sharded batch under a mesh."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib
from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib
from test_granite_hybrid import _recurrence

BF16, F32 = jnp.bfloat16, jnp.float32
HIGHEST = jax.default_matmul_precision("highest")
NAMES = ("x", "dt", "A", "B", "C", "D")


def _inputs(S, b=2, H=2, P=64, N=128, dtype=F32, seed=0):
    """Operands in the mixer's ranges (dt = softplus(. - 3)); x, B, C in
    ``dtype``, the decays float32."""
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (b, S, H, P)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (b, S, H)) - 3.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (b, S, N)).astype(dtype),
            jax.random.normal(k[4], (b, S, N)).astype(dtype),
            0.5 + jax.random.normal(k[5], (H,)))


def _value_and_grads(fn, args):
    """``fn``'s value and its six cotangents under a fixed random weighting."""
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    y = fn(*args)
    return y, jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(6))(*args)


def _xla(chunk):
    def scan(*args):
        with mock.patch.object(ssd_lib, "_kernel_plan", lambda *a: None):
            return ssd_lib.ssd(*args, chunk=chunk)
    return scan


def _kernels(chunk):
    def scan(*args):
        text = str(jax.make_jaxpr(
            lambda *a: ssd_lib.ssd(*a, chunk=chunk))(*args))
        assert "ssd_fwd" in text, "the plan refused a shape the test is of"
        return ssd_lib.ssd(*args, chunk=chunk)
    return scan


def _close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= tol * scale, (
        what, np.abs(got - want).max(), scale)


SHAPES = [pytest.param(128, 128, id="one_chunk"),
          pytest.param(384, 128, id="three_chunks"),     # the carried state
          pytest.param(300, 256, id="padded_two_row_blocks")]


@pytest.mark.parametrize("S,chunk", SHAPES)
def test_kernels_match_the_jnp_scan_and_the_recurrence_in_float32(S, chunk):
    args = _inputs(S)
    with HIGHEST:
        y, grads = _value_and_grads(_kernels(chunk), args)
        y_xla, g_xla = _value_and_grads(_xla(chunk), args)
        y_rec, g_rec = _value_and_grads(_recurrence, args)
    assert y.dtype == F32 and y.shape == args[0].shape
    # the running log-decay is summed in another order (a product with a
    # triangle of ones, not a cumsum), and the decays are differences of it
    _close(y, y_xla, 2e-5, "y against the jnp scan")
    _close(y, y_rec, 2e-5, "y against the recurrence")
    for name, g, gx, gr in zip(NAMES, grads, g_xla, g_rec):
        assert g.dtype == gr.dtype and g.shape == gr.shape
        _close(g, gx, 5e-5, f"d{name} against the jnp scan")
        _close(g, gr, 5e-5, f"d{name} against the recurrence")


@pytest.mark.parametrize("S,chunk", SHAPES)
def test_kernels_with_bf16_operands_keep_float32_decays(S, chunk):
    """bf16 x, B, C; float32 dt, A, D. Both paths round the same matmul
    operands, so they agree far inside what either is off the float32 scan."""
    args = _inputs(S, dtype=BF16)
    y, grads = _value_and_grads(_kernels(chunk), args)
    y_xla, g_xla = _value_and_grads(_xla(chunk), args)
    with HIGHEST:
        y_true, g_true = _value_and_grads(
            _recurrence, tuple(a.astype(F32) for a in args))
    assert y.dtype == F32  # the accumulator, not rounded again
    _close(y, y_xla, 1e-3, "y against the jnp scan")
    _close(y, y_true, 1e-2, "y against float32")
    for name, g, gx, gt, a in zip(NAMES, grads, g_xla, g_true, args):
        assert g.dtype == a.dtype
        _close(g, gx, 2e-2, f"d{name} against the jnp scan")
        _close(g, gt, 3e-2, f"d{name} against float32")
    # the two small float32 leaves the chip benchmark's grad_leaf rests on:
    # no further from the float32 scan than the jnp scan's are, with room
    for i in (2, 5):
        off = lambda g: float(jnp.linalg.norm(g - g_true[i])
                              / jnp.linalg.norm(g_true[i]))
        assert off(grads[i]) <= max(3 * off(g_xla[i]), 5e-3), NAMES[i]


def test_strong_decay_stays_finite():
    """A = -20 with dt near 5: above the diagonal a_t - a_s reaches +12,700
    a chunk. A mask after the exp would give inf there, and inf * 0 = NaN in
    anything that multiplies by it."""
    x, dt, A, B, C, D = _inputs(256)
    args = (x, dt + 5.0, jnp.full_like(A, -20.0), B, C, D)
    with HIGHEST:
        y, grads = _value_and_grads(_kernels(128), args)
        y_rec, g_rec = _value_and_grads(_recurrence, args)
    assert all(bool(jnp.isfinite(g).all()) for g in (y, *grads))
    _close(y, y_rec, 1e-5, "y")
    for name, g, gr in zip(NAMES, grads, g_rec):
        _close(g, gr, 1e-4, f"d{name}")


def test_one_head_per_lane_block_and_no_skip_term():
    """P = 128 (a head is a whole lane block) and D = None."""
    x, dt, A, B, C, _ = _inputs(256, H=2, P=128)
    scan = lambda *a: _kernels(128)(*a, None)
    with HIGHEST:
        y = scan(x, dt, A, B, C)
        want = _recurrence(x, dt, A, B, C, jnp.zeros_like(A))
        got = jax.grad(lambda *a: jnp.sum(jnp.square(scan(*a))),
                       argnums=range(5))(x, dt, A, B, C)
        ref = jax.grad(lambda *a: jnp.sum(jnp.square(
            _recurrence(*a, jnp.zeros_like(A)))), argnums=range(5))(
                x, dt, A, B, C)
    _close(y, want, 2e-5, "y")
    for name, g, r in zip(NAMES, got, ref):
        _close(g, r, 1e-4, f"d{name}")


@pytest.mark.parametrize("H,P,N,chunk,dtype,admitted", [
    (64, 64, 128, 256, BF16, 16),   # granite-4.0-h-micro
    (64, 64, 128, 256, F32, 8),
    (2, 128, 128, 128, F32, 2),
    (4, 16, 16, 8, F32, None),             # the rehearsal twin's widths
    (4, 16, 16, 36, F32, None),            # ... and its whole sequence
    (4, 48, 128, 128, BF16, None),         # P off the lane tiling
    (3, 64, 128, 128, BF16, None),         # half a lane block of heads
    (4, 64, 64, 128, BF16, None),          # N off the lane tiling
    (4, 64, 128, 192, BF16, None),         # chunk off the lane tiling
    (4, 64, 128, 128, jnp.float16, None),  # Mosaic refuses fp16 loads
], ids=["granite_bf16", "granite_fp32", "p128", "tiny_chunk8", "tiny_s36",
        "p48", "odd_heads", "n64", "chunk192", "fp16"])
def test_plan(H, P, N, chunk, dtype, admitted):
    assert ssd_lib._kernel_plan(H, P, N, chunk, dtype) == admitted


@pytest.mark.parametrize("S,H,P,N,chunk", [
    (36, 4, 16, 16, 8), (36, 4, 16, 16, 256), (128, 2, 48, 128, 128)],
    ids=["tiny_chunk8", "tiny_s36", "p48"])
def test_refused_shapes_run_the_jnp_body(S, H, P, N, chunk):
    args = _inputs(S, H=H, P=P, N=N)
    fn = lambda *a: ssd_lib.ssd(*a, chunk=chunk)
    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(
        lambda *a: fn(*a).sum(), argnums=range(6)))(*args))
    with HIGHEST:
        y, grads = _value_and_grads(fn, args)
        y_rec, g_rec = _value_and_grads(_recurrence, args)
    _close(y, y_rec, 1e-5, "y")
    for name, g, gr in zip(NAMES, grads, g_rec):
        _close(g, gr, 1e-4, f"d{name}")


def test_sharded_batch_under_a_mesh_equals_one_device(devices):
    """Batch over ``fsdp=4`` through ``mesh_lib.manual_call``; A's and D's
    cotangents are summed over the devices outside the kernels."""
    args = _inputs(256, b=4)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    grad = jax.grad(lambda *a: jnp.sum(ssd_lib.ssd(*a, chunk=128) * w),
                    argnums=range(6))
    want = jax.jit(grad)(*args)
    mesh = mesh_lib.build_mesh({"fsdp": 4}, devices=devices[:4])
    batch = lambda a: NamedSharding(
        mesh, P(mesh_lib.BATCH_AXES, *([None] * (a.ndim - 1))))
    placed = [jax.device_put(a, batch(a) if a.ndim > 1
                             else NamedSharding(mesh, P())) for a in args]
    with mesh_lib.use_mesh(mesh):
        text = str(jax.make_jaxpr(grad)(*placed))
        got = jax.jit(grad)(*placed)
    assert "shard_map" in text and "ssd_fwd" in text and "ssd_bwd" in text
    for name, g, r in zip(NAMES, got, want):
        _close(g, r, 1e-5, f"d{name}")
    assert got[0].sharding.spec[0] == mesh_lib.BATCH_AXES

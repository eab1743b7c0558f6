"""The ``nemotron_h`` family (``models/nemotron_h.py``; the grouped B/C of
``ops/ssd.py`` and ``models/granite_hybrid.MambaMixer``; the ungated experts
of ``ops/grouped_matmul.py`` and ``parallel/moe.py``): the grouped scan
against the recurrence and the ``jax.numpy`` scan with every gradient, the
ungated held experts against a dense loop at a width off the lane tiling, the
shares adding up to the uncut layer, the model against the benchmark's plain
reference, the published entry's shape and the chip's share of it, and the
preset through the ``Trainer``. Float32 on the CPU at toy widths."""

import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import weights  # noqa: E402
from chipbench.references import nemotron3_nano as reference  # noqa: E402
from pytorch_distributed_training_example_tpu.core import train_loop  # noqa: E402
from pytorch_distributed_training_example_tpu.core.trainer import Trainer  # noqa: E402
from pytorch_distributed_training_example_tpu.models import (  # noqa: E402
    granite_hybrid, nemotron_h, registry)
from pytorch_distributed_training_example_tpu.ops import (  # noqa: E402
    grouped_matmul as gmm_lib, ssd as ssd_lib)
from pytorch_distributed_training_example_tpu.parallel import moe as moe_lib  # noqa: E402
from pytorch_distributed_training_example_tpu.utils import telemetry  # noqa: E402
from pytorch_distributed_training_example_tpu.utils.config import from_preset  # noqa: E402

HIGHEST = jax.default_matmul_precision("highest")
RULES = [["(scale|/D)$", "const", 1.0], ["dt_bias$", "const", -3.0],
         ["bias$", "const", 0.0], ["A_log$", "normal", 1.0],
         ["conv_kernel$", "normal", 0.3], [".*", "normal", 0.02]]


# -- the grouped scan --------------------------------------------------------------


def _scan_inputs(S, H, groups, b=1, P=64, N=128, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (b, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (b, S, H)) - 1.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (H,))),
            0.3 * jax.random.normal(k[3], (b, S, groups, N)),
            0.3 * jax.random.normal(k[4], (b, S, groups, N)),
            jax.random.normal(k[5], (H,)))


def _recurrence(x, dt, A, B, C, D):
    """The definition, a token at a time: head h reads group h // (H / G)."""
    b, S, H, P = x.shape
    per = H // B.shape[2]
    Bh, Ch = jnp.repeat(B, per, axis=2), jnp.repeat(C, per, axis=2)

    def step(state, at):
        x_t, dt_t, B_t, C_t = at
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, C_t)

    first = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, B.shape[-1])),
                        (first(x), first(dt), first(Bh), first(Ch)))
    return jnp.moveaxis(y, 0, 1) + x * D[:, None]


def _value_and_grads(fn, args):
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * w),
                              argnums=tuple(range(6)))(*args)


def _all_close(got, want, tol, what):
    for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))):
        assert g.shape == w.shape, (what, i)
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(g - w))) <= tol * scale, (
            what, i, float(jnp.max(jnp.abs(g - w))), scale)


@pytest.mark.parametrize("H,groups,plan", [
    (8, 1, None),      # one group, B and C [b, S, N]: today's callers
    (8, 2, None),      # a program of two whole groups
    (16, 4, None),     # of four, as Nemotron's bf16 plan
    (16, 2, 8),        # a program a group, as the float32 plan may
    (32, 2, 8),        # a program inside a group: two share each group
])
def test_grouped_scan_is_the_recurrence_with_every_gradient(H, groups, plan):
    """Kernels (interpret mode) and the ``jax.numpy`` scan against the
    recurrence, over two chunks of 128, y and all six gradients; ``dB`` and
    ``dC`` are summed over the heads of their own group alone."""
    args = _scan_inputs(256, H, groups)
    if groups == 1:
        args = args[:3] + (args[3][:, :, 0], args[4][:, :, 0]) + args[5:]
    scan = lambda *a: ssd_lib.ssd(*a, chunk=128)
    with HIGHEST:
        want = _value_and_grads(
            lambda x, dt, A, B, C, D: _recurrence(
                x, dt, A, B.reshape(*B.shape[:2], groups, -1),
                C.reshape(*C.shape[:2], groups, -1), D), args)
        with mock.patch.object(ssd_lib, "_kernel_plan", lambda *a: None):
            xla = _value_and_grads(scan, args)
        if plan is not None:
            with mock.patch.object(ssd_lib, "_kernel_plan", lambda *a: plan):
                kernels = _value_and_grads(scan, args)
        else:
            assert ssd_lib._kernel_plan(H, 64, 128, 128, jnp.float32,
                                        groups) == H
            assert "ssd_bwd" in str(jax.make_jaxpr(
                lambda *a: _value_and_grads(scan, a))(*args))
            kernels = _value_and_grads(scan, args)
    _all_close(xla, want, 2e-5, "xla")
    _all_close(kernels, want, 2e-5, "kernels")


@pytest.mark.parametrize("H,P,N,Q,dtype,groups,heads", [
    (64, 64, 128, 128, jnp.bfloat16, 8, 32),   # Nemotron: four whole groups
    (64, 64, 128, 128, jnp.float32, 8, 16),
    (64, 64, 128, 256, jnp.bfloat16, 1, 16),   # Granite, as before
    (64, 64, 128, 256, jnp.float32, 1, 8),
    (64, 64, 128, 256, jnp.bfloat16, 8, 8),    # at chunk 256, one group
    (64, 64, 128, 256, jnp.bfloat16, 2, 16),   # half of a group of 32
    (24, 64, 128, 128, jnp.float32, 3, 24),
    (64, 64, 128, 128, jnp.bfloat16, 5, None),   # groups that do not divide
])
def test_plan_keeps_a_programs_heads_to_whole_groups(H, P, N, Q, dtype, groups,
                                                     heads):
    got = ssd_lib._kernel_plan(H, P, N, Q, dtype, groups)
    assert got == heads
    if got is not None:
        held, shared_by = ssd_lib._group_span(H, got, groups)
        assert got * shared_by == held * (H // groups) and 1 in (held, shared_by)


def test_ssd_plan_record_under_the_span_that_traced():
    """One ``ssd_plan`` record a traced call, a child of the span open on the
    tracing thread: the kernels' heads a program, or ``xla``."""
    rec = telemetry.recorder()
    mark = len(rec.records())
    shape = lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(s, dtype)
    bf16 = jnp.bfloat16
    with rec.span("trace_here", bucket=None):
        jax.eval_shape(
            lambda *a: ssd_lib.ssd(*a, chunk=128),
            shape(1, 8192, 64, 64, dtype=bf16), shape(1, 8192, 64), shape(64),
            shape(1, 8192, 8, 128, dtype=bf16),
            shape(1, 8192, 8, 128, dtype=bf16), shape(64))
        jax.eval_shape(lambda *a: ssd_lib.ssd(*a, chunk=8),
                       *jax.tree.map(lambda a: shape(*a.shape),
                                     _scan_inputs(16, 4, 2, P=16, N=16)))
    new = rec.records()[mark:]
    span = next(r for r in new if r.kind == "span" and r.name == "trace_here")
    said = [r for r in new if r.name == "ssd_plan"]
    assert [r.kind for r in said] == ["compile"] * 2
    assert all(r.parent == span.id and r.seconds == 0 for r in said)
    assert said[0].value == {"H": 64, "P": 64, "N": 128, "groups": 8,
                             "chunk": 128, "heads_per_program": 32}
    assert said[1].value["heads_per_program"] == "xla"
    assert "ssd_plan" in telemetry.COMPILE_RECORDS


def test_gmm_plan_record_under_the_span_that_traced():
    """One ``gmm_plan`` record a traced call of each kernel of
    ``ops/grouped_matmul.py``, a child of the span open on the tracing
    thread: the gated form's kernels at LFM2's widths (the forward that
    keeps ``gate`` and ``up``, then the backward's four), and the plain
    kernels of an ungated layer at Nemotron's."""
    rec = telemetry.recorder()
    mark = len(rec.records())
    bf16, i32 = jnp.bfloat16, jnp.int32
    shape = lambda *s, dtype=bf16: jax.ShapeDtypeStruct(s, dtype)
    tiles = lambda rows: (shape(rows // 128, dtype=i32),
                          shape(rows // 128, dtype=i32), shape(1, dtype=i32))

    def gated(x, wg, wu, wd, tiles, dy):
        _, gate, up = gmm_lib.gated_ffn_padded_kept(x, wg, wu, wd, tiles)
        return gmm_lib.gated_ffn_padded_bwd(x, gate, up, wg, wu, wd, tiles, dy)

    def ungated(x, wu, wd, tiles, dy):
        _, up = gmm_lib.ungated_ffn_padded_kept(x, wu, wd, tiles)
        return gmm_lib.ungated_ffn_padded_bwd(x, up, wu, wd, tiles, dy)

    with rec.span("trace_here", bucket=None):
        jax.eval_shape(gated, shape(17408, 2048), shape(8, 2048, 1792),
                       shape(8, 2048, 1792), shape(8, 1792, 2048),
                       tiles(17408), shape(17408, 2048))
        jax.eval_shape(ungated, shape(7168, 2688), shape(8, 2688, 1856),
                       shape(8, 1856, 2688), tiles(7168), shape(7168, 2688))
    new = rec.records()[mark:]
    span = next(r for r in new if r.kind == "span" and r.name == "trace_here")
    said = [r for r in new if r.name == "gmm_plan"]
    assert all(r.kind == "compile" and r.parent == span.id and r.seconds == 0
               for r in said)
    brief = [(r.value["kernel"], r.value["form"], r.value["block"],
              r.value["blocks"]) for r in said]
    assert brief == [
        ("gated_ffn_up", "gated", 896, 2), ("gated_ffn_down", "gated", 1024, 2),
        ("gated_ffn_dh", "gated", 896, 2), ("gated_ffn_dx", "gated", 1024, 2),
        ("gated_ffn_dw_up", "gated", 512, 4),
        ("gated_ffn_dw_down", "gated", 512, 4),
        ("grouped_matmul", "plain", 640, 3), ("grouped_matmul", "plain", 896, 3),
        ("grouped_matmul", "plain", 640, 3), ("grouped_matmul_dw", "plain", 512, 6),
        ("grouped_matmul", "plain", 896, 3), ("grouped_matmul_dw", "plain", 384, 5),
    ], brief
    assert said[0].value == {
        "kernel": "gated_ffn_up", "form": "gated", "rows": 17408, "d": 2048,
        "f": 1792, "bt": 128, "block": 896, "blocks": 2,
        # two weight blocks of 3.5 MiB, the x tile and two [128, 896]
        # results, each double-buffered
        "vmem": 2 * (2 * 2048 * 896 + 128 * 2048 + 2 * 128 * 896) * 2}
    # two accumulators side by side take more than the default scope: the
    # kernel asks for that and its tiles
    assert said[4].value["vmem"] > gmm_lib._SCOPED_VMEM
    assert "gmm_plan" in telemetry.COMPILE_RECORDS


def test_mixer_at_one_group_is_granites():
    """``groups`` 1: the parameter paths and shapes of the mixer that Granite
    builds, and its traced program, are what they were without the field."""
    sizes = dict(num_heads=4, head_dim=16, state_dim=16, conv_width=4, chunk=8,
                 epsilon=1e-5, dtype=jnp.float32, param_dtype=jnp.float32)
    h = jnp.ones((2, 16, 32))
    one = granite_hybrid.MambaMixer(**sizes)
    two = granite_hybrid.MambaMixer(**sizes, groups=2)
    shapes = lambda m: {k: v.shape for k, v in weights.flatten(jax.eval_shape(
        lambda: m.init(jax.random.key(0), h))["params"]).items()}
    assert one.groups == 1 and shapes(one) == {
        "A_log": (4,), "D": (4,), "conv_bias": (96,), "conv_kernel": (4, 96),
        "dt_bias": (4,), "in_proj/kernel": (32, 164), "norm/scale": (64,),
        "out_proj/kernel": (64, 32)}
    assert shapes(two) == {**shapes(one), "conv_bias": (128,),
                           "conv_kernel": (4, 128),
                           "in_proj/kernel": (32, 196)}
    params = one.init(jax.random.key(0), h)
    text = str(jax.make_jaxpr(lambda p: one.apply(p, h))(params))
    assert "reshape" in text and "groups" not in text
    # the gated norm over all 64 channels at once: one mean over the last axis
    assert text.count("reduce_sum") == str(jax.make_jaxpr(
        lambda p: granite_hybrid.RMSNorm().apply(
            {"params": p["params"]["norm"]}, jnp.ones((2, 16, 64))))(
                params)).count("reduce_sum")


def test_gated_norm_norms_each_group_by_itself():
    x = jax.random.normal(jax.random.key(0), (2, 5, 24)) * jnp.repeat(
        jnp.array([1.0, 10.0, 0.1]), 8)
    scale = 1.0 + jnp.arange(24.0) / 24
    got = granite_hybrid.GroupRMSNorm(3).apply({"params": {"scale": scale}}, x)
    want = jnp.concatenate([
        part / jnp.sqrt(jnp.mean(part ** 2, -1, keepdims=True) + 1e-5)
        for part in jnp.split(x, 3, axis=-1)], -1) * scale
    np.testing.assert_allclose(got, want, rtol=1e-6)


# -- the gate with its group norm as one kernel pair (interpreted here) ----------------


def _gate_norm_body(groups, dtype):
    return lambda y, z, scale: ssd_lib.group_rms_norm(
        y * jax.nn.silu(z.astype(jnp.float32)), scale, groups, 1e-5, dtype)


def _norm_gate_body(groups, dtype):
    """The other order (``ops/ssd.norm_gate``): a head's norm with the one
    scale all heads share, the gate after."""
    return lambda y, z, scale: (ssd_lib.group_rms_norm(
        y, jnp.tile(scale, groups), groups, 1e-5, jnp.float32)
        * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)


def _gate_inputs(S, C, dtype, b=2, scale=None):
    """``((y, z, scale), the result's cotangent)``; ``scale``: its width
    where it is not one value a channel."""
    k = jax.random.split(jax.random.key(4), 4)
    return ((3.0 * jax.random.normal(k[0], (b, S, C)),
             jax.random.normal(k[1], (b, S, C)).astype(dtype),
             1.0 + 0.1 * jax.random.normal(k[2], (scale or C,))),
            jax.random.normal(k[3], (b, S, C)))


def _cotangents(fn, args, w):
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(*args)


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 32 rows of whole groups: 256 lanes, or one group."""
    monkeypatch.setattr(ssd_lib, "STAGE_TILE", 32 * 256)
    monkeypatch.setattr(ssd_lib, "STAGE_COLS", 256)


def _stage(order, groups, dtype):
    """``(the stage in that order, its jax.numpy body, how many groups share
    one run of the scale)``."""
    run = lambda *a: getattr(ssd_lib, order)(*a, groups=groups, epsilon=1e-5,
                                             dtype=dtype)
    if order == "gate_norm":
        return run, _gate_norm_body(groups, dtype), 1
    return run, _norm_gate_body(groups, dtype), groups


def _same_leaves(got, want, tol, what):
    for i, (g, t) in enumerate(zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))):
        assert g.shape == t.shape and g.dtype == t.dtype, (what, i)
    _all_close(jax.tree.map(lambda a: a.astype(jnp.float32), got),
               jax.tree.map(lambda a: a.astype(jnp.float32), want), tol, what)


@pytest.mark.parametrize("S", [64, 80], ids=["tiles", "ragged"])
@pytest.mark.parametrize("groups,C,cols", [(1, 512, 512), (2, 256, 256),
                                           (8, 1024, 256)],
                         ids=["g1", "g2", "g8"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("order", ["gate_norm", "norm_gate"])
def test_gate_norm_kernels_are_the_jax_numpy_body(small_tiles, order, dtype,
                                                  tol, groups, C, cols, S):
    """Values, ``dy`` (float32), ``dz`` and ``d scale`` of the kernel pair, in
    both orders (``norm_gate``'s scale one vector that every group shares), at
    two sequences and two or three row tiles (the last one part-filled where
    S is 80), a program holding one group wider than a block, one block of two
    groups, and two groups of eight, against the ``jax.numpy`` body of that
    order differentiated by XLA."""
    run, body, sharing = _stage(order, groups, dtype)
    args, w = _gate_inputs(S, C, dtype, scale=C // sharing)
    assert ssd_lib._stage_plan(order, S, C, groups, dtype) == (
        32 * 256 // cols, cols)
    assert f"name={order}_fwd" in str(jax.make_jaxpr(run)(*args))
    got = _cotangents(run, args, w)
    want = _cotangents(body, args, w)
    assert got[1][0].dtype == jnp.float32 and got[1][1].dtype == dtype
    _same_leaves(got, want, tol, order)


@pytest.mark.parametrize("S", [64, 80], ids=["tiles", "ragged"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("offset,read_in_place", [(512, True), (384, False)],
                         ids=["block_edge", "off_edge"])
def test_norm_gate_reads_its_gate_out_of_a_wider_source(
        small_tiles, offset, read_in_place, dtype, tol, S):
    """``z`` as lanes ``offset : offset + C`` of a wider array (the delta-rule
    mixer's ``qkvz``): on a column block's edge the kernels' operand is the
    source itself and no slice of it reaches them; off it they take ``z`` as
    an array of its own. Either way the result and every cotangent, the
    source's (zero outside ``z``'s lanes) among them, are the body's."""
    groups, C = 4, 512
    (y, _, scale), w = _gate_inputs(S, C, dtype, scale=C // groups)
    source = jax.random.normal(jax.random.key(5),
                               (2, S, offset + C + 128)).astype(dtype)
    cut = lambda src: src[..., offset:offset + C]
    run = lambda y, src, scale: ssd_lib.norm_gate(
        y, cut(src), scale, groups=groups, epsilon=1e-5, dtype=dtype,
        source=src, offset=offset)
    body = _norm_gate_body(groups, dtype)
    call = next(e for e in jax.make_jaxpr(run)(y, source, scale).eqns
                if e.primitive.name == "custom_vjp_call")
    shapes = [v.aval.shape for v in call.invars]
    assert (source.shape in shapes[2:]) == read_in_place, shapes
    got = _cotangents(run, (y, source, scale), w)
    want = _cotangents(lambda y, src, scale: body(y, cut(src), scale),
                       (y, source, scale), w)
    assert got[1][1].shape == source.shape and got[1][1].dtype == dtype
    assert not np.asarray(got[1][1][..., :offset], np.float32).any()
    _same_leaves(got, want, tol, "norm_gate from a source")


@pytest.mark.parametrize("C,groups,dtype,why", [
    (256, 2, jnp.float16, "fp16"),
    (192, 1, jnp.float32, "channels off the lane tiling"),
    (384, 6, jnp.float32, "a group off the lane tiling"),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
@pytest.mark.parametrize("order", ["gate_norm", "norm_gate"])
def test_gate_norm_plan_refuses_and_the_body_runs(small_tiles, order, C,
                                                  groups, dtype, why):
    assert ssd_lib._stage_plan(order, 64, C, groups, dtype) is None, why
    run, body, sharing = _stage(order, groups, dtype)
    args, w = _gate_inputs(64, C, dtype, scale=C // sharing)
    got = _cotangents(run, args, w)
    want = _cotangents(body, args, w)
    for g, t in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, t)
    assert "pallas_call" not in str(jax.make_jaxpr(run)(*args))


def test_norm_gate_shorter_than_a_tile_runs_the_body(small_tiles):
    """A sequence of fewer rows than a tile's (32 here) is refused whatever
    the widths, and a source then changes nothing."""
    assert ssd_lib._stage_plan("norm_gate", 24, 512, 4, jnp.float32) is None
    (y, z, scale), _ = _gate_inputs(24, 512, jnp.float32, scale=128)
    got = ssd_lib.norm_gate(y, z, scale, groups=4, epsilon=1e-5,
                            dtype=jnp.float32, source=z, offset=0)
    np.testing.assert_array_equal(
        got, _norm_gate_body(4, jnp.float32)(y, z, scale))


def test_mixer_plan_record_under_the_span_that_traced():
    """One ``mixer_plan`` record a traced call of a stage, a child of the span
    open on the tracing thread: the tile, or ``xla``."""
    rec = telemetry.recorder()
    mark = len(rec.records())
    shape = lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(s, dtype)
    bf16 = jnp.bfloat16
    norm = lambda groups: lambda *a: ssd_lib.gate_norm(
        *a, groups=groups, epsilon=1e-5, dtype=bf16)
    # the delta-rule mixer's call at the published widths: 32 heads of 128,
    # z the last 4,096 lanes of qkvz's 12,288
    heads = lambda y, qkvz, scale: ssd_lib.norm_gate(
        y, qkvz[..., 8192:], scale, groups=32, epsilon=1e-6, dtype=bf16,
        source=qkvz, offset=8192)
    with rec.span("trace_here", bucket=None):
        jax.eval_shape(ssd_lib.conv_silu, shape(1, 8192, 6144, dtype=bf16),
                       shape(4, 6144), shape(6144))
        jax.eval_shape(norm(8), shape(1, 8192, 4096),
                       shape(1, 8192, 4096, dtype=bf16), shape(4096))
        jax.eval_shape(norm(2), shape(1, 8192, 192),
                       shape(1, 8192, 192, dtype=bf16), shape(192))
        jax.eval_shape(heads, shape(1, 8192, 4096),
                       shape(1, 8192, 12288, dtype=bf16), shape(128))
        jax.eval_shape(heads, shape(1, 8192, 4096),
                       shape(1, 8192, 12288, dtype=jnp.float16), shape(128))
    new = rec.records()[mark:]
    span = next(r for r in new if r.kind == "span" and r.name == "trace_here")
    said = [r for r in new if r.name == "mixer_plan"]
    assert said.pop().value == {
        "stage": "norm_gate", "rows": 8192, "channels": 4096, "groups": 32,
        "tile": "xla"}
    assert said.pop().value == {
        "stage": "norm_gate", "rows": 8192, "channels": 4096, "groups": 32,
        "tile": [256, 512]}
    assert [r.kind for r in said] == ["compile"] * 3
    assert all(r.parent == span.id and r.seconds == 0 for r in said)
    assert said[0].value == {
        "stage": "conv_silu", "rows": 8192, "channels": 6144, "groups": 1,
        "tile": list(ssd_lib._stage_plan("conv_silu", 8192, 6144, 1, bf16, 4))}
    assert said[1].value == {
        "stage": "gate_norm", "rows": 8192, "channels": 4096, "groups": 8,
        "tile": list(ssd_lib._stage_plan("gate_norm", 8192, 4096, 8, bf16))}
    assert said[2].value["tile"] == "xla"
    assert "mixer_plan" in telemetry.COMPILE_RECORDS


# -- the ungated experts -------------------------------------------------------------


def _ungated_layer(held, ffn_dim=136, shared=0, num_experts=8):
    return moe_lib.SharedExpertMoE(
        num_experts=num_experts, ffn_dim=ffn_dim, top_k=2, held_experts=held,
        shared_ffn_dim=shared, route_scale=2.5, gated=False)


def _layer_params(layer, x, seed=1, std=0.3):
    return weights.make_like(jax.eval_shape(
        lambda: layer.init(jax.random.key(0), x, train=False)["params"]),
        [[".*", "normal", std]], weights.seed_key(seed))


def _dense(p, x, bias, first, held, scale=2.5):
    """The held experts of an ungated layer, as a loop over them."""
    scores = jax.nn.sigmoid(x[0] @ p["router"])
    _, chosen = jax.lax.top_k(scores + bias, 2)
    picked = jnp.take_along_axis(scores, chosen, -1)
    weight = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    out = 0.0
    for e in range(held):
        mine = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)
        h = jnp.square(jax.nn.relu(x[0] @ p["w_up"][e]))
        out = out + mine[:, None] * (h @ p["w_down"][e])
    return out[None]


@pytest.mark.parametrize("routing", ["level", "collapsed"])
def test_ungated_held_experts_are_the_dense_loop(routing):
    """Two held of sixteen at a width of 136 (a whole lane tile and a
    part-filled one in every blocked product): the result and the gradients
    of the router, ``w_up``, ``w_down`` and the rows are the dense loop's,
    whole in the bounded layout and, with every token on the held two, in its
    parts; nothing dropped either way."""
    d, T, E = 32, 64, 16
    assert gmm_lib._block_cols(136, d, 4) == 128      # 128 + 8 of 128
    x = jax.random.normal(jax.random.key(2), (1, T, d))
    layer = _ungated_layer((2, 6), num_experts=E)
    params = _layer_params(layer, x, std=0.15)    # outputs of the size of 1
    assert set(params) == {"router", "w_up", "w_down"}
    bias = jnp.zeros((E,))
    if routing == "collapsed":
        bias = bias.at[6:8].set(5.0)
    run = lambda p, x: layer.apply(
        {"params": p, "batch_stats": {"expert_bias": bias}}, x, train=False)
    with HIGHEST:
        _all_close(run(params, x), _dense(params, x, bias, 6, 2), 2e-6,
                   "result")
        got = jax.grad(lambda p, x: jnp.sum(jnp.sin(run(p, x))),
                       argnums=(0, 1))(params, x)
        want = jax.grad(lambda p, x: jnp.sum(jnp.sin(
            _dense(p, x, bias, 6, 2))), argnums=(0, 1))(params, x)
    _all_close(got, want, 2e-5, routing)
    _, sown = layer.apply(
        {"params": params, "batch_stats": {"expert_bias": bias}}, x,
        train=False, mutable=["telemetry"])
    assert float(sown["telemetry"]["moe_whole"][0]) == (routing == "level")
    zero = float(sown["telemetry"]["moe_gate_zero"][0])
    assert 0.4 < zero < 0.6 if routing == "level" else np.isnan(zero)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("routing", ["level", "collapsed"])
def test_ungated_bounded_backward_is_plain_ad_of_the_routine(routing, remat):
    """``_routed_bounded``'s hand-written backward on two-matrix experts
    against plain AD of ``_routed``: bit for bit from the kept ``up`` where
    the rows fit whole, to 1e-6 of a leaf's scale part by part."""
    T, d, f, E, k, held, first = 64, 32, 136, 16, 2, 2, 6
    keys = jax.random.split(jax.random.key(3), 5)
    tokens = jax.random.normal(keys[0], (T, d))
    scores = jax.random.uniform(keys[1], (T, E))
    if routing == "collapsed":
        scores = scores.at[:, first:first + held].add(5.0)
    _, chosen = jax.lax.top_k(scores, k)
    weights_ = jax.random.uniform(keys[2], (T, k), minval=0.2)
    experts = (0.3 * jax.random.normal(keys[3], (held, d, f)),
               0.3 * jax.random.normal(keys[4], (held, f, d)))
    counts = jnp.bincount(chosen.reshape(-1), length=E)[
        first:first + held].astype(jnp.int32)
    bt, chunks = 8, E // (2 * held)
    whole = bool(moe_lib._fits(counts, bt, moe_lib._bounded_tiles(
        chosen, experts, bt, chunks)))
    assert whole == (routing == "level")
    bounded = lambda t, w, e: moe_lib._routed_bounded(
        t, chosen, w, e, counts, first, bt, chunks, "relu2")
    plain = lambda t, w, e: moe_lib._routed(t, chosen, w, e, first, bt,
                                            act="relu2")
    grads = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2)))(
            tokens, weights_, experts)
    with HIGHEST:
        want_out, want = grads(plain)
        got_out, got = grads(jax.checkpoint(bounded) if remat else bounded)
    np.testing.assert_allclose(got_out, want_out, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if whole:
            np.testing.assert_array_equal(g, w)
        else:
            np.testing.assert_allclose(
                g, w, rtol=0, atol=1e-6 * float(jnp.max(jnp.abs(w))))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips hold an expert each of sixteen: the routed parts that
    the shares give, with the shared expert counted once, are the uncut
    layer's output; and a share's gradient of its own experts is the uncut
    layer's gradient of them."""
    E, d = 16, 32
    x = jax.random.normal(jax.random.key(4), (1, 48, d))
    whole = _ungated_layer(None, ffn_dim=24, shared=48, num_experts=E)
    params = _layer_params(whole, x)
    bias = {"expert_bias": 0.2 * jnp.sin(jnp.arange(float(E)))}
    run = lambda layer, p: layer.apply(
        {"params": p, "batch_stats": bias}, x, train=False)
    part = lambda first, held: {**params, **{
        k: params[k][first:first + held] for k in ("w_up", "w_down")}}
    share = lambda held, first: _ungated_layer(
        (held, first), ffn_dim=24, shared=48, num_experts=E)
    with HIGHEST:
        want = run(whole, params)
        no_shared = {k: v for k, v in params.items() if k != "shared"}
        shared = want - run(_ungated_layer(None, ffn_dim=24, num_experts=E),
                            no_shared)
        parts = [run(share(1, e), part(e, 1)) - shared for e in range(E)]
        np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                                   atol=2e-4)
        g = jax.grad(lambda p: jnp.sum(jnp.sin(run(whole, p))))(params)
        rest = want - run(share(4, 8), part(8, 4))
        mine = jax.grad(lambda p: jnp.sum(jnp.sin(
            run(share(4, 8), p) + rest)))(part(8, 4))
    for name in ("w_up", "w_down"):
        scale = float(jnp.max(jnp.abs(g[name])))
        np.testing.assert_allclose(mine[name], g[name][8:12],
                                   atol=1e-5 * scale)
    assert float(jnp.max(jnp.abs(parts[3]))) > 0.1   # a share does something


def test_the_form_is_the_one_the_model_gives():
    """Gated and ungated layers differ in their leaves and in nothing a flag
    or the environment says; an activation of the other form fails loudly."""
    x = jnp.ones((1, 8, 16))
    leaves = lambda gated: set(jax.eval_shape(lambda: moe_lib.SharedExpertMoE(
        num_experts=4, ffn_dim=8, top_k=2, shared_ffn_dim=8, gated=gated).init(
            jax.random.key(0), x, train=False))["params"])
    assert leaves(True) == {"router", "w_gate", "w_up", "w_down", "shared"}
    assert leaves(False) == {"router", "w_up", "w_down", "shared"}
    assert sorted(gmm_lib.FFN_FORMS) == [2, 3] and list(gmm_lib.ACTS) == [
        "relu2"]
    up = jnp.array([[-2.0, 0.0, 3.0]], jnp.bfloat16)
    np.testing.assert_array_equal(gmm_lib._activated(up), [[0.0, 0.0, 9.0]])
    with pytest.raises(KeyError):
        gmm_lib._activated(up, "silu")


# -- the model -------------------------------------------------------------------------


def _model_dict(module: nemotron_h.NemotronH, held_layers=None) -> dict:
    """The reference's ``model`` group for a program module."""
    held, first = module.held_experts or (module.num_experts, 0)
    return {
        "hidden_size": module.d_model, "head_dim": module.head_dim,
        "num_attention_heads": module.num_heads,
        "num_key_value_heads": module.num_kv_heads,
        "mamba_num_heads": module.mamba_heads,
        "mamba_head_dim": module.mamba_head_dim,
        "ssm_state_size": module.mamba_state, "n_groups": module.mamba_groups,
        "conv_kernel": module.mamba_conv, "chunk_size": module.mamba_chunk,
        "moe_intermediate_size": module.expert_ffn_dim,
        "moe_shared_expert_intermediate_size": module.shared_ffn_dim,
        "n_shared_experts": 1, "n_routed_experts": held,
        "held_experts_start": first, "routed_experts": module.num_experts,
        "num_experts_per_tok": module.top_k,
        "routed_scaling_factor": module.route_scale,
        "layer_norm_epsilon": module.epsilon,
        "hybrid_override_pattern": module.pattern,
        "num_hidden_layers": module.num_layers,
        "held_layers": held_layers or list(range(module.num_layers)),
        "load_balance_coeff": module.balance_coeff,
        "vocab_size": module.vocab_size}


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
    rows = [stats.get(f"block_{i}", {}).get("moe", {}).get(
        "expert_bias", jnp.zeros((module.num_experts,)))
        for i in range(module.num_layers)]
    return jnp.stack(rows)


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "share"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_matches_the_plain_reference(held, remat):
    """Logits, loss, every leaf's gradient and the bias after the step, in
    float32 (the Granite and Trinity tests' tolerances); and the reference's
    layer-by-layer gradient, which the chip's comparison follows, is its
    ``jax.grad``."""
    module = nemotron_h.nemotron_h_tiny(remat=remat, held_experts=held)
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
    assert float(jnp.sum(counts)) == 2 * 2 * 40 * module.top_k


def test_published_entry_and_its_share():
    """The published sizes give the published parameter count, and the
    share's is the configuration file's; no width differs."""
    full = nemotron_h.nemotron3_nano()
    assert nemotron_h.num_params(full) == 31_577_937_344
    assert (full.num_layers, full.pattern.count("M"), full.pattern.count("E"),
            full.pattern.count("*")) == (52, 23, 23, 6)
    share = nemotron_h.chip_share(full)
    assert nemotron_h.num_params(share) == 666_962_944
    assert (share.pattern, share.held_experts, share.vocab_size) == (
        "MEMEM*EME", (8, 0), 16384)
    assert nemotron_h.chip_share(full, chip=15).held_experts == (8, 120)
    widths = lambda m: {f: getattr(m, f) for f in m.__dataclass_fields__
                        if f not in ("pattern", "held_experts", "vocab_size",
                                     "parent", "name")}
    assert widths(share) == widths(full)
    leaves = jax.eval_shape(lambda: share.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    assert sum(x.size for x in jax.tree.leaves(leaves["params"])) \
        == 666_962_944
    tiny = nemotron_h.nemotron_h_tiny()
    made = jax.eval_shape(lambda: tiny.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), train=False))
    assert sum(x.size for x in jax.tree.leaves(made["params"])) \
        == nemotron_h.num_params(tiny)
    assert set("ME*") == set(tiny.pattern) and tiny.mamba_groups == 2
    assert tiny.expert_ffn_dim % 128 not in (0, tiny.expert_ffn_dim)


def test_forward_flops_agree_with_the_benchmarks_count():
    share = nemotron_h.chip_share(nemotron_h.nemotron3_nano())
    ours = 8192 * nemotron_h.forward_flops_per_token(share, 8192)
    theirs = reference.forward_flops(_model_dict(share), {"seq_len": 8192})
    assert ours == pytest.approx(theirs, rel=1e-12)
    assert ours == pytest.approx(5.857e12, rel=1e-3)


def test_a_letter_the_pattern_does_not_have_fails_loudly():
    module = nemotron_h.nemotron_h_tiny(pattern="ME-M")
    with pytest.raises(ValueError, match=r"unknown layer letters \['-'\]"):
        module.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="layers"):
        reference._sizes({**_model_dict(nemotron_h.nemotron_h_tiny()),
                          "hybrid_override_pattern": "ME-EM"})


def test_preset_trains_through_the_trainer_with_named_regions(devices):
    cfg = from_preset("nemotron3_nano_share", model="nemotron_h_tiny",
                      seq_len=32, global_batch_size=8, precision="fp32",
                      epochs=1, steps_per_epoch=3, workers=0, log_every=100,
                      checkpoint_dir=None, attn_impl="xla")
    trainer = Trainer(cfg)
    trainer.train_epoch(0)
    assert int(trainer.state.step) == 3
    bias = weights.flatten(trainer.state.batch_stats)
    assert sorted(bias) == ["block_1/moe/expert_bias",
                            "block_3/moe/expert_bias"]
    assert all(float(jnp.max(jnp.abs(b))) > 0 for b in bias.values())
    batch = next(iter(trainer._make_step_iter(0, 0)))
    text = trainer.train_step.lower(trainer.state, batch).as_text(
        debug_info=True)
    for scope in ("mamba", "conv1d", "ssd", "gated_norm", "attn", "mlp", "moe",
                  "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
                  "moe_shared", "embed", "head_loss"):
        assert f"/{scope}/" in text or f"/{scope}\"" in text, scope


def test_what_the_family_does_not_do_fails_loudly():
    module = nemotron_h.nemotron_h_tiny()
    with pytest.raises(NotImplementedError, match="recurrent-state cache"):
        module.apply({}, jnp.zeros((1, 4), jnp.int32), decode_ctx={})
    kw = dict(seq_len=32, dtype=jnp.float32, param_dtype=jnp.float32,
              remat=False, logits_dtype=jnp.float32, num_classes=0,
              image_size=0)
    with pytest.raises(ValueError, match="tensor- or sequence-parallel"):
        registry.create_model("nemotron_h_tiny", sp=True, **kw)
    for strategy in ("tp", "fsdp_tp"):
        cfg = from_preset("nemotron3_nano_share", model="nemotron_h_tiny",
                          strategy=strategy, seq_len=32, global_batch_size=8,
                          workers=0, checkpoint_dir=None)
        with pytest.raises(ValueError):
            Trainer(cfg)

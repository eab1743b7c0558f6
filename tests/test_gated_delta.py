"""``ops/gated_delta.py`` and the stages around it that the ``qwen3_next``
mixer adds: the chunked gated delta rule against the token-by-token recurrence
with every gradient (bf16 and float32, sequences that are no multiple of the
chunk, chunks of 16 and 64, a padded step), the chunk's triangular solve and
its hand-written transpose, the rule's plan record, ``ops/ssd.norm_gate``
against its two lines, and ``afmoe.GatedAttention``'s rotary slice. Float32 on
the CPU at toy widths (the XLA body); the Pallas kernel pair interpreted at
lane-wide heads against the same loop, recurrence and body."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_example_tpu.models import afmoe, llama
from pytorch_distributed_training_example_tpu.ops import gated_delta
from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib
from pytorch_distributed_training_example_tpu.utils import telemetry

HIGHEST = jax.default_matmul_precision("highest")


# -- the chunked gated delta rule ------------------------------------------------


def _rule_inputs(S, b=2, Hk=2, Hv=4, Dk=8, Dv=8, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed + S), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(k[0], (b, S, Hk, Dk))) / np.sqrt(Dk)
    key = unit(jax.random.normal(k[1], (b, S, Hk, Dk)))
    v = jax.random.normal(k[2], (b, S, Hv, Dv))
    g = -0.3 * jax.nn.softplus(jax.random.normal(k[3], (b, S, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (b, S, Hv)))
    return (q.astype(dtype), key.astype(dtype), v.astype(dtype), g, beta)


def _loop(q, k, v, g, beta):
    """The definition, a token at a time in float64 on the host: decay, the
    key's readout, the write of the difference, the query's readout."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    b, S, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    o = np.zeros((b, S, Hv, Dv))
    for i in range(b):
        for h in range(Hv):
            state = np.zeros((Dk, Dv))
            for t in range(S):
                key, query = k[i, t, h // (Hv // Hk)], q[i, t, h // (Hv // Hk)]
                state = np.exp(g[i, t, h]) * state
                read = state.T @ key
                state = state + np.outer(key, beta[i, t, h] * (v[i, t, h]
                                                               - read))
                o[i, t, h] = state.T @ query
    return o


def _recurrence(q, k, v, g, beta):
    """The same recurrence as a differentiable ``lax.scan`` over tokens."""
    R = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(a.astype(jnp.float32), R, axis=2) for a in (q, k))
    v = v.astype(jnp.float32)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t,
                                   b_t[..., None] * (v_t - read))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    first = lambda a: jnp.moveaxis(a, 1, 0)
    b, _, Hv, Dv = v.shape
    _, o = jax.lax.scan(token, jnp.zeros((b, Hv, q.shape[-1], Dv)),
                        tuple(map(first, (q, k, v, g, beta))))
    return first(o)


@pytest.mark.parametrize("S,chunk", [(64, 64), (37, 16), (100, 64), (5, 64),
                                     (48, 16)],
                         ids=lambda v: str(v))
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["fp32", "bf16"])
def test_gated_delta_rule_is_the_recurrence_with_every_gradient(dtype, tol, S,
                                                                chunk):
    """``o`` against a float64 loop over tokens, and the gradients of ``q``,
    ``k``, ``v``, ``g`` and ``beta`` against plain AD of the recurrence as a
    scan over tokens; sequences that are no multiple of the chunk (one shorter
    than a chunk); float32 out whatever comes in."""
    args = _rule_inputs(S, dtype=dtype)
    w = jax.random.normal(jax.random.key(9), (2, S, 4, 8))
    # jitted, as a model runs it (op by op, XLA's CPU backend has no thunk
    # for one of the scan's bf16 products)
    rule = jax.jit(lambda *a: gated_delta.gated_delta_rule(*a, chunk=chunk))
    with HIGHEST:
        o = rule(*args)
        assert o.dtype == jnp.float32 and o.shape == (2, S, 4, 8)
        want = _loop(*args)
        scale = float(np.abs(want).max())
        np.testing.assert_allclose(o, want, rtol=0, atol=tol * scale)
        loss = lambda rule: lambda *a: jnp.sum(rule(*a) * w)
        got = jax.grad(loss(rule), argnums=(0, 1, 2, 3, 4))(*args)
        ref = jax.grad(loss(_recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, r in zip(("q", "k", "v", "g", "beta"), got, ref):
        assert a.dtype == r.dtype, name
        r = np.asarray(r.astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(a.astype(jnp.float32)), r, rtol=0,
            atol=4 * tol * float(np.abs(r).max()), err_msg=name)


def test_chunks_of_16_and_64_agree_and_a_padded_step_changes_nothing():
    """The chunk is an implementation's: 16 and 64 give one answer; and a
    step with ``g = 0`` and ``beta = 0`` neither decays nor writes, so the
    tokens after it read what they read without it."""
    args = _rule_inputs(128)
    rule = jax.jit(gated_delta.gated_delta_rule, static_argnames="chunk")
    with HIGHEST:
        a = rule(*args, chunk=16)
        b = rule(*args, chunk=64)
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
        q, k, v, g, beta = _rule_inputs(40)
        idle = lambda x, fill: jnp.concatenate(
            [x[:, :20], jnp.full_like(x[:, :3], fill), x[:, 20:]], axis=1)
        with_idle = rule(idle(q, 0.3), idle(k, 0.3), idle(v, 5.0),
                         idle(g, 0.0), idle(beta, 0.0), chunk=16)
        plain = rule(q, k, v, g, beta, chunk=16)
    np.testing.assert_allclose(
        jnp.concatenate([with_idle[:, :20], with_idle[:, 23:]], axis=1),
        plain, rtol=0, atol=2e-6)
    with pytest.raises(ValueError, match="power of two"):
        gated_delta.gated_delta_rule(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="do not divide"):
        gated_delta.gated_delta_rule(q, k, v[:, :, :3], g[..., :3],
                                     beta[..., :3])


def test_the_rule_corrects_what_the_state_already_holds():
    """What the readout ``r_t`` is for: the same key written twice with
    ``beta = 1`` and no decay leaves the second value, not their sum (an
    outer-product state, ``ops/ssd.py``'s, would hold both)."""
    key = jnp.zeros((1, 2, 1, 4)).at[:, :, 0, 1].set(1.0)
    v = jnp.stack([jnp.full((1, 1, 4), 3.0), jnp.full((1, 1, 4), 7.0)], 1)
    o = gated_delta.gated_delta_rule(key, key, v, jnp.zeros((1, 2, 1)),
                                     jnp.ones((1, 2, 1)))
    np.testing.assert_allclose(o[0, :, 0], [[3.0] * 4, [7.0] * 4], atol=1e-6)


def test_unit_lower_inverse_is_the_inverse_and_its_gradient():
    """By halves from blocks of one, against ``linalg.inv``; the hand-written
    transpose against AD of ``linalg.inv`` under the triangle."""
    A = 0.3 * jnp.tril(jax.random.normal(jax.random.key(0), (3, 2, 32, 32)),
                       -1)
    w = jax.random.normal(jax.random.key(1), A.shape)
    eye = jnp.eye(32)
    with HIGHEST:
        T = gated_delta._unit_lower_inverse(A)
        np.testing.assert_allclose(T @ (eye + A), jnp.broadcast_to(
            eye, A.shape), atol=2e-5)
        assert not np.asarray(jnp.triu(T, 1)).any()
        got = jax.grad(lambda a: jnp.sum(
            gated_delta._unit_lower_inverse(a) * w))(A)
        want = jnp.tril(jax.grad(lambda a: jnp.sum(
            jnp.linalg.inv(eye + a) * w))(A), -1)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("width,body", [
    (128, {"body": "kernel", "key_heads_per_program": 8}),
    (8, {"body": "xla"})], ids=["published", "narrow"])
def test_delta_rule_plan_record_under_the_span_that_traced(width, body):
    """One ``delta_rule_plan`` record a traced call, a child of the span open
    on the tracing thread: what the call was given and which body runs it: the
    kernels with eight key heads a program at the published widths, XLA's
    scan at heads that are no whole lane tile."""
    rec = telemetry.recorder()
    mark = len(rec.records())
    shape = lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(s, dtype)
    bf16 = jnp.bfloat16
    with rec.span("trace_here", bucket=None):
        jax.eval_shape(gated_delta.gated_delta_rule,
                       shape(1, 8192, 16, width, dtype=bf16),
                       shape(1, 8192, 16, width, dtype=bf16),
                       shape(1, 8192, 32, width, dtype=bf16),
                       shape(1, 8192, 32), shape(1, 8192, 32))
    new = rec.records()[mark:]
    span = next(r for r in new if r.kind == "span" and r.name == "trace_here")
    said = [r for r in new if r.name == "delta_rule_plan"]
    assert [r.kind for r in said] == ["compile"]
    assert said[0].parent == span.id and said[0].seconds == 0
    assert said[0].value == {"key_heads": 16, "value_heads": 32,
                             "key_dim": width, "value_dim": width,
                             "chunk": 64, "chunks": 128, **body}
    assert "delta_rule_plan" in telemetry.COMPILE_RECORDS


# -- the Pallas kernel pair, interpreted -----------------------------------------


@pytest.fixture
def uncached():
    """The persistent compile cache off for a test of the interpreted kernels:
    their CPU executables are tens of megabytes each, and serialising one for
    the cache under six workers once took a worker down."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    # A worker that ran ``test_attention.py`` before this file holds that
    # file's interpreted kernels, and the next one's compile then aborts the
    # process (PR 52: two whole runs of three lost a worker here): let go of
    # every executable the process keeps before compiling these.
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _xla_body(*args, chunk=64):
    """The rule with the plan refused: today's ``jax.numpy`` body on the same
    inputs."""
    from unittest import mock
    with mock.patch.object(gated_delta, "_kernel_plan", lambda *a: None):
        return gated_delta.gated_delta_rule(*args, chunk=chunk)


@pytest.mark.parametrize("S,Hk,R", [(128, 1, 2), (150, 2, 2), (256, 1, 4)],
                         ids=lambda v: str(v))
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["fp32", "bf16"])
def test_kernels_are_the_recurrence_with_every_gradient(uncached, dtype, tol,
                                                        S, Hk, R):
    """The kernel pair at heads of 128 (two to four chunks, one sequence that
    is no multiple of the chunk, one and two pairs of value heads a key head),
    at the tolerances the XLA body is held to: ``o`` against the float64 loop,
    the five gradients against plain AD of the recurrence; and both against
    the XLA body on the same inputs."""
    args = _rule_inputs(S, b=1, Hk=Hk, Hv=Hk * R, Dk=128, Dv=128, dtype=dtype)
    assert gated_delta._kernel_plan(Hk, Hk * R, 128, 128, 64, dtype) == Hk
    w = jax.random.normal(jax.random.key(9), (1, S, Hk * R, 128))
    rule = jax.jit(gated_delta.gated_delta_rule)
    body = jax.jit(_xla_body)
    loss = lambda rule: lambda *a: jnp.sum(rule(*a) * w)
    every = (0, 1, 2, 3, 4)
    with HIGHEST:
        o = rule(*args)
        assert o.dtype == jnp.float32 and o.shape == w.shape
        want = _loop(*args)
        scale = float(np.abs(want).max())
        np.testing.assert_allclose(o, want, rtol=0, atol=tol * scale)
        np.testing.assert_allclose(o, body(*args), rtol=0, atol=tol * scale)
        got = jax.grad(loss(rule), argnums=every)(*args)
        ref = jax.grad(loss(_recurrence), argnums=every)(*args)
        other = jax.grad(loss(body), argnums=every)(*args)
    for name, a, r, x in zip(("q", "k", "v", "g", "beta"), got, ref, other):
        assert a.dtype == r.dtype, name
        a, r, x = (np.asarray(y.astype(jnp.float32)) for y in (a, r, x))
        top = float(np.abs(r).max())
        np.testing.assert_allclose(a, r, rtol=0, atol=4 * tol * top,
                                   err_msg=name)
        np.testing.assert_allclose(a, x, rtol=0, atol=4 * tol * top,
                                   err_msg=name + " against the XLA body")


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["fp32", "bf16"])
def test_kernel_keeps_the_state_each_chunk_started_from(uncached, dtype, tol):
    """The forward's second output, the backward's residual: the state before
    chunk ``c`` is the recurrence's after ``64 c`` tokens (zero before the
    first), float32 whatever the operands."""
    S, Hk, Hv = 192, 1, 2
    q, k, v, g, beta = _rule_inputs(S, b=1, Hk=Hk, Hv=Hv, Dk=128, Dv=128,
                                    dtype=dtype)
    gamma = jnp.cumsum(g.reshape(1, 3, 64, Hv), axis=2).reshape(g.shape)
    _, states = gated_delta._fwd_call(
        q.reshape(1, S, -1), k.reshape(1, S, -1), v.reshape(1, S, -1), gamma,
        beta, plan=(64, 128, 128, 2, 1))
    assert states.dtype == jnp.float32 and states.shape == (1, 3, Hv, 128,
                                                            128)
    f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    q, k, v, g, beta = map(f64, (q, k, v, g, beta))
    state = np.zeros((Hv, 128, 128))
    for t in range(128):
        if t % 64 == 0:
            np.testing.assert_allclose(
                states[0, t // 64], state, rtol=0,
                atol=tol * max(np.abs(state).max(), 1e-3))
        for h in range(Hv):
            state[h] *= np.exp(g[0, t, h])
            read = state[h].T @ k[0, t, 0]
            state[h] += np.outer(k[0, t, 0], beta[0, t, h] * (v[0, t, h]
                                                              - read))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_in_kernel_solve_is_unit_lower_inverse(uncached, seed):
    """The kernels' solve for two pairs at once (``_diagonals``,
    ``_substitute``, ``_from_diagonals``: the diagonal blocks of 16 by forward
    substitution in diagonal form, the levels at 16 and 32 by halves) against
    ``_unit_lower_inverse`` on the same ``A``, for both heads of each pair: a
    kernel of its own around it, interpreted."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Q, pairs = 64, 2
    key = jax.random.split(jax.random.key(seed), 3)
    k = jax.random.normal(key[0], (pairs, Q, 128))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = jnp.cumsum(-0.3 * jax.nn.softplus(
        jax.random.normal(key[1], (pairs, 2, Q))), axis=2)
    beta = jax.nn.sigmoid(jax.random.normal(key[2], (pairs, 2, Q)))

    def kernel(k_ref, g_ref, b_ref, out_ref, a_ref, diag):
        rows = lambda ref: [ref[p].reshape(1, 2 * Q) for p in range(pairs)]
        found = gated_delta._diagonals([k_ref[p] for p in range(pairs)],
                                       rows(g_ref), rows(b_ref))
        for p in range(pairs):
            diag[p] = found[p]
        diag[...] = gated_delta._substitute(diag[...])
        row, s, first = gated_delta._pair_grid(Q)
        for p in range(pairs):
            col = lambda ref: jnp.where(first, ref[p, 0:1, :].T,
                                        ref[p, 1:2, :].T)
            kk = jnp.dot(k_ref[p], k_ref[p].T)
            D = jnp.exp(jnp.where(row >= s, col(g_ref) - rows(g_ref)[p],
                                  -jnp.inf))
            a_ref[p] = jnp.where(row > s, jnp.concatenate([kk, kk], 1) * D
                                 * col(b_ref), 0.0)
        for p, inv in enumerate(gated_delta._from_diagonals(
                [diag[p] for p in range(pairs)],
                [a_ref[p] for p in range(pairs)])):
            out_ref[p] = inv

    pair = jax.ShapeDtypeStruct((pairs, Q, 2 * Q), jnp.float32)
    with HIGHEST:
        inv, A = pl.pallas_call(
            kernel, out_shape=(pair, pair), interpret=True,
            scratch_shapes=[pltpu.VMEM((pairs, gated_delta.BASE, 2 * Q),
                                       jnp.float32)])(k, g, beta)
        heads = lambda a: jnp.stack([a[..., :Q], a[..., Q:]])
        want = gated_delta._unit_lower_inverse(heads(A))
    assert float(jnp.abs(A).max()) > 0.05
    np.testing.assert_allclose(heads(inv), want, rtol=0, atol=2e-6)
    assert not np.asarray(jnp.triu(heads(inv), 1)).any()


def test_kernel_plan_admits_what_the_kernels_are_written_for():
    """Shapes decide the body, nothing else: bf16 or float32, a chunk of 64,
    an even number of value heads a key head, head widths of whole lane
    tiles; G the most key heads whose backward program fits VMEM."""
    plan = gated_delta._kernel_plan
    assert plan(16, 32, 128, 128, 64, jnp.bfloat16) == 8
    assert plan(16, 32, 128, 128, 64, jnp.float32) == 8
    assert plan(2, 4, 128, 256, 64, jnp.bfloat16) == 2
    assert plan(16, 32, 128, 128, 64, jnp.float16) is None
    assert plan(16, 32, 128, 128, 32, jnp.bfloat16) is None     # the chunk
    assert plan(16, 16, 128, 128, 64, jnp.bfloat16) is None     # no pair
    assert plan(16, 32, 64, 128, 64, jnp.bfloat16) is None      # half a tile
    assert plan(2, 4, 8, 8, 64, jnp.float32) is None            # the tests'


# -- the norm-then-gate stage, the rotary slice ----------------------------------


def test_norm_gate_is_its_two_lines_and_gate_norm_is_the_other_order():
    """``norm(y) * w * silu(z)`` a head, one ``w`` for all heads; Mamba-2's
    ``gate_norm`` (gate first) gives another answer on the same inputs, and
    its default body did not move."""
    k = jax.random.split(jax.random.key(0), 3)
    y = jax.random.normal(k[0], (2, 7, 4 * 16))
    z = jax.random.normal(k[1], (2, 7, 4 * 16))
    scale = 1.0 + 0.1 * jax.random.normal(k[2], (16,))
    got = ssd_lib.norm_gate(y, z, scale, groups=4, epsilon=1e-6,
                            dtype=jnp.float32)
    heads = y.reshape(2, 7, 4, 16)
    normed = heads / jnp.sqrt(jnp.mean(heads ** 2, -1, keepdims=True) + 1e-6)
    want = (normed * scale).reshape(y.shape) * (z / (1 + jnp.exp(-z)))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    other = ssd_lib.gate_norm(y, z, jnp.tile(scale, 4), groups=4,
                              epsilon=1e-6, dtype=jnp.float32)
    np.testing.assert_array_equal(other, ssd_lib.group_rms_norm(
        y * jax.nn.silu(z), jnp.tile(scale, 4), 4, 1e-6, jnp.float32))
    assert float(jnp.max(jnp.abs(other - got))) > 0.1
    assert ssd_lib.norm_gate(y, z.astype(jnp.bfloat16), scale, groups=4,
                             epsilon=1e-6,
                             dtype=jnp.bfloat16).dtype == jnp.bfloat16


def test_partial_rotary_leaves_the_other_columns_and_the_defaults_alone():
    """``rotary_dim`` 4 of a head of 16: a key's columns 4.. are what they
    are without positions, columns 0..3 are ``llama.rope`` of those four
    alone; and ``GatedAttention`` at its defaults (no ``rotary_dim``) gives
    the digits it gave: the whole head rotated under a window, nothing in a
    full layer."""
    h = jax.random.normal(jax.random.key(0), (1, 12, 32))
    sizes = dict(num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=1e4,
                 epsilon=1e-6, dtype=jnp.float32, param_dtype=jnp.float32,
                 attn_impl="xla", window=None)
    ours = afmoe.GatedAttention(**sizes, rotary=True, rotary_dim=4)
    params = ours.init(jax.random.key(1), h)
    assert set(params["params"]) == {"query", "key", "value", "gate", "out",
                                     "q_norm", "k_norm"}
    seen = {}
    real = afmoe.attn_lib.attention

    def spy(q, k, v, **kw):
        seen["q"], seen["k"] = q, k
        return real(q, k, v, **kw)

    def keys(module):
        afmoe.attn_lib.attention = spy
        try:
            module.apply(params, h)
        finally:
            afmoe.attn_lib.attention = real
        return seen["q"], seen["k"]

    q_cut, k_cut = keys(ours)
    q_none, k_none = keys(afmoe.GatedAttention(**sizes))        # a full layer
    q_all, k_all = keys(afmoe.GatedAttention(**sizes, rotary=True))
    np.testing.assert_array_equal(k_cut[..., 4:], k_none[..., 4:])
    np.testing.assert_array_equal(q_cut[..., 4:], q_none[..., 4:])
    positions = jnp.arange(12)[None, :]
    np.testing.assert_allclose(
        k_cut[..., :4], llama.rope(k_none[..., :4], positions, 1e4),
        atol=1e-6)
    assert float(jnp.max(jnp.abs(k_all[:, 5, :, 4:] - k_none[:, 5, :, 4:]))) \
        > 1e-3
    # the defaults are the afmoe layers': the whole head under a window
    np.testing.assert_allclose(
        keys(afmoe.GatedAttention(**{**sizes, "window": 64}))[1],
        llama.rope(k_none, positions, 1e4), atol=1e-6)
    assert afmoe.GatedAttention(**sizes).rotary_dim is None

"""The gated delta rule of Gated DeltaNet, chunked (Yang, Kautz, Hatamizadeh
2024, arXiv:2412.06464; the linear-attention mixer of the ``qwen3_next``
models).

Per value head a matrix state ``S`` in R^{Dk x Dv} (key by value), decayed by
one scalar a step and corrected by the key's own readout before the write::

    S' = exp(g_t) S_{t-1};  r_t = S'^T k_t
    S_t = S' + k_t (outer) beta_t (v_t - r_t);  o_t = S_t^T q_t,   S_0 = 0

(``ops/ssd.py``'s state is written by the outer product alone: ``r_t`` is what
this rule adds.) :func:`gated_delta_rule` computes it in chunks of ``chunk``
tokens. With ``gamma`` the running sum of ``g`` inside a chunk, ``D_ij =
exp(gamma_i - gamma_j)`` for ``i >= j`` (every exponent <= 0), ``K_b = beta *
K`` and ``V_b = beta * V``::

    T = (I + tril(K_b K^T o D, -1))^-1
    U = T V_b;  W = T (K_b * exp(gamma))
    V_new = U - W S                                   (S: the chunk's start)
    O = (Q * exp(gamma)) S + tril(Q K^T o D) V_new
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V_new

``T`` is the chunk's triangular solve (exact elimination, as forward
substitution is). The decays, ``T`` and the state are float32 whatever the
compute dtype; every product takes operands in ``v.dtype`` and accumulates in
float32; ``o`` comes back float32.

Two bodies, one algorithm, chosen by :func:`_kernel_plan` from what the call
shows (dtype, chunk, head widths, value heads a key head):

- a Pallas kernel pair under a ``custom_vjp`` (``delta_rule_fwd`` /
  ``delta_rule_bwd``): a program is one chunk of ``G`` key heads with the
  value heads they serve, the grid walks a sequence's chunks in order (the
  backward from the last) with the state in float32 scratch, and nothing of a
  chunk but its inputs, its output and the state it started from reaches HBM:
  ``D``, ``A``, ``T``, ``U``, ``W``, ``M`` and ``V_new`` live and die in VMEM.
  The backward remakes them from the inputs and the saved start states, its
  only residuals. The solve: the diagonal blocks of 16 by forward
  substitution on the vector unit (:func:`_substitute`), the levels at 16 and
  32 by halves as float32 matmuls (:func:`_from_diagonals`). A program's
  heads go through every stage together (``TOGETHER``): one head's work is
  a chain of dependent operations, and the chip overlaps only what is
  independent. Compiled on ``tpu``, interpreted on ``cpu``; under a mesh per
  device with the batch sharded.
- :func:`_rule_xla`, plain ``jax.numpy`` that XLA differentiates, under its
  own ``jax.checkpoint`` (everything that does not read the carried state is
  made for all chunks at once and goes through HBM, and the three lines that
  do run under one ``lax.scan``): every shape the plan refuses, and what the
  kernels are tested against.

No packed documents (no state reset) and no recurrent-state cache for serving.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_example_tpu.ops import backend
from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib
from pytorch_distributed_training_example_tpu.ops.flash_attention import (
    _nt_dot, _tn_dot)
from pytorch_distributed_training_example_tpu.ops.ssd import (
    LANES, _column, _columns, _row, _set_column)

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: Block size from which a level of :func:`_unit_lower_inverse` is a matmul;
#: under it the products are a multiply and a sum that XLA fuses (a batched
#: dot of 8 x 8 blocks fills a 128 x 128 systolic array to a 256th).
_DOT_FROM = 16


def _small_matmul(a, b):
    """``a @ b`` over the last two axes in float32: a matmul at full float32
    precision from ``_DOT_FROM`` columns on, a fused multiply and sum under."""
    if a.shape[-1] >= _DOT_FROM:
        return jnp.matmul(a, b, precision=HIGHEST)
    return jnp.sum(a[..., :, :, None] * b[..., None, :, :], axis=-2)


@jax.custom_vjp
def _unit_lower_inverse(A):
    """``(I + A)^-1`` for ``A [..., C, C]`` strictly lower triangular, ``C`` a
    power of two, float32. By halves: with the inverses ``P``, ``R`` of two
    neighbouring diagonal blocks and ``L`` the block under the first, the
    inverse of the pair is ``[[P, 0], [-R L P, R]]``; from blocks of one (whose
    inverse is 1) that is ``log2(C)`` levels of two products each. Exact
    elimination: no power of ``A`` is formed, so nothing large cancels (the
    Neumann series' ``A^k`` reach ``C`` choose ``k`` times the entries' k-th
    power before they vanish). Differentiated by hand from the inverse alone
    (``dA = -T^T dT T^T`` under the triangle): plain AD keeps four arrays of
    the inverse's size a level, 1.6 GB a layer at the published widths."""
    C = A.shape[-1]
    lead = A.shape[:-2]
    inv = jnp.ones(lead + (C, 1, 1), F32)
    s = 1
    while s < C:
        n = C // (2 * s)
        tiles = A.reshape(lead + (n, 2, s, n, 2, s))
        # the block under each pair's first diagonal block, [..., n, s, s]:
        # picked by a mask and a sum (one term of each sum is not zero), not
        # by a gather
        own = jnp.eye(n, dtype=bool)[:, None, :, None]
        under = jnp.sum(jnp.where(own, tiles[..., :, 1, :, :, 0, :], 0.0),
                        axis=-2)
        pairs = inv.reshape(lead + (n, 2, s, s))
        P, R = pairs[..., 0, :, :], pairs[..., 1, :, :]
        low = -_small_matmul(R, _small_matmul(under, P))
        zero = jnp.zeros_like(P)
        inv = jnp.concatenate([jnp.concatenate([P, zero], -1),
                               jnp.concatenate([low, R], -1)], -2)
        s *= 2
    return inv.reshape(lead + (C, C))


def _unit_lower_inverse_fwd(A):
    T = _unit_lower_inverse(A)
    return T, T


def _unit_lower_inverse_bwd(T, dT):
    Tt = jnp.swapaxes(T, -1, -2)
    dA = -jnp.matmul(Tt, jnp.matmul(dT, Tt, precision=HIGHEST),
                     precision=HIGHEST)
    C = T.shape[-1]
    return (jnp.where(jnp.tril(jnp.ones((C, C), bool), -1), dA, 0.0),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _say_plan(Hk, Hv, Dk, Dv, Q, chunks, plan):
    """One ``delta_rule_plan`` record a traced call of
    :func:`gated_delta_rule`: what it was given, and which body runs the
    chunks: the kernels, with the key heads a program, or XLA's scan."""
    said = {"key_heads": Hk, "value_heads": Hv, "key_dim": Dk,
            "value_dim": Dv, "chunk": Q, "chunks": chunks}
    if plan is None:
        said["body"] = "xla"
    else:
        said.update(body="kernel", key_heads_per_program=plan)
    ssd_lib._say("delta_rule_plan", said)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, *, chunk: int = 64) -> jax.Array:
    """Chunked gated delta rule.

    ``q``, ``k`` [b, S, Hk, Dk] (already normalised and scaled: the rule takes
    them as they are) and ``v`` [b, S, Hv, Dv] in the compute dtype, a key
    head serving ``Hv / Hk`` value heads in a row; ``g`` [b, S, Hv] float32,
    the log of a step's decay (<= 0); ``beta`` [b, S, Hv] float32, the write's
    strength. Returns ``o`` [b, S, Hv, Dv] in float32, as accumulated. A
    sequence that is no multiple of ``chunk`` (a power of two) is padded here:
    a padded step has ``g = 0`` and ``beta = 0``, so it neither decays nor
    writes.

    Shapes :func:`_kernel_plan` admits take the Pallas kernels; every other
    one takes :func:`_rule_xla` under a ``jax.checkpoint`` (its chunk tensors,
    1.7 GB a layer at the published widths, then live only while its own
    transpose runs), which is also what the kernels are tested against.
    """
    b, S, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    if Hv % Hk:
        raise ValueError(f"{Hk} key heads do not divide {Hv} value heads")
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is no power of two")
    cd = v.dtype
    Q = min(chunk, 1 << max(S - 1, 0).bit_length())
    pad = -S % Q
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = widen(q), widen(k), widen(v), widen(g), widen(beta)
    Sp = S + pad
    q, k, g, beta = q.astype(cd), k.astype(cd), g.astype(F32), beta.astype(F32)
    plan = _kernel_plan(Hk, Hv, Dk, Dv, Q, cd)
    _say_plan(Hk, Hv, Dk, Dv, Q, Sp // Q, plan)
    if plan is None:
        o = jax.checkpoint(functools.partial(_rule_xla, Q=Q))(q, k, v, g, beta)
    else:
        # the running log-decay inside a chunk stays in XLA, which
        # differentiates it: small [b, S, Hv] float32 work, as a product with
        # a triangle of ones at full precision (``ops/ssd.py`` says why not
        # ``cumsum``)
        gamma = jnp.einsum("ts,bcsh->bcth", jnp.tril(jnp.ones((Q, Q), F32)),
                           g.reshape(b, -1, Q, Hv), precision=HIGHEST)
        o = _rule_kernels(
            q.reshape(b, Sp, Hk * Dk), k.reshape(b, Sp, Hk * Dk),
            v.reshape(b, Sp, Hv * Dv), gamma.reshape(b, Sp, Hv), beta,
            (Q, Dk, Dv, Hv // Hk, plan)).reshape(b, Sp, Hv, Dv)
    return o[:, :S] if pad else o


def _rule_xla(q, k, v, g, beta, Q):
    """The rule in plain ``jax.numpy`` on a sequence of whole chunks of ``Q``
    (``q``, ``k`` in ``v.dtype``; ``g``, ``beta`` float32): XLA
    differentiates it; everything that does not read the carried state is
    made for all chunks at once and passes through HBM, the three lines that
    do run under one ``lax.scan`` over the chunks."""
    b, S, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    R, cd, nc = Hv // Hk, v.dtype, S // Q
    # by key head ``h`` and the ``r``-th value head it serves, a chunk's tokens
    # next to the width: every product below is a batched matmul as it stands
    heads = lambda a: jnp.moveaxis(a.reshape(b, nc, Q, *a.shape[2:]), 2,
                                   a.ndim - 1)
    qc, kc = heads(q.astype(cd)), heads(k.astype(cd))       # [b,c,h,Q,n]
    vc = heads(v.reshape(b, S, Hk, R, Dv))                  # [b,c,h,r,Q,p]
    gc = jnp.moveaxis(g.astype(F32).reshape(b, nc, Q, Hk, R), 2, -1)
    bc = jnp.moveaxis(beta.astype(F32).reshape(b, nc, Q, Hk, R), 2, -1)
    # the running log-decay inside a chunk, as a product with a triangle of
    # ones at full precision (``ops/ssd.py`` says why not ``cumsum``)
    gamma = jnp.einsum("ts,bchrs->bchrt", jnp.tril(jnp.ones((Q, Q), F32)), gc,
                       precision=HIGHEST)                   # [b,c,h,r,Q]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    # masked before the exp: above the diagonal the difference is positive
    # and may overflow, and 0 * inf in the backward would be NaN
    D = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :],
                          -jnp.inf))                        # [b,c,h,r,t,s]
    # a key head's rows for each value head it serves, a scalar a row
    scaled = lambda a, by: (a[:, :, :, None].astype(F32)
                            * by[..., None]).astype(cd)

    # 1. the chunk's solve: T = (I + tril(K_b K^T o D, -1))^-1
    kk = jnp.einsum("bchtn,bchsn->bchts", kc, kc,
                    preferred_element_type=F32)
    A = jnp.where(jnp.tril(lower, -1),
                  kk[:, :, :, None] * D * bc[..., :, None], 0.0)
    T = _unit_lower_inverse(A).astype(cd)                   # [b,c,h,r,t,s]
    U = jnp.einsum("bchrts,bchrsp->bchrtp", T,
                   (vc.astype(F32) * bc[..., None]).astype(cd),
                   preferred_element_type=F32)
    W = jnp.einsum("bchrts,bchrsn->bchrtn", T,
                   scaled(kc, bc * jnp.exp(gamma)),
                   preferred_element_type=F32).astype(cd)

    # 2. what the chunk's own tokens give each other: tril(Q K^T o D)
    qk = jnp.einsum("bchtn,bchsn->bchts", qc, kc, preferred_element_type=F32)
    M = (qk[:, :, :, None] * D).astype(cd)                  # zero above t = s
    q_in = scaled(qc, jnp.exp(gamma))                       # reads the start
    k_out = scaled(kc, jnp.exp(gamma[..., -1:] - gamma))    # writes the end
    total = jnp.exp(gamma[..., -1])                         # [b,c,h,r]

    # 3. chunk by chunk: the state is read before it is written
    def step(state, xs):
        U_c, W_c, M_c, q_c, k_c, total_c = xs
        start = state.astype(cd)
        v_new = U_c - jnp.einsum("bhrtn,bhrnp->bhrtp", W_c, start,
                                 preferred_element_type=F32)
        fresh = v_new.astype(cd)
        o = jnp.einsum("bhrtn,bhrnp->bhrtp", q_c, start,
                       preferred_element_type=F32) \
            + jnp.einsum("bhrts,bhrsp->bhrtp", M_c, fresh,
                         preferred_element_type=F32)
        state = total_c[..., None, None] * state + jnp.einsum(
            "bhrsn,bhrsp->bhrnp", k_c, fresh, preferred_element_type=F32)
        return state, o

    by_chunk = lambda a: jnp.moveaxis(a, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, Hk, R, Dk, Dv), F32),
                        tuple(map(by_chunk, (U, W, M, q_in, k_out, total))))
    # [c,b,h,r,Q,p] -> [b,c,Q,h,r,p]
    o = jnp.moveaxis(by_chunk(o), 4, 2).reshape(b, S, Hv, Dv)
    return o


# ---------------------------------------------------------------------------
# The Pallas kernel pair. A program is one (sequence, run of G key heads,
# chunk); the grid walks a run's chunks in order (the backward from the last)
# with the state of its value heads, [G*R, Dk, Dv] float32, in VMEM scratch.
# ``q``, ``k``, ``v``, ``o`` and their cotangents stay lane-dense
# [b, S, heads * width]: a head is a lane slice. Everything [Q, Q] is held for
# a *pair* of value heads side by side on the 128 lanes, [Q, 2Q] (the chunk is
# 64): one vector operation serves both, and so does one pass of the matrix
# unit, ``[X0 | X1] @ [[Y0, 0], [0, Y1]] = [X0 Y0 | X1 Y1]`` (:func:`_both`).
# ---------------------------------------------------------------------------

#: Rows of a diagonal block of ``I + A`` that :func:`_substitute` inverts by
#: forward substitution on the vector unit; the levels from here to the chunk
#: are float32 matmuls by halves (a level took 0.8 ms a pass of the
#: published layer on the chip, the fifteen steps for all of a program's
#: pairs at once 0.3: blocks of 8 and a third level read 10% slower, blocks
#: of 32 and one level 45%).
BASE = 16
#: Bytes :func:`_kernel_plan` lets the backward program hold, under the 16 MB
#: of scoped VMEM the v5e compiler gives a kernel.
KERNEL_VMEM_BUDGET = ssd_lib.KERNEL_VMEM_BUDGET


def _kernel_plan(Hk, Hv, Dk, Dv, Q, dtype):
    """G, the key heads a program of the kernels holds, or None where the
    kernels do not serve the shape and :func:`_rule_xla` runs instead.

    Admitted: bf16 or float32 operands (Mosaic refuses fp16 loads); a chunk of
    64, so that a pair of value heads' [Q, Q] tiles fill the 128 lanes; an
    even number of value heads a key head (a pair is one key head's); head
    widths of whole lane tiles. G is the largest divisor of ``Hk`` whose
    pairs are whole sublane tiles of the per-pair rows (or all of them) and
    whose backward program fits the budget: its double-buffered blocks (q, k
    and their cotangents, v and its cotangent, ``do`` in float32, the start
    states) and every held head's state cotangent in scratch. At the
    published widths (16 key heads to 32 value heads of 128) that is 8 key
    heads a program in bf16 and in float32."""
    if dtype not in (jnp.bfloat16, jnp.float32):
        return None
    R = Hv // Hk
    if 2 * Q != LANES or R % 2 or Dk % LANES or Dv % LANES:
        return None
    item = jnp.dtype(dtype).itemsize
    for G in range(Hk, 0, -1):
        pairs = G * R // 2
        if Hk % G or (pairs % 8 and G != Hk):
            continue
        held = G * R * Dk * Dv * 4
        blocks = 2 * (4 * Q * G * Dk * item + Q * G * R * Dv * (2 * item + 4)
                      + held + 6 * Q * LANES * 4)
        if blocks + held + 2 ** 20 <= KERNEL_VMEM_BUDGET:
            return G
    return None


def _dot32(a, b):
    """``a @ b`` of float32 tiles at full float32 precision."""
    return jnp.dot(a, b, preferred_element_type=F32, precision=HIGHEST)


def _pair_grid(Q):
    """Index tiles of a pair's [Q, 2Q]: the token ``t`` (row), the token ``s``
    within its head (lane mod Q), and whether the lane is the first head's."""
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, 2 * Q), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, 2 * Q), 1)
    return row, lane & (Q - 1), lane < Q


def _both(y):
    """``[[Y0, 0], [0, Y1]]`` [2Q, 2Q] from a pair's ``[Y0 | Y1]`` [Q, 2Q]:
    the right operand under which one matmul serves both heads."""
    Q = y.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (2 * Q, 2 * Q), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (2 * Q, 2 * Q), 1)
    return jnp.where((row < Q) == (lane < Q),
                     jnp.concatenate([y, y], axis=0), 0.0)


def _side_by_side(a):
    """``[a | a]``: one key head's [Q, Q] tile under both heads of a pair."""
    return jnp.concatenate([a, a], axis=1)


# A program's heads in lockstep. What a pair of value heads asks of the chip
# is one long chain of dependent operations (a matmul's result feeds the next
# matmul's operand; on the chip a chain of four float32 matmuls took 1,400
# cycles a pair, and the substitution's fifteen steps 2,700, whatever the
# tiles' sizes), and a loop over the heads runs the chains one after another.
# So the bodies below are written *a stage at a time over a list of heads*
# (``TOGETHER`` key heads a turn of the loop over the program's): a stage's
# operations do not depend on one another, and the chip overlaps them.
#
# The solve, ``(I + A)^-1`` for a pair of value heads, in three steps.
# (1) The diagonal blocks of ``BASE`` rows, all of both heads at once, by
# forward substitution in *diagonal form*: with ``X_e[t] = X[t, t - e]`` (the
# e-th diagonal under the main one, a token a lane), ``T_d[t] = -sum_{e=1..d}
# A_e[t] T_{d-e}[t - e]``. :func:`_diagonals` makes ``A``'s diagonals where
# they lie; :func:`_substitute` is the recurrence, for every pair of the
# program at once. (2) :func:`_from_diagonals` puts ``T``'s diagonals back at
# rows and columns and (3) goes from blocks of ``BASE`` to the chunk by
# halves, as :func:`_unit_lower_inverse` does, two float32 matmuls a level for
# both heads (:func:`_both`). Exact elimination in float32 throughout.

#: Key heads a turn of a kernel's loop over its program's heads works
#: together, a stage at a time.
TOGETHER = 4


def _in_turns(n, body):
    """``body(js)`` for the program's ``n`` key heads, ``js`` a list of
    ``TOGETHER`` traced indices a turn (all of them where ``TOGETHER`` does
    not divide ``n``), in a loop left rolled."""
    step = TOGETHER if n % TOGETHER == 0 else n

    def turn(i, carry):
        body([i * step + u for u in range(step)])
        return carry

    jax.lax.fori_loop(0, n // step, turn, 0)


def _roll_rows_on(x, shift):
    """Row ``r`` of ``x`` rolled ``shift + r`` lanes on (the rotate takes no
    stride the other way)."""
    return pltpu.roll(x, shift, x.ndim - 1, stride=1, stride_axis=x.ndim - 2)


def _diagonals(ks, g_rows, b_rows):
    """For each pair of value heads, ``a[e][t] = A[t, t - e]`` inside the
    diagonal blocks of ``BASE`` rows (zero elsewhere, and for ``e = 0``),
    float32 [BASE, 2Q]: from its key head's ``k`` [Q, Dk] and its running
    log-decay and beta as rows [1, 2Q]. ``K K^T`` is taken with its columns in
    reverse order (``k``'s rows reversed by a permutation on the matrix unit,
    which is exact), so that rolling every row on by its own index leaves
    ``kk[t, t - e]`` at ``[t, e]``; a transpose then puts a token a lane."""
    Q, cd = ks[0].shape[0], ks[0].dtype
    flip = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            + jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1) == Q - 1
            ).astype(cd)
    backs = [jnp.dot(flip, k, preferred_element_type=F32,
                     precision=HIGHEST if cd == F32 else None).astype(cd)
             for k in ks]
    kks = [_nt_dot(k, back) for k, back in zip(ks, backs)]
    kks = [_roll_rows_on(_side_by_side(kk), 1).T[:BASE] for kk in kks]
    shape = (BASE, 2 * Q)
    e = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # inside its block of BASE, under the main diagonal; elsewhere the
    # exponent may be positive
    valid = (e >= 1) & ((lane & (BASE - 1)) >= e)
    out = []
    for kk, g_row, b_row in zip(kks, g_rows, b_rows):
        g_before = _roll_rows_on(jnp.broadcast_to(g_row, shape), 0)
        out.append(jnp.where(valid, _side_by_side(kk) * jnp.exp(
            jnp.where(valid, g_row - g_before, 0.0)) * b_row, 0.0))
    return out


def _substitute(a):
    """``T``'s diagonals from ``A``'s, [pairs, BASE, 2Q] both, ``T``'s in
    reverse order (``T_d`` at sublane ``BASE - 1 - d``). ``facing[e][t]``
    holds ``T_{d-e}[t - e]``: the tile moves one sublane down and one lane on
    a step, so the pairs of the sum always face each other. ``BASE - 1``
    steps, unrolled: their length does not depend on the program."""
    e = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    one = jnp.ones((a.shape[0], 1, a.shape[2]), F32)
    facing = jnp.where(e == 1, one, 0.0)
    found = [one]
    for d in range(1, BASE):
        found.append(-jnp.sum(a * facing, axis=1, keepdims=True))
        if d < BASE - 1:
            facing = pltpu.roll(pltpu.roll(
                jnp.where(e == 0, found[d], facing), 1, 1), 1, 2)
    return jnp.concatenate(found[::-1], axis=1)


def _from_diagonals(t_revs, As):
    """For each pair, ``(I + A)^-1`` [Q, 2Q] float32 from the diagonals of
    its blocks of ``BASE`` (``t_rev[j][t] = T[t, t - (BASE - 1 - j)]``) and
    ``A``."""
    Q = As[0].shape[0]
    row, s, first = _pair_grid(Q)
    level = BASE.bit_length() - 1
    invs = []
    for t_rev in t_revs:
        by_token = jnp.concatenate(
            [t_rev, jnp.zeros((2 * Q - BASE, 2 * Q), F32)], axis=0).T
        inv = jnp.where(first,
                        _roll_rows_on(by_token[:Q], LANES - (BASE - 1)),
                        _roll_rows_on(by_token[Q:], Q - (BASE - 1)))
        invs.append(jnp.where((row >> level) == (s >> level), inv, 0.0))
    while (1 << level) < Q:
        under = (((row >> level) & 1) == 1) & (
            (s >> level) == (row >> level) - 1)
        lows = [_dot32(jnp.where(under, A, 0.0), _both(inv))
                for A, inv in zip(As, invs)]
        invs = [inv - _dot32(inv, _both(low)) for inv, low in zip(invs, lows)]
        level += 1
    return invs


def _all_diagonals(k_ref, gr_ref, br_ref, diag, R):
    """``diag[pair]``: every pair's :func:`_substitute` of its
    :func:`_diagonals`, a program's [G*R/2, BASE, 2Q] scratch."""
    half = R // 2
    Dk = k_ref.shape[2] // (diag.shape[0] // half)
    g_rows, b_rows = gr_ref[0, 0], br_ref[0, 0]               # [G*R/2, 2Q]

    def heads(js):
        pairs = [j * half + a for j in js for a in range(half)]
        found = _diagonals(
            [k_ref[0, :, _lanes(j, Dk)] for j in js for _ in range(half)],
            [_row(g_rows, pair) for pair in pairs],
            [_row(b_rows, pair) for pair in pairs])
        for pair, a in zip(pairs, found):
            diag[pair] = a

    _in_turns(k_ref.shape[2] // Dk, heads)
    diag[...] = _substitute(diag[...])


#: A pair of value heads, or one value head, in a kernel's lockstep: its tiles
#: by name.
_Tiles = types.SimpleNamespace


def _pairs_of(js, k_ref, q_ref, gc_ref, bc_ref, gr_ref, diag, R, Dk):
    """The turn's pairs with their tiles, a stage at a time: ``k``, ``q``
    [Q, Dk] (and float32 copies), ``qk`` [Q, Q], the columns of the two
    heads, and, [Q, 2Q] float32, ``beta``, ``D`` (zero above the diagonal),
    ``kkD = K K^T o D`` and ``inv = (I + A)^-1``."""
    g_all, b_all = gc_ref[0, 0], bc_ref[0, 0]                 # [Q, G*R]
    g_rows = gr_ref[0, 0]                                     # [G*R/2, 2Q]
    Q = k_ref.shape[1]
    row, s, first = _pair_grid(Q)
    pairs = []
    for j in js:
        at = _lanes(j, Dk)
        k, q = k_ref[0, :, at], q_ref[0, :, at]
        both = _nt_dot(jnp.concatenate([k, q], axis=0), k)    # one right operand
        for a in range(R // 2):
            p = _Tiles()
            p.j, p.at, p.k, p.q, p.first = j, at, k, q, a == 0
            p.kf, p.qf = k.astype(F32), q.astype(F32)
            p.id = j * (R // 2) + a
            p.kk, p.qk = both[:Q], both[Q:]
            pairs.append(p)
    for p in pairs:
        p.heads = (2 * p.id, 2 * p.id + 1)
        p.g_cols = [_column(g_all, h) for h in p.heads]
        p.b_cols = [_column(b_all, h) for h in p.heads]
        p.beta = jnp.where(first, p.b_cols[0], p.b_cols[1])
        # masked before the exp: above the diagonal the difference is
        # positive and may overflow
        p.D = jnp.exp(jnp.where(
            row >= s, jnp.where(first, p.g_cols[0], p.g_cols[1])
            - _row(g_rows, p.id), -jnp.inf))
        p.kkD = _side_by_side(p.kk) * p.D
    invs = _from_diagonals(
        [diag[p.id] for p in pairs],
        [jnp.where(row > s, p.kkD * p.beta, 0.0) for p in pairs])
    for p, inv in zip(pairs, invs):
        p.inv = inv
    return pairs


def _heads_of(pairs, v_ref, starts, cd, Dv):
    """The pairs' value heads with their tiles, a stage at a time, operands
    rounded to ``cd`` as the module's contract says: ``vb = V_b``, ``kg = K_b
    exp(gamma)``, ``W``, ``fresh = V_new``, ``q_in = Q exp(gamma)``, ``k_out
    = K exp(gamma_C - gamma)`` (float32), ``total = exp(gamma_C)``, ``read =
    q_in S``. ``starts(h)``: the state head ``h`` starts from, float32.
    Products that meet the same right operand are one product of their
    stacked rows: a pass of 64 rows costs the matrix unit its operand's load,
    not the rows."""
    Q = pairs[0].k.shape[0]
    heads = []
    for p in pairs:
        T = p.inv.astype(cd)
        M = (_side_by_side(p.qk) * p.D).astype(cd)
        for i, at in enumerate(p.heads):
            h = _Tiles()
            h.pair, h.i, h.at, h.out = p, i, at, _lanes(at, Dv)
            h.g, h.b = p.g_cols[i], p.b_cols[i]
            h.T, h.M = T[:, i * Q:(i + 1) * Q], M[:, i * Q:(i + 1) * Q]
            h.start = starts(at)
            h.startc = h.start.astype(cd)
            h.vf = v_ref[0, :, h.out].astype(F32)
            h.decay = jnp.exp(h.g)
            h.vb = (h.vf * h.b).astype(cd)
            h.kg = (p.kf * (h.b * h.decay)).astype(cd)
            h.q_in = (p.qf * h.decay).astype(cd)
            g_end = h.g[Q - 1:Q]
            h.to_end = jnp.exp(g_end - h.g)
            h.k_out, h.total = p.kf * h.to_end, jnp.exp(g_end)
            heads.append(h)
    for h in heads:
        h.UW = jnp.dot(h.T, jnp.concatenate([h.vb, h.kg], axis=1),
                       preferred_element_type=F32)
    for h in heads:
        h.W = h.UW[:, Dv:].astype(cd)
        reads = jnp.dot(jnp.concatenate([h.W, h.q_in], axis=0), h.startc,
                        preferred_element_type=F32)           # [2Q, Dv]
        h.fresh, h.read = (h.UW[:, :Dv] - reads[:Q]).astype(cd), reads[Q:]
    return heads


def _lanes(at, width):
    """Lanes ``[at * width, (at + 1) * width)`` for a traced ``at``."""
    return pl.ds(pl.multiple_of(at * width, LANES), width)


def _rule_fwd_kernel(q_ref, k_ref, v_ref, gc_ref, bc_ref, gr_ref, br_ref,
                     o_ref, s0_ref, state, diag, *, R):
    """One chunk of G key heads. ``q_ref``, ``k_ref`` [1, Q, G*Dk];
    ``v_ref`` [1, Q, G*R*Dv]; ``gc_ref``, ``bc_ref`` [1, 1, Q, G*R] (the
    running log-decay and beta, a value head a lane: columns); ``gr_ref``,
    ``br_ref`` [1, 1, G*R/2, 2Q] (the same, a pair of value heads a row).
    Out: ``o_ref`` [1, Q, G*R*Dv] float32 and ``s0_ref`` [1, 1, G*R, Dk, Dv],
    the state the chunk started from (the backward's residual). ``state``
    [G*R, Dk, Dv] float32 carries the heads' states from chunk to chunk;
    ``diag`` [G*R/2, BASE, 2Q] holds the pairs' solves between their two
    steps."""
    Q, cd = q_ref.shape[1], v_ref.dtype
    Dk, Dv = state.shape[1:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, F32)

    s0_ref[0, 0] = state[...]
    _all_diagonals(k_ref, gr_ref, br_ref, diag, R)

    def key_heads(js):
        pairs = _pairs_of(js, k_ref, q_ref, gc_ref, bc_ref, gr_ref, diag, R,
                          Dk)
        heads = _heads_of(pairs, v_ref, lambda h: state[h], cd, Dv)
        # M v_new and k_out^T v_new: one right operand
        wrote = [jnp.dot(jnp.concatenate([h.M, h.k_out.T.astype(cd)], axis=0),
                         h.fresh, preferred_element_type=F32)  # [Q + Dk, Dv]
                 for h in heads]
        for h, w in zip(heads, wrote):
            o_ref[0, :, h.out] = h.read + w[:Q]
            state[h.at] = h.total * h.start + w[Q:]

    _in_turns(q_ref.shape[2] // Dk, key_heads)


def _rule_bwd_kernel(q_ref, k_ref, v_ref, gc_ref, bc_ref, gr_ref, br_ref,
                     s0_ref, do_ref, dq_ref, dk_ref, dv_ref, dgc_ref, dbc_ref,
                     dgr_ref, dstate, diag, *, R):
    """The forward's program, backwards: chunks arrive last first and
    ``dstate`` [G*R, Dk, Dv] carries the cotangent of the state a chunk
    leaves. ``T``, ``U``, ``W``, ``V_new`` and ``M`` are remade from the
    inputs and ``s0_ref``, the state the chunk started from; the solve's
    transpose is ``dA = -T^T dT T^T`` under the triangle, in float32. Out:
    ``dq_ref``, ``dk_ref`` [1, Q, G*Dk] (summed over a key head's value
    heads), ``dv_ref`` [1, Q, G*R*Dv]; ``dbc_ref`` [1, 1, Q, G*R] of beta; of
    the running log-decay ``dgc_ref`` [1, 1, Q, G*R] less ``dgr_ref``
    [1, 1, G*R/2, 2Q] (what a token gathers as t, a column, and what it loses
    as s, a row: both sums of the one tile, so that what cancels between them
    cancels to the bit)."""
    Q, cd = q_ref.shape[1], v_ref.dtype
    Dk, Dv = dstate.shape[1:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, F32)

    _all_diagonals(k_ref, gr_ref, br_ref, diag, R)
    last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    dot = functools.partial(jnp.dot, preferred_element_type=F32)
    per_row = lambda a: jnp.sum(a, axis=1, keepdims=True)
    row, s, first = _pair_grid(Q)

    def key_heads(js):
        pairs = _pairs_of(js, k_ref, q_ref, gc_ref, bc_ref, gr_ref, diag, R,
                          Dk)
        heads = _heads_of(pairs, v_ref, lambda h: s0_ref[0, 0, h], cd, Dv)
        # o = q_in S + M v_new;  S' = total S + k_out^T v_new  (as in the
        # forward, products that meet one right operand are one product of
        # their stacked rows)
        for h in heads:
            h.dleft = dstate[h.at]                            # [Dk, Dv]
            h.dleftc = h.dleft.astype(cd)
            h.do = do_ref[0, :, h.out].astype(cd)
            h.of_do = _tn_dot(jnp.concatenate([h.M, h.q_in], axis=1),
                              h.do)                           # [Q + Dk, Dv]
            h.dM = _nt_dot(h.do, h.fresh)                     # [Q, Q]
            h.by_left = dot(h.k_out.astype(cd), h.dleftc)     # [Q, Dv]
            h.dk_out = _nt_dot(h.fresh, h.dleftc)             # [Q, Dk]
        # v_new = T v_b - W S;  W = T k_g
        for h in heads:
            h.dfresh = (h.of_do[:Q] + h.by_left).astype(cd)   # [Q, Dv]
            h.by_start = _nt_dot(jnp.concatenate([h.do, h.dfresh], axis=0),
                                 h.startc)                    # [2Q, Dk]
            h.of_dfresh = _tn_dot(jnp.concatenate([h.W, h.T], axis=1),
                                  h.dfresh)                   # [Dk + Q, Dv]
        for h in heads:
            h.dq_in, h.dW = h.by_start[:Q], (-h.by_start[Q:]).astype(cd)
            dstate[h.at] = (h.of_do[Q:] + h.total * h.dleft
                            - h.of_dfresh[:Dk])
            h.dT = _nt_dot(h.dfresh, h.vb) + _nt_dot(h.dW, h.kg)
            h.dkg = _tn_dot(h.T, h.dW)                        # [Q, Dk]
        # the row scalings: beta, exp(gamma), exp(gamma_C - gamma)
        for h in heads:
            p, dvb = h.pair, h.of_dfresh[Dk:]                 # [Q, Dv]
            dv_ref[0, :, h.out] = (dvb * h.b).astype(dv_ref.dtype)
            of_kg, of_q = per_row(h.dkg * p.kf), per_row(h.dq_in * p.qf)
            lost = per_row(h.dk_out * p.kf) * h.to_end
            end = h.total * jnp.sum(h.dleft * h.start, keepdims=True) \
                + jnp.sum(lost, keepdims=True)
            h.of_beta = per_row(dvb * h.vf) + of_kg * h.decay
            h.of_g = ((of_kg * h.b + of_q) * h.decay - lost
                      + jnp.where(last, end, 0.0))
            h.dq = h.dq_in * h.decay
            h.dk = h.dkg * (h.b * h.decay) + h.dk_out * h.to_end
        # the solve, D and A = tril(kk o D * beta, -1), for both heads
        for p in pairs:
            p.mine = [h for h in heads if h.pair is p]
            p.Tt = jnp.concatenate([p.inv[:, :Q].T, p.inv[:, Q:].T], axis=1)
        for p in pairs:
            p.dA = _dot32(jnp.concatenate([h.dT for h in p.mine], axis=1),
                          _both(p.Tt))
        for p in pairs:
            p.dA = jnp.where(row > s, -_dot32(p.Tt, _both(p.dA)), 0.0)
        for p in pairs:
            of_A = p.dA * p.kkD                               # d(beta) a row
            of_M = jnp.concatenate([h.dM for h in p.mine], axis=1) * p.D
            to_kk = p.dA * p.D * p.beta
            p.dkk = to_kk[:, :Q] + to_kk[:, Q:]
            p.dqk = of_M[:, :Q] + of_M[:, Q:]
            of_D = of_A * p.beta + of_M * _side_by_side(p.qk)  # dD o D
            dgr_ref[0, 0] = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, dgr_ref.shape[2:], 0)
                == p.id, jnp.sum(of_D, axis=0, keepdims=True), dgr_ref[0, 0])
            for h in p.mine:
                half = first if h.i == 0 else ~first
                _set_column(dgc_ref, slice(None), h.at, h.of_g + per_row(
                    jnp.where(half, of_D, 0.0)))
                _set_column(dbc_ref, slice(None), h.at, h.of_beta + per_row(
                    jnp.where(half, of_A, 0.0)))
        # kk = k k^T, qk = q k^T: a key head's, summed over its pairs
        for lead in (p for p in pairs if p.first):
            own = [p for p in pairs if p.j is lead.j]
            dkk = sum(p.dkk for p in own).astype(cd)
            dqk = sum(p.dqk for p in own).astype(cd)
            dq = sum(h.dq for p in own for h in p.mine)
            dk = sum(h.dk for p in own for h in p.mine)
            dq_ref[0, :, lead.at] = (dq + dot(dqk, lead.k)).astype(
                dq_ref.dtype)
            dk_ref[0, :, lead.at] = (
                dk + dot(dkk, lead.k) + _tn_dot(dkk, lead.k)
                + _tn_dot(dqk, lead.q)).astype(dk_ref.dtype)

    _in_turns(q_ref.shape[2] // Dk, key_heads)


def _pair_rows(a, Q):
    """[b, S, Hv] -> [b, S/Q, Hv/2, 2Q]: a pair of value heads a row, the
    first's chunk of tokens on the first Q lanes and the second's beside it."""
    b, S, H = a.shape
    return a.reshape(b, S // Q, Q, H // 2, 2).transpose(0, 1, 3, 4, 2).reshape(
        b, S // Q, H // 2, 2 * Q)


def _specs(Q, Dk, Dv, R, G, order):
    """Block specs for the grid (sequence, run of key heads, chunk), by what
    a block holds; ``order`` maps the grid's chunk index to the chunk."""
    GR = G * R
    return dict(
        keys=pl.BlockSpec((1, Q, G * Dk), lambda i, g, c: (i, order(c), g)),
        values=pl.BlockSpec((1, Q, GR * Dv), lambda i, g, c: (i, order(c), g)),
        cols=pl.BlockSpec((1, 1, Q, GR), lambda i, g, c: (i, g, order(c), 0)),
        rows=pl.BlockSpec((1, 1, GR // 2, 2 * Q),
                          lambda i, g, c: (i, order(c), g, 0)),
        state=pl.BlockSpec((1, 1, GR, Dk, Dv),
                           lambda i, g, c: (i, order(c), g, 0, 0)))


#: What both kernels read first, by the name of its block spec.
_OPERANDS = ("keys", "keys", "values", "cols", "cols", "rows", "rows")
#: Both kernels' grid: the chunks of a run of heads in order.
_GRID_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _operands(q, k, v, gamma, beta, plan):
    """Both kernels' first seven operands: the running log-decay and beta as
    columns and as rows of pairs."""
    Q, _, _, R, G = plan
    return (q, k, v, _columns(gamma, G * R), _columns(beta, G * R),
            _pair_rows(gamma, Q), _pair_rows(beta, Q))


@functools.partial(jax.jit, static_argnames="plan")
def _fwd_call(q, k, v, gamma, beta, *, plan):
    """``o`` [b, S, Hv*Dv] float32 and the states the chunks started from,
    [b, S/Q, Hv, Dk, Dv] float32. Under ``jit`` so that a model's layers, and
    the recomputation in its backward, share one trace and one lowering."""
    Q, Dk, Dv, R, G = plan
    b, S, _ = q.shape
    Hv, nc = gamma.shape[2], S // Q
    spec = _specs(Q, Dk, Dv, R, G, lambda c: c)
    return pl.pallas_call(
        functools.partial(_rule_fwd_kernel, R=R),
        name="delta_rule_fwd",
        grid=(b, Hv // (G * R), nc),
        in_specs=[spec[n] for n in _OPERANDS],
        out_specs=(spec["values"], spec["state"]),
        out_shape=(jax.ShapeDtypeStruct((b, S, Hv * Dv), F32),
                   jax.ShapeDtypeStruct((b, nc, Hv, Dk, Dv), F32)),
        scratch_shapes=[pltpu.VMEM((G * R, Dk, Dv), F32),
                        pltpu.VMEM((G * R // 2, BASE, 2 * Q), F32)],
        compiler_params=_GRID_ORDER,
        interpret=not backend.on_tpu(),
    )(*_operands(q, k, v, gamma, beta, plan))


@functools.partial(jax.jit, static_argnames="plan")
def _bwd_call(q, k, v, gamma, beta, states, do, *, plan):
    """Cotangents of q, k, v (in their dtypes), gamma and beta (float32)."""
    Q, Dk, Dv, R, G = plan
    b, S, _ = q.shape
    Hv, nc = gamma.shape[2], S // Q
    spec = _specs(Q, Dk, Dv, R, G, lambda c: nc - 1 - c)
    columns = jax.ShapeDtypeStruct((b, Hv // (G * R), S, G * R), F32)
    dq, dk, dv, dgc, dbc, dgr = pl.pallas_call(
        functools.partial(_rule_bwd_kernel, R=R),
        name="delta_rule_bwd",
        grid=(b, Hv // (G * R), nc),
        in_specs=[spec[n] for n in _OPERANDS + ("state", "values")],
        out_specs=tuple(spec[n] for n in (
            "keys", "keys", "values", "cols", "cols", "rows")),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), columns, columns,
                   jax.ShapeDtypeStruct((b, nc, Hv // 2, 2 * Q), F32)),
        scratch_shapes=[pltpu.VMEM((G * R, Dk, Dv), F32),
                        pltpu.VMEM((G * R // 2, BASE, 2 * Q), F32)],
        compiler_params=_GRID_ORDER,
        interpret=not backend.on_tpu(),
    )(*_operands(q, k, v, gamma, beta, plan), states, do)
    heads = lambda a: a.transpose(0, 2, 1, 3).reshape(b, S, Hv)
    lost = dgr.reshape(b, nc, Hv // 2, 2, Q).transpose(0, 1, 4, 2, 3)
    return dq, dk, dv, heads(dgc) - lost.reshape(b, S, Hv), heads(dbc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule_kernels(q, k, v, gamma, beta, plan):
    """The rule through the kernels. ``q``, ``k`` [b, S, Hk*Dk] and ``v``
    [b, S, Hv*Dv] in the compute dtype; ``gamma`` (the log-decay's running sum
    inside each chunk) and ``beta`` [b, S, Hv] float32; ``plan`` (Q, Dk, Dv,
    R, G). Returns ``o`` [b, S, Hv*Dv] float32."""
    return _rule_fwd(q, k, v, gamma, beta, plan)[0]


def _rule_fwd(q, k, v, gamma, beta, plan):
    o, states = ssd_lib._per_device(functools.partial(_fwd_call, plan=plan),
                                    q, k, v, gamma, beta, n_out=2)
    return o, (q, k, v, gamma, beta, states)


def _rule_bwd(plan, res, do):
    return ssd_lib._per_device(functools.partial(_bwd_call, plan=plan),
                               *res, do, n_out=5)


_rule_kernels.defvjp(_rule_fwd, _rule_bwd)

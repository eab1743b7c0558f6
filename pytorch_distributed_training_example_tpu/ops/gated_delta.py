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

``T`` is the chunk's triangular solve (:func:`_unit_lower_inverse`, by halves:
exact elimination, as forward substitution is); everything that does not read
the carried state is made for all chunks at once, and the three lines that do
run under one ``lax.scan`` over the chunks. The decays, ``T`` and the state
are float32 whatever the compute dtype; every product takes operands in
``v.dtype`` and accumulates in float32; ``o`` comes back float32.

Plain ``jax.numpy`` that XLA differentiates: no kernel yet (``chipbench``'s
``qwen3n_delta_rule_roofline`` is the yardstick one will be judged by). No
packed documents (no state reset) and no recurrent-state cache for serving.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pytorch_distributed_training_example_tpu.ops import ssd as ssd_lib

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


def _say_plan(Hk, Hv, Dk, Dv, Q, chunks):
    """One ``delta_rule_plan`` record a traced call of
    :func:`gated_delta_rule`: what it was given, and that the chunks run under
    XLA's scan."""
    ssd_lib._say("delta_rule_plan", {
        "key_heads": Hk, "value_heads": Hv, "key_dim": Dk, "value_dim": Dv,
        "chunk": Q, "chunks": chunks, "body": "xla"})


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
    """
    b, S, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    if Hv % Hk:
        raise ValueError(f"{Hk} key heads do not divide {Hv} value heads")
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is no power of two")
    R, cd = Hv // Hk, v.dtype
    Q = min(chunk, 1 << max(S - 1, 0).bit_length())
    pad = -S % Q
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = widen(q), widen(k), widen(v), widen(g), widen(beta)
    nc = (S + pad) // Q
    _say_plan(Hk, Hv, Dk, Dv, Q, nc)

    # by key head ``h`` and the ``r``-th value head it serves, a chunk's tokens
    # next to the width: every product below is a batched matmul as it stands
    heads = lambda a: jnp.moveaxis(a.reshape(b, nc, Q, *a.shape[2:]), 2,
                                   a.ndim - 1)
    qc, kc = heads(q.astype(cd)), heads(k.astype(cd))       # [b,c,h,Q,n]
    vc = heads(v.reshape(b, S + pad, Hk, R, Dv))            # [b,c,h,r,Q,p]
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
    o = jnp.moveaxis(by_chunk(o), 4, 2).reshape(b, S + pad, Hv, Dv)
    return o[:, :S] if pad else o

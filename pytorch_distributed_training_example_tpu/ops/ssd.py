"""State-space duality (SSD) scan of Mamba-2, chunked, and the causal
depthwise convolution that feeds it (Dao & Gu 2024, arXiv:2405.21060).

Per head (``x_t`` in R^P, one scalar decay ``A < 0``, ``B_t``/``C_t`` in R^N
shared by every head of the one group)::

    S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t,    S_{-1} = 0
    y_t = S_t C_t + D * x_t

:func:`ssd` computes it in chunks of ``chunk`` tokens. Inside a chunk the
masked quadratic form ``(L o C B^T)(dt * x)`` with ``L_ts = exp(sum_{r=s+1..t}
dt_r A)``; between chunks the state each chunk leaves, carried forward by the
chunks' total decays (a [chunks x chunks] lower-triangular product per head:
no sequential loop). Plain ``jax.numpy``: XLA differentiates it, and a block's
``remat`` recomputes the tiles in the backward. Decays, ``dt``, ``A``, ``D``
and the cumulative sums are float32 whatever the compute dtype; every matmul
takes operands in ``x.dtype`` and accumulates in float32.

No packed documents (no state or mask resets) and no recurrent-state cache
for serving: one document a sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_conv1d(x: jax.Array, kernel: jax.Array,
                  bias: jax.Array | None = None) -> jax.Array:
    """Causal depthwise convolution over the sequence with zero history.

    ``x`` [B, S, C]; ``kernel`` [K, C]; ``y_t = sum_k kernel[k] *
    x_{t-(K-1)+k} (+ bias)``. Float32 accumulation, ``x.dtype`` out. K shifted
    reads of one padded array: an elementwise fusion, no conv op.
    """
    K, S = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    w = kernel.astype(F32)
    y = sum(xp[:, k:k + S] * w[k] for k in range(K))
    if bias is not None:
        y = y + bias.astype(F32)
    return y.astype(x.dtype)


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array | None = None, *,
        chunk: int = 256) -> jax.Array:
    """Chunked SSD scan.

    ``x`` [b, S, H, P] (compute dtype); ``dt`` [b, S, H] float32, already
    through its softplus; ``A`` [H] float32, negative; ``B``, ``C`` [b, S, N]
    (one group); ``D`` [H] or None. Returns ``y`` [b, S, H, P] in float32, as
    accumulated (see :class:`models.granite_hybrid.MambaMixer` for why).
    A sequence that is no multiple of ``chunk`` is padded here: a padded step
    has ``dt = 0``, so it neither decays nor feeds the state.
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    cd = x.dtype
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
    nc = (S + pad) // Q
    dt = dt.astype(F32)
    xc = x.reshape(b, nc, Q, H, P)
    Bc = B.reshape(b, nc, Q, N).astype(cd)
    Cc = C.reshape(b, nc, Q, N).astype(cd)
    dtc = dt.reshape(b, nc, Q, H)
    # log-decay of each step and its running sum inside the chunk
    cum = jnp.cumsum(dtc * A.astype(F32), axis=2)            # [b,c,Q,H]
    xdt = (xc.astype(F32) * dtc[..., None]).astype(cd)       # dt * x

    # 1. inside a chunk: (L o C B^T) (dt x)
    scores = jnp.einsum("bctn,bcsn->bcts", Cc, Bc,
                        preferred_element_type=F32)          # [b,c,Q,Q]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    cumh = cum.transpose(0, 1, 3, 2)                         # [b,c,H,Q]
    seg = cumh[..., :, None] - cumh[..., None, :]            # [b,c,H,t,s]
    # masked before the exp: above the diagonal the difference is positive
    # and may overflow, and 0 * inf in the backward would be NaN
    L = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    M = (L * scores[:, :, None]).astype(cd)                  # [b,c,H,t,s]
    y = jnp.einsum("bchts,bcshp->bcthp", M, xdt,
                   preferred_element_type=F32)

    # 2. the state each chunk leaves: sum_s exp(cum_end - cum_s) dt x (x) B
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)                # [b,c,Q,H]
    left = jnp.einsum("bcshp,bcsn->bchpn",
                      (xdt.astype(F32) * to_end[..., None]).astype(cd), Bc,
                      preferred_element_type=F32)            # [b,c,H,P,N]

    # 3. the state each chunk starts from: the earlier chunks' states, decayed
    #    by every chunk in between (strictly lower triangular over chunks)
    if nc > 1:
        total = cum[:, :, -1, :]                             # [b,c,H]
        run = jnp.cumsum(total, axis=1)
        # chunk z starts from sum_{c<z} exp(run[z-1] - run[c]) left[c]
        between = (run - total)[:, :, None, :] - run[:, None, :, :]  # [b,z,c,H]
        before = jnp.tril(jnp.ones((nc, nc), bool), -1)
        carry = jnp.exp(jnp.where(before[:, :, None], between, -jnp.inf))
        start = jnp.einsum("bzch,bchpn->bzhpn", carry, left)  # float32
        # 4. what the carried state adds: exp(cum_t) C_t S_start
        y = y + jnp.einsum("bctn,bchpn->bcthp", Cc, start.astype(cd),
                           preferred_element_type=F32) \
            * jnp.exp(cum)[..., None]
    if D is not None:
        y = y + xc.astype(F32) * D.astype(F32)[:, None]
    y = y.reshape(b, nc * Q, H, P)
    return y[:, :S] if pad else y

"""Mixture-of-Experts with expert parallelism over the ``expert`` mesh axis.

SURVEY.md §2c "EP": Switch/GShard-style token routing, built the GSPMD way —
expert-stacked FFN weights sharded on ``expert``; XLA partitions the expert
einsums and inserts the token all-to-all automatically (no hand-written
routing transport).

Top-k gating (k=1 Switch, k=2 GShard defaults), capacity factor with token
dropping, and the standard load-balancing auxiliary loss (mean(gates)*
fraction-routed per expert, scaled by E), surfaced via the flax ``sow``
mechanism under the ``"losses"`` collection as ``moe_aux_loss``.

Routing bookkeeping is compact-index (MegaBlocks' lesson, Gale et al. 2023):
one stable argsort + bincount over ``expert_idx`` (``routing_stats``) yields
the per-expert counts, segment starts, and within-queue positions that the
dispatch, the Switch aux loss, the z-loss, and the telemetry sows all share.
No fp32 ``[T, E]``/``[T, k, E]`` one-hot is materialized outside the einsum
dispatch impl (whose explicit masks are its definition); the shared stats
are ``[E]``/``[k·T]``-shaped int32. The routing *decision* (fp32 softmax +
``lax.top_k``) is unchanged — the compact path is equivalence-tested
against the one-hot reference in tests/test_moe_router.py.

Three capacity-dropped dispatch implementations share identical
routing/drop semantics (the priority order is: earlier tokens first, k=0
choices before k=1) and are equivalence-tested against each other — see
``dispatch_impl`` on ``MoEBlock``. A fourth, ``"dropless"``, retires the
capacity machinery entirely (MegaBlocks): the ragged per-expert segments
the stats' argsort produces feed a Pallas grouped matmul
(ops/grouped_matmul.py) directly — no ``[E, C, d]`` buffer, no dropped
tokens, capacity factor irrelevant; it is equivalence-tested against the
einsum path at a capacity factor high enough to never drop. The step
regions are tagged with ``jax.named_scope`` (``moe_router`` /
``moe_dispatch`` / ``moe_experts`` / ``moe_combine`` / ``moe_aux``, plus
``moe_experts_gmm`` inside the dropless kernel) so a trace's device time
can be attributed per region (``benchmarks/profile_step.build_op_moe_tags``;
graftlint's GL102/GL104/GL105 key on the same tags).

The dropless path additionally supports **expert-parallel sharded
execution** (``ep_dispatch``, r17): instead of replicated-pinning the sorted
tokens and all-gathering the expert weights every step, the contiguous
per-expert segments are all-to-all'd to the devices that own the experts
(weights stay sharded ``P('expert', None, None)`` per EP_RULES) and
``gmm()`` runs against LOCAL weights only, with a device-local tile table
derived from the local segment counts. ``"a2a_overlap"`` splits the token
dim into double-buffered chunks so the next chunk's all-to-all is issued
before the current chunk's grouped matmul — program order XLA's async
scheduler can overlap on a chip. Both variants are bitwise-identical to the
replicated path (same rows, same weights, same single-dot full-``d``
contraction per row; tested in tests/test_moe_dropless.py). This is what
makes E ≫ devices representable: per-device expert memory is ``E/ep``
weight blocks instead of all ``E``.

Beside ``MoEBlock``, the expert layers of models that are told which experts
this chip holds (the second half of this file) are two halves that a block
calls where its model says: *a router* (scores to the chosen experts, their
weights and every expert's count: ``route_sigmoid_bias`` for the ``afmoe``
family, :class:`TopKSoftmaxRouter` for ``smallthinker``) and *the held
experts' routine* (``_held_sum`` over ``(tokens, chosen, weights, counts)``:
``_routed`` / ``_routed_bounded`` through ``ops/grouped_matmul.py``'s gated
FFN, with the telemetry). :class:`SharedExpertMoE` is both in one module with
a shared expert beside them; :class:`HeldExperts` is the routine alone, for a
block that routes on another tensor than the experts read.
"""

from __future__ import annotations

import functools
import json
import os
import warnings
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_example_tpu.core import mesh as mesh_lib

BATCH = mesh_lib.BATCH_AXES

#: Valid values for ``MoEBlock.ep_dispatch`` (dropless only).
EP_DISPATCH_IMPLS = ("replicated", "a2a", "a2a_overlap")

#: jsonl path: trace-time a2a chunk geometry (static shapes only, so two
#: same-seed runs produce byte-identical logs — asserted by dryrun leg 17).
A2A_CHUNK_LOG_ENV = "PDTX_A2A_CHUNK_LOG"

#: "native" (lax.all_to_all; default — verified correct under the gloo CPU
#: cross-process backend) or "ppermute" (decomposed fallback hatch).
EP_A2A_IMPL_ENV = "PDTX_EP_A2A_IMPL"

_capacity_clamp_warned = False
_ep_fallback_warned = False


def _warn_ep_fallback(ep_dispatch, num_experts, n_rows, ep):
    """One-time trace-time warning when a requested sharded EP dispatch
    falls back to replicated because the shape doesn't tile the EP axis."""
    global _ep_fallback_warned
    if _ep_fallback_warned:
        return
    _ep_fallback_warned = True
    warnings.warn(
        f"MoE ep_dispatch={ep_dispatch!r} requested but E={num_experts} or "
        f"sorted rows kT={n_rows} does not divide the expert mesh axis "
        f"(size {ep}); falling back to the replicated dropless path. "
        f"(warned once per process)", RuntimeWarning, stacklevel=3)


def _ep_degree(ep_dispatch: str, num_experts: int, n_rows: int) -> int:
    """Static EP fan-out for the dropless dispatch: the expert mesh axis
    size when the sharded path can run, else 1 (replicated execution).

    All inputs are trace-time static; init-time tracing outside
    ``use_mesh`` (mesh None) collapses to 1 like the attention dispatcher
    does, so param structure is identical across paths.
    """
    if ep_dispatch not in EP_DISPATCH_IMPLS:
        raise ValueError(f"unknown ep_dispatch {ep_dispatch!r}; "
                         f"have {list(EP_DISPATCH_IMPLS)}")
    if ep_dispatch == "replicated":
        return 1
    mesh = mesh_lib.current_mesh()
    ep = mesh.shape.get("expert", 1) if mesh is not None else 1
    if ep <= 1:
        return 1
    if num_experts % ep or n_rows % ep:
        _warn_ep_fallback(ep_dispatch, num_experts, n_rows, ep)
        return 1
    return ep


def _log_a2a_chunks(scope: str, mode: str, *, ep: int, rows_per_device: int,
                    d_model: int, chunk_rows, dtype, impl: str) -> None:
    """Append the static a2a geometry to ``A2A_CHUNK_LOG_ENV`` (trace time).

    Everything here is compile-time static (no data, no clocks), so the log
    is byte-identical across same-seed runs — the dryrun leg's determinism
    contract for the sharded dispatch.
    """
    path = os.environ.get(A2A_CHUNK_LOG_ENV)
    if not path:
        return
    itemsize = jnp.dtype(dtype).itemsize
    row = {"scope": scope, "mode": mode, "ep": ep,
           "rows_per_device": int(rows_per_device), "d_model": int(d_model),
           "n_chunks": len(chunk_rows),
           "chunk_rows": [int(w) for w in chunk_rows],
           "send_bytes_per_chunk": [int(ep * w * d_model * itemsize)
                                    for w in chunk_rows],
           "dtype": str(jnp.dtype(dtype).name), "impl": impl}
    with open(path, "a") as fh:
        fh.write(json.dumps(row, sort_keys=True) + "\n")


def _warn_capacity_clamp(capacity_factor, T, top_k, num_experts):
    """Loud one-time warning when ``int(cf*T*k/E)`` lands at 0 and the
    capacity is silently clamped to 1 slot per expert — tiny T·k/E shapes
    (small batches, many experts) drop almost every token in that regime.
    Trace-time only (static shapes): no host sync in the compiled step.
    """
    global _capacity_clamp_warned
    if _capacity_clamp_warned:
        return
    _capacity_clamp_warned = True
    warnings.warn(
        f"MoE expert capacity clamped to 1: int(capacity_factor * T * k / E)"
        f" = int({capacity_factor} * {T} * {top_k} / {num_experts}) = 0. "
        f"With one slot per expert most (token, choice) assignments will be "
        f"DROPPED. Raise capacity_factor / batch size, or switch to "
        f"dispatch_impl='dropless' (no capacity, no drops). "
        f"(warned once per process)", RuntimeWarning, stacklevel=3)


def _ep_sharded_ffn(x_loc, w_up, w_down, starts, counts, *, ep, a2a_impl):
    """shard_map body (manual over 'expert'): a2a dispatch + LOCAL gmm.

    ``x_loc`` is this device's contiguous ``[R, d]`` slice of the globally
    expert-sorted ``[kT, d]`` array (R = kT/ep); ``w_up``/``w_down`` are the
    local ``[E/ep, ...]`` expert shards; ``starts``/``counts`` the GLOBAL
    ``[E]`` segment table (replicated — O(E) ints).

    Two contiguity invariants carry the whole formulation:

    1. a contiguous slice of the sorted array splits into ≤ ep contiguous
       destination chunks with boundaries ``clip(starts[q·E/ep] − p·R, 0,
       R)`` — so the send buffer is ep static windows, no scatter;
    2. source-major concatenation of the valid received rows IS the global
       sorted order restricted to this device's experts — so ONE compaction
       gather yields an expert-sorted local array and the unchanged
       ``grouped_ffn`` kernel runs against it with the device-local tile
       table built from ``counts[p·E/ep : (p+1)·E/ep]``.

    The local row buffer is padded to the static worst case kT (all tokens
    routed here); padding rows are zero, steered into the last local
    expert's segment (zero rows contribute zero to outputs and to dw), and
    never scattered back. Per-row outputs are bitwise-identical to the
    replicated path: same rows, same weights, and the kernel contracts the
    full ``d`` dim in one fp32-accumulated dot regardless of tile layout.
    """
    from pytorch_distributed_training_example_tpu.ops import (
        collectives, grouped_matmul as gmm_lib)

    p = jax.lax.axis_index("expert")
    R = x_loc.shape[0]
    E_l = w_up.shape[0]
    Tk = R * ep
    st_ext = jnp.concatenate([starts, jnp.array([Tk], starts.dtype)])
    ar = jnp.arange(R)
    with jax.named_scope("moe_dispatch"):
        # Invariant 1: my rows' destination-chunk boundaries.
        bounds = jnp.clip(st_ext[::E_l][:ep + 1] - p * R, 0, R)   # [ep+1]
        pos = bounds[:-1, None] + ar[None, :]
        valid = pos < bounds[1:, None]
        send = jnp.where(valid[..., None],
                         x_loc[jnp.clip(pos, 0, R - 1)], 0)       # [ep, R, d]
        recv = collectives.all_to_all_blocks(send, "expert", impl=a2a_impl)
        # Source-side geometry: source s sent me its rows [lo_s, hi_s).
        s_ar = jnp.arange(ep)
        lo = jnp.clip(st_ext[p * E_l] - s_ar * R, 0, R)
        hi = jnp.clip(st_ext[(p + 1) * E_l] - s_ar * R, 0, R)
        seg = hi - lo
        off = jnp.concatenate([jnp.zeros((1,), seg.dtype), jnp.cumsum(seg)])
        T_l = off[-1]                       # my valid token count (traced)
        # Invariant 2: compaction gather -> expert-sorted local rows.
        j = jnp.arange(Tk)
        sj = jnp.clip(jnp.searchsorted(off, j, side="right") - 1, 0, ep - 1)
        flat = recv.reshape(Tk, -1)
        gidx = jnp.clip(sj * R + (j - off[sj]), 0, Tk - 1)
        x_l = jnp.where((j < T_l)[:, None], flat[gidx], 0)        # [kT, d]
        # Device-local tile table: local counts, last segment inflated to
        # absorb the zero padding so the segments tile [0, kT) exactly.
        ct_l = jax.lax.dynamic_slice(counts, (p * E_l,), (E_l,))
        ct_l = ct_l.at[-1].add((Tk - T_l).astype(ct_l.dtype))
        st_l = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(ct_l)[:-1].astype(jnp.int32)])
    with jax.named_scope("moe_experts_gmm"):
        y_l = gmm_lib.grouped_ffn(x_l, w_up, w_down, st_l, ct_l)
    with jax.named_scope("moe_dispatch"):
        # Inverse transport: return chunk for source s = rows [off_s,
        # off_s + seg_s) of the local result, then reassemble my slice.
        bidx = jnp.clip(off[:-1, None] + ar[None, :], 0, Tk - 1)
        bvalid = ar[None, :] < seg[:, None]
        back = jnp.where(bvalid[..., None], y_l[bidx], 0)         # [ep, R, d]
        rb = collectives.all_to_all_blocks(back, "expert", impl=a2a_impl)
        qr = jnp.clip(jnp.searchsorted(bounds, ar, side="right") - 1,
                      0, ep - 1)
        return rb.reshape(Tk, -1)[qr * R + (ar - bounds[qr])]     # [R, d]


def _ep_overlap_ffn(x_loc, w_up, w_down, starts, counts, *, ep, chunk_rows,
                    a2a_impl):
    """shard_map body: double-buffered chunked a2a/gmm overlap variant.

    Same transport geometry as :func:`_ep_sharded_ffn`, but the
    per-destination ``R`` rows are split into ``chunk_rows`` windows (the
    last may be torn) and the loop is unrolled so chunk ``c+1``'s dispatch
    all-to-all is issued BEFORE chunk ``c``'s grouped matmul — independent
    ops in program order that XLA's async scheduler can overlap on a chip
    (a2a-start / gmm / a2a-done). Each received chunk is locally re-sorted
    by expert (ids derived from the static geometry, no extra metadata on
    the wire) and fed to ``gmm`` with chunk-local counts; per-chunk dw
    contributions sum under autodiff.
    """
    from pytorch_distributed_training_example_tpu.ops import (
        collectives, grouped_matmul as gmm_lib)

    p = jax.lax.axis_index("expert")
    R = x_loc.shape[0]
    E_l = w_up.shape[0]
    Tk = R * ep
    Rc = chunk_rows[0] if chunk_rows else R
    st_ext = jnp.concatenate([starts, jnp.array([Tk], starts.dtype)])
    bounds = jnp.clip(st_ext[::E_l][:ep + 1] - p * R, 0, R)
    s_ar = jnp.arange(ep)
    lo = jnp.clip(st_ext[p * E_l] - s_ar * R, 0, R)
    hi = jnp.clip(st_ext[(p + 1) * E_l] - s_ar * R, 0, R)
    seg = hi - lo

    def make_send(c, w):
        jr = jnp.arange(w)
        pos = bounds[:-1, None] + c * Rc + jr[None, :]
        valid = pos < bounds[1:, None]
        return jnp.where(valid[..., None],
                         x_loc[jnp.clip(pos, 0, R - 1)], 0)       # [ep, w, d]

    def expert_chunk(c, recv):
        """Local FFN on one received chunk: geometry-derived expert ids,
        chunk-local stable sort, gmm with chunk-local counts, inverse."""
        w = recv.shape[1]
        jr = jnp.arange(w)
        o = lo[:, None] + c * Rc + jr[None, :]     # source-slice offsets
        valid = (c * Rc + jr[None, :]) < seg[:, None]
        g = s_ar[:, None] * R + o                  # global sorted index
        eid = jnp.searchsorted(st_ext[1:], g, side="right")
        eid_l = jnp.clip(eid - p * E_l, 0, E_l - 1)
        # Invalid (padding) rows are zeroed and steered into the last
        # local expert's segment: zero rows through any expert are zero.
        eid_l = jnp.where(valid, eid_l, E_l - 1)
        xs_c = jnp.where(valid[..., None], recv, 0).reshape(ep * w, -1)
        keys = eid_l.reshape(-1).astype(jnp.int32)
        perm = jnp.argsort(keys, stable=True)
        ct_c = jnp.bincount(keys, length=E_l).astype(jnp.int32)
        st_c = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(ct_c)[:-1].astype(jnp.int32)])
        with jax.named_scope("moe_experts_gmm"):
            y_sorted = gmm_lib.grouped_ffn(xs_c[perm], w_up, w_down,
                                           st_c, ct_c)
        y_c = jnp.zeros_like(y_sorted).at[perm].set(y_sorted)
        return jnp.where(valid.reshape(-1)[:, None], y_c,
                         0).reshape(ep, w, -1)

    a2a = functools.partial(collectives.all_to_all_blocks, axis="expert",
                            impl=a2a_impl)
    n_chunks = len(chunk_rows)
    with jax.named_scope("moe_dispatch"):
        sends = [make_send(c, w) for c, w in enumerate(chunk_rows)]
        recv = [None] * n_chunks
        recv[0] = a2a(sends[0])
    y_slice = jnp.zeros((R + 1, x_loc.shape[1]), x_loc.dtype)
    ar = jnp.arange(R)
    for c, w in enumerate(chunk_rows):
        if c + 1 < n_chunks:
            # Double buffering: next chunk's a2a precedes this chunk's gmm
            # in program order (the overlap the HLO test inspects).
            with jax.named_scope("moe_dispatch"):
                recv[c + 1] = a2a(sends[c + 1])
        y_c = expert_chunk(c, recv[c])
        with jax.named_scope("moe_dispatch"):
            rb = a2a(y_c)                          # [ep, w, d] back to me
            jr = jnp.arange(w)
            pos = bounds[:-1, None] + c * Rc + jr[None, :]
            valid = pos < bounds[1:, None]
            tgt = jnp.where(valid, pos, R)         # row R = trash
            y_slice = y_slice.at[tgt.reshape(-1)].set(rb.reshape(ep * w, -1))
    return y_slice[:R]


class ExpertFFN(nn.Module):
    """Stacked expert MLPs applied to dispatched tokens [E, C, d]."""

    num_experts: int
    ffn_dim: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):  # [E, C, d]
        d = x.shape[-1]
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (self.num_experts, d, self.ffn_dim), self.param_dtype)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (self.num_experts, self.ffn_dim, d), self.param_dtype)
        h = jnp.einsum("ecd,edf->ecf", x, w_up.astype(self.dtype),
                       preferred_element_type=jnp.float32).astype(self.dtype)
        h = nn.gelu(h)
        out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype),
                         preferred_element_type=jnp.float32).astype(self.dtype)
        return out


class GroupedExpertFFN(nn.Module):
    """Expert MLPs over the SORTED ragged token layout ``[kT, d]`` (dropless).

    Same math as ``ExpertFFN`` but computed by the Pallas grouped matmul
    (ops/grouped_matmul.py) over contiguous per-expert segments instead of
    a padded ``[E, C, d]`` einsum. Param names/shapes/init are identical to
    ``ExpertFFN`` (``w_up`` ``[E, d, f]``, ``w_down`` ``[E, f, d]``,
    lecun_normal, ``param_dtype``), so checkpoints and the
    ``experts/w_(up|down)`` sharding rules (EP_RULES, llama TP_RULES) are
    unchanged when flipping ``dispatch_impl`` to ``"dropless"``.

    ``ep_dispatch`` selects the execution layout (see the module
    docstring): ``"replicated"`` runs the r14 single-program kernel on the
    replicated sorted array; ``"a2a"`` shard_maps over the ``expert`` mesh
    axis — the weight in_specs match EP_RULES exactly, so no resharding —
    and ``"a2a_overlap"`` additionally splits the transport into
    ``ep_overlap_chunks`` double-buffered windows. Sharded paths fall back
    to replicated when the mesh has no expert axis > 1 or the shape does
    not tile it (one-time warning), keeping init-time tracing and
    single-device runs on the identical param structure.
    """

    num_experts: int
    ffn_dim: int
    dtype: Any
    param_dtype: Any
    ep_dispatch: str = "replicated"  # "replicated" | "a2a" | "a2a_overlap"
    ep_overlap_chunks: int = 2       # a2a_overlap double-buffer windows

    @nn.compact
    def __call__(self, x_sorted, starts, counts):  # [kT, d], [E], [E]
        from pytorch_distributed_training_example_tpu.ops import (
            grouped_matmul as gmm_lib)

        d = x_sorted.shape[-1]
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (self.num_experts, d, self.ffn_dim), self.param_dtype)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (self.num_experts, self.ffn_dim, d), self.param_dtype)
        ep = _ep_degree(self.ep_dispatch, self.num_experts, x_sorted.shape[0])
        if ep == 1:
            # Replicated execution: every device runs all experts over the
            # whole sorted array (P() specs — GSPMD cannot partition the
            # Mosaic kernel, see mesh_lib.manual_call).
            with jax.named_scope("moe_experts_gmm"):
                return mesh_lib.manual_call(
                    gmm_lib.grouped_ffn, x_sorted, w_up.astype(self.dtype),
                    w_down.astype(self.dtype), starts, counts,
                    in_specs=P(), out_specs=P())
        # Sharded EP execution: manual over 'expert' only; the other mesh
        # axes are unmentioned (the sorted array is replicated over the
        # batch axes exactly like the r14 path — shard_map's transpose
        # handles the unmentioned-axis cotangents, grads oracle-tested).
        mesh = mesh_lib.current_mesh()
        a2a_impl = os.environ.get(EP_A2A_IMPL_ENV, "native")
        R = x_sorted.shape[0] // ep
        if self.ep_dispatch == "a2a_overlap":
            n = max(1, min(int(self.ep_overlap_chunks), R))
            rc = -(-R // n)
            chunk_rows = tuple(min(rc, R - c * rc) for c in range(n)
                               if R - c * rc > 0)  # torn last chunk
            body = functools.partial(_ep_overlap_ffn, ep=ep,
                                     chunk_rows=chunk_rows, a2a_impl=a2a_impl)
        else:
            chunk_rows = (R,)
            body = functools.partial(_ep_sharded_ffn, ep=ep,
                                     a2a_impl=a2a_impl)
        try:
            scope = "/".join(self.scope.path)
        except Exception:
            scope = str(self.name)
        _log_a2a_chunks(scope, self.ep_dispatch, ep=ep, rows_per_device=R,
                        d_model=d, chunk_rows=chunk_rows, dtype=self.dtype,
                        impl=a2a_impl)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("expert", None), P("expert", None, None),
                      P("expert", None, None), P(None), P(None)),
            out_specs=P("expert", None), check_vma=False)
        return fn(x_sorted, w_up.astype(self.dtype),
                  w_down.astype(self.dtype), starts, counts)


class RouterDense(nn.Module):
    """Router logits in fp32 WITHOUT an fp32 copy of the [T, d] token block.

    ``nn.Dense(dtype=f32)`` promotes bf16 activations before the dot, which
    materializes an fp32 [T, d] array in the forward and an fp32 [T, d]
    cotangent + downcast chain in the backward — pure residual-stream
    bandwidth charged to the router region. A mixed-precision
    ``lax.dot_general`` with ``preferred_element_type=f32`` produces
    bit-identical logits (bf16 values are exactly representable in fp32, so
    promoting per-element inside the MXU pass changes nothing) with no
    promoted operand in the program.

    ``compute_dtype`` None/fp32 keeps that exact contract (ST-MoE fp32
    router). bf16 casts BOTH operands to bf16 — halved logits-matmul read
    traffic, still fp32 accumulation via ``preferred_element_type`` — and is
    the opt-in ``router_dtype`` A/B; softmax/top-k stay fp32 downstream
    either way.

    Param path/init match ``nn.Dense(name="router")`` exactly ("kernel",
    lecun_normal, fp32), so checkpoints and the ``router/kernel`` sharding
    rules are unaffected.
    """

    features: int
    compute_dtype: Any = None  # None/f32 -> exact mixed dot; bf16 -> bf16 dot

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        cdt = self.compute_dtype
        if cdt is not None and cdt != jnp.float32:
            x = x.astype(cdt)
            kernel = kernel.astype(cdt)
        return jax.lax.dot_general(
            x, kernel, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


class RoutingStats(NamedTuple):
    """Compact-index routing bookkeeping shared by dispatch/aux/telemetry.

    Everything is int32/bool and [E]- or [k·T]-shaped — the fp32 one-hot
    position chain, the aux-loss top-1 fraction, and the load-entropy
    telemetry all derive from these instead of materializing [T, E] masks.
    """

    counts: jax.Array      # [E] assignments per expert (pre-capacity)
    starts: jax.Array      # [E] exclusive-cumsum segment starts
    order: jax.Array       # [k·T] stable argsort of (choice, token) by expert
    pos: jax.Array         # [T, k] position within the expert's queue
    within_cap: jax.Array  # [T, k] bool, pos < capacity


def routing_stats(expert_idx, num_experts: int, capacity: int) -> RoutingStats:
    """One stable argsort + bincount over ``expert_idx`` -> shared stats.

    Flattens the (choice, token) pairs in the priority order (index
    j = k_idx*T + t: all k=0 choices for tokens 0..T-1, then k=1) and
    stable-argsorts by expert id; the within-queue position — rank in
    sorted order minus the expert's segment start — equals the legacy
    [k·T, E] one-hot-cumsum position exactly, drop for drop (stable sort
    preserves the priority order within each expert's run).
    """
    T, k = expert_idx.shape
    e_flat = expert_idx.T.reshape(-1).astype(jnp.int32)         # [kT]
    order = jnp.argsort(e_flat, stable=True)                    # [kT]
    sorted_e = e_flat[order]
    counts = jnp.bincount(e_flat, length=num_experts).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    # Routing index vectors are O(E) and O(k·T) ints — pin them replicated
    # so sharding propagation (backward from the expert-sharded dispatch)
    # can never turn `starts[sorted_e]` into a sharded-operand gather
    # (an SPMD-partitioner miscompile guard; see MoEBlock._combine).
    counts = mesh_lib.constrain(counts, P(None))
    starts = mesh_lib.constrain(starts, P(None))
    pos_sorted = (jnp.arange(k * T, dtype=jnp.int32) - starts[sorted_e])
    # Invert the permutation to per-(token, choice) positions.
    pos_flat = jnp.zeros((k * T,), jnp.int32).at[order].set(
        pos_sorted, unique_indices=True)
    pos = pos_flat.reshape(k, T).T                              # [T, k]
    within_cap = pos < capacity
    return RoutingStats(counts, starts, order, pos, within_cap)


class MoEBlock(nn.Module):
    """Router + expert FFNs; drop-in replacement for a dense MLP block.

    Dispatch implementations, equivalence-tested against each other (all
    three consume the shared ``routing_stats`` positions):

    - ``"sort"`` (recommended; MegaBlocks-style reformulation): read
      per-expert queues as contiguous runs of the stats' stable-argsort
      order and take the first ``capacity`` entries of each run as the
      ``[E, C, d]`` dispatch. Index work is the shared O(T·k log T·k) sort +
      O(T·k) segment arithmetic — no ``E·C``-slot scatter.
    - ``"gather"``: scatter token ids into an ``[E*C]`` slot table, gather
      token vectors into ``[E, C, d]``, gather expert outputs back by slot.
      Memory O(E*C*d + T*k).
    - ``"einsum"``: the GShard/Switch formulation with an explicit
      ``[T, E, C]`` dispatch/combine mask. O(T*E*C) memory; kept because its
      einsums partition very predictably under GSPMD (useful oracle and
      fallback).
    - ``"dropless"`` (MegaBlocks-style): NO capacity and NO dropped tokens —
      ``capacity_factor`` is irrelevant. Tokens are gathered once into the
      stats' sorted layout and the expert FFNs run as ragged grouped Pallas
      matmuls over the contiguous per-expert segments
      (ops/grouped_matmul.py); combine is the inverse-permutation gather.
      ``moe_drop_fraction`` sows an exact constant 0.0. Matches the einsum
      oracle at a never-drop capacity factor (tests/test_moe_dropless.py);
      the kernel runs interpret-mode off-TPU. ``ep_dispatch`` selects the
      execution layout: ``"replicated"`` (r14 default — single-program
      kernel on the replicated sorted array), ``"a2a"`` (sorted segments
      all-to-all'd to per-device expert shards, gmm against LOCAL weights
      only), or ``"a2a_overlap"`` (chunked double-buffered a2a so expert
      compute hides interconnect latency). All three are bitwise-identical
      per row; see the module docstring and PROFILE_MOE.md r17 addendum.

    ``router_dtype`` sets the logits-matmul precision (``RouterDense``):
    None/fp32 is the exact ST-MoE contract and the default; bf16 halves the
    matmul's read traffic with fp32 accumulation, parity-bounded in
    tests/test_moe_router.py. Softmax/top-k/logsumexp are always fp32.

    ``router_impl`` selects the softmax+top-k+gates computation:
    ``"reference"`` (default; plain XLA fp32 chain) or ``"fused"`` (the
    single-pass Pallas kernel in ops/fused_router.py — one VMEM-resident
    pass over the [T, E] logits, interpret-mode validated on CPU). Both
    produce identical routing decisions; ``fused`` stays opt-in until a
    chip A/B (PROFILE_MOE.md hooks).

    ``combine_dtype`` sets the precision of the output combine (the
    slot-gather of expert outputs + the ``tk,tkd->td`` gate einsum). It
    defaults to fp32 — the historical behavior and the equivalence oracle.
    The combine is pure bandwidth (its FLOPs are negligible; the gather of
    ``[T, k, d]`` expert outputs dominates), so running it in bf16 halves
    its HBM traffic; accumulation stays fp32 via
    ``preferred_element_type``.
    """

    num_experts: int
    ffn_dim: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 1e-3
    dispatch_impl: str = "gather"  # "sort" | "gather" | "einsum" | "dropless"
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    combine_dtype: Any = None  # None -> fp32 (exact); bf16 halves combine BW
    router_dtype: Any = None   # None -> fp32 logits matmul (exact); bf16 A/B
    router_impl: str = "reference"  # "reference" | "fused" (Pallas)
    # Dropless-only EP execution layout (module docstring; r17):
    # "replicated" = r14 single-program kernel; "a2a" = sharded segments to
    # per-device expert shards; "a2a_overlap" = chunked double-buffered a2a.
    ep_dispatch: str = "replicated"
    ep_overlap_chunks: int = 2

    @nn.compact
    def __call__(self, x, train: bool = True,
                 decode: bool = False):  # x: [B, S, d]
        B, S, d = x.shape
        E = self.num_experts
        tokens = x.reshape(B * S, d)
        T = B * S
        # Serving decode (models/llama.py threads ``decode_ctx`` down as
        # ``decode=True``) always routes DROPLESS, whatever dispatch_impl
        # the checkpoint trained with: capacity-dropped dispatch is
        # non-causal — a token's drop depends on capacity competition from
        # tokens AFTER it and on capacity = f(T) itself — so it has no
        # exact incremental equivalent, while dropless routing is
        # per-token-independent (bitwise row-invariant, r14/r17 contract)
        # and therefore identical between the [T_train] training forward
        # and [B*S] batch-decode shapes. Params are shared across impls
        # (``experts/w_up``/``w_down``), so this is a pure routing switch.
        dropless = self.dispatch_impl == "dropless" or decode
        if self.ep_dispatch != "replicated" and not dropless:
            raise ValueError(
                f"ep_dispatch={self.ep_dispatch!r} only applies to "
                f"dispatch_impl='dropless' (got {self.dispatch_impl!r}); "
                "the capacity-dropped impls shard through GSPMD alone")
        if dropless:
            # No capacity in the dropless formulation; a never-drop value
            # keeps stats.within_cap trivially all-true (and DCE'd — nothing
            # downstream reads it).
            capacity = T * self.top_k
        else:
            raw_capacity = int(self.capacity_factor * T * self.top_k / E)
            if raw_capacity < 1:
                _warn_capacity_clamp(self.capacity_factor, T, self.top_k, E)
            capacity = max(raw_capacity, 1)

        # Router logits in fp32 accumulation (standard for stability); the
        # softmax/top-k decision chain is always fp32.
        with jax.named_scope("moe_router"):
            router_logits = RouterDense(
                E, self.router_dtype, name="router")(tokens)        # [T, E]
            if self.router_impl == "fused":
                from pytorch_distributed_training_example_tpu.ops import (
                    fused_router as fused_router_lib)

                gate_vals, expert_idx, router_lse, router_me = (
                    fused_router_lib.fused_router(router_logits, self.top_k))
                probs = None
            elif self.router_impl == "reference":
                probs = jax.nn.softmax(router_logits, axis=-1)      # [T, E]
                # Top-k expert choice per token.
                gate_vals, expert_idx = jax.lax.top_k(
                    probs, self.top_k)                              # [T, k]
                gate_vals = gate_vals / jnp.maximum(
                    gate_vals.sum(-1, keepdims=True), 1e-9)
                router_lse = router_me = None
            else:
                raise ValueError(
                    f"unknown router_impl {self.router_impl!r}; "
                    "have ['reference', 'fused']")

        with jax.named_scope("moe_dispatch"):
            stats = routing_stats(expert_idx, E, capacity)
            if dropless:
                # Every (token, choice) is kept by construction: sow the
                # exact constant 0.0 instead of the within_cap reductions so
                # XLA DCEs the mask work rather than computing an
                # identically-zero value.
                self.sow("telemetry", "moe_drop_fraction",
                         jnp.zeros((), jnp.float32))
            else:
                gate_vals = gate_vals * stats.within_cap
                # Telemetry (ST-MoE router diagnostics): fraction of
                # (token, choice) assignments beyond expert capacity — exact
                # from the shared [E] counts, no mask re-materialized. sow
                # is a no-op unless the step runs with the "telemetry"
                # collection mutable (utils/telemetry health pack), and XLA
                # DCEs the unused reduction in that case.
                kept = jnp.sum(jnp.minimum(stats.counts, capacity))
                self.sow("telemetry", "moe_drop_fraction",
                         1.0 - kept.astype(jnp.float32) / (T * self.top_k))

        if dropless:
            out = self._dropless_route(tokens, expert_idx, stats, gate_vals)
        elif self.dispatch_impl == "sort":
            out = self._sort_route(tokens, expert_idx, stats, gate_vals,
                                   capacity)
        elif self.dispatch_impl == "einsum":
            out = self._einsum_route(tokens, expert_idx, stats, gate_vals,
                                     capacity)
        else:
            out = self._gather_route(tokens, expert_idx, stats, gate_vals,
                                     capacity)

        with jax.named_scope("moe_aux"):
            # Load-balancing aux loss (Switch eq. 4): E * sum_e f_e * P_e.
            # The gradient flows only through me (counts are int-derived),
            # so the compact ce is exactly gradient-equivalent to the
            # one-hot mean it replaces.
            me = router_me if router_me is not None else probs.mean(0)
            top1 = jnp.bincount(expert_idx[:, 0].astype(jnp.int32), length=E)
            top1 = mesh_lib.constrain(top1, P(None))
            ce = top1.astype(jnp.float32) / T           # top-1 routed frac
            aux = E * jnp.sum(me * ce)
            self.sow("losses", "moe_aux_loss", self.aux_loss_weight * aux)
            # Router z-loss (ST-MoE): keeps logits from drifting to
            # magnitudes where fp32 softmax saturates.
            lse = (router_lse if router_lse is not None else
                   jax.scipy.special.logsumexp(router_logits, axis=-1))
            z = jnp.mean(lse ** 2)
            self.sow("losses", "moe_z_loss", self.z_loss_weight * z)
            # Telemetry: entropy of the routed-load distribution over all k
            # choices (pre-capacity), normalized by ln(E) so 1.0 = perfectly
            # balanced, 0.0 = collapsed onto one expert. Shares the [E]
            # counts with dispatch — zero extra router-region traffic.
            load = stats.counts.astype(jnp.float32) / (T * self.top_k)
            ent = -jnp.sum(load * jnp.log(load + 1e-9)) / jnp.log(float(E))
            self.sow("telemetry", "router_load_entropy", ent)

        return out.reshape(B, S, d).astype(self.dtype)

    def _experts(self, dispatched):
        with jax.named_scope("moe_experts"):
            dispatched = mesh_lib.constrain(dispatched, P("expert", None, None))
            expert_out = ExpertFFN(self.num_experts, self.ffn_dim, self.dtype,
                                   self.param_dtype, name="experts")(dispatched)
            return mesh_lib.constrain(expert_out, P("expert", None, None))

    def _combine(self, expert_out, slot, gate_vals, n_slots):
        """Gather expert outputs back by slot and mix by gate weight.

        [E, C, d] expert outputs -> [T, k, d] gather by slot (the trash row
        n_slots reads zeros for dropped tokens) -> gate-weighted sum over k.
        Runs in ``combine_dtype`` (fp32 default); the einsum accumulates in
        fp32 either way via preferred_element_type.
        """
        with jax.named_scope("moe_combine"):
            d = expert_out.shape[-1]
            cdt = self.combine_dtype or jnp.float32
            out_pad = jnp.concatenate(
                [expert_out.reshape(n_slots, d).astype(cdt),
                 jnp.zeros((1, d), cdt)])                       # trash row
            # Replicate the slot table before the combine gather. Every
            # token needs rows from every expert, so GSPMD must all-gather
            # the [E·C, d] outputs over 'expert' here regardless; making it
            # explicit also sidesteps an SPMD partitioner
            # miscompile for gathers with sharded operands (wrong values,
            # reproduced in tests/test_moe_sort_dispatch.py's EP suite).
            out_pad = mesh_lib.constrain(out_pad, P(None, None))
            y = out_pad[slot]                                   # [T, k, d]
            return jnp.einsum("tk,tkd->td", gate_vals.astype(cdt), y,
                              preferred_element_type=jnp.float32)

    def _dropless_route(self, tokens, expert_idx, stats, gate_vals):
        """Dropless dispatch (MegaBlocks): ragged grouped matmul, no capacity.

        The shared stats' stable argsort already lays the (token, choice)
        pairs out as contiguous per-expert segments, so dispatch is ONE
        ``[kT, d]`` gather into sorted order and the expert FFNs consume the
        ragged layout directly via the Pallas gmm kernel with the ``[E]``
        segment starts/counts — no ``[E, C, d]`` buffer exists in the
        program. Combine is the scatter-add back through the sort
        permutation, read-side: the permutation is a bijection (nothing
        dropped, no trash row), so each (t, k)'s output row sits at
        ``slot = starts[expert] + pos`` and a gather + gate einsum is exact.
        """
        T, d = tokens.shape
        ep = _ep_degree(self.ep_dispatch, self.num_experts,
                        stats.order.shape[0])
        with jax.named_scope("moe_dispatch"):
            tok_flat = (stats.order % T).astype(jnp.int32)
            x_sorted = tokens[tok_flat].astype(self.dtype)       # [kT, d]
            # Pin the sorted layout: replicated for the single-program
            # kernel (pallas_call does not partition under GSPMD, and the
            # pin also sidesteps the sharded-operand gather
            # miscompile — see _combine); expert-sliced for the sharded EP
            # paths, matching the shard_map in_specs so GSPMD feeds the
            # manual region without a reshard.
            x_sorted = mesh_lib.constrain(
                x_sorted, P("expert", None) if ep > 1 else P(None, None))
        with jax.named_scope("moe_experts"):
            y_sorted = GroupedExpertFFN(
                self.num_experts, self.ffn_dim, self.dtype, self.param_dtype,
                ep_dispatch=self.ep_dispatch,
                ep_overlap_chunks=self.ep_overlap_chunks,
                name="experts")(x_sorted, stats.starts, stats.counts)
        with jax.named_scope("moe_combine"):
            cdt = self.combine_dtype or jnp.float32
            slot = stats.starts[expert_idx] + stats.pos          # [T, k]
            y_sorted = mesh_lib.constrain(y_sorted.astype(cdt), P(None, None))
            y = y_sorted[slot]                                   # [T, k, d]
            return jnp.einsum("tk,tkd->td", gate_vals.astype(cdt), y,
                              preferred_element_type=jnp.float32)

    def _sort_route(self, tokens, expert_idx, stats, gate_vals, capacity):
        """Sort-based dispatch (MegaBlocks-style, capacity-dropped).

        Expert e's queue = sorted entries [starts[e], starts[e]+C) of the
        shared stats order: one [E, C] take of token rows — no E*C scatter,
        no [T, k, E] mask. Overflow entries (c >= counts[e]) read the zero
        row T.
        """
        T, d = tokens.shape
        E = self.num_experts
        k = self.top_k
        n_slots = E * capacity
        with jax.named_scope("moe_dispatch"):
            tok_flat = (stats.order % T).astype(jnp.int32)
            take = stats.starts[:, None] + jnp.arange(
                capacity, dtype=jnp.int32)[None, :]
            valid = (jnp.arange(capacity)[None, :]
                     < stats.counts[:, None])                    # [E, C]
            tok_for_slot = jnp.where(
                valid, tok_flat[jnp.minimum(take, k * T - 1)], T)
            tokens_pad = jnp.concatenate(
                [tokens, jnp.zeros((1, d), tokens.dtype)])       # row T = 0
            dispatched = tokens_pad[tok_for_slot].astype(self.dtype)
        expert_out = self._experts(dispatched)
        slot = jnp.where(stats.within_cap,
                         expert_idx * capacity + stats.pos, n_slots)  # [T, k]
        return self._combine(expert_out, slot, gate_vals, n_slots)

    def _gather_route(self, tokens, expert_idx, stats, gate_vals, capacity):
        T, d = tokens.shape
        E = self.num_experts
        n_slots = E * capacity
        with jax.named_scope("moe_dispatch"):
            # Each kept (token, choice) owns one slot; the trash row (index
            # n_slots) absorbs dropped tokens. Slots are unique per expert
            # queue position, so the scatter has no collisions.
            slot = jnp.where(stats.within_cap,
                             expert_idx * capacity + stats.pos,
                             n_slots)                               # [T, k]
            tok_ids = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[:, None], slot.shape)
            token_for_slot = jnp.full((n_slots + 1,), T, jnp.int32)
            token_for_slot = token_for_slot.at[slot.reshape(-1)].set(
                tok_ids.reshape(-1))
            tokens_pad = jnp.concatenate(
                [tokens, jnp.zeros((1, d), tokens.dtype)])          # row T = 0
            dispatched = tokens_pad[token_for_slot[:n_slots]].reshape(
                E, capacity, d).astype(self.dtype)
        expert_out = self._experts(dispatched)
        return self._combine(expert_out, slot, gate_vals, n_slots)

    def _einsum_route(self, tokens, expert_idx, stats, gate_vals, capacity):
        E = self.num_experts
        with jax.named_scope("moe_dispatch"):
            # The explicit-mask formulation IS this impl's definition: the
            # one-hots here are its dispatch/combine operands, built from
            # the shared stats positions (not a second position chain).
            onehot = jax.nn.one_hot(expert_idx, E,
                                    dtype=jnp.float32)              # [T,k,E]
            cap_onehot = jax.nn.one_hot(stats.pos, capacity,
                                        dtype=jnp.float32)          # [T,k,C]
            dispatch = jnp.einsum(
                "tke,tkc->tec", onehot,
                cap_onehot * stats.within_cap[..., None])
            combine = jnp.einsum("tke,tkc,tk->tec", onehot, cap_onehot,
                                 gate_vals)
            dispatched = jnp.einsum(
                "tec,td->ecd", dispatch,
                tokens.astype(jnp.float32)).astype(self.dtype)
        expert_out = self._experts(dispatched)
        with jax.named_scope("moe_combine"):
            return jnp.einsum("tec,ecd->td", combine,
                              expert_out.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Expert layers that are told which experts they hold: a router, and the
# held experts' routine over its plan. Together with a shared expert and a
# bias that only chooses (afmoe, models/afmoe.py: SharedExpertMoE), or apart
# (smallthinker, models/smallthinker.py: TopKSoftmaxRouter ahead of
# attention, HeldExperts after it).
# ---------------------------------------------------------------------------


#: Row tile of the held experts' grouped matmuls: an expert sees T*k/E rows
#: on average (512 at 8,192 tokens and 8 of 128, 768 at 6 of 64), and a
#: taller tile pads more of them.
EXPERT_TILE_ROWS = 128


def _rows(x, index):
    """``x[index]`` with zeros where ``index`` is past the last row."""
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


#: The most bytes of a row-gather's source that XLA copies into VMEM and
#: gathers from there: a v5e's 128 MiB less the 16 MiB the compiler keeps for
#: scoped use. Measured (``benchmarks/moe_rows_micro.py --sweep``; PERF.md
#: section 6, PR 40): the combine over ``bf16[P, 2560]`` reads 0.89 ms at
#: 111.9 MiB and, at 112.5 MiB, 2.70 ms whole (from HBM) and 1.20 in two parts;
#: compiled for a described v5e, 22,936 rows are placed and 22,944 are not.
GATHER_SOURCE_BYTES = 112 * 2**20


def _source_parts(rows, d, itemsize):
    """In how many column parts a ``[rows, d]`` source of row-gathers is asked
    for: the fewest whose part is whole 128-lane columns of at most
    ``GATHER_SOURCE_BYTES``; 1 where the whole source is, or no part would
    be."""
    for n in range(1, d // 128 + 1):
        if d % (128 * n) == 0 and rows * (d // n) * itemsize \
                <= GATHER_SOURCE_BYTES:
            return n
    return 1


def _choice_sum(x_pad, pair_row, weights=None):
    """``sum_c weights[t, c] * x_pad[pair_row[c, t]]`` as ``[T, d]`` float32 (a
    choice with no padded row adds nothing; no ``weights``: ones), in as many
    column parts as ``_source_parts`` says of the source."""
    return _choice_sum_in(x_pad, pair_row, weights, _source_parts(
        *x_pad.shape, x_pad.dtype.itemsize))


def _choice_sum_in(x_pad, pair_row, weights, n):
    """``_choice_sum`` over ``n`` column parts of ``x_pad``, their ``[T, d /
    n]`` sums joined along the columns: the same sum of the same terms in the
    same order in every element, whatever ``n``. Asked for choice-major: a
    ``[T, d / n]`` slab of rows a choice, summed in the order c = 0..k-1. With
    the choice axis between rows and lanes XLA relays the gathered rows out
    into ``[T, k, d]`` tiles first, wherever k is no multiple of 8. A part is
    cut only once the part before it is summed (the barrier): XLA else makes
    all the parts in one fusion, side by side in HBM."""
    width, sums = x_pad.shape[1] // n, []
    for lo in range(0, n * width, width):
        if sums:
            x_pad, sums[-1] = jax.lax.optimization_barrier((x_pad, sums[-1]))
        slabs = (_rows(x_pad[:, lo:lo + width], index).astype(jnp.float32)
                 for index in pair_row)
        if weights is not None:
            slabs = (slab * w[:, None] for slab, w in zip(slabs, weights.T))
        sums.append(functools.reduce(jnp.add, slabs))
    # (one part: a slice of every column and a join of one array trace to
    # nothing, and the lowered text is what it was)
    return jnp.concatenate(sums, axis=1)


@jax.custom_vjp
def _dispatch_rows(tokens, row_token, pair_row):
    """``tokens [T, d]`` into the experts' padded layout ``[P, d]``: padded
    row p holds token ``row_token[p]`` (T = none: zeros). ``pair_row [k, T]``
    is the inverse (the padded row of a token's c-th choice, P = none), so
    the transpose is a gather too and no scatter-add is ever lowered."""
    return _rows(tokens, row_token)


def _dispatch_fwd(tokens, row_token, pair_row):
    return _rows(tokens, row_token), (row_token, pair_row)


def _dispatch_bwd(res, d_pad):
    row_token, pair_row = res
    return (_choice_sum(d_pad, pair_row).astype(d_pad.dtype),
            _int_zeros(row_token), _int_zeros(pair_row))


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine_rows(y_pad, weights, pair_row, row_pair):
    """``out[t] = sum_c weights[t, c] * y_pad[pair_row[c, t]]`` in float32;
    ``row_pair [P]`` is the inverse (the flat (token, choice) of a padded
    row, T*k = none)."""
    return _choice_sum(y_pad, pair_row, weights)


def _combine_fwd(y_pad, weights, pair_row, row_pair):
    return (_combine_rows(y_pad, weights, pair_row, row_pair),
            (y_pad, weights, pair_row, row_pair))


def _combine_bwd(res, d_out):
    """Both from one gather of ``d_out``'s float32 rows into the padded layout:
    a pair's weight gets its padded row's ``<y_pad[p], d_out[t]>``, a scalar.
    """
    y_pad, weights, pair_row, row_pair = res
    k = weights.shape[1]
    d_rows = _rows(d_out, row_pair // k)                            # [P, d]
    dw_pad = jnp.sum(y_pad.astype(jnp.float32) * d_rows, axis=-1)
    d_pad = (d_rows.astype(y_pad.dtype)
             * _rows(weights.reshape(-1, 1), row_pair))
    d_weights = _rows(dw_pad, pair_row).T                           # [T, k]
    return (d_pad.astype(y_pad.dtype), d_weights.astype(weights.dtype),
            _int_zeros(pair_row), _int_zeros(row_pair))


_combine_rows.defvjp(_combine_fwd, _combine_bwd)


def _int_zeros(index):
    return np.zeros(index.shape, jax.dtypes.float0)


def _held_keys(chosen, first, held):
    """``[n*k]``: the held expert (0..held-1) a (token, choice) pair chose,
    or ``held`` where it chose another chip's."""
    local = chosen.reshape(-1) - first
    return jnp.where((local >= 0) & (local < held), local, held)


def _held_counts(key, held):
    return jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)


def _plan(chosen, first, held, bt, max_tiles, counts):
    """The integer plan ``(tiles, pair_row [k, n], row_pair [P])`` of the held
    experts' padded layout for ``chosen [n, k]``: the (token, choice) pairs
    sorted by held expert, the pairs that chose another chip's expert behind
    them all; integers only. Both directions of every move are gathers."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    n, k = chosen.shape
    key = _held_keys(chosen, first, held)                           # [n*k]
    pairs = jnp.arange(n * k, dtype=jnp.int32)
    _, order = jax.lax.sort((key, pairs), num_keys=1)
    _, rank = jax.lax.sort((order, pairs), num_keys=1)
    if counts is None:
        counts = _held_counts(key, held)
    starts = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    tiles, src, dst = gmm_lib._padded_layout(
        starts, counts, n * k, held, bt, max_tiles)
    row_pair = _rows(order, src) + (src >= n * k) * (n * k)         # [P]
    pair_row = dst[rank].reshape(n, k).T                            # [k, n]
    return tiles, pair_row, row_pair


def _routed_kept(tokens, chosen, weights, experts, first, bt, max_tiles=None,
                 counts=None, act="silu"):
    """The held experts' part of the layer's sum for these tokens, ``[n, d]``
    float32, and what a backward reads of this forward beside its inputs:
    ``experts = (w_gate, w_up, w_down)`` are the experts ``first`` onwards
    (``act`` their gate's function, a key of ``grouped_matmul.GATES``), or
    ``(w_up, w_down)`` ungated ones (``act`` a key of ``grouped_matmul.ACTS``;
    ``grouped_matmul.FFN_FORMS`` has both forms' routines),
    ``chosen [n, k]`` indexes all the router's experts, the padded layout
    takes at most ``max_tiles`` tiles of ``bt`` rows, and ``counts`` are the
    held experts' rows where the caller has them (the router counts all the
    tokens). Kept: the integer plan ``(tiles, pair_row, row_pair)`` and the
    two projections ``(gate, up)`` of the padded rows in the compute dtype
    (``(up,)`` alone of an ungated expert);
    ``x_pad`` is one gather of rows from the plan and ``y_pad`` one grouped
    matmul from ``gate`` and ``up``, and whatever is kept here lives from the
    forward to the backward in the step XLA schedules (151 MB more a layer
    at Trinity's published widths, were both kept)."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    k, held = chosen.shape[1], experts[0].shape[0]
    with jax.named_scope("moe_dispatch"):
        tiles, pair_row, row_pair = _plan(chosen, first, held, bt, max_tiles,
                                          counts)
        x_pad = _dispatch_rows(tokens, row_pair // k, pair_row)
    with jax.named_scope("moe_experts"):
        y_pad, *pre = mesh_lib.manual_call(
            functools.partial(gmm_lib.FFN_FORMS[len(experts)][0], act=act),
            x_pad, *experts, tiles, in_specs=P(), out_specs=P())
    with jax.named_scope("moe_combine"):
        out = _combine_rows(y_pad, weights, pair_row, row_pair)
    return out, ((tiles, pair_row, row_pair), tuple(pre))


def _routed(tokens, chosen, weights, experts, first, bt, max_tiles=None,
            counts=None, act="silu"):
    """``_routed_kept``'s sum alone: under plain AD where every expert is
    held (no ``cond``), and the routine that the bounded layout repeats."""
    return _routed_kept(tokens, chosen, weights, experts, first, bt,
                        max_tiles, counts, act)[0]


def _routed_kept_bwd(kept, tokens, weights, experts, d_out, act="silu"):
    """``(d_tokens, d_weights, d_experts)`` of ``_routed_kept``'s sum from
    what it kept: the transposes that plain AD of ``_routed`` strings
    together, in its order, on the forward's own ``gate`` and ``up``; of the
    forward only the gather of rows and the down projection run again."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    (tiles, pair_row, row_pair), pre = kept
    _, down, bwd = gmm_lib.FFN_FORMS[len(experts)]
    with jax.named_scope("moe_dispatch"):
        row_token = row_pair // weights.shape[1]
        x_pad = _rows(tokens, row_token)
    with jax.named_scope("moe_experts"):
        y_pad = mesh_lib.manual_call(
            functools.partial(down, act=act), *pre, experts[-1], tiles,
            in_specs=P(), out_specs=P())
    with jax.named_scope("moe_combine"):
        dy_pad, d_weights = _combine_bwd(
            (y_pad, weights, pair_row, row_pair), d_out)[:2]
    with jax.named_scope("moe_experts"):
        dx_pad, *d_experts = mesh_lib.manual_call(
            functools.partial(bwd, act=act), x_pad, *pre, *experts, tiles,
            dy_pad, in_specs=P(), out_specs=P())
    with jax.named_scope("moe_dispatch"):
        d_tokens = _dispatch_bwd((row_token, pair_row), dx_pad)[0]
    return d_tokens, d_weights, tuple(d_experts)


def _bounded_tiles(chosen, experts, bt, chunks):
    """Tiles of the bounded layout: the worst case of a ``chunks``-th part
    of the tokens, every choice of each held here."""
    (n, k), held = chosen.shape, experts[0].shape[0]
    return -(-(n // chunks) * k // bt) + held


def _fits(counts, bt, cap):
    """Do the held experts' ``counts`` rows fit ``cap`` tiles of ``bt``?"""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    return gmm_lib.num_tiles(counts, bt) <= cap


def _in_parts(tokens, chosen, weights, experts, first, bt, chunks, cap, act):
    """``_routed`` over ``chunks`` parts of the tokens one after another,
    each counting its own rows; a part's residuals are its inputs."""
    n = chosen.shape[0]
    split = lambda a: a.reshape(chunks, n // chunks, *a.shape[1:])
    one = lambda a: _routed(*a, experts, first, bt, cap, act=act)
    return jax.lax.map(jax.checkpoint(one), (
        split(tokens), split(chosen), split(weights))).reshape(
            n, tokens.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _routed_bounded(tokens, chosen, weights, experts, counts, first, bt,
                    chunks, act="silu"):
    """``_routed`` in a layout of bounded size, whatever the router does.

    The layout's static size is its worst case: every choice of every token
    held here, E/held times the rows a balanced router sends. So the tokens
    are taken whole in a layout of the ``chunks``-th part of that where the
    router's ``counts`` of the held experts' rows say they fit (the rule,
    unless routing collapses), and else in ``chunks`` parts one after
    another, each of whose worst case is that same layout: the same routine
    either way, nothing dropped, and buffers of the smaller size alone.

    Differentiated by hand, because residuals that cross a ``cond`` are
    materialised and plain AD hands out the float32 intermediates of the
    gate and copies of the experts' weights among them (+1.5 GB). The forward
    rule's ``cond`` hands out, beside the sum, what ``_routed_kept`` keeps:
    the integer plan and ``gate`` and ``up`` in the compute dtype (zeros of
    those shapes from the parts, which nobody reads). The backward rule
    branches on the same counts: the whole layout's side strings the
    transposes together from what was kept (``_routed_kept_bwd``), the
    parts' side computes its forward again part by part, as ``lax.map`` over
    a checkpointed part does. This function itself computes the sum alone.
    """
    cap = _bounded_tiles(chosen, experts, bt, chunks)
    return jax.lax.cond(
        _fits(counts, bt, cap),
        lambda: _routed(tokens, chosen, weights, experts, first, bt, cap,
                        counts, act),
        lambda: _in_parts(tokens, chosen, weights, experts, first, bt, chunks,
                          cap, act))


def _routed_bounded_fwd(tokens, chosen, weights, experts, counts, first, bt,
                        chunks, act):
    cap = _bounded_tiles(chosen, experts, bt, chunks)
    whole = lambda: _routed_kept(tokens, chosen, weights, experts, first, bt,
                                 cap, counts, act)
    kept = jax.eval_shape(whole)[1]
    out, kept = jax.lax.cond(
        _fits(counts, bt, cap), whole,
        lambda: (_in_parts(tokens, chosen, weights, experts, first, bt,
                           chunks, cap, act),
                 jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), kept)))
    return out, (tokens, chosen, weights, experts, counts, kept)


def _routed_bounded_bwd(first, bt, chunks, act, res, d_out):
    tokens, chosen, weights, experts, counts, kept = res
    cap = _bounded_tiles(chosen, experts, bt, chunks)

    def parts():
        _, vjp = jax.vjp(
            lambda *a: _in_parts(a[0], chosen, *a[1:], first, bt, chunks,
                                 cap, act), tokens, weights, experts)
        return vjp(d_out)

    d_tokens, d_weights, d_experts = jax.lax.cond(
        _fits(counts, bt, cap),
        lambda: _routed_kept_bwd(kept, tokens, weights, experts, d_out, act),
        parts)
    return (d_tokens, _int_zeros(chosen), d_weights, d_experts,
            _int_zeros(counts))


_routed_bounded.defvjp(_routed_bounded_fwd, _routed_bounded_bwd)


class Route(NamedTuple):
    """A router's plan for ``T`` tokens: what the held experts' routine
    reads."""
    chosen: jax.Array    # [T, k] int32, over all the router's experts
    weights: jax.Array   # [T, k] float32
    load: jax.Array      # [E] the (token, choice) pairs that chose each


def _scores(tokens, kernel):
    """``tokens @ kernel`` as a true float32 product: two scores that nearly
    tie must come out in the order the published float32 router gives."""
    return jnp.dot(tokens.astype(jnp.float32), kernel,
                   precision=jax.lax.Precision.HIGHEST)            # [T, E]


def _load(chosen, num_experts):
    load = jnp.bincount(chosen.reshape(-1), length=num_experts)    # [E]
    return mesh_lib.constrain(load, P(None))


def route_sigmoid_bias(tokens, kernel, bias, k, route_scale) -> Route:
    """``s = sigmoid(tokens W_r)``; the ``k`` largest of ``s + bias`` are
    chosen; weights ``route_scale * s_i / (sum of the chosen s + 1e-20)``:
    the bias chooses and nothing more (torchtitan's router as the ``afmoe``
    models configure it)."""
    scores = jax.nn.sigmoid(_scores(tokens, kernel))
    _, chosen = jax.lax.top_k(scores + bias, k)                     # [T, k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = route_scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return Route(chosen, weights, _load(chosen, kernel.shape[1]))


def route_softmax_chosen(tokens, kernel, k) -> Route:
    """The ``k`` largest logits of ``tokens W_r`` are chosen; weights: the
    softmax over the chosen logits alone, then divided by their sum (the
    identity but for rounding; the published ``norm_topk_prob``). All
    float32 (the ``smallthinker`` models' primary router)."""
    top, chosen = jax.lax.top_k(_scores(tokens, kernel), k)         # [T, k]
    weights = jax.nn.softmax(top, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return Route(chosen, weights, _load(chosen, kernel.shape[1]))


class Held(NamedTuple):
    """What ``_held_sum`` made on its way, for its callers' telemetry."""
    experts: tuple       # (w_gate, w_up, w_down) in the compute dtype, or
                         # (w_up, w_down) of ungated experts
    tokens: jax.Array    # [T, d] in the compute dtype
    first: int           # the first held expert
    load: jax.Array      # [held] int32: the held experts' rows
    whole: jax.Array     # 1.0 where the rows went through the layout whole
    bt: int              # the layout's tile rows
    cap: int | None      # the bounded layout's tiles (None: unbounded)
    parts: int           # the column parts its padded rows are gathered in


def _held_sum(module, tokens, route: Route, ffn_dim, held_experts, act,
              gated=True):
    """The held experts' routine inside ``module`` (which gets the stacked
    ``w_gate``, ``w_up``, ``w_down``; ``w_up`` and ``w_down`` alone where the
    experts are not ``gated``: ``act(x W_up) W_down``, ``act`` then a key of
    ``grouped_matmul.ACTS``): the part of ``sum_c weights[t, c] *
    Expert_chosen[t, c](tokens[t])`` that the experts ``held_experts = (how
    many, starting where)`` give, ``[T, d]`` float32, dropless whatever the
    imbalance; and a :class:`Held`.

    Where under half of the experts are held the rows go through
    ``_routed_bounded``: one ``cond`` on the router's own counts of the held
    experts' rows, which in the forward that a backward follows hands out
    the integer plan and the experts' ``gate`` and ``up`` projections in the
    compute dtype, so that the backward runs no routed forward again; no
    float32 intermediate and no copy of a weight crosses it. Where all are
    held (``chunks == 1``) ``_routed`` runs under plain AD."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    (T, d), (_, k), E = tokens.shape, route.chosen.shape, route.load.shape[0]
    held, first = held_experts or (E, 0)
    if not (0 < held and 0 <= first and first + held <= E):
        raise ValueError(f"held_experts={held_experts} of {E}")
    stacked = lambda name, shape: module.param(
        name, nn.initializers.lecun_normal(), (held, *shape),
        module.param_dtype).astype(module.dtype)
    experts = ((stacked("w_gate", (d, ffn_dim)),) if gated else ()) + (
        stacked("w_up", (d, ffn_dim)), stacked("w_down", (ffn_dim, d)))
    bt = min(EXPERT_TILE_ROWS, gmm_lib._block_rows(T * k, held))
    held_load = route.load[first:first + held].astype(jnp.int32)

    # rows in a layout of bounded size: see ``_routed_bounded``
    tokens = tokens.astype(module.dtype)
    chunks = max(1, E // (2 * held))
    if chunks == 1 or T % chunks:
        cap = None
        out = _routed(tokens, route.chosen, route.weights, experts, first, bt,
                      act=act)
        whole = jnp.ones((), jnp.float32)
    else:
        cap = _bounded_tiles(route.chosen, experts, bt, chunks)
        out = _routed_bounded(tokens, route.chosen, route.weights, experts,
                              held_load, first, bt, chunks, act)
        whole = _fits(held_load, bt, cap).astype(jnp.float32)
    tiles = -(-T * k // bt) + held      # ``_padded_layout``'s own bound
    parts = _source_parts(bt * min(tiles, cap or tiles), d,
                          tokens.dtype.itemsize)
    return out, Held(experts, tokens, first, held_load, whole, bt, cap, parts)


def _sow_telemetry(module, **values):
    """Sow each value into ``telemetry`` (fetched at the log cadence) under
    its name with the enclosing block's name behind a dot."""
    layer = "." + module.path[-2] if len(module.path) > 1 else ""
    for name, value in values.items():
        module.sow("telemetry", name + layer, value)


def _held_peak(rows):
    """The fullest held expert's rows over their mean."""
    return jnp.max(rows) / jnp.maximum(jnp.mean(rows), 1.0)


class SharedExpertMoE(nn.Module):
    """A sigmoid-and-bias router and the held experts' routine beside a
    shared expert, on the chip that holds ``held_experts`` of the experts
    (torchtitan's MoE as the ``afmoe`` models configure it; the equations
    are in ``models/afmoe.py``).

    ``s = sigmoid(x W_r)`` over all ``num_experts``, in float32; the ``top_k``
    largest of ``s + b`` are chosen (``b``, ``expert_bias``, is a buffer in
    the ``batch_stats`` collection: no gradient, no optimizer state); the
    weights are ``route_scale * s_i / (sum of the chosen s + 1e-20)``: the
    bias chooses and nothing more (``route_sigmoid_bias``). ``y = Shared(x)
    + sum_i w_i Expert_i(x)`` with SwiGLU experts, or where ``gated`` is
    false with two-matrix squared-ReLU ones, ``relu(x W_up)^2 W_down``, the
    shared expert likewise (the ``nemotron_h`` models). After a training step ``b
    += d - mean(d)``, ``d = balance_coeff * sign(mean(c) - c)``, ``c`` the
    tokens of this call that chose each expert (all ``num_experts``, this
    chip's tokens).

    ``held_experts = (how many, starting where)``: the layer routes over all
    the experts and computes the part of the sum that its own give, for the
    tokens that chose them (``_held_sum``); what the others would add is left
    out, and is the business of the chips that hold them (their results
    would be summed over the ``expert`` axis; on one chip the layer runs
    without that exchange). Dropless: every (token, choice) that lands here
    is computed, whatever the imbalance. The rows are gathered straight into
    the grouped matmul's tile layout (``ops/grouped_matmul.py``), whose
    kernels run only the tiles in use: a row that chose no held expert costs
    no matmul tile.

    Sows into ``telemetry`` (fetched at the log cadence): ``moe_held_rows``
    (the rows that landed on held experts), ``moe_held_peak`` (the fullest
    held expert's rows over their mean), ``moe_bias_peak`` (largest |b|) and
    ``moe_whole`` (1.0 where the held rows fit the whole layout and the kept
    residuals serve the backward, 0.0 where the tokens went in parts) and
    ``moe_source_parts`` (the column parts the padded rows are gathered back
    in: ``_source_parts``), each with the enclosing block's name behind a dot;
    an ungated layer also ``moe_gate_zero`` (the share of the held rows' ``up``
    pre-activations that the squared ReLU zeroes: ``_gate_zero_share``), in a
    run that collects ``telemetry``.
    """

    num_experts: int
    ffn_dim: int
    top_k: int
    held_experts: tuple | None = None   # (how many, starting where)
    shared_ffn_dim: int = 0
    route_scale: float = 1.0
    balance_coeff: float = 0.0
    gated: bool = True                  # SwiGLU experts; False: squared ReLU
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        """``x [B, S, d]`` in any float dtype: the router reads it as it
        comes (float32 from a caller that keeps it so), the experts in the
        compute dtype."""
        B, S, d = x.shape
        E = self.num_experts
        tokens = x.reshape(B * S, d)
        bias = self.variable("batch_stats", "expert_bias",
                             lambda: jnp.zeros((E,), jnp.float32))

        with jax.named_scope("moe_router"):
            kernel = self.param("router", nn.initializers.lecun_normal(),
                                (d, E), jnp.float32)
            route = route_sigmoid_bias(tokens, kernel, bias.value, self.top_k,
                                       self.route_scale)
            if train and not self.is_initializing() \
                    and self.is_mutable_collection("batch_stats"):
                mean = jnp.mean(route.load.astype(jnp.float32))
                delta = self.balance_coeff * jnp.sign(mean - route.load)
                bias.value = bias.value + delta - jnp.mean(delta)

        out, held = _held_sum(self, tokens, route, self.ffn_dim,
                              self.held_experts,
                              "silu" if self.gated else "relu2", self.gated)
        if self.shared_ffn_dim:
            with jax.named_scope("moe_shared"):
                shared = SwiGLU if self.gated else SquaredReLU
                out = out + shared(self.shared_ffn_dim, self.dtype,
                                   self.param_dtype, name="shared")(
                    held.tokens).astype(jnp.float32)

        rows = held.load.astype(jnp.float32)
        sown = dict(moe_held_rows=jnp.sum(rows),
                    moe_held_peak=_held_peak(rows),
                    moe_bias_peak=jnp.max(jnp.abs(bias.value)),
                    moe_whole=held.whole,
                    moe_source_parts=jnp.float32(held.parts))
        if not self.gated and self.is_mutable_collection("telemetry"):
            sown["moe_gate_zero"] = _gate_zero_share(route.chosen, held)
        _sow_telemetry(self, **sown)
        return out.reshape(B, S, d).astype(self.dtype)


class TopKSoftmaxRouter(nn.Module):
    """The router alone, for a block that routes on another tensor than its
    experts read (the ``smallthinker`` models route on the attention's input,
    ahead of attention): ``route_softmax_chosen`` over ``x [B, S, d]``
    flattened to tokens, float32 throughout on a float32 input. One
    parameter, ``kernel [d, num_experts]`` float32. Give it the name
    ``moe_router``: the module's name is its scope in the step program."""
    num_experts: int
    top_k: int

    @nn.compact
    def __call__(self, x) -> Route:
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.num_experts), jnp.float32)
        return route_softmax_chosen(x.reshape(-1, x.shape[-1]), kernel,
                                    self.top_k)


class HeldExperts(nn.Module):
    """The held experts' routine alone (``_held_sum``), over a plan that a
    router made elsewhere: ``y[t] = sum_c weights[t, c] * Expert_chosen[t, c]
    (x[t])`` over the choices that fell on the experts held here, ``Expert(x)
    = (act(x W_gate) * x W_up) W_down``. No shared expert, no bias, no
    buffer: a token none of whose choices is held gets exactly zero.

    Sows into ``telemetry`` as :class:`SharedExpertMoE` does:
    ``moe_held_rows``, ``moe_held_peak``, ``moe_whole``,
    ``moe_source_parts``, and ``moe_gate_zero`` (the share of the held rows'
    gate activations that ``relu`` zeroes: what a kernel that skipped them
    would have to gain from; NaN where the rows did not fit the layout
    whole). The last costs the plan and the gate projection once more, and is
    computed only in a run that collects ``telemetry``."""
    ffn_dim: int
    held_experts: tuple | None = None   # (how many, starting where)
    act: str = "relu"                   # a key of ops.grouped_matmul.GATES
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, route: Route):
        out, held = _held_sum(self, x.reshape(-1, x.shape[-1]), route,
                              self.ffn_dim, self.held_experts, self.act)
        rows = held.load.astype(jnp.float32)
        sown = dict(moe_held_rows=jnp.sum(rows),
                    moe_held_peak=_held_peak(rows), moe_whole=held.whole,
                    moe_source_parts=jnp.float32(held.parts))
        if self.is_mutable_collection("telemetry"):
            sown["moe_gate_zero"] = _gate_zero_share(route.chosen, held)
        _sow_telemetry(self, **sown)
        return out.reshape(x.shape).astype(self.dtype)


def _gate_zero_share(chosen, held: Held):
    """The share of the held rows' gate pre-activations ``x W_gate`` that are
    not positive (``relu`` zeroes them); of an ungated layer, whose first
    matrix is ``W_up``, the share of ``x W_up`` that its squared ReLU zeroes.
    Telemetry only: the plan and the
    gate projection once more, outside the routine and its ``cond``. NaN
    where the rows do not fit the bounded layout whole."""
    from pytorch_distributed_training_example_tpu.ops import (
        grouped_matmul as gmm_lib)

    tokens, w_gate = jax.lax.stop_gradient((held.tokens, held.experts[0]))
    k = chosen.shape[1]
    tiles, _, row_pair = _plan(chosen, held.first, w_gate.shape[0], held.bt,
                               held.cap, held.load)
    gate = mesh_lib.manual_call(
        gmm_lib._gmm_padded, _rows(tokens, row_pair // k), w_gate, tiles,
        in_specs=P(), out_specs=P())
    # padding rows are zero rows and give zeros; the tiles past the last one
    # in use are never written
    live = jnp.arange(gate.shape[0]) // held.bt < tiles[2][0]
    positive = jnp.sum((gate > 0) & live[:, None])
    share = 1.0 - positive / jnp.maximum(
        jnp.sum(held.load) * w_gate.shape[2], 1)
    return jnp.where(held.whole > 0, share, jnp.nan)


class SwiGLU(nn.Module):
    """``down(silu(gate(h)) * up(h))`` without biases, as a module of its
    own (``models.llama.swiglu_mlp`` builds the same three layers into the
    calling block)."""
    ffn_dim: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, h):
        dense = lambda feat, name: nn.Dense(
            feat, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        return dense(h.shape[-1], "down")(
            nn.silu(dense(self.ffn_dim, "gate")(h))
            * dense(self.ffn_dim, "up")(h))


class SquaredReLU(nn.Module):
    """``down(relu(up(h))^2)`` without biases: the ungated two-matrix MLP of
    the ``nemotron_h`` models' shared expert, activated as the routed ones
    are (``ops.grouped_matmul._activated``: in float32, rounded once)."""
    ffn_dim: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, h):
        dense = lambda feat, name: nn.Dense(
            feat, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        from pytorch_distributed_training_example_tpu.ops import (
            grouped_matmul as gmm_lib)

        return dense(h.shape[-1], "down")(
            gmm_lib._activated(dense(self.ffn_dim, "up")(h)))


#: Expert-parallel rules: stacked expert weights shard on the 'expert' axis
#: (composes with fsdp on the remaining dims via AUTO composition).
EP_RULES = (
    (r"experts/w_(up|down)", P("expert", None, None)),
    (r"router/kernel", P()),
)

"""The online flash kernels as they stood before the causal schedule (PR 42):
the whole (q blocks x kv blocks) rectangle as the grid, every computed block
masked by position. Kept as the tests' oracle: the scheduled kernels must
give these results bitwise (tests/test_attention.py), and lower a call that
is not causal to this text (tests/test_chip_compile.py). Not a test file."""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_training_example_tpu.ops.flash_attention import (
    LSE_LANES, NEG_INF, _delta_rows, _fit_block, _mxu)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                sm_scale: float, causal: bool, block_q: int, block_kv: int):
    qi = pl.program_id(2)
    kvi = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kvi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: kv block strictly above the diagonal contributes nothing.
    run = True
    if causal:
        run = kvi * block_kv <= (qi + 1) * block_q - 1

    @pl.when(run)
    def _compute():
        # MXU-native operands: dots take q/k/v in their stored dtype (bf16 in
        # training) with fp32 accumulation via preferred_element_type — the
        # FlashAttention-2 scheme. Upcasting operands to fp32 here measured
        # ~20 TF/s on v5e (fp32 MXU rate); bf16 operands run ~2-3x faster.
        # All softmax state (m, l, acc) stays fp32.
        q = _mxu(q_ref[0, 0])                         # [bq, D]
        k = _mxu(k_ref[0, 0])                         # [bkv, D]
        v = _mxu(v_ref[0, 0])                         # [bkv, D]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bkv]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 0)
            k_pos = kvi * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 1)
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)

        m_prev = m_ref[:, :1]                         # [bq, 1] (lane-bcast)
        block_max = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        p = jnp.exp(logits - m_new)                   # [bq, bkv]
        correction = jnp.exp(m_prev - m_new)          # [bq, 1]
        l_new = l_ref[:, :1] * correction + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * correction + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kvi == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        # lse rows broadcast over LSE_LANES (Mosaic tiling needs >= 2D tiles).
        lse_ref[0, 0] = (m_ref[:, :LSE_LANES]
                         + jnp.log(jnp.maximum(l_ref[:, :LSE_LANES], 1e-30)))


def _flash_fwd(q, k, v, *, causal: bool, block_q: int, block_kv: int):
    """Returns (out [B,S,H,D], lse [B,H,S]) with K/V already GQA-expanded."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    # head-major layout for the kernel
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    block_q = _fit_block(Sq, block_q)
    block_kv = _fit_block(Skv, block_kv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, Skv, block_q, block_kv)
    grid = (B, H, Sq // block_q, Skv // block_kv)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=1.0 / math.sqrt(D),
                          causal=causal, block_q=block_q, block_kv=block_kv),
        name="flash_fwd_online",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LSE_LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, LSE_LANES), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # m
            pltpu.VMEM((block_q, 128), jnp.float32),   # l
            pltpu.VMEM((block_q, D), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(qt, kt, vt)
    return jnp.transpose(out, (0, 2, 1, 3)), lse


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, sm_scale, causal, block_q, block_kv):
    qi = pl.program_id(2)
    kvi = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kvi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = kvi * block_kv <= (qi + 1) * block_q - 1

    @pl.when(run)
    def _compute():
        # Native-dtype matmul operands, fp32 accumulation (see _fwd_kernel).
        q = _mxu(q_ref[0, 0])
        k = _mxu(k_ref[0, 0])
        v = _mxu(v_ref[0, 0])
        do = _mxu(do_ref[0, 0])
        lse = lse_ref[0, 0, :, :1]               # [bq, 1]
        delta = delta_ref[0, 0, :, :1]           # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kvi * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                     # [bq, bkv]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        acc_ref[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)

    @pl.when(kvi == n_kv - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                sm_scale, causal, block_q, block_kv):
    kvi = pl.program_id(2)
    qi = pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (qi + 1) * block_q - 1 >= kvi * block_kv

    @pl.when(run)
    def _compute():
        # Native-dtype matmul operands, fp32 accumulation (see _fwd_kernel).
        q = _mxu(q_ref[0, 0])
        k = _mxu(k_ref[0, 0])
        v = _mxu(v_ref[0, 0])
        do = _mxu(do_ref[0, 0])
        lse = lse_ref[0, 0, :, :1]               # [bq, 1]
        delta = delta_ref[0, 0, :, :1]           # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kvi * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                     # [bq, bkv]
        # dV += P^T dO
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        # dK += dS^T Q
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, *, causal, block_q, block_kv):
    """q,k,v,o,g: [B,S,H,D] (kv already GQA-expanded); lse: [B,H,Sq]."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    block_q = _fit_block(Sq, block_q)
    block_kv = _fit_block(Skv, block_kv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, Skv, block_q, block_kv)
    sm_scale = 1.0 / math.sqrt(D)
    delta = _delta_rows(g, o)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    dot = jnp.transpose(g, (0, 2, 1, 3))

    qspec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block_kv, D), lambda b, h, i, j: (b, h, j, 0))
    lspec = pl.BlockSpec((1, 1, block_q, LSE_LANES),
                         lambda b, h, i, j: (b, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_kv=block_kv),
        name="flash_bwd_dq",
        grid=(B, H, Sq // block_q, Skv // block_kv),
        in_specs=[qspec, kspec, kspec, qspec, lspec, lspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(qt, kt, vt, dot, lse, delta)

    # dk/dv pass: kv blocks outer (parallel), q blocks inner (accumulated).
    qspec2 = pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, 0))
    kspec2 = pl.BlockSpec((1, 1, block_kv, D), lambda b, h, j, i: (b, h, j, 0))
    lspec2 = pl.BlockSpec((1, 1, block_q, LSE_LANES),
                          lambda b, h, j, i: (b, h, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_kv=block_kv),
        name="flash_bwd_dkv",
        grid=(B, H, Skv // block_kv, Sq // block_q),
        in_specs=[qspec2, kspec2, kspec2, qspec2, lspec2, lspec2],
        out_specs=(kspec2, kspec2),
        out_shape=(jax.ShapeDtypeStruct((B, H, Skv, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Skv, D), v.dtype)),
        scratch_shapes=[pltpu.VMEM((block_kv, D), jnp.float32),
                        pltpu.VMEM((block_kv, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(qt, kt, vt, dot, lse, delta)

    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return tr(dq), tr(dk), tr(dv)

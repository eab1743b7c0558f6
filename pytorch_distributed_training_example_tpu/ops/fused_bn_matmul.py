"""Fused (BN-apply + ReLU) -> 1x1-conv matmul -> BN-statistics Pallas kernel.

The ResNet-50 profile (PROFILE_RN50.md) pins 46% of the v5e step on
BatchNorm-statistics reductions and another 22% on the elementwise
BN-apply/ReLU passes — both pure HBM traffic over activation tensors that
the convolutions already stream through VMEM. A 1x1 convolution in NHWC is
exactly a matmul ``[B*H*W, Cin] @ [Cin, Cout]`` (most of ResNet-50's convs:
the bottleneck reduce/expand pair), so this kernel fuses, in ONE pass over
the activation:

- prologue: per-channel affine (the *previous* BN's fold: ``x*scale+bias``)
  + ReLU, applied to the block while it sits in VMEM;
- body: the MXU matmul;
- epilogue: per-channel ``sum(y)`` and ``sum(y^2)`` of the conv *output*
  accumulated across row-blocks — the statistics the *next* BN needs,
  computed without ever re-reading ``y`` from HBM.

Relative to XLA's schedule (separate BN-apply pass + conv + separate
``convert_reduce_fusion`` stats pass) this removes an elementwise
read+write of the input tensor and a full re-read of the output tensor:
for the canonical ``[128*56*56, 256] @ [256, 64]`` bottleneck conv that is
~720 MB -> ~260 MB of logical HBM traffic (2.8x) for the segment.

Grid: 1-D over row blocks (the full ``[Cin, Cout]`` weight tile stays
resident in VMEM — 1x1-conv weights are <=1 MB). The stats output block
maps every grid step to the same ``[8, Cout]`` tile; TPU grids execute
sequentially, so read-modify-write accumulation across steps is sound
(same revisiting pattern as the flash-attention kernel's accumulators).

``fused_stats_matmul`` is the raw kernel; ``bn_stats_matmul`` wraps it
with channel padding to the 128-lane boundary and returns
``(y, mean, var)`` — a drop-in for ``relu(x*s+b) @ w`` + ``moments(y)``.
Microbenchmark + parity artifact: benchmarks/fused_bn_bench.py ->
BENCH_FUSED_BN.json (VERDICT r2 #1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

STATS_ROWS = 8  # f32 sublane tile height; row 0 = sum, row 1 = sum of squares


def _kernel(x_ref, w_ref, scale_ref, bias_ref, y_ref, stats_ref, *,
            relu: bool, affine: bool):
    i = pl.program_id(0)
    x = x_ref[:]
    if affine:
        x = x * scale_ref[:] + bias_ref[:]
    if relu:
        x = jnp.maximum(x, 0.0)
    y = jnp.dot(x.astype(w_ref.dtype), w_ref[:],
                preferred_element_type=jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)

    @pl.when(i == 0)
    def _init():
        stats_ref[:] = jnp.zeros_like(stats_ref)

    zeros = jnp.zeros((STATS_ROWS - 2, y.shape[1]), jnp.float32)
    block = jnp.concatenate(
        [jnp.sum(y, 0)[None], jnp.sum(y * y, 0)[None], zeros], 0)
    stats_ref[:] += block


def fused_stats_matmul(x, w, scale=None, bias=None, *, relu: bool = True,
                       block_n: int = 1024, out_dtype=None,
                       interpret: bool = False):
    """``y = maybe_relu(x*scale+bias) @ w`` plus per-column sum/sumsq of y.

    x: [N, K] (N % block_n == 0), w: [K, C] with C a multiple of 128.
    scale/bias: [1, K] per-channel affine on x (None = skip).
    Returns (y [N, C], stats [STATS_ROWS, C] f32) with stats[0]=sum(y),
    stats[1]=sum(y^2) over rows.
    """
    N, K = x.shape
    K2, C = w.shape
    assert K == K2, (x.shape, w.shape)
    block_n = min(block_n, N)
    assert N % block_n == 0, (N, block_n)
    assert C % 128 == 0, f"pad Cout to the 128-lane boundary (got {C})"
    affine = scale is not None or bias is not None
    if scale is None:
        scale = jnp.ones((1, K), x.dtype)
    if bias is None:
        bias = jnp.zeros((1, K), x.dtype)
    out_dtype = out_dtype or x.dtype
    grid = (N // block_n,)
    y, stats = pl.pallas_call(
        functools.partial(_kernel, relu=relu, affine=affine),
        name="fused_bn_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, K), lambda i: (i, 0)),
            pl.BlockSpec((K, C), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, C), lambda i: (i, 0)),
            pl.BlockSpec((STATS_ROWS, C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, C), out_dtype),
            jax.ShapeDtypeStruct((STATS_ROWS, C), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, scale, bias)
    return y, stats


def bn_stats_matmul(x, w, scale=None, bias=None, *, relu: bool = True,
                    block_n: int = 1024, interpret: bool = False):
    """Channel-padding wrapper returning ``(y, mean, var)`` of the output.

    Pads Cout up to 128 lanes (zero columns produce zero stats and are
    sliced away), so it accepts the raw ResNet channel counts (64, ...).
    """
    N, K = x.shape
    C = w.shape[1]
    Cp = max(128, -(-C // 128) * 128)
    if Cp != C:
        w = jnp.pad(w, ((0, 0), (0, Cp - C)))
    y, stats = fused_stats_matmul(x, w, scale, bias, relu=relu,
                                  block_n=block_n, interpret=interpret)
    mean = stats[0, :C] / N
    var = stats[1, :C] / N - mean * mean
    return y[:, :C], mean, var

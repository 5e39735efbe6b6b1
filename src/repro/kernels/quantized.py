"""int8 symmetric per-channel quantized matmul (Pallas).

Three kernels back the quantized glass tier:

  * ``quantize_rowwise`` — per-row symmetric int8 quantization:
    ``scale[m] = max_k |x[m, k]| / 127`` (1.0 for an all-zero row),
    ``q = clip(round(x / scale), -127, 127)``. Round-to-nearest gives
    the per-element round-trip bound ``|dequant(quant(x)) - x| <=
    scale / 2``. Per-output-channel weight quantization is the same
    kernel applied to ``w.T`` (see ``ops.quantize_colwise``).
  * ``dequantize_rowwise`` — ``q.astype(f32) * scale`` (the packed
    wire format a consuming tier unpacks before fusion).
  * ``int8_matmul`` — the fused ``int8 x int8 -> int32 -> scaled f32``
    GEMM: ``out[m, n] = (sum_k xq[m, k] * wq[k, n]) * sx[m] * sw[n]``.
    The contraction accumulates EXACTLY in int32 (no overflow for
    ``K <= 2^31 / 127^2 ~ 133k``, asserted in the wrapper), so the
    only error vs fp32 is the input quantization itself.

Blocking: the grid tiles M (and N for the GEMM); K is kept whole per
block — every matmul in this repo has K = the model width (<= a few
hundred), far under VMEM pressure. Inputs are zero-padded to block
multiples (zero rows quantize to scale 1.0 / q 0 and contribute 0 to
the dot); pad rows/cols are sliced off the output.

On CPU the kernels run with ``interpret=True`` (see ``ops``); on TPU
the same calls lower to Mosaic with the int8 (32, 128) tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# int32 accumulator headroom: K * 127 * 127 must stay below 2^31
MAX_K = (1 << 31) // (127 * 127)


def _pad_to(x, mult, axis):
    p = (-x.shape[axis]) % mult
    if not p:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, p)
    return jnp.pad(x, pads)


# ----------------------------------------------------------------------
# quantize / dequantize
# ----------------------------------------------------------------------

def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:].astype(jnp.float32)                    # (bm, K)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)   # (bm, 1)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[:] = q.astype(jnp.int8)
    s_ref[:] = scale


def quantize_rowwise(x, *, block_m: int = 32, interpret: bool = False):
    """x: (M, K) float -> (q int8 (M, K), scale f32 (M, 1))."""
    M, K = x.shape
    bm = min(block_m, max(M, 1))
    xp = _pad_to(x, bm, 0)
    nm = xp.shape[0] // bm
    q, s = pl.pallas_call(
        _quantize_kernel,
        grid=(nm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((xp.shape[0], K), jnp.int8),
                   jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xp)
    return q[:M], s[:M]


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:]


def dequantize_rowwise(q, scale, *, block_m: int = 32,
                       interpret: bool = False):
    """(q int8 (M, K), scale (M, 1)) -> f32 (M, K)."""
    M, K = q.shape
    bm = min(block_m, max(M, 1))
    qp = _pad_to(q, bm, 0)
    sp = _pad_to(scale.astype(jnp.float32), bm, 0)
    nm = qp.shape[0] // bm
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(nm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], K), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(qp, sp)
    return out[:M]


# ----------------------------------------------------------------------
# fused int8 x int8 -> int32 -> scaled f32 GEMM
# ----------------------------------------------------------------------

def _matmul_kernel(xq_ref, wq_ref, sx_ref, sw_ref, o_ref):
    acc = jax.lax.dot_general(
        xq_ref[:], wq_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)               # exact in int32
    o_ref[:] = acc.astype(jnp.float32) * sx_ref[:] * sw_ref[:]


def int8_matmul(xq, sx, wq, sw, *, block_m: int = 32, block_n: int = 128,
                interpret: bool = False):
    """Fused quantized GEMM.

    xq: (M, K) int8, sx: (M, 1) f32 row scales,
    wq: (K, N) int8, sw: (1, N) f32 output-channel scales
    -> (M, N) f32 ``(xq @ wq) * sx * sw``.
    """
    M, K = xq.shape
    K2, N = wq.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: {xq.shape} x {wq.shape}")
    if K > MAX_K:
        raise ValueError(f"K={K} overflows the int32 accumulator "
                         f"(max {MAX_K})")
    bm = min(block_m, max(M, 1))
    bn = min(block_n, max(N, 1))
    xp = _pad_to(xq, bm, 0)
    sxp = _pad_to(sx.astype(jnp.float32), bm, 0)
    wp = _pad_to(wq, bn, 1)
    swp = _pad_to(sw.astype(jnp.float32), bn, 1)
    nm, nn = xp.shape[0] // bm, wp.shape[1] // bn
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(nm, nn),
        in_specs=[pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
                  pl.BlockSpec((K, bn), lambda i, j: (0, j)),
                  pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xp, wp, sxp, swp)
    return out[:M, :N]


def quantized_matmul(x, wq, sw, *, block_m: int = 32, block_n: int = 128,
                     interpret: bool = False):
    """fp32 activations x pre-quantized weights, one fused path:
    rowwise-quantize ``x`` then ``int8_matmul``. x: (M, K) f32."""
    xq, sx = quantize_rowwise(x, block_m=block_m, interpret=interpret)
    return int8_matmul(xq, sx, wq, sw, block_m=block_m, block_n=block_n,
                       interpret=interpret)

"""Jit'd public wrappers for the Pallas kernels.

This module is the one place that decides interpret mode: on the CPU
backend the kernels run with ``interpret=True`` (Pallas executes the
kernel body in Python); on TPU the same calls lower to Mosaic. There is
no switch that interprets on a TPU. Tests that check a kernel body on
its own call the raw kernels with ``interpret=True``.
"""
from __future__ import annotations

from functools import partial

import jax

from . import decode_attention as _da
from . import flash_attention as _fa
from . import quantized as _q
from . import rwkv6 as _rw


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, kv_lengths=None,
                    segment_ids=None, block_q=128, block_k=128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               kv_lengths=kv_lengths,
                               segment_ids=segment_ids, block_q=block_q,
                               block_k=block_k, interpret=_interpret())


@partial(jax.jit, static_argnames=("block_t",))
def rwkv6_scan(r, k, v, w, u, s0=None, *, block_t=64):
    return _rw.rwkv6_scan(r, k, v, w, u, s0, block_t=block_t,
                          interpret=_interpret())


@partial(jax.jit, static_argnames=("window", "block_k"))
def decode_attention(q, kbuf, vbuf, slot_pos, t, *, window=0, block_k=256):
    return _da.decode_attention(q, kbuf, vbuf, slot_pos, t, window=window,
                                block_k=block_k, interpret=_interpret())


# ----------------------------------------------------------------------
# int8 symmetric per-channel quantization (the quantized glass tier)
# ----------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block_m",))
def quantize_rowwise(x, *, block_m=32):
    """x (M, K) f32 -> (q int8 (M, K), scale f32 (M, 1)); symmetric,
    round-to-nearest, so |dequant(q) - x| <= scale/2 elementwise."""
    return _q.quantize_rowwise(x, block_m=block_m, interpret=_interpret())


@partial(jax.jit, static_argnames=("block_m",))
def quantize_colwise(w, *, block_m=32):
    """Per-output-channel weight quantization: w (K, N) f32 ->
    (q int8 (K, N), scale f32 (1, N)) — the rowwise kernel on w.T."""
    q, s = _q.quantize_rowwise(w.T, block_m=block_m, interpret=_interpret())
    return q.T, s.T


@partial(jax.jit, static_argnames=("block_m",))
def dequantize_rowwise(q, scale, *, block_m=32):
    return _q.dequantize_rowwise(q, scale, block_m=block_m,
                                 interpret=_interpret())


@partial(jax.jit, static_argnames=("block_m", "block_n"))
def int8_matmul(xq, sx, wq, sw, *, block_m=32, block_n=128):
    """Fused int8 x int8 -> int32 -> scaled f32 GEMM."""
    return _q.int8_matmul(xq, sx, wq, sw, block_m=block_m,
                          block_n=block_n, interpret=_interpret())


@partial(jax.jit, static_argnames=("block_m", "block_n"))
def quantized_matmul(x, wq, sw, *, block_m=32, block_n=128):
    """fp32 activations x pre-quantized int8 weights: rowwise-quantize
    then the fused GEMM. Leading dims of x are flattened into M."""
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    out = _q.quantized_matmul(x2, wq, sw, block_m=block_m,
                              block_n=block_n, interpret=_interpret())
    return out.reshape(lead + (wq.shape[1],))

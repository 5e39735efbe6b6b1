"""Pallas TPU kernel for one-token decode attention against a
ring-buffer KV cache (the serving hot spot).

Differences from the prefill flash kernel:
  * queries are the G grouped heads of one new token — the "query block"
    is (G, D), tiny; the work is streaming the (W, D) cache through VMEM;
  * validity comes from the cache's per-slot *position* array (slot is
    valid iff 0 <= pos <= t and t - pos < window) rather than iota
    causality — the same masking rule as
    ``repro.models.attention.plain_attention_vs_cache``;
  * grid = (batch*kv_heads, cache_blocks), cache innermost/sequential,
    online-softmax state in VMEM scratch (a flash-decode split-K variant
    with cross-core combine is the natural next step on real hardware;
    this single-pass form is the correctness/roofline reference).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(t_ref, q_ref, k_ref, v_ref, pos_ref, o_ref, m_ref, l_ref,
            acc_ref, *, scale, window, n_blocks):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = t_ref[0]
    q = q_ref[0].astype(jnp.float32)                    # (G, D)
    k = k_ref[0].astype(jnp.float32)                    # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = pos_ref[...]                                  # (bk,)
    valid = (pos >= 0) & (pos <= t)
    if window:
        valid &= t - pos < window
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def decode_attention(q, kbuf, vbuf, slot_pos, t, *, window=0, scale=None,
                     block_k=256, interpret=False):
    """q: (B, 1, H, D); kbuf/vbuf: (B, W, KV, D); slot_pos: (W,) int32;
    t: scalar int32 current position. Returns (B, 1, H, Dv)."""
    B, _, H, D = q.shape
    _, W, KV, Dv = vbuf.shape
    G = H // KV
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bk = min(block_k, W)
    pk = (-W) % bk
    qr = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    kr = jnp.moveaxis(kbuf, 2, 1).reshape(B * KV, W, D)
    vr = jnp.moveaxis(vbuf, 2, 1).reshape(B * KV, W, Dv)
    pos = slot_pos
    if pk:
        kr = jnp.pad(kr, ((0, 0), (0, pk), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, pk), (0, 0)))
        pos = jnp.pad(pos, (0, pk), constant_values=-1)
    nk = (W + pk) // bk
    t_arr = jnp.asarray(t, jnp.int32).reshape(1)

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               n_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * KV, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda bh, ki: (0,)),
            pl.BlockSpec((1, G, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((bk,), lambda bh, ki: (ki,)),
        ],
        out_specs=pl.BlockSpec((1, G, Dv), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(t_arr, qr, kr, vr, pos)
    return out.reshape(B, 1, KV * G, Dv)

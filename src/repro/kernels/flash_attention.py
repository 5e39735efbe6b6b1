"""Pallas TPU flash-attention (forward) kernel.

Blocked online-softmax attention with GQA, causal and sliding-window
masking. TPU-native layout decisions:
  * grid = (batch*heads, q_blocks, kv_blocks), kv innermost and
    sequential so the f32 running max / denominator / accumulator live
    in VMEM scratch across kv steps;
  * block shapes default to (128, head_dim) — MXU-aligned multiples of
    128 on both matmul dims;
  * GQA is handled in the k/v BlockSpec index maps (query head h reads
    kv head h // group_size) — no materialized head repetition in HBM;
  * masks come from broadcasted iotas; fully-masked kv blocks still
    execute and contribute zeros (structural simplicity over
    skip-scheduling; the ~2x causal overhead is quantified in
    EXPERIMENTS.md §Perf).

Validated in interpret mode on CPU against ``ref.attention_ref``; the
TPU path is the same `pl.pallas_call` with interpret=False. The masking
operands use tiling-legal TPU layouts: per-row key counts ride in SMEM
as a scalar-prefetch operand, and segment ids are read through a
``(B, S, 1)`` query view and a ``(B, 1, S)`` key view (a key block is
then ``(1, 1, bk)``, which the TPU compiler accepts for ``bk % 128 ==
0``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(*refs, scale, causal, window, bq, bk, seq_k, n_kv_blocks,
            q_offset, heads, has_lengths, has_segments):
    len_ref = sq_ref = sk_ref = None
    if has_lengths:
        len_ref, *refs = refs
    q_ref, k_ref, v_ref, *refs = refs
    if has_segments:
        sq_ref, sk_ref, *refs = refs
    o_ref, m_ref, l_ref, acc_ref = refs
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                  # (bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if has_segments:
        # ragged layout: a key is visible iff it belongs to the same row
        # segment as the query; padding carries segment id -1 and is
        # never equal to a valid id, so block/tail padding and foreign
        # rows mask out identically. A fully-masked q row outputs 0.
        sq = sq_ref[0]                                # (bq, 1) int32
        sk = sk_ref[0]                                # (1, bk) int32
        mask = (sq == sk) & (sk >= 0)
    else:
        q_pos = (q_offset + qi * bq
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        # kv padding: block padding, or the row's true key count
        mask = k_pos < (len_ref[pl.program_id(0) // heads] if has_lengths
                        else seq_k)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # re-mask after the shift: when every key so far is masked
    # (m_new == NEG_INF) the subtraction above yields exp(0) = 1, which
    # would let zero-length rows attend uniformly instead of outputting 0.
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    v = v_ref[0].astype(jnp.float32)                  # (bk, Dv)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    kv_lengths=None, segment_ids=None, block_q=128,
                    block_k=128, interpret=False):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D/Dv). Returns (B, Sq, H, Dv).

    ``kv_lengths``: optional (B,) int32 per-row key count — keys at
    positions >= kv_lengths[b] are masked out (key-padding mask for
    length-bucketed batches). A zero-length row outputs exactly 0.
    Non-causal only: the causal q/k alignment would need a per-row
    offset, which no caller needs yet.

    ``segment_ids``: optional (B, S) int32 for the concatenated ragged
    layout — many natural-length rows packed into one sequence. A query
    attends a key iff their ids match; id -1 marks padding (between
    aligned rows, and block-tail padding) and masks for every query, so
    a -1 query row outputs exactly 0. Requires Sq == Sk and causal=False.
    Unlike the other paths, block shapes are taken exactly as requested
    (sequence padded up to a block multiple): fixed per-block reduction
    shapes are what make a packed call bit-identical to per-row calls
    whose rows start on block boundaries.
    """
    if causal and (kv_lengths is not None or segment_ids is not None):
        raise NotImplementedError(
            "kv_lengths/segment_ids require causal=False (per-row causal "
            "alignment is not implemented)")
    if kv_lengths is not None and segment_ids is not None:
        raise ValueError("kv_lengths and segment_ids are mutually exclusive")
    B, Sq, H, D = q.shape
    _, Sk, KV, Dv = v.shape
    if segment_ids is not None and Sq != Sk:
        raise ValueError("segment_ids requires Sq == Sk (self-attention "
                         "over one packed buffer)")
    G = H // KV
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    if segment_ids is not None:
        bq, bk = block_q, block_k
    else:
        bq = min(block_q, Sq)
        bk = min(block_k, Sk)
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    qr = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, D)
    kr = jnp.moveaxis(k, 2, 1).reshape(B * KV, Sk, D)
    vr = jnp.moveaxis(v, 2, 1).reshape(B * KV, Sk, Dv)
    if pq:
        qr = jnp.pad(qr, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kr = jnp.pad(kr, ((0, 0), (0, pk), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, pk), (0, 0)))
    nq = (Sq + pq) // bq
    nk = (Sk + pk) // bk

    # index maps take (bh, qi, ki) plus the scalar-prefetch ref, if any
    def q_index(bh, qi, ki, *_):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki, *_):
        return ((bh // H) * KV + (bh % H) // G, ki, 0)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, bq=bq, bk=bk,
        seq_k=Sk, n_kv_blocks=nk, q_offset=(Sk - Sq) if causal else 0,
        heads=H, has_lengths=kv_lengths is not None,
        has_segments=segment_ids is not None)

    in_specs = [
        pl.BlockSpec((1, bq, D), q_index),
        pl.BlockSpec((1, bk, D), kv_index),
        pl.BlockSpec((1, bk, Dv), kv_index),
    ]
    operands = [qr, kr, vr]
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        if pk:
            seg = jnp.pad(seg, ((0, 0), (0, pk)), constant_values=-1)
        # one (B, S) id array, two views: a query column and a key row
        in_specs.append(pl.BlockSpec(
            (1, bq, 1), lambda bh, qi, ki, *_: (bh // H, qi, 0)))
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda bh, qi, ki, *_: (bh // H, 0, ki)))
        operands.extend([seg[:, :, None], seg[:, None, :]])
    prefetch = []
    if kv_lengths is not None:
        # (B,) key counts in SMEM, read per (batch, head) program
        prefetch = [kv_lengths.astype(jnp.int32)]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B * H, nq, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, Dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq, Dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq + pq, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *operands)
    out = out[:, :Sq].reshape(B, H, Sq, Dv)
    return jnp.moveaxis(out, 1, 2)

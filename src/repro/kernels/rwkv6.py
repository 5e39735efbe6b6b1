"""Pallas TPU kernel for the RWKV6 time-mix recurrence.

    S_t = diag(w_t) S_{t-1} + k_t^T v_t ;   y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

TPU adaptation: the recurrence is inherently sequential in t, but each
(batch, head) pair is independent and the per-head state is a dense
(n, n) = (64, 64) f32 tile — a perfect VMEM/VPU working set. Layout:
  * grid = (batch*heads, time_chunks); time innermost and sequential so
    the state tile persists in VMEM scratch across chunks (never spilled
    to HBM between chunks — the HBM-resident state of a GPU-style
    implementation is the thing this kernel removes);
  * r/k/v/w stream through VMEM in (chunk, n) blocks;
  * an optional initial state input supports chunked prefill / decode
    restart, and the final state is written out once.

Validated in interpret mode against ``ref.rwkv6_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sf_ref,
            state, *, bt, n_chunks):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        state[...] = s0_ref[0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                    # (n,)

    def body(t, _):
        r_t = r_ref[0, t, :].astype(jnp.float32)        # (n,)
        k_t = k_ref[0, t, :].astype(jnp.float32)
        v_t = v_ref[0, t, :].astype(jnp.float32)
        w_t = w_ref[0, t, :].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]                # (n, n)
        s_prev = state[...]
        y = jax.lax.dot_general(
            r_t[None, :], s_prev + u[:, None] * kv,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (1, n)
        y_ref[0, t, :] = y[0].astype(y_ref.dtype)
        state[...] = w_t[:, None] * s_prev + kv
        return 0

    jax.lax.fori_loop(0, bt, body, 0)

    @pl.when(ti == n_chunks - 1)
    def _finish():
        sf_ref[0] = state[...].astype(sf_ref.dtype)


def rwkv6_scan(r, k, v, w, u, s0=None, *, block_t=64, interpret=False):
    """r/k/v/w: (B, S, H, n); u: (H, n); s0: (B, H, n, n) or None.

    Returns (y (B, S, H, n) f32, final_state (B, H, n, n) f32)."""
    B, S, H, n = r.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, n, n), jnp.float32)
    bt = min(block_t, S)
    pt = (-S) % bt
    resh = lambda a: jnp.moveaxis(a, 2, 1).reshape(B * H, S, n)
    rr, kk, vv, ww = map(resh, (r, k, v, w))
    if pt:
        # pad with w=1, k=0: state passes through unchanged on pad steps
        rr = jnp.pad(rr, ((0, 0), (0, pt), (0, 0)))
        kk = jnp.pad(kk, ((0, 0), (0, pt), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, pt), (0, 0)))
        ww = jnp.pad(ww, ((0, 0), (0, pt), (0, 0)), constant_values=1.0)
    nt = (S + pt) // bt
    ur = u.reshape(H, n)
    s0r = s0.reshape(B * H, n, n)

    kernel = functools.partial(_kernel, bt=bt, n_chunks=nt)
    t_spec = pl.BlockSpec((1, bt, n), lambda bh, ti: (bh, ti, 0))
    y, sf = pl.pallas_call(
        kernel,
        grid=(B * H, nt),
        in_specs=[
            t_spec, t_spec, t_spec, t_spec,
            pl.BlockSpec((1, n), lambda bh, ti: (bh % H, 0)),
            pl.BlockSpec((1, n, n), lambda bh, ti: (bh, 0, 0)),
        ],
        out_specs=[
            t_spec,
            pl.BlockSpec((1, n, n), lambda bh, ti: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S + pt, n), jnp.float32),
            jax.ShapeDtypeStruct((B * H, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rr, kk, vv, ww, ur, s0r)
    y = y[:, :S].reshape(B, H, S, n)
    return jnp.moveaxis(y, 1, 2), sf.reshape(B, H, n, n)

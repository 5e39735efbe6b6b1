"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Expert-parallel friendly: tokens are routed top-k, flattened, sorted by
expert id, scattered into a fixed (E, C, d) dispatch buffer (capacity
C = ceil(N*k/E * capacity_factor); overflow tokens are dropped, the
standard GShard/Switch discipline), batch-matmul'd against stacked
expert weights, and combined back with router weights. All shapes are
static, so the whole thing lowers under pjit with the expert dimension
sharded on the `model` mesh axis (the dispatch scatter becomes an
all-to-all).

DeepSeek-style shared experts are a plain dense MLP added to every
token. The auxiliary load-balance loss (Switch form: E * sum_e f_e *
p_e) is returned for the trainer to accumulate.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import layers as L


def moe_init(key, cfg):
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    dt = L.pdtype(cfg)
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * scale
                   ).astype(jnp.float32),  # router kept f32 for stable top-k
        "gate": (jax.random.normal(ks[1], (E, d, ff), jnp.float32) * scale).astype(dt),
        "up": (jax.random.normal(ks[2], (E, d, ff), jnp.float32) * scale).astype(dt),
        "down": (jax.random.normal(ks[3], (E, ff, d), jnp.float32)
                 * (1.0 / math.sqrt(ff))).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], d, ff * cfg.n_shared_experts,
                                 "swiglu", dtype=dt)
    return p


def capacity(n_tokens: int, cfg) -> int:
    c = int(math.ceil(n_tokens * cfg.experts_per_tok / cfg.n_experts
                      * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8


def moe_forward(p, x, cfg):
    """x: (B, S, d) -> (y, aux_loss)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_tok
    N = B * S
    xf = x.reshape(N, d)

    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)                     # (N, E)
    top_w, top_i = jax.lax.top_k(probs, k)                      # (N, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- aux load-balance loss (Switch): E * sum_e f_e * p_e ----
    counts = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = counts / (N * k)
    pbar = probs.mean(axis=0)
    aux = E * jnp.sum(f * pbar)

    # ---- sort-based dispatch ----
    Nk = N * k
    eids = top_i.reshape(Nk)
    tids = jnp.arange(Nk, dtype=jnp.int32) // k
    order = jnp.argsort(eids)                                   # stable
    se = eids[order]
    st = tids[order]
    sw = top_w.reshape(Nk)[order]
    starts = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype))
    pos = jnp.arange(Nk, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    C = capacity(N, cfg)
    keep = pos < C

    buf = jnp.zeros((E, C, d), x.dtype)
    vals = jnp.where(keep[:, None], xf[st], 0)
    buf = buf.at[se, pos].set(vals, mode="drop")                # (E, C, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["down"])          # (E, C, d)

    pos_c = jnp.minimum(pos, C - 1)
    contrib = out_buf[se, pos_c] * (sw * keep)[:, None]
    y = jnp.zeros((N, d), jnp.float32).at[st].add(contrib.astype(jnp.float32))
    y = y.astype(x.dtype).reshape(B, S, d)

    if cfg.n_shared_experts:
        y = y + L.mlp(p["shared"], x, "swiglu")
    return y, aux


# ----------------------------------------------------------------------
# Explicit all-to-all expert parallelism (shard_map)
# ----------------------------------------------------------------------

def moe_forward_a2a(p, x, cfg, *, mesh, token_axes, expert_axes,
                    pair_capacity_factor=2.0):
    """Expert-parallel MoE with explicit ``lax.all_to_all`` dispatch.

    Under GSPMD auto-partitioning, the sort-based dispatch's
    gather/scatter against an expert-sharded (E, C, d) buffer is
    partitioned as materialize-everywhere + all-reduce — ~100x the
    traffic of real expert parallelism (measured in EXPERIMENTS.md
    §Perf). This shard_map implementation is the production path: each
    device routes its local tokens, exchanges exactly
    (n_dev, C_pair, d) with its expert-parallel group, runs its local
    experts, and reverses the exchange. Traffic per device per layer =
    2 x C_pair x n_dev x d — the textbook all-to-all cost.

    token_axes: mesh axes sharding the flattened token dim of x
                (e.g. ('pod','data','model') under the fsdp strategy).
    expert_axes: mesh axes the expert dim is sharded over — must be a
                suffix of token_axes; the all-to-all runs over them,
                outer axes form independent groups.
    Tokens overflowing per-pair or per-expert capacity are dropped
    (standard capacity discipline, same as the dispatch path).
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_tok
    shared = p.get("shared")

    def body(xf, router, gate, up, down):
        # xf: (N_loc, d); gate/up/down: (E_loc, ...) local expert slices
        N_loc = xf.shape[0]
        E_loc = gate.shape[0]
        n_dev = E // E_loc
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        counts = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
        f = jax.lax.pmean(counts / (N_loc * k), expert_axes)
        pbar = jax.lax.pmean(probs.mean(axis=0), expert_axes)
        aux = E * jnp.sum(f * pbar)

        Nk = N_loc * k
        eids = top_i.reshape(Nk)
        tids = jnp.arange(Nk, dtype=jnp.int32) // k
        order = jnp.argsort(eids)
        se, st = eids[order], tids[order]
        sw = top_w.reshape(Nk)[order]
        dest = se // E_loc                               # target device
        starts = jnp.searchsorted(se, jnp.arange(0, E, E_loc,
                                                 dtype=se.dtype))
        pos = jnp.arange(Nk, dtype=jnp.int32) - starts[dest].astype(jnp.int32)
        Cp = max(8, -(-math.ceil(Nk / n_dev * pair_capacity_factor) // 8) * 8)
        keep = pos < Cp

        send_x = jnp.zeros((n_dev, Cp, d), x.dtype)
        send_x = send_x.at[dest, pos].set(
            jnp.where(keep[:, None], xf[st], 0), mode="drop")
        # local expert id at destination; -1 = empty slot
        send_e = jnp.full((n_dev, Cp), -1, jnp.int32)
        send_e = send_e.at[dest, pos].set(
            jnp.where(keep, se % E_loc, -1), mode="drop")

        recv_x = jax.lax.all_to_all(send_x, expert_axes, 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, expert_axes, 0, 0, tiled=False)
        rx = recv_x.reshape(n_dev * Cp, d)
        re_ = recv_e.reshape(n_dev * Cp)

        # second-level dispatch into the E_loc local experts
        keys = jnp.where(re_ < 0, E_loc, re_)            # empties sort last
        order2 = jnp.argsort(keys)
        se2k = keys[order2]                              # ascending
        C2 = n_dev * Cp
        starts2 = jnp.searchsorted(se2k, jnp.arange(E_loc, dtype=se2k.dtype))
        eid2 = jnp.clip(se2k, 0, E_loc - 1)
        pos2 = jnp.arange(C2, dtype=jnp.int32) - starts2[eid2].astype(jnp.int32)
        valid2 = se2k < E_loc
        buf = jnp.zeros((E_loc, C2, d), x.dtype)
        buf = buf.at[eid2, jnp.where(valid2, pos2, C2)].set(
            jnp.where(valid2[:, None], rx[order2], 0), mode="drop")

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate)) \
            * jnp.einsum("ecd,edf->ecf", buf, up)
        out_buf = jnp.einsum("ecf,efd->ecd", h, down)    # (E_loc, C2, d)

        # undo second-level permutation
        back = jnp.zeros((C2, d), x.dtype)
        vals = out_buf[eid2, jnp.minimum(pos2, C2 - 1)] * valid2[:, None]
        back = back.at[order2].set(vals)
        back = back.reshape(n_dev, Cp, d)
        ret = jax.lax.all_to_all(back, expert_axes, 0, 0, tiled=False)

        y = jnp.zeros((N_loc, d), jnp.float32)
        contrib = ret[dest, jnp.minimum(pos, Cp - 1)] * (sw * keep)[:, None]
        y = y.at[st].add(contrib.astype(jnp.float32))
        aux = jax.lax.pmean(aux, token_axes)             # fully replicated
        return y.astype(x.dtype), aux

    from jax.sharding import PartitionSpec as P
    tok_spec = P(token_axes, None)
    exp_spec = P(expert_axes, None, None)
    in_specs = (tok_spec, P(None, None), exp_spec, exp_spec, exp_spec)
    out_specs = (tok_spec, P())
    sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    xf = x.reshape(B * S, d)
    y, aux = sm(xf, p["router"], p["gate"], p["up"], p["down"])
    y = y.reshape(B, S, d)
    if shared is not None:
        y = y + L.mlp(shared, x, "swiglu")
    return y, aux


def moe_ref(p, x, cfg):
    """O(N*E) dense oracle (every expert applied to every token, masked).

    Used only in tests to validate the dispatch path on small shapes.
    """
    B, S, d = x.shape
    N = B * S
    xf = x.reshape(N, d)
    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.experts_per_tok)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    gate_w = jnp.zeros((N, cfg.n_experts), jnp.float32)
    gate_w = jax.vmap(lambda g, i, w: g.at[i].set(w))(gate_w, top_i, top_w)
    h = jax.nn.silu(jnp.einsum("nd,edf->nef", xf, p["gate"])) \
        * jnp.einsum("nd,edf->nef", xf, p["up"])
    o = jnp.einsum("nef,efd->ned", h, p["down"])
    y = jnp.einsum("ned,ne->nd", o.astype(jnp.float32), gate_w)
    y = y.astype(x.dtype).reshape(B, S, d)
    if cfg.n_shared_experts:
        y = y + L.mlp(p["shared"], x, "swiglu")
    return y

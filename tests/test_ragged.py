"""Ragged grouped flush path: one kernel per modality, one grouped tail.

Bit-parity tier (atol 0, ``np.array_equal``): the packing itself must
not change a single bit. The laws that make this possible on XLA CPU —
fixed flash-block reduction shapes (segment-masked kernel), block-
aligned row starts, a structurally identical scan body across the
natural/bucketed/ragged vitals paths, and exact zero contribution of
zero-filled fusion slices — are each pinned here at three levels:
kernel, encoder, and the full engine against the per-event unbucketed
reference (``core.engine.EMSServe``) on every LAG_SCENARIOS preset.

Regression tier: the three flush-accounting bugs that rode along —
duplicate-submission latency overwrites, the bucketer histogram
counting unserved modalities, and ``stack_bucketed`` silently dropping
mismatched dict keys.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.emsnet import tiny
from repro.core import (LAG_SCENARIOS, async_episode, emsnet_module,
                        emsnet_zoo, merge_arrivals, split)
from repro.core.bucketing import Bucketer, RaggedBatch, stack_bucketed
from repro.core.engine import EMSServe
from repro.core.episodes import Event
from repro.kernels.flash_attention import flash_attention
from repro.models import emsnet as E
from repro.serving.api import build_engine

ALL = ("text", "vitals", "scene")


# ======================================================================
# Fixtures: the bit-parity model config (segment flash on BOTH sides)
# ======================================================================

@pytest.fixture(scope="module")
def ragged_cfg():
    return tiny(text_encoder="microbert", use_flash_text=True,
                flash_segments=True, flash_block=8)


@pytest.fixture(scope="module")
def ragged_zoo(ragged_cfg):
    cfg = ragged_cfg
    zoo = emsnet_zoo(cfg)
    splits = {k: split(m) for k, m in zoo.items()}
    shared = zoo["text+vitals+scene"].init_fn(jax.random.PRNGKey(0))
    params = {k: shared for k in zoo}
    return cfg, splits, shared, params


def _payload(cfg, sid, ev):
    r = np.random.default_rng(abs(hash((sid, ev.modality, ev.index)))
                              % 2**32)
    if ev.modality == "text":
        n = int(r.integers(1, cfg.max_text_len + 1))
        return jnp.asarray(r.integers(1, cfg.vocab_size, (1, n)), jnp.int32)
    if ev.modality == "vitals":
        n = int(r.integers(1, cfg.vitals_len + 1))
        return jnp.asarray(r.normal(size=(1, n, cfg.n_vitals)), jnp.float32)
    return jnp.asarray(r.integers(0, 2, (1, cfg.scene_dim)), jnp.float32)


def _lag_episodes(n_per_scenario=1):
    return {f"s{i}{j}": async_episode(name, seed=i * 7 + j,
                                      n_vitals=2, n_scene=2)
            for i, name in enumerate(sorted(LAG_SCENARIOS))
            for j in range(n_per_scenario)}


def _assert_bitwise(got, want, ctx=""):
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert np.array_equal(g, w), \
            f"{ctx}[{k}]: max|diff|={np.abs(g - w).max()}"


# ======================================================================
# RaggedBatch builder invariants
# ======================================================================

def test_ragged_pack_text_layout():
    """Offsets start on align boundaries, segments tile the buffer
    exactly, surplus rows are (offset=total, length=0), and T/R are
    powers of two — including len-0 and len==cap rows."""
    rb = RaggedBatch(align=8, max_lengths={"text": 16})
    rng = np.random.default_rng(0)
    lens = [0, 16, 1, 5, 20]          # empty, ==cap, tiny, mid, > cap
    rows = [np.asarray(rng.integers(1, 99, (1, n)), np.int32)
            for n in lens]
    p = rb.pack("text", rows)
    offsets = np.asarray(p["offsets"])
    lengths = np.asarray(p["lengths"])
    toks = np.asarray(p["tokens"])
    T = toks.shape[1]
    assert T & (T - 1) == 0 and len(offsets) & (len(offsets) - 1) == 0
    assert len(offsets) == len(lengths) >= len(rows)
    # cropped-at-cap lengths; rows recoverable from the flat buffer
    for i, (r, n) in enumerate(zip(rows, lens)):
        want_n = min(n, 16)
        assert lengths[i] == want_n
        assert offsets[i] % 8 == 0
        got = toks[0, offsets[i]:offsets[i] + want_n]
        assert np.array_equal(got, r[0, :want_n])     # crop keeps head
    # surplus rows: zero-length at the packed extent -> segments tile
    # the buffer exactly (engine offset gathers stay in-bounds)
    total = max(int(o + -(-l // 8) * 8) for o, l in zip(offsets, lengths))
    for i in range(len(rows), len(offsets)):
        assert lengths[i] == 0 and offsets[i] == total <= T
    # row_ids: -1 exactly where no live row's tokens are
    seg = np.asarray(p["row_ids"])
    for i, (o, l) in enumerate(zip(offsets[:len(rows)],
                                   lengths[:len(rows)])):
        assert np.all(seg[o:o + l] == i)
    assert np.all(lengths >= 0) and rb.n_shapes() == 1


def test_ragged_pack_vitals_layout():
    """Vitals pack back-to-back (align 1) with reset flags on each
    row's first step; crop keeps the TAIL (latest vitals win)."""
    rb = RaggedBatch(max_lengths={"vitals": 8})
    rng = np.random.default_rng(1)
    lens = [3, 0, 8, 12, 1]
    rows = [rng.standard_normal((1, n, 2)).astype(np.float32)
            for n in lens]
    p = rb.pack("vitals", rows)
    x, reset = np.asarray(p["x"]), np.asarray(p["reset"])
    offsets, lengths = np.asarray(p["offsets"]), np.asarray(p["lengths"])
    o = 0
    for r, n in zip(rows, lens):
        keep = min(n, 8)
        assert np.array_equal(x[0, o:o + keep], r[0, n - keep:])
        if keep:
            assert reset[o, 0, 0]
            assert not reset[o + 1:o + keep, 0, 0].any()
        o += keep
    assert np.all(lengths[:len(rows)] == [min(n, 8) for n in lens])
    with pytest.raises(ValueError):
        rb.pack("scene", [np.zeros((1, 3), np.float32)])


# ======================================================================
# Kernel tier: segment-masked flash == per-row flash, bit for bit
# ======================================================================

def test_segment_flash_packed_equals_per_row():
    """Rows packed at block-aligned offsets through ONE segment-masked
    kernel call reproduce each per-row call bitwise: fixed (bq, bk)
    block shapes make the online-softmax reduction structure
    independent of how many rows share the buffer."""
    H, D, b = 2, 8, 8
    rng = np.random.default_rng(2)
    lens = [8, 3, 16, 1]
    offs = np.cumsum([0] + [-(-n // b) * b for n in lens])
    T = int(offs[-1])
    q = np.zeros((1, T, H, D), np.float32)      # flash layout (B, S, H, D)
    seg = np.full((T,), -1, np.int32)
    per_row = []
    for i, (n, o) in enumerate(zip(lens, offs[:-1])):
        x = rng.standard_normal((1, n, H, D)).astype(np.float32)
        q[:, o:o + n] = x
        seg[o:o + n] = i
        per_row.append(x)
    qj = jnp.asarray(q)
    packed = flash_attention(qj, qj, qj, causal=False,
                             segment_ids=jnp.asarray(seg)[None],
                             block_q=b, block_k=b, interpret=True)
    packed = np.asarray(packed)
    for i, (n, o, x) in enumerate(zip(lens, offs[:-1], per_row)):
        xp = np.zeros((1, -(-n // b) * b, H, D), np.float32)
        xp[:, :n] = x
        sr = np.full((xp.shape[1],), -1, np.int32)
        sr[:n] = 0
        solo = flash_attention(jnp.asarray(xp), jnp.asarray(xp),
                               jnp.asarray(xp), causal=False,
                               segment_ids=jnp.asarray(sr)[None],
                               block_q=b, block_k=b, interpret=True)
        assert np.array_equal(packed[:, o:o + n],
                              np.asarray(solo)[:, :n]), f"row {i}"


# ======================================================================
# Encoder tier: ragged == natural per-row, bit for bit
# ======================================================================

def test_text_encoder_ragged_bitwise(ragged_cfg):
    cfg = ragged_cfg
    p = E.init_params(cfg, jax.random.PRNGKey(0), ("text",))
    rng = np.random.default_rng(3)
    enc_nat = jax.jit(lambda t: E.encode(p, cfg, "text", t))
    enc_rag = jax.jit(lambda d: E.encode(p, cfg, "text", d))
    for trial in range(3):
        lens = ([0, cfg.max_text_len, 1, 5] if trial == 0 else
                [int(x) for x in rng.integers(0, cfg.max_text_len + 1,
                                              size=4)])
        rows = [np.asarray(rng.integers(1, cfg.vocab_size, (1, n)),
                           np.int32) for n in lens]
        rb = RaggedBatch(align=cfg.flash_block,
                         max_lengths={"text": cfg.max_text_len})
        out = np.asarray(enc_rag(rb.pack("text", rows)))
        for i, (r, n) in enumerate(zip(rows, lens)):
            want = (np.zeros((1, cfg.text_dims[1]), np.float32) if n == 0
                    else np.asarray(enc_nat(jnp.asarray(r))))
            assert np.array_equal(out[i:i + 1], want), \
                (trial, i, n, np.abs(out[i:i + 1] - want).max())


@pytest.mark.parametrize("kind", ["rnn", "gru", "lstm"])
def test_vitals_encoder_ragged_bitwise(kind):
    cfg = tiny(vitals_encoder=kind)
    p = E.init_params(cfg, jax.random.PRNGKey(1), ("vitals",))
    rng = np.random.default_rng(4)
    enc_nat = jax.jit(lambda v: E.encode(p, cfg, "vitals", v))
    enc_rag = jax.jit(lambda d: E.encode(p, cfg, "vitals", d))
    for trial in range(3):
        lens = ([0, cfg.vitals_len, 1] if trial == 0 else
                [int(x) for x in rng.integers(0, cfg.vitals_len + 1,
                                              size=4)])
        rows = [rng.standard_normal((1, n, cfg.n_vitals)).astype(np.float32)
                for n in lens]
        rb = RaggedBatch(max_lengths={"vitals": cfg.vitals_len})
        out = np.asarray(enc_rag(rb.pack("vitals", rows)))
        for i, (r, n) in enumerate(zip(rows, lens)):
            want = (np.zeros((1, cfg.vitals_hidden), np.float32) if n == 0
                    else np.asarray(enc_nat(jnp.asarray(r))))
            assert np.array_equal(out[i:i + 1], want), \
                (trial, i, n, np.abs(out[i:i + 1] - want).max())


# ======================================================================
# Tail tier: grouped full-head tail == sliced subset tails
# ======================================================================

def test_grouped_tail_equals_subset_tails(ragged_cfg):
    """For every non-empty modality subset, running the FULL fusion
    heads over features with zeros in the missing slices reproduces the
    subset-sliced heads bitwise at the same row count: a zero K-slice
    contributes exactly 0.0 to the fusion GEMM."""
    from itertools import combinations
    cfg = ragged_cfg
    params = E.init_params(cfg, jax.random.PRNGKey(0), ALL)
    dims = cfg.feature_dims
    rng = np.random.default_rng(5)
    R = 4
    feats = {m: jnp.asarray(rng.standard_normal((R, dims[m])),
                            jnp.float32) for m in ALL}
    for r in range(1, 4):
        for subset in combinations(ALL, r):
            ph = E.slice_heads(params["heads"], cfg, ALL, subset)
            want = E.fuse_and_heads(ph, feats, subset)
            filled = {m: (feats[m] if m in subset
                          else jnp.zeros((R, dims[m]), jnp.float32))
                      for m in ALL}
            got = E.fuse_and_heads(params["heads"], filled, ALL)
            _assert_bitwise(got, want, ctx=f"subset={subset}")


# ======================================================================
# Engine tier: ragged flush == the per-event unbucketed reference
# ======================================================================

def test_engine_ragged_matches_unbucketed_reference(ragged_zoo):
    """Ragged engine at the reference's own cadence (flush per event)
    == ``core.engine.EMSServe`` (per-event, natural shapes, no
    bucketing) bitwise on every LAG_SCENARIOS preset, with ONE packed
    encoder call and ONE grouped tail per flush."""
    cfg, splits, shared, params = ragged_zoo
    eps = _lag_episodes()
    refs = {sid: EMSServe(splits, params, cached=True, real_time=True,
                          session=sid) for sid in eps}
    eng = build_engine(splits, params, "batch+stream",
                       share_encoders=True, ragged=True,
                       deadline_s=0.0, max_history=None)
    checked = 0
    for _t, sid, ev in merge_arrivals(eps):
        p = _payload(cfg, sid, ev)
        rec = refs[sid].on_event(ev, p)
        rep = eng.submit(sid, ev, p)
        assert rep.n_encoder_calls <= 1 and rep.n_tail_calls <= 1
        if rec.recommendation is None:
            assert not rep.predictions
            continue
        (pred,) = rep.predictions
        assert pred.sid == sid
        _assert_bitwise(pred.outputs, rec.recommendation,
                        ctx=f"{sid}@{ev.index}")
        checked += 1
    assert checked > len(eps)


def test_engine_ragged_coalescing_bitwise_invariant(ragged_zoo):
    """Coalescing sessions into one packed flush changes NOTHING:
    deadline-coalesced ragged flushes emit bitwise the same predictions
    as flush-per-arrival ragged serving, while issuing O(modalities)+1
    kernels per flush and strictly less padded-FLOP than the bucketed
    baseline."""
    cfg, splits, shared, params = ragged_zoo
    eps = _lag_episodes(2)

    def run(sim_window, ragged):
        eng = build_engine(splits, params, "batch+stream",
                           share_encoders=True, ragged=ragged,
                           deadline_s=None, batch_bucket_min=2,
                           max_history=None)
        eng.run_arrivals(eps, lambda sid, ev: _payload(cfg, sid, ev),
                         sim_window=sim_window)
        return eng

    per_event = run(0.0, True)
    coalesced = run(3.0, True)
    bucketed = run(3.0, False)
    assert coalesced.flushes_total < per_event.flushes_total

    for f in coalesced.flushes:
        assert f.n_encoder_calls <= len(ALL)
        assert f.n_tail_calls <= 1
    # finals identical bit for bit; so is every prediction both
    # cadences emitted for the same (sid, step)
    a = {(p.sid, p.step): p for s in per_event.sessions.values()
         for p in s.predictions}
    b = {(p.sid, p.step): p for s in coalesced.sessions.values()
         for p in s.predictions}
    for sid in eps:
        pa = per_event.sessions[sid].predictions[-1]
        pb = coalesced.sessions[sid].predictions[-1]
        assert pa.kind == pb.kind == "final"
        _assert_bitwise(pb.outputs, pa.outputs, ctx=sid)
    common = set(a) & set(b)
    assert common
    for key in common:
        _assert_bitwise(b[key].outputs, a[key].outputs, ctx=str(key))

    # fewer dispatches, strictly less padding tax than bucketed
    assert sum(f.n_encoder_calls + f.n_tail_calls
               for f in coalesced.flushes) \
        < sum(f.n_encoder_calls + f.n_tail_calls for f in bucketed.flushes)
    frac_r = np.mean([f.padded_flop_frac for f in coalesced.flushes])
    frac_b = np.mean([f.padded_flop_frac for f in bucketed.flushes])
    assert frac_r < frac_b
    # the packed-shape histogram stays bounded (compile plateau)
    assert coalesced.ragged.n_shapes() <= 8


def test_engine_ragged_off_is_inert(ragged_zoo):
    """BatchPolicy.ragged defaults False: a default engine has no
    RaggedBatch and runs the legacy bucketed encode + per-model tails."""
    cfg, splits, shared, params = ragged_zoo
    eng = build_engine(splits, params, "batch+stream",
                       share_encoders=True, deadline_s=0.0)
    assert eng.ragged is None
    ev = Event(index=0, modality="scene", arrival_time=0.0)
    rep = eng.submit("s0", ev, _payload(cfg, "s0", ev))
    assert rep is not None and rep.n_events == 1


# ======================================================================
# Regressions: the three flush-accounting bugs
# ======================================================================

@pytest.fixture(scope="module")
def one_model(ragged_cfg):
    cfg = ragged_cfg
    mod = emsnet_module(cfg, ("scene",))
    splits = {"m": split(mod)}
    params = {"m": mod.init_fn(jax.random.PRNGKey(0))}
    return cfg, splits, params


def test_flush_latency_dedupes_duplicate_submission(one_model):
    """A duplicate (sid, idx) submission used to overwrite the first
    latency entry and double-count n_events; the report now keys by
    arrival and keeps the EARLIEST submit time."""
    cfg, splits, params = one_model
    clock = [10.0]
    eng = build_engine(splits, params, "batch",
                       time_fn=lambda: clock[0])
    ev = Event(index=0, modality="scene", arrival_time=0.0)
    x = jnp.zeros((1, cfg.scene_dim), jnp.float32)
    eng.submit("s0", ev, x)
    clock[0] = 11.0
    eng.submit("s0", ev, x)       # retransmit of the same arrival
    clock[0] = 12.0
    rep = eng.flush()
    assert rep.n_events == 1
    assert set(rep.latencies) == {("s0", 0)}
    assert rep.latencies[("s0", 0)] == pytest.approx(2.0)  # from t=10


def test_bucketer_histogram_counts_served_groups_only(one_model):
    """An arrival of a modality NO model consumes must not reach the
    bucketer: the histogram (and its compile/bucket stats) used to be
    inflated before the consumer filter ran."""
    cfg, splits, params = one_model        # consumes scene only
    bk = Bucketer(max_buckets={"vitals": 8})
    eng = build_engine(splits, params, "batch", bucketer=bk)
    eng.submit("s0", Event(index=0, modality="vitals", arrival_time=0.0),
               jnp.zeros((1, 5, cfg.n_vitals), jnp.float32))
    rep = eng.flush()
    assert rep.n_encoder_calls == 0
    assert bk.n_buckets() == 0 and bk.histogram == {}


def test_stack_bucketed_raises_on_key_mismatch():
    """Dict payloads with different key sets used to be silently merged
    using the first payload's keys; now a mismatch is an error."""
    a = {"x": jnp.zeros((1, 4)), "mask": jnp.ones((1, 4))}
    b = {"x": jnp.zeros((1, 4))}
    with pytest.raises(ValueError, match="key"):
        stack_bucketed([a, b], 2)
    # matching keys still stack fine
    out = stack_bucketed([a, {"x": jnp.ones((1, 4)),
                              "mask": jnp.zeros((1, 4))}], 4)
    assert out["x"].shape == (4, 4) and out["mask"].shape == (4, 4)

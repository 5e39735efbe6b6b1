"""Property-based tests (hypothesis) on the ragged packed layout.

Random length mixes — including empty rows and rows at the length cap —
must (a) produce offsets/lengths that tile the packed buffer exactly
and (b) leave every encoded row bit-identical (atol 0) to the per-row
natural-shape reference; the grouped fusion tail must equal the sliced
subset tail for every modality subset.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dev dep (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.configs.emsnet import tiny
from repro.core.bucketing import RaggedBatch
from repro.models import emsnet as E

SETTINGS = dict(max_examples=20, deadline=None)
TEXT_CAP = 16
VITALS_CAP = 8
ALL = ("text", "vitals", "scene")


@functools.lru_cache(maxsize=None)
def _text_setup():
    cfg = tiny(text_encoder="microbert", use_flash_text=True,
               flash_segments=True, flash_block=8)
    p = E.init_params(cfg, jax.random.PRNGKey(0), ("text",))
    nat = jax.jit(lambda t: E.encode(p, cfg, "text", t))
    rag = jax.jit(lambda d: E.encode(p, cfg, "text", d))
    return cfg, nat, rag


@functools.lru_cache(maxsize=None)
def _vitals_setup(kind):
    cfg = tiny(vitals_encoder=kind)
    p = E.init_params(cfg, jax.random.PRNGKey(1), ("vitals",))
    nat = jax.jit(lambda v: E.encode(p, cfg, "vitals", v))
    rag = jax.jit(lambda d: E.encode(p, cfg, "vitals", d))
    return cfg, nat, rag


lens_strategy = st.lists(
    st.one_of(st.just(0), st.just(TEXT_CAP),
              st.integers(0, TEXT_CAP)),
    min_size=1, max_size=4)


@settings(**SETTINGS)
@given(lens_strategy, st.integers(0, 2**31 - 1))
def test_text_pack_tiles_buffer_exactly(lens, seed):
    """Row intervals are disjoint, align-started, in-bounds; surplus
    rows sit at the packed extent with length 0; row_ids mark exactly
    the live non-PAD tokens."""
    rng = np.random.default_rng(seed)
    rows = [np.asarray(rng.integers(1, 99, (1, n)), np.int32)
            for n in lens]
    rb = RaggedBatch(align=8, max_lengths={"text": TEXT_CAP})
    p = rb.pack("text", rows)
    offsets = np.asarray(p["offsets"])
    lengths = np.asarray(p["lengths"])
    seg = np.asarray(p["row_ids"])
    T = np.asarray(p["tokens"]).shape[1]
    covered = np.zeros(T, bool)
    extent = 0
    for i, n in enumerate(lens):
        o, l = int(offsets[i]), int(lengths[i])
        assert l == min(n, TEXT_CAP) and o % 8 == 0
        span = -(-l // 8) * 8
        assert o + span <= T
        assert not covered[o:o + span].any()        # disjoint
        covered[o:o + span] = True
        assert np.all(seg[o:o + l] == i)
        extent = max(extent, o + span)
    # surplus rows tile the remainder as zero-length at the extent
    for i in range(len(lens), len(offsets)):
        assert int(lengths[i]) == 0 and int(offsets[i]) == extent
    assert np.all(seg[~covered] == -1)
    assert not (T & (T - 1)) and not (len(offsets) & (len(offsets) - 1))


@settings(**SETTINGS)
@given(lens_strategy, st.integers(0, 2**31 - 1))
def test_text_ragged_rows_bitwise_equal_natural(lens, seed):
    cfg, nat, rag = _text_setup()
    rng = np.random.default_rng(seed)
    rows = [np.asarray(rng.integers(1, cfg.vocab_size, (1, n)), np.int32)
            for n in lens]
    rb = RaggedBatch(align=cfg.flash_block,
                     max_lengths={"text": cfg.max_text_len})
    out = np.asarray(rag(rb.pack("text", rows)))
    for i, (r, n) in enumerate(zip(rows, lens)):
        want = (np.zeros((1, cfg.text_dims[1]), np.float32) if n == 0
                else np.asarray(nat(jnp.asarray(r))))
        assert np.array_equal(out[i:i + 1], want), (i, n)


@settings(**SETTINGS)
@given(st.sampled_from(["rnn", "gru", "lstm"]),
       st.lists(st.integers(0, VITALS_CAP), min_size=1, max_size=3),
       st.integers(0, 2**31 - 1))
def test_vitals_ragged_rows_bitwise_equal_natural(kind, lens, seed):
    cfg, nat, rag = _vitals_setup(kind)
    rng = np.random.default_rng(seed)
    rows = [rng.standard_normal((1, n, cfg.n_vitals)).astype(np.float32)
            for n in lens]
    rb = RaggedBatch(max_lengths={"vitals": cfg.vitals_len})
    out = np.asarray(rag(rb.pack("vitals", rows)))
    for i, (r, n) in enumerate(zip(rows, lens)):
        want = (np.zeros((1, cfg.vitals_hidden), np.float32) if n == 0
                else np.asarray(nat(jnp.asarray(r))))
        assert np.array_equal(out[i:i + 1], want), (i, n, kind)


@settings(**SETTINGS)
@given(st.sets(st.sampled_from(ALL), min_size=1).map(
           lambda s: tuple(m for m in ALL if m in s)),
       st.integers(1, 4), st.integers(0, 2**31 - 1))
def test_grouped_full_tail_equals_sliced_subset_tail(subset, R, seed):
    """Zero-filling the missing modality slices and running the FULL
    fusion heads == the subset-sliced heads, bit for bit, at every row
    count (the law the engine's ONE grouped tail call rests on)."""
    cfg = tiny()
    params = E.init_params(cfg, jax.random.PRNGKey(2), ALL)
    dims = cfg.feature_dims
    rng = np.random.default_rng(seed)
    feats = {m: jnp.asarray(rng.standard_normal((R, dims[m])),
                            jnp.float32) for m in ALL}
    ph = E.slice_heads(params["heads"], cfg, ALL, subset)
    want = E.fuse_and_heads(ph, feats, subset)
    filled = {m: (feats[m] if m in subset
                  else jnp.zeros((R, dims[m]), jnp.float32))
              for m in ALL}
    got = E.fuse_and_heads(params["heads"], filled, ALL)
    for k in want:
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), \
            (subset, R, k)

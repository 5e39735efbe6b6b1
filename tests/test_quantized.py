"""Quantized glass tier: kernel parity, the int8 sidecar parameter
factory, packed feature transport, and the joint (tier, precision)
placement co-decision.

Tolerances documented here ARE the contract:

  * quantize->dequantize round trip: <= scale/2 per element (symmetric
    round-to-nearest over 127 levels);
  * fused int8 GEMM vs the int8 reference: exact (both accumulate in
    int32 and apply the identical scale product);
  * quantized_matmul vs the fp32 GEMM: the analytical first-order bound
    ``|err_ij| <= sw_j/2 * sum_k|x_ik| + sx_i/2 * sum_k|w_hat_kj|``
    elementwise (quantization error propagated through the dot);
  * precision OFF: the tiered engine is bit-identical (atol 0) to the
    precision-less engine on every LAG_SCENARIOS arrival ordering.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BandwidthTrace, LAG_SCENARIOS, ProfileTable,
                        async_episode, emsnet_zoo, nlos_bandwidth, split)
from repro.core.episodes import Event
from repro.core.modular import MultimodalModule
from repro.core.offload import HeartbeatMonitor, MultiTierPolicy
from repro.core.splitter import payload_nbytes
from repro.kernels import ops, ref
from repro.models import quantized as Q
from repro.serving.api import build_engine

ALL = ("text", "vitals", "scene")
TIERS = ("glass", "ph1", "edge64x")
BASE = {"enc:text": 0.08, "enc:vitals": 0.01, "enc:scene": 0.05,
        "tail": 0.005, "full": 0.15}

# (M, K, N) including non-divisible-by-block padding paths
GEMM_SHAPES = [(8, 32, 16), (32, 64, 128), (33, 100, 130), (1, 7, 5),
               (64, 128, 256)]


@pytest.fixture(scope="module")
def zoo_models(tiny_emsnet_cfg):
    cfg = tiny_emsnet_cfg
    zoo = emsnet_zoo(cfg)
    splits = {k: split(m) for k, m in zoo.items()}
    shared = zoo["text+vitals+scene"].init_fn(jax.random.PRNGKey(0))
    params = {k: shared for k in zoo}
    rng = np.random.default_rng(0)
    payloads = {
        "text": jnp.asarray(rng.integers(1, cfg.vocab_size, (1, 11)),
                            jnp.int32),
        "vitals": jnp.asarray(rng.normal(size=(1, 5, cfg.n_vitals)),
                              jnp.float32),
        "scene": jnp.asarray(rng.integers(0, 2, (1, cfg.scene_dim)),
                             jnp.float32),
    }
    return cfg, splits, shared, params, payloads


def _lag_episodes():
    return {f"s{i}": async_episode(name, seed=i * 7, n_vitals=2,
                                   n_scene=2)
            for i, name in enumerate(sorted(LAG_SCENARIOS))}


def _tiered(splits, params, *, bandwidth=5.0, **kw):
    kw.setdefault("max_history", None)
    kw.setdefault("tier_traces",
                  {"ph1": BandwidthTrace.static(nlos_bandwidth(0.0))})
    kw.setdefault("trace", BandwidthTrace.static(nlos_bandwidth(bandwidth)))
    kw.setdefault("tiers", TIERS)
    return build_engine(
        splits, params, "tiered", share_encoders=True,
        profile=ProfileTable(base=dict(BASE)), **kw)


# ====================================================== kernel parity

@pytest.mark.parametrize("shape", [(4, 16), (33, 100), (1, 7), (32, 128)])
def test_quantize_roundtrip_error_bound(key, shape):
    """Round-trip error <= scale/2 per element, per row."""
    x = jax.random.normal(key, shape) * 3.0
    q, s = ops.quantize_rowwise(x)
    assert q.dtype == jnp.int8 and s.shape == (shape[0], 1)
    back = ops.dequantize_rowwise(q, s)
    bound = np.asarray(s) / 2.0 + 1e-7
    assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound).all()


def test_quantize_zero_row_guard(key):
    """An all-zero row must quantize to zeros with a finite scale, not
    divide by zero."""
    x = jnp.zeros((3, 16)).at[1].set(jax.random.normal(key, (16,)))
    q, s = ops.quantize_rowwise(x)
    assert np.isfinite(np.asarray(s)).all()
    assert np.abs(np.asarray(q)[0]).max() == 0
    assert np.abs(np.asarray(q)[2]).max() == 0


@pytest.mark.parametrize("shape", [(4, 16), (33, 100)])
def test_quantize_rowwise_matches_ref(key, shape):
    """Kernel q values match the jnp oracle exactly; scales to 1 ulp
    (jit may turn /127 into a multiply by reciprocal)."""
    x = jax.random.normal(key, shape) * 2.0
    q, s = ops.quantize_rowwise(x)
    qr, sr = ref.quantize_rowwise_ref(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


@pytest.mark.parametrize("shape", GEMM_SHAPES)
def test_int8_matmul_exact_vs_ref(key, shape):
    """Given identical int8 inputs, the fused Pallas GEMM and the jnp
    oracle agree EXACTLY: both accumulate in int32 (no overflow below
    MAX_K) and apply the same scale product."""
    M, K, N = shape
    k1, k2 = jax.random.split(key)
    xq, sx = ref.quantize_rowwise_ref(jax.random.normal(k1, (M, K)))
    wq, sw = ref.quantize_rowwise_ref(jax.random.normal(k2, (N, K)))
    wq, sw = wq.T, sw.T                      # colwise layout (K, N), (1, N)
    got = ops.int8_matmul(xq, sx, wq, sw)
    want = ref.int8_matmul_ref(xq, sx, wq, sw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", GEMM_SHAPES)
def test_quantized_matmul_within_analytical_bound(key, shape):
    """quantized_matmul vs the fp32 GEMM, elementwise under the
    propagated first-order quantization bound (the documented
    tolerance — not an arbitrary atol)."""
    M, K, N = shape
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (M, K))
    w = jax.random.normal(k2, (K, N)) / np.sqrt(K)
    wq, sw = ops.quantize_colwise(w)
    got = np.asarray(ops.quantized_matmul(x, wq, sw))
    want = np.asarray(x @ w)
    xn = np.asarray(x)
    w_hat = np.asarray(wq, np.float32) * np.asarray(sw)
    _, sx = ref.quantize_rowwise_ref(x)
    bound = (np.asarray(sw) / 2.0 * np.abs(xn).sum(1, keepdims=True)
             + np.asarray(sx) / 2.0 * np.abs(w_hat).sum(0, keepdims=True))
    assert (np.abs(got - want) <= bound + 1e-5).all()


def test_int8_matmul_k_guard():
    from repro.kernels.quantized import MAX_K
    K = MAX_K + 1
    xq = jnp.zeros((1, K), jnp.int8)
    wq = jnp.zeros((K, 4), jnp.int8)
    with pytest.raises(ValueError, match="int32 accumulator"):
        ops.int8_matmul(xq, jnp.ones((1, 1)), wq, jnp.ones((1, 4)))


def test_quantized_matmul_leading_dims(key):
    """(B, S, K) activations flatten through the GEMM and reshape back."""
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (2, 5, 32))
    w = jax.random.normal(k2, (32, 16)) / np.sqrt(32)
    wq, sw = ops.quantize_colwise(w)
    got = ops.quantized_matmul(x, wq, sw)
    assert got.shape == (2, 5, 16)
    flat = ops.quantized_matmul(x.reshape(10, 32), wq, sw)
    np.testing.assert_array_equal(np.asarray(got).reshape(10, 16),
                                  np.asarray(flat))


# =========================================== hypothesis property tier

def test_roundtrip_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           m=st.integers(1, 9), k=st.integers(1, 65),
           scale=st.floats(1e-3, 1e3))
    def check(seed, m, k, scale):
        x = jax.random.normal(jax.random.PRNGKey(seed), (m, k)) * scale
        q, s = ops.quantize_rowwise(x)
        back = ops.dequantize_rowwise(q, s)
        bound = np.asarray(s) / 2.0 * (1 + 1e-6) + 1e-12
        assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound).all()

    check()


# ============================================= sidecar param factory

def test_sidecar_shares_fp32_by_reference(zoo_models):
    """Only GEMM-heavy dense weights are replaced; embeddings, norms,
    the recurrent wh, and the heads are the SAME objects (id-shared),
    so fleet placement ships each fp32 tensor once."""
    cfg, splits, shared, params, payloads = zoo_models
    qp = Q.quantize_emsnet_params(shared)
    assert qp["heads"] is shared["heads"]
    assert qp["text"]["tok"] is shared["text"]["tok"]
    assert qp["text"]["blocks"][0]["ln1"] is \
        shared["text"]["blocks"][0]["ln1"]
    assert qp["vitals"]["wh"] is shared["vitals"]["wh"]
    blk = qp["text"]["blocks"][0]
    for k in ("wqkv", "wo", "w1", "w2"):
        assert set(blk[k]) >= {"w_q", "w_scale"} and "w" not in blk[k]
        assert blk[k]["w_q"].dtype == jnp.int8
    assert qp["vitals"]["wx"]["w_q"].dtype == jnp.int8
    assert qp["scene"]["fc"]["w_q"].dtype == jnp.int8


def test_quantized_encoders_track_fp32(zoo_models):
    """The sidecar pytree through the UNMODIFIED jitted encoders stays
    within a few percent of fp32 on every modality."""
    cfg, splits, shared, params, payloads = zoo_models
    sm = splits["text+vitals+scene"]
    qp = sm.quantize_params(shared)
    for m in ALL:
        f32 = np.asarray(sm.encoders[m](shared, payloads[m]))
        q = np.asarray(sm.encoders[m](qp, payloads[m]))
        rel = np.abs(q - f32).max() / (np.abs(f32).max() + 1e-9)
        assert rel < 0.08, (m, rel)


def test_quantize_params_requires_quantize_fn(zoo_models):
    cfg, splits, shared, params, payloads = zoo_models
    from dataclasses import replace as dc_replace
    bare = dc_replace(splits["text"].module, quantize_fn=None)
    sm = split(bare)
    with pytest.raises(ValueError, match="quantize_fn"):
        sm.quantize_params(shared)


# ============================================ packed feature transport

def test_feature_pack_shrinks_payload_and_roundtrips(zoo_models):
    """payload_nbytes of the packed wire form is >= 3x smaller and the
    round trip stays within scale/2 per element."""
    cfg, splits, shared, params, payloads = zoo_models
    sm = splits["text+vitals+scene"]
    for m in ALL:
        f = sm.encoders[m](shared, payloads[m])
        pack = Q.quantize_feature(f)
        assert Q.is_quantized_feature(pack)
        assert not Q.is_quantized_feature(f)
        raw_b, pack_b = payload_nbytes(f), payload_nbytes(pack)
        # ~4x asymptotically; the per-row f32 scale is the only
        # overhead, so tiny features (scene d=8 here) still shrink but
        # land under 3x
        assert pack_b < raw_b, (m, raw_b, pack_b)
        if f.size >= 16:
            assert pack_b * 3 <= raw_b, (m, raw_b, pack_b)
        back = np.asarray(Q.dequantize_feature(pack))
        bound = np.asarray(pack["scale"]) / 2.0 + 1e-7
        assert (np.abs(back - np.asarray(f)) <= bound).all()
        # identity on raw features
        assert Q.dequantize_feature(f) is f


# ================================== joint (tier, precision) co-decision

def _policy(bw_mbps, **kw):
    trace = BandwidthTrace.static(bw_mbps * 1e6 / 8)
    mon = {"edge": HeartbeatMonitor(trace, period=1.0)}
    return MultiTierPolicy(
        ProfileTable(base=dict(BASE)), mon, local="glass",
        tier_of={"glass": "glass", "edge": "edge64x"}, **kw)


def test_joint_decision_int8_wins_on_slow_link():
    """A slow radio makes the int8 candidate's smaller feature return
    beat fp32 on the same tier — the precision rides on the decision."""
    pol = _policy(0.5, precisions={"edge": ("fp32", "int8")})
    dec = pol.decide("enc:text", 200_000, 0.0, feat_bytes=400_000)
    est = dec.estimates["edge"]
    assert est.precision == "int8"
    # and the engine-visible decision carries the winning precision
    assert dec.precision == dec.estimates[dec.tier].precision


def test_joint_decision_ties_keep_fp32():
    """With compute scale 1.0 and no feature bytes, int8 buys nothing —
    the per-tier argmin must keep fp32 (no gratuitous quantization)."""
    pol = _policy(100.0, precisions={"edge": ("fp32", "int8")},
                  int8_compute_scale=1.0)
    dec = pol.decide("enc:text", 1000, 0.0, feat_bytes=0)
    assert dec.estimates["edge"].precision == "fp32"
    assert dec.precision == "fp32"


def test_joint_enumeration_all_fp32_matches_legacy():
    """precisions armed but fp32-only == precisions=None, decision for
    decision across payloads and times (the enumeration's fp32 leg IS
    the legacy estimate at feat_bytes=0; with feat_bytes > 0 the armed
    model deliberately charges every remote candidate the feature
    return trip — that refinement exists only once the rung is on,
    which is why the ENGINE disarms entirely for all-fp32 maps)."""
    legacy = _policy(2.0)
    armed = _policy(2.0, precisions={"edge": ("fp32",)})
    for payload in (0, 10_000, 1_000_000):
        for t in (0.0, 3.5, 10.0):
            a = legacy.decide("enc:text", payload, t)
            b = armed.decide("enc:text", payload, t)
            assert a.tier == b.tier
            assert a.precision == b.precision == "fp32"
            for n in a.estimates:
                assert a.estimates[n].cost == b.estimates[n].cost
    # armed + feat_bytes: the remote fp32 candidate pays the return trip
    c = armed.decide("enc:text", 10_000, 0.0, feat_bytes=123)
    d = legacy.decide("enc:text", 10_000, 0.0, feat_bytes=123)
    assert c.estimates["edge"].transfer_s > d.estimates["edge"].transfer_s


def test_policy_rejects_bad_precision_map():
    with pytest.raises(ValueError, match="unknown host or precision"):
        _policy(2.0, precisions={"nope": ("int8",)})
    with pytest.raises(ValueError, match="unknown host or precision"):
        _policy(2.0, precisions={"edge": ("int4",)})


# ================================================= engine-level rungs

def test_engine_precision_off_bit_identical_lag_scenarios(zoo_models):
    """All-fp32 precision map == no precision map, bit for bit (atol 0)
    on every LAG_SCENARIOS arrival ordering: timelines, tiers, and
    output arrays."""
    cfg, splits, shared, params, payloads = zoo_models
    eps = _lag_episodes()
    pay = lambda sid, ev: payloads[ev.modality]  # noqa: E731
    plain = _tiered(splits, params)
    mapped = _tiered(splits, params,
                     precision={"ph1": "fp32", "edge64x": "fp32"})
    plain.run_arrivals(eps, pay)
    mapped.run_arrivals(eps, pay)
    assert len(plain.records) == len(mapped.records) > 0
    for a, b in zip(plain.records, mapped.records):
        assert (a.sid, a.index, a.tier, a.enc_tier, a.tail_tier) == \
               (b.sid, b.index, b.tier, b.enc_tier, b.tail_tier)
        assert a.t_emit == b.t_emit and a.t_start == b.t_start
        assert a.precision == b.precision == "fp32"
        if a.outputs is not None:
            for k in a.outputs:
                np.testing.assert_array_equal(np.asarray(a.outputs[k]),
                                              np.asarray(b.outputs[k]))


def test_engine_int8_packs_cache_and_shrinks_feature_wire(zoo_models):
    """int8 flights commit the packed form to the glass cache and the
    remote->glass feature links carry >= 3x fewer bytes than the same
    workload served fp32."""
    cfg, splits, shared, params, payloads = zoo_models
    eps = _lag_episodes()
    pay = lambda sid, ev: payloads[ev.modality]  # noqa: E731
    f32 = _tiered(splits, params, bandwidth=30.0)
    q8 = _tiered(splits, params, bandwidth=30.0,
                 precision={"ph1": "int8", "edge64x": "int8"})
    f32.run_arrivals(eps, pay)
    q8.run_arrivals(eps, pay)
    q_recs = [r for r in q8.records if r.precision == "int8"]
    assert q_recs, "slow uplink never chose an int8 flight"
    # the cache holds the packed wire form for int8-encoded modalities
    packed = 0
    for r in q_recs:
        if r.model is None:
            continue
        e = q8.cache.peek(q8._cache_key(r.sid, r.model), r.modality)
        if e is not None and Q.is_quantized_feature(e.feature):
            packed += 1
    assert packed > 0

    # the FEATURE payload itself shrinks >= 3x (text is wide enough for
    # the asymptotic ratio on the tiny config)...
    sm = splits["text+vitals+scene"]
    raw_text = sm.encoders["text"](shared, payloads["text"])
    text_recs = [r for r in q_recs
                 if r.modality == "text" and r.model is not None]
    assert text_recs
    e = q8.cache.peek(q8._cache_key(text_recs[0].sid, text_recs[0].model),
                      "text")
    assert Q.is_quantized_feature(e.feature)
    assert payload_nbytes(e.feature) * 3 <= payload_nbytes(raw_text)

    # ...and the total remote->glass wire (features + the un-quantized
    # fp32 head outputs, which dominate at tiny scale) still shrinks
    def down_bytes(eng):
        return sum(s["bytes"] for link, s in eng.fabric.stats().items()
                   if link.endswith("->glass"))
    assert down_bytes(q8) < down_bytes(f32), \
        (down_bytes(q8), down_bytes(f32))
    # quantized serving still emits finals with sane outputs
    finals = [r for r in q8.records if r.kind == "final"]
    assert finals
    for r in finals:
        for v in r.outputs.values():
            assert np.isfinite(np.asarray(v)).all()


def test_engine_qparams_derived_once_for_shared_zoo(zoo_models):
    """A share_encoders zoo aliases ONE fp32 pytree, so the sidecar is
    derived exactly once however many subset models serve int8."""
    cfg, splits, shared, params, payloads = zoo_models
    eng = _tiered(splits, params, bandwidth=30.0,
                  precision={"ph1": "int8", "edge64x": "int8"})
    eps = _lag_episodes()
    eng.run_arrivals(eps, lambda sid, ev: payloads[ev.modality])
    assert len(eng._qparams_cache) == 1


def test_engine_rejects_bad_precision_config(zoo_models):
    cfg, splits, shared, params, payloads = zoo_models
    with pytest.raises(ValueError, match="unknown host or precision"):
        _tiered(splits, params, precision={"mars": "int8"})
    with pytest.raises(ValueError, match="unknown host or precision"):
        _tiered(splits, params, precision={"ph1": "int4"})


def test_engine_rejects_zoo_without_quantize_fn(zoo_models):
    """An int8-enabled spec over a model with no quantized variant must
    refuse to build, not silently serve fp32."""
    cfg, splits, shared, params, payloads = zoo_models
    from dataclasses import replace as dc_replace
    bare = {k: split(dc_replace(sm.module, quantize_fn=None))
            for k, sm in splits.items()}
    with pytest.raises(ValueError, match="quantize_fn"):
        _tiered(bare, params, precision={"ph1": "int8"})
    # ...but an all-fp32 map over the same zoo is fine (legacy rule)
    _tiered(bare, params, precision={"ph1": "fp32"})


def test_engine_int8_staleness_semantics_unchanged(zoo_models):
    """Packed cache entries obey the same <=1-step staleness contract:
    a provisional read of a quantized feature succeeds within the bound
    and the versioned entries still re-stamp on touch."""
    cfg, splits, shared, params, payloads = zoo_models
    eng = _tiered(splits, params, bandwidth=30.0,
                  precision={"ph1": "int8", "edge64x": "int8"})
    for i, m in enumerate(ALL):
        eng.submit("s0", Event(i, m, float(i)), payloads[m])
    # one more text arrival: re-fuses against cached (possibly packed)
    # vitals/scene one step behind — the tolerated bound
    rec = eng.submit("s0", Event(3, "text", 3.0), payloads["text"])
    assert rec.outputs is not None and rec.kind == "final"

"""EMSServe core: splitter equivalence, feature cache invariants,
offloading decisions, episodes, med-math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdaptiveOffloadPolicy, BandwidthTrace, EMSServe,
                        FeatureCache, HeartbeatMonitor, ProfileTable,
                        StalenessError, emsnet_module, nlos_bandwidth, split,
                        table6)
from repro.core import episodes as EP
from repro.core import medmath as MM


@pytest.fixture(scope="module")
def tiny_models(tiny_emsnet_cfg):
    cfg = tiny_emsnet_cfg
    key = jax.random.PRNGKey(0)
    mods = {
        "m1": emsnet_module(cfg, ("text",)),
        "m2": emsnet_module(cfg, ("text", "vitals")),
        "m3": emsnet_module(cfg, ("text", "vitals", "scene")),
    }
    splits = {k: split(m) for k, m in mods.items()}
    params = {k: m.init_fn(jax.random.fold_in(key, i))
              for i, (k, m) in enumerate(mods.items())}
    return cfg, splits, params


def _payloads(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "text": jnp.asarray(rng.integers(1, cfg.vocab_size,
                                         (1, cfg.max_text_len)), jnp.int32),
        "vitals": jnp.asarray(rng.normal(size=(1, cfg.vitals_len,
                                               cfg.n_vitals)), jnp.float32),
        "scene": jnp.asarray(rng.integers(0, 2, (1, cfg.scene_dim)),
                             jnp.float32),
    }


# ------------------------------------------------------------- splitter

def test_split_equals_full(tiny_models):
    """tail(encoders(x)) == full(x): the split is lossless."""
    cfg, splits, params = tiny_models
    batch = _payloads(cfg)
    sm = splits["m3"]
    feats = {m: sm.encoders[m](params["m3"], batch[m])
             for m in sm.modalities()}
    via_split = sm.tail(params["m3"], feats)
    via_full = sm.full(params["m3"], batch)
    for k in via_full:
        np.testing.assert_allclose(via_split[k], via_full[k], atol=1e-5)


def test_split_names_each_program(tiny_models):
    """Every piece lowers under its own name (compile logs and the
    device trace's module line tell them apart), not ``jit__lambda``."""
    cfg, splits, params = tiny_models
    batch = _payloads(cfg)
    sm, p = splits["m2"], params["m2"]
    feats = {m: sm.encoders[m](p, batch[m]) for m in sm.modalities()}
    names = {
        "jit_encode_text": sm.encoders["text"].lower(p, batch["text"]),
        "jit_encode_vitals": sm.encoders["vitals"].lower(p, batch["vitals"]),
        "jit_tail_text_vitals": sm.tail.lower(p, feats),
        "jit_full_text_vitals": sm.full.lower(
            p, {m: batch[m] for m in sm.modalities()}),
    }
    for name, lowered in names.items():
        assert lowered.as_text().startswith(f"module @{name} "), name


class _FakeSplit:
    """select_model only needs .modalities()."""
    def __init__(self, *mods):
        self._mods = tuple(mods)

    def modalities(self):
        return self._mods


def test_select_model_prefers_largest_subset_deterministically():
    """Regression: when several models consume equally many observed
    modalities, the winner must not depend on dict insertion order."""
    from repro.core.splitter import select_model
    tv, ts, vs = (_FakeSplit("text", "vitals"), _FakeSplit("text", "scene"),
                  _FakeSplit("vitals", "scene"))
    observed = {"text", "vitals", "scene"}
    winners = {select_model(dict(order), observed)
               for order in [
                   [("a", tv), ("b", ts), ("c", vs)],
                   [("c", vs), ("b", ts), ("a", tv)],
                   [("b", ts), ("a", tv), ("c", vs)]]}
    assert winners == {"a"}      # ("text","vitals") sorts above the others
    # largest subset still beats any tie-break
    full = _FakeSplit("text", "vitals", "scene")
    assert select_model({"a": tv, "z": full}, observed) == "z"
    assert select_model({"z": full, "a": tv}, observed) == "z"
    # same modality set under two names: the greater name wins, any order
    assert select_model({"x": tv, "y": _FakeSplit("text", "vitals")},
                        {"text", "vitals"}) == "y"
    assert select_model({"y": _FakeSplit("text", "vitals"), "x": tv},
                        {"text", "vitals"}) == "y"
    # nothing satisfiable -> None
    assert select_model({"a": tv}, {"scene"}) is None


# -------------------------------------------------------- feature cache

def test_cache_staleness_invariant():
    c = FeatureCache(max_staleness=1)
    c.put("s", "text", 1.0, step=1)
    assert c.get("s", "text", input_step=2).feature == 1.0   # 1 step: OK
    with pytest.raises(StalenessError):
        c.get("s", "text", input_step=3)


def test_cache_versioning_and_tiers():
    c = FeatureCache()
    c.put("s", "v", 1, step=1, tier="edge")
    c.put("s", "v", 2, step=2, tier="edge")
    assert c.get("s", "v").version == 1
    c.drop_tier("edge")
    assert c.get("s", "v") is None
    assert c.misses == 1


def test_cache_touch_restamps():
    c = FeatureCache(max_staleness=1)
    c.put("s", "t", 0, step=1)
    c.touch("s", "t", 5)
    assert c.get("s", "t", input_step=5).feature == 0


def test_cache_double_commit_is_structural_noop():
    """Idempotent commits (ISSUE 6): re-committing the step the entry
    already holds — a losing speculative racer landing late — changes
    NOTHING: feature, step, and version all stand (the version not
    bumping is what keeps tier replicas from re-shipping), and the
    refusal is audited."""
    c = FeatureCache()
    assert c.put("s", "t", 1.0, step=3, tier="glass")
    assert not c.put("s", "t", 2.0, step=3, tier="edge")
    e = c.get("s", "t")
    assert (e.feature, e.step, e.version, e.tier) == (1.0, 3, 0, "glass")
    assert c.duplicate_commits == 1 and c.stale_commits == 0


def test_cache_stale_late_commit_refused():
    """Monotone commits (ISSUE 6): a commit at an OLDER step than the
    stored entry — a crash-delayed straggler — is refused outright, so
    a late flight can never regress the staleness clock."""
    c = FeatureCache(max_staleness=1)
    assert c.put("s", "t", 1.0, step=5)
    assert not c.put("s", "t", 0.0, step=4, tier="edge")
    e = c.get("s", "t", input_step=6)     # still 1 step: still fresh
    assert (e.feature, e.step, e.version) == (1.0, 5, 0)
    assert c.stale_commits == 1 and c.duplicate_commits == 0
    # a genuinely newer commit still lands and bumps the version
    assert c.put("s", "t", 2.0, step=6)
    assert c.get("s", "t").version == 1


# ----------------------------------------------------------- offloading

def test_offload_rule_exact():
    prof = ProfileTable(base={"enc:text": 0.1}, host_tier="edge4c")
    mon = HeartbeatMonitor(BandwidthTrace.static(1e6))
    pol = AdaptiveOffloadPolicy(prof, mon)
    # t_edge = 0.1, t_glass = 0.1*107/2.7 ≈ 3.96
    d = pol.decide("enc:text", payload_bytes=int(0.5e6), now=0.0)  # dt=0.5
    assert d.tier == "edge" and d.delta_t == pytest.approx(0.5)
    d = pol.decide("enc:text", payload_bytes=int(10e6), now=0.0)   # dt=10
    assert d.tier == "glass"


def test_heartbeat_quantization():
    tr = BandwidthTrace([(0.0, 100.0), (1.0, 200.0)])
    mon = HeartbeatMonitor(tr, period=1.0)
    assert mon.bandwidth(0.4) == 100.0
    assert mon.bandwidth(1.7) == 200.0


def test_nlos_bandwidth_monotone():
    bws = [nlos_bandwidth(d) for d in (0, 5, 10, 20, 30)]
    assert all(a > b for a, b in zip(bws, bws[1:]))


def test_bandwidth_trace_piecewise_constant_boundaries():
    """bisect boundary semantics: right-continuous steps, clamped at
    and before the first point, held after the last."""
    tr = BandwidthTrace([(1.0, 10.0), (2.0, 20.0), (4.0, 40.0)])
    assert tr.at(-5.0) == 10.0      # before the first point: clamp back
    assert tr.at(0.0) == 10.0
    assert tr.at(1.0) == 10.0       # exactly ON a breakpoint: its value
    assert tr.at(1.999) == 10.0     # just before the next: old value
    assert tr.at(2.0) == 20.0       # a new measurement applies at its t
    assert tr.at(3.0) == 20.0
    assert tr.at(4.0) == 40.0
    assert tr.at(100.0) == 40.0     # after the last point: hold


def test_bandwidth_trace_sorts_validates_and_breaks_ties():
    # unsorted points are normalized at construction
    tr = BandwidthTrace([(2.0, 20.0), (0.0, 5.0)])
    assert tr.at(1.0) == 5.0 and tr.at(2.0) == 20.0
    # duplicate timestamps: the last-listed measurement wins
    tr = BandwidthTrace([(0.0, 1.0), (1.0, 2.0), (1.0, 3.0)])
    assert tr.at(1.0) == 3.0 and tr.at(1.5) == 3.0
    # an empty trace fails eagerly, not inside a lookup mid-serve
    with pytest.raises(ValueError):
        BandwidthTrace([])


def test_heartbeat_monitor_sees_quantized_trace_at_boundaries():
    """The monitor samples on the heartbeat grid: a bandwidth change at
    t=1.0 is visible exactly from the 1.0 tick, not before."""
    tr = BandwidthTrace([(0.0, 100.0), (1.0, 200.0)])
    mon = HeartbeatMonitor(tr, period=1.0)
    assert mon.bandwidth(0.999) == 100.0
    assert mon.bandwidth(1.0) == 200.0
    # delta_t uses the same quantized measurement
    assert mon.delta_t(400, 1.3) == pytest.approx(2.0)


# -------------------------------------------------------------- episodes

def test_table6_matches_paper():
    eps = table6()
    for i in (1, 2, 3):
        kinds = [e.modality for e in eps[i]]
        assert len(kinds) == 21
        assert kinds.count("text") == 1
        assert kinds.count("vitals") == 10
        assert kinds.count("scene") == 10
    assert [e.modality for e in eps[1][:2]] == ["text", "vitals"]


def test_random_episode_has_text():
    ev = EP.random_episode(15, seed=3)
    assert any(e.modality == "text" for e in ev)
    assert [e.index for e in ev] == list(range(15))


@pytest.mark.parametrize("scenario", sorted(EP.LAG_SCENARIOS))
def test_async_episode_invariants(scenario):
    ev = EP.async_episode(scenario, seed=7, n_vitals=4, n_scene=3)
    assert len(ev) == 1 + 4 + 3
    assert sum(e.modality == "text" for e in ev) == 1
    times = [e.arrival_time for e in ev]
    assert times == sorted(times) and all(t >= 0 for t in times)
    assert [e.index for e in ev] == list(range(len(ev)))
    # deterministic per seed
    again = EP.async_episode(scenario, seed=7, n_vitals=4, n_scene=3)
    assert ev == again


def test_async_episode_scenarios_reorder_modalities():
    """The presets really change which modality arrives first."""
    first = {s: EP.async_episode(s, seed=0, n_vitals=2, n_scene=2)[0].modality
             for s in ("text_first", "vitals_first")}
    assert first["text_first"] == "text"
    assert first["vitals_first"] == "vitals"
    # scene-late: the scene feed onsets after text and vitals
    ev = EP.async_episode("scene_late", seed=0, n_vitals=2, n_scene=2)
    t_scene = min(e.arrival_time for e in ev if e.modality == "scene")
    t_other = max(e.arrival_time for e in ev
                  if e.modality == "text")
    assert t_scene > t_other


def test_async_episode_custom_lags():
    ev = EP.async_episode(lags={"text": (0.0, 0.0), "vitals": (1.0, 0.0)},
                          seed=0, n_vitals=2)
    assert [e.modality for e in ev] == ["text", "vitals", "vitals"]
    assert ev[1].arrival_time == pytest.approx(1.0)


# ---------------------------------------------------------------- engine

def test_engine_cached_matches_direct_outputs(tiny_models):
    """The feature cache must not change recommendations, only cost."""
    cfg, splits, params = tiny_models
    payloads = _payloads(cfg)
    outs = {}
    for cached in (False, True):
        eng = EMSServe(splits, params, cached=cached, real_time=True)
        eng.run_episode(table6()[2], lambda ev: payloads[ev.modality])
        recs = [r.recommendation for r in eng.records
                if r.recommendation is not None]
        outs[cached] = recs
    assert len(outs[True]) == len(outs[False])
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_allclose(a["protocol_logits"],
                                   b["protocol_logits"], atol=1e-5)


def test_engine_cache_cheaper_than_direct(tiny_models):
    cfg, splits, params = tiny_models
    payloads = _payloads(cfg)
    times = {}
    for cached in (False, True):
        eng = EMSServe(splits, params, cached=cached, real_time=True)
        # warmup compile
        eng.run_episode(table6()[1], lambda ev: payloads[ev.modality])
        eng2 = EMSServe(splits, params, cached=cached, real_time=True)
        eng2.run_episode(table6()[1], lambda ev: payloads[ev.modality])
        times[cached] = eng2.cumulative_time()
    assert times[True] < times[False]


def test_engine_fault_tolerance(tiny_models):
    """Edge crash mid-episode: serving continues on-glass, recommendations
    keep flowing, staleness invariant holds throughout."""
    cfg, splits, params = tiny_models
    payloads = _payloads(cfg)
    prof_base = {"enc:text": 0.05, "enc:vitals": 0.001, "enc:scene": 0.001,
                 "tail": 0.001, "full": 0.06}
    pol = AdaptiveOffloadPolicy(
        ProfileTable(base=prof_base),
        HeartbeatMonitor(BandwidthTrace.static(nlos_bandwidth(0))))
    eng = EMSServe(splits, params, policy=pol, cached=True)
    events = table6()[1]
    for i, ev in enumerate(events):
        if i == 8:
            eng.crash_edge()
        rec = eng.on_event(ev, payloads[ev.modality])
        if i > 8:
            assert rec.tier == "glass"
    assert eng.records[-1].recommendation is not None


def test_engine_adaptive_beats_forced_edge_under_mobility(tiny_models):
    """Scenario 3: with degrading bandwidth, adaptive < always-offload."""
    cfg, splits, params = tiny_models
    payloads = _payloads(cfg)
    prof_base = {"enc:text": 0.05, "enc:vitals": 0.001, "enc:scene": 0.005,
                 "tail": 0.001, "full": 0.06}
    dist = list(np.linspace(0, 60, 21))     # walking away
    results = {}
    for adaptive in (True, False):
        pol = AdaptiveOffloadPolicy(
            ProfileTable(base=prof_base),
            HeartbeatMonitor(BandwidthTrace.walk(dist, nlos_bandwidth)),
            adaptive=adaptive)
        eng = EMSServe(splits, params, policy=pol, cached=True)
        eng.run_episode(table6()[1], lambda ev: payloads[ev.modality])
        results[adaptive] = eng.cumulative_time()
    assert results[True] <= results[False]


# -------------------------------------------------------------- med math

def test_med_math_paper_example():
    assert MM.med_math(21.0, 4.2) == pytest.approx(5.0)


def test_med_math_rejects_bad_concentration():
    with pytest.raises(ValueError):
        MM.med_math(1.0, 0.0)


def test_ed_match_corrects_ocr_noise():
    assert MM.ed_match("nal0xone") == "naloxone"
    assert MM.ed_match("atrovnet") == "atrovent"
    assert MM.ed_match("zzzzqqqq") is None


def test_dosage_pipeline():
    out = MM.dosage_from_label(10.0, "naloxon")
    assert out["medicine"] == "naloxone"
    assert out["dosage_ml"] == pytest.approx(
        10.0 / out["concentration_mg_per_ml"])
    assert len(out["disease_history"]) > 0
    assert all(0 <= d < MM.N_DISEASES for d in out["disease_history"])

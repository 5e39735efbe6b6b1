"""Unified serving observability: tracer, metrics registry, auditor.

Three claims under test:

  * **defaults off** — ``Tracer.disabled`` is a falsy no-op and every
    engine wires it by default, so an untraced run records nothing and
    the legacy counter attributes still read correctly through the
    metrics registry;
  * **deterministic traces** — events carry a monotone per-tracer seq,
    export stable-sorts at equal timestamps and serializes canonically,
    so two identical simulated-clock runs produce byte-identical files
    that validate as Chrome trace-event JSON;
  * **the auditor proves the invariants from the trace alone** — zero
    violations across every scenario family the repo serves (all
    LAG_SCENARIOS stream orderings, the 3^4 forced-placement sweep,
    speculation races incl. cancelled flights, seeded chaos schedules),
    and tampering with a trace (version skip, unstamped fuse input,
    cancel-after-deliver, emit without fuse) is caught.
"""
import dataclasses
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BandwidthTrace, LAG_SCENARIOS, ProfileTable,
                        async_episode, emsnet_zoo, horizon,
                        nlos_bandwidth, split)
from repro.core.episodes import Event
from repro.core.offload import SpeculationPolicy
from repro.obs import (Metrics, QuantileSketch, StreamingTracer, Tracer,
                       audit_doc, audit_file, audit_tracer, jsonl_to_chrome,
                       validate_chrome)
from repro.obs.audit import main as audit_main
from repro.serving.api import build_engine
from repro.serving.transport import TransportChannel

ALL = ("text", "vitals", "scene")
TIERS = ("glass", "ph1", "edge64x")
BASE = {"enc:text": 0.08, "enc:vitals": 0.01, "enc:scene": 0.05,
        "tail": 0.005, "full": 0.15}
RACE_ALWAYS = SpeculationPolicy(deadline_s=0.0, margin_s=0.0)


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


def _tiered(splits, params, *, bandwidth=5.0, **kw):
    kw.setdefault("max_history", None)
    kw.setdefault("tier_traces",
                  {"ph1": BandwidthTrace.static(nlos_bandwidth(0.0))})
    kw.setdefault("trace", BandwidthTrace.static(nlos_bandwidth(bandwidth)))
    kw.setdefault("tiers", TIERS)
    return build_engine(
        splits, params, "tiered", share_encoders=True,
        profile=ProfileTable(base=dict(BASE)), **kw)


def _episode():
    return [Event(i, m, float(i)) for i, m in enumerate(ALL)]


def _audit_ok(eng):
    rep = audit_tracer(eng.tracer,
                       other_data={"transport": eng.fabric.stats()})
    assert rep.ok, rep.violations
    return rep


# ====================================================== defaults off

def test_disabled_tracer_is_falsy_noop_default(zoo_models):
    cfg, splits, shared, params, payloads = zoo_models
    assert not Tracer.disabled and bool(Tracer())
    Tracer.disabled.span("x", "c", 0.0, 1.0)
    Tracer.disabled.instant("y", "c", 0.0)
    assert Tracer.disabled.events == []
    eng = _tiered(splits, params)
    eng.submit("s0", Event(0, "text", 0.0), payloads["text"])
    assert eng.tracer is Tracer.disabled and eng.tracer.events == []


def test_legacy_counters_read_through_registry(zoo_models):
    """The historical attributes and the registry are the same number:
    migrating the counters changed their storage, not their meaning."""
    cfg, splits, shared, params, payloads = zoo_models
    eng = _tiered(splits, params)
    for ev in _episode():
        eng.submit("s0", ev, payloads[ev.modality])
    m = eng.metrics
    assert eng.cache.hits == int(m.get("cache.hits")) > 0
    assert eng.cache.duplicate_commits == int(m.get("cache.duplicate_commits"))
    assert eng.fallback_count == int(m.get("placement.fallbacks"))
    assert eng.evicted_count == int(m.get("engine.evicted_sessions"))
    for name, ch in eng.fabric.stats().items():
        assert ch["bytes"] == int(m.get(f"transport.{name}.bytes"))
        assert ch["cancelled_msgs"] == int(
            m.get(f"transport.{name}.cancelled_msgs"))
    snap = eng.metrics_snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["histograms"]["serve.latency_s"]["count"] == 3
    assert snap["gauges"]["engine.sessions_live"] == 1


# ============================================== deterministic export

def test_trace_export_is_byte_reproducible(zoo_models, tmp_path):
    cfg, splits, shared, params, payloads = zoo_models
    paths = []
    for n in (1, 2):
        eng = _tiered(splits, params, tracer=Tracer(),
                      speculation=RACE_ALWAYS)
        for ev in _episode():
            eng.submit("s0", ev, payloads[ev.modality])
        p = tmp_path / f"t{n}.json"
        eng.tracer.export(p, other_data={"transport": eng.fabric.stats()})
        paths.append(p)
    b1, b2 = paths[0].read_bytes(), paths[1].read_bytes()
    assert b1 == b2 and len(b1) > 0


def test_seq_is_monotone_and_ties_sort_stably():
    tr = Tracer()
    for i in range(5):
        tr.instant("tie", "t", 1.0, track="a", i=i)   # all at the same ts
    tr.span("before", "t", 0.0, 1.0, track="b")
    doc = tr.to_chrome()
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    seqs = [e["args"]["seq"] for e in evs]
    assert evs[0]["name"] == "before"                  # ts order first
    assert [e["args"]["i"] for e in evs[1:]] == list(range(5))
    assert sorted(set(seqs)) == sorted(seqs)           # unique, monotone


def test_chrome_schema_tracks_and_units(zoo_models):
    cfg, splits, shared, params, payloads = zoo_models
    eng = _tiered(splits, params, tracer=Tracer())
    for ev in _episode():
        eng.submit("s0", ev, payloads[ev.modality])
    doc = eng.tracer.to_chrome()
    assert validate_chrome(doc) == []
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert {"cache", "session:s0"} <= names
    assert any(n.startswith("host:") for n in names)
    assert any(n.startswith("link:") for n in names)
    # lifecycle span for each arrival, in microseconds on the session tid
    spans = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"] == "text#0"]
    assert len(spans) == 1 and spans[0]["dur"] > 0


# ==================================================== metrics registry

def test_metrics_registry_basics():
    m = Metrics()
    m.inc("a")
    m.inc("a", 2.5)
    assert m.get("a") == 3.5 and m.get("absent") == 0
    m.set_gauge("g", 7)
    m.gauge_fn("live", lambda: 42)
    for v in (1.0, 2.0, 3.0):
        m.observe("h", v)
    snap = m.snapshot()
    assert snap["counters"] == {"a": 3.5}
    assert snap["gauges"] == {"g": 7, "live": 42}
    assert snap["histograms"]["h"]["count"] == 3
    assert snap["histograms"]["h"]["mean"] == pytest.approx(2.0)
    json.dumps(snap)                       # JSON-serializable end to end
    m.reset()
    snap = m.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}
    assert snap["gauges"] == {"live": 42}  # callable gauges survive reset


def test_sketch_rank_error_bound_seeded():
    rng = np.random.default_rng(3)
    xs = rng.lognormal(mean=2.0, sigma=1.5, size=4000)
    sk = QuantileSketch(rel_err=0.01)
    for x in xs:
        sk.add(float(x))
    srt = np.sort(xs)
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        true = float(srt[int(np.floor(q * (len(xs) - 1)))])
        got = sk.quantile(q)
        assert abs(got - true) <= 1.01 * sk.rel_err * true, q


def test_sketch_merge_is_associative_on_state():
    rng = np.random.default_rng(4)
    sks = []
    for _ in range(3):
        sk = QuantileSketch(rel_err=0.02)
        for x in rng.uniform(0.0, 50.0, size=200):
            sk.add(float(x))
        sks.append(sk)
    a, b, c = sks
    left, right = a.merge(b).merge(c), a.merge(b.merge(c))
    assert left.state() == right.state()
    for q in (0.1, 0.5, 0.99):
        assert left.quantile(q) == right.quantile(q)


# ============================================ auditor: scenario families

def test_audit_all_lag_scenarios_stream(zoo_models):
    """One streaming run holding every LAG_SCENARIOS arrival ordering at
    once replays through the auditor with zero violations."""
    cfg, splits, shared, params, payloads = zoo_models
    eng = build_engine(splits, params, "stream", share_encoders=True,
                       max_history=None, tracer=Tracer())
    eps = {name: async_episode(name, seed=i)
           for i, name in enumerate(sorted(LAG_SCENARIOS))}
    eng.run_arrivals(eps, lambda sid, ev: payloads[ev.modality],
                     sim_window=0.0)
    rep = audit_tracer(eng.tracer)
    assert rep.ok, rep.violations
    assert rep.checks["fuses"] > 0 and rep.checks["emits"] > 0


def test_audit_placement_sweep_81(zoo_models):
    """Every 3^4 forced per-submodule tier assignment produces a trace
    the auditor accepts, with the transport cross-checked against the
    live fabric stats."""
    cfg, splits, shared, params, payloads = zoo_models
    submods = ("enc:text", "enc:vitals", "enc:scene", "tail")
    total = dict.fromkeys(("commits", "fuses", "flights"), 0)
    for combo in itertools.product(TIERS, repeat=len(submods)):
        eng = _tiered(splits, params, tracer=Tracer(),
                      force=dict(zip(submods, combo)))
        for ev in _episode():
            eng.submit("s0", ev, payloads[ev.modality])
        rep = _audit_ok(eng)
        for k in total:
            total[k] += rep.checks[k]
    assert total["commits"] >= 81 * 3 and total["fuses"] >= 81 * 3
    assert total["flights"] > 0


def test_audit_speculation_remote_wins(zoo_models):
    cfg, splits, shared, params, payloads = zoo_models
    eng = _tiered(splits, params, tracer=Tracer(), speculation=RACE_ALWAYS)
    for ev in _episode():
        eng.submit("s0", ev, payloads[ev.modality])
    assert eng.spec_count == 3
    rep = _audit_ok(eng)
    assert rep.checks["flights"] > 0
    races = [e for e in eng.tracer.events if e.name == "race.start"]
    wins = [e for e in eng.tracer.events if e.name == "race.win"]
    assert len(races) == 3 and len(wins) == 3


def test_audit_speculation_glass_wins_cancelled_flight(zoo_models):
    """The cancel-on-commit path: the starved uplink flight is
    cancelled, and the auditor both accepts the trace AND accounts the
    cancelled bytes in its conservation check."""
    cfg, splits, shared, params, payloads = zoo_models
    eng = _tiered(splits, params, trace=BandwidthTrace.static(200.0),
                  tier_traces={}, speculation=RACE_ALWAYS, tracer=Tracer())
    rec = eng.submit("s0", Event(0, "text", 0.0), payloads["text"])
    assert rec.race_winner == "glass"
    rep = _audit_ok(eng)
    assert rep.checks["cancels"] == 1


def test_audit_seeded_chaos_schedule(zoo_models):
    cfg, splits, shared, params, payloads = zoo_models
    from repro.serving.chaos import chaos_schedule
    eps = {f"s{i}": async_episode("text_first", seed=i) for i in range(2)}
    sched = chaos_schedule(5, horizon=horizon(eps),
                           tiers=("ph1", "edge64x"),
                           mean_up_s=1.5, mean_down_s=0.6,
                           min_up_s=0.4, min_down_s=0.3)
    eng = _tiered(splits, params, tracer=Tracer(), redispatch=True,
                  speculation=RACE_ALWAYS)
    eng.run_arrivals(eps, lambda sid, ev: payloads[ev.modality],
                     schedule=sched)
    assert eng.rejoin_count >= 1
    rep = _audit_ok(eng)
    names = {e.name for e in eng.tracer.events}
    assert {"crash.inject", "rejoin"} <= names
    assert rep.checks["commits"] > 0 and rep.checks["flights"] > 0


# ================================================= auditor: tampering

def _minimal_doc():
    """Hand-built clean trace: commit -> fuse -> emit, one flight +
    cancel, all in program order."""
    tr = Tracer()
    tr.instant("cache.commit", "cache", 0.0, track="cache", key="s0",
               modality="text", step=0, tier="glass", accepted=True,
               version=0)
    tr.span("transport.flight", "transport", 0.0, 1.0, track="link:u",
            flight=0, channel="u", nbytes=100, t_send=0.0, t_deliver=1.0,
            queued_s=0.0)
    tr.instant("transport.cancel", "transport", 0.5, track="link:u",
               flight=0, channel="u", nbytes=100, t=0.5)
    tr.instant("fuse", "serve", 1.0, track="session:s0", key="s0",
               model="text", step=0, consumed={"text": [0, 0]})
    tr.instant("emit", "serve", 1.0, track="session:s0", key="s0",
               model="text", step=0, kind="partial")
    return tr.to_chrome()


def test_audit_accepts_minimal_doc_then_catches_tampering():
    doc = _minimal_doc()
    assert audit_doc(doc).ok

    def ev(name):
        return next(e for e in doc["traceEvents"] if e.get("name") == name)

    # I1: accepted version skips
    d = json.loads(json.dumps(doc))
    next(e for e in d["traceEvents"]
         if e.get("name") == "cache.commit")["args"]["version"] = 3
    assert any("I1" in v for v in audit_doc(d).violations)

    # I4: fuse consumes a step never stamped
    d = json.loads(json.dumps(doc))
    next(e for e in d["traceEvents"]
         if e.get("name") == "fuse")["args"]["consumed"] = {"text": [5, 5]}
    assert any("I4" in v for v in audit_doc(d).violations)

    # I2: staleness beyond the bound
    d = json.loads(json.dumps(doc))
    next(e for e in d["traceEvents"]
         if e.get("name") == "fuse")["args"]["consumed"] = {"text": [0, 2]}
    assert any("I2" in v for v in audit_doc(d).violations)

    # I3: cancel at/after the delivery instant
    d = json.loads(json.dumps(doc))
    next(e for e in d["traceEvents"]
         if e.get("name") == "transport.cancel")["args"]["t"] = 1.0
    assert any("I3" in v for v in audit_doc(d).violations)

    # I4: emit with no prior fuse
    d = json.loads(json.dumps(doc))
    next(e for e in d["traceEvents"]
         if e.get("name") == "emit")["args"]["key"] = "ghost"
    assert any("I4" in v for v in audit_doc(d).violations)

    assert ev("emit")["args"]["key"] == "s0"   # originals untouched


def test_audit_cli_exit_codes(zoo_models, tmp_path, capsys):
    cfg, splits, shared, params, payloads = zoo_models
    eng = _tiered(splits, params, tracer=Tracer())
    for ev in _episode():
        eng.submit("s0", ev, payloads[ev.modality])
    clean = tmp_path / "clean.json"
    eng.tracer.export(clean, other_data={"transport": eng.fabric.stats()})
    assert audit_main([str(clean)]) == 0
    assert "audit OK" in capsys.readouterr().out

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"nope": 1}))
    assert audit_main([str(bad)]) == 2

    doc = json.loads(clean.read_text())
    for e in doc["traceEvents"]:
        if e.get("name") == "emit":
            e["args"]["key"] = "ghost"
    tampered = tmp_path / "tampered.json"
    tampered.write_text(json.dumps(doc))
    assert audit_main([str(tampered)]) == 1


# =============================== byte conservation, random cancels

def test_byte_conservation_under_random_cancel_schedule():
    """Seeded random send/cancel schedule on a raw channel: the trace
    replays cleanly against the live stats, and corrupting the stats by
    one byte is detected."""
    rng = np.random.default_rng(7)
    tr = Tracer()
    ch = TransportChannel(BandwidthTrace.static(1e4), name="g->e",
                          metrics=Metrics(), tracer=tr, max_history=None)
    t = 0.0
    for _ in range(60):
        t += float(rng.uniform(0.0, 0.05))
        d = ch.send(int(rng.integers(1, 5000)), t)
        if rng.random() < 0.4:
            tc = d.t_send + 0.9 * float(rng.random()) * (d.t_deliver
                                                         - d.t_send)
            ch.cancel(d.flight, tc)
    assert ch.cancelled_msgs > 0
    stats = {ch.name: ch.stats()}
    rep = audit_doc(tr.to_chrome({"transport": stats}))
    assert rep.ok, rep.violations
    assert rep.checks["flights"] == 60
    assert rep.checks["cancels"] == ch.cancelled_msgs
    bad = {ch.name: dict(ch.stats(), bytes=ch.stats()["bytes"] + 1)}
    assert not audit_doc(tr.to_chrome({"transport": bad})).ok


# ====================================== streaming (bounded) tracer

def _record_mixed(tr, n=100):
    for i in range(n):
        if i % 3 == 0:
            tr.span(f"work#{i}", "w", i * 0.01, i * 0.01 + 0.002,
                    track=f"r{i % 2}", i=i)
        else:
            tr.instant(f"mark#{i}", "m", i * 0.01, track="fleet", i=i)


def test_streaming_tracer_bounded_ring_and_exact_roundtrip(tmp_path):
    """The ring never exceeds ``buffer`` entries, and the JSONL file
    converts offline to the EXACT Chrome doc a plain Tracer would
    export for the same event stream."""
    plain = Tracer()
    _record_mixed(plain)
    p = tmp_path / "stream.jsonl"
    st = StreamingTracer(p, buffer=8)
    high = 0
    for i in range(100):
        if i % 3 == 0:
            st.span(f"work#{i}", "w", i * 0.01, i * 0.01 + 0.002,
                    track=f"r{i % 2}", i=i)
        else:
            st.instant(f"mark#{i}", "m", i * 0.01, track="fleet", i=i)
        high = max(high, len(st.events))
    assert high <= 8                      # O(buffer), never O(events)
    other = {"metrics": {"counters": {"x": 1}}}
    assert st.close(other_data=other) == 100
    assert st.close() == 100              # idempotent no-op
    assert jsonl_to_chrome(p) == plain.to_chrome(other)
    rep = audit_file(p)
    assert rep.ok, rep.violations


def test_streaming_tracer_guards(tmp_path):
    with pytest.raises(ValueError, match="buffer"):
        StreamingTracer(tmp_path / "x.jsonl", buffer=0)
    p = tmp_path / "t.jsonl"
    with StreamingTracer(p, buffer=4) as st:
        st.instant("a", "t", 0.0)
        with pytest.raises(ValueError, match="jsonl_to_chrome"):
            st.export(tmp_path / "elsewhere.json")
    # context exit closed the file; export() on own path stays a no-op
    assert st.export() == 1
    assert len(jsonl_to_chrome(p)["traceEvents"]) == 3  # 2 meta + 1 event


def test_streaming_trace_audits_like_the_inmemory_export(
        zoo_models, tmp_path):
    """A tiered engine traced through the bounded streaming writer
    yields the same auditable doc as the in-memory tracer (the
    simulated clock makes both runs identical)."""
    cfg, splits, shared, params, payloads = zoo_models
    p = tmp_path / "tiered.jsonl"
    eng = _tiered(splits, params, tracer=StreamingTracer(p, buffer=16))
    for ev in _episode():
        eng.submit("s0", ev, payloads[ev.modality])
    stats = {"transport": eng.fabric.stats()}
    n_stream = eng.tracer.export(other_data=stats)

    ref = _tiered(splits, params, tracer=Tracer())
    for ev in _episode():
        ref.submit("s0", ev, payloads[ev.modality])
    assert n_stream == len(ref.tracer.events) > 16   # ring really spilled
    assert jsonl_to_chrome(p) == ref.tracer.to_chrome(stats)
    rep = audit_file(p)
    assert rep.ok, rep.violations


# ======================================================================
# regression tests: transport/metrics seams (PR 10 bugfix sweep)
# ======================================================================

def test_cancel_survives_history_pruning():
    """A flight whose t_deliver is still in the future must stay
    cancellable no matter how many receipts scroll past it.

    Regression: ``send()`` used to prune ``_flights`` down to the
    flights still present in the last ``max_history`` deliveries, so a
    long-queued live flight silently vanished from the cancel index
    under fleet-scale load and ``cancel()`` returned False.
    """
    from repro.core import BandwidthTrace
    tr = BandwidthTrace.static(1e6)                 # 1 MB/s
    ch = TransportChannel(tr, latency_s=0.0, overhead_bytes=0,
                          max_history=4)
    big = ch.send(int(1e7), 0.0)                    # 10 s on the wire
    assert big.t_deliver >= 10.0
    for i in range(100):                            # >> 4*max_history
        ch.send(100, 0.001 * (i + 1))
    # the big flight is still in the air at t=5 -> must cancel cleanly
    assert ch.cancel(big.flight, 5.0) is True
    assert big.cancelled
    # settled flights DO get pruned once the clock passes them: the
    # index stays bounded after everything has delivered
    for i in range(100):
        ch.send(100, 20.0 + 0.001 * i)
    assert len(ch._flights) <= 4 * ch.max_history + 1


def test_prometheus_collision_disambiguated():
    """Distinct registry keys that sanitize to one Prometheus name
    (``cache.hits`` vs ``cache_hits``) must export under distinct
    names with exactly one ``# TYPE`` line each (duplicate TYPE lines
    are invalid exposition and scrapers reject the whole page)."""
    m = Metrics()
    m.inc("cache.hits", 3)
    m.inc("cache_hits", 5)
    m.set_gauge("cache.hits", 7)                    # cross-kind collision
    text = m.to_prometheus()
    type_lines = [l for l in text.splitlines() if l.startswith("# TYPE")]
    names = [l.split()[2] for l in type_lines]
    assert len(names) == len(set(names)) == 3
    assert "emsserve_cache_hits 3.0" in text
    assert "emsserve_cache_hits_2 5.0" in text
    assert "emsserve_cache_hits_3 7.0" in text
    # deterministic: same registry exports byte-identically
    assert m.to_prometheus() == text


def test_sketch_boundary_value_keeps_error_bound():
    """A value sitting exactly on a bucket boundary (v == gamma^i) must
    keep the advertised |q̂ - q| <= rel_err*q bound.

    Regression: float slop in ``log(v)/log_gamma`` pushed the ratio
    just above the integer i, ``ceil`` landed the value in bucket i+1,
    and the reported midpoint overshot the bound by one ulp-cascade.
    gamma^16 at rel_err=0.01 is such a value on this float stack.
    """
    s = QuantileSketch(rel_err=0.01)
    v = s._gamma ** 16
    s.add(v)
    s.add(10.0 * v)                   # keep min/max clamp from saving us
    got = s.quantile(0.0)
    assert abs(got - v) <= s.rel_err * v
    # structural pin: the boundary value sits in bucket i, not i+1
    assert s._buckets.get(16) == 1


# ================================================ Tracer.scope, flush phases

def _ticks(start=1.0, step=0.001):
    """A fake clock: every reading is ``step`` later than the last."""
    return itertools.count(start, step).__next__


def test_scope_records_one_span_with_its_args(tmp_path):
    tr = Tracer(clock=_ticks())
    with tr.scope("work", "c", track="t", a=1) as sc:
        sc.set(b=2)
    with tr.scope("late", "c", at=0.5):
        pass
    (work, late) = tr.events
    assert (work.name, work.cat, work.track) == ("work", "c", "t")
    assert (work.ts, work.dur) == pytest.approx((1.0, 0.001))
    assert work.args == {"a": 1, "b": 2}
    assert late.ts == 0.5 and late.dur == pytest.approx(1.002 - 0.5)
    # the streaming tracer inherits it and spills what it records
    st = StreamingTracer(tmp_path / "s.jsonl", buffer=1, clock=_ticks())
    with st.scope("work", "c"):
        pass
    assert st.events_written == 1
    st.close()


def test_scope_of_the_disabled_tracer_is_one_shared_noop():
    a = Tracer.disabled.scope("x", "c", flush_id=0)
    with a as sc:
        sc.set(calls=3)
    assert a is Tracer.disabled.scope("y", "c")
    assert Tracer.disabled.events == []


PHASES = ("flush.prep", "flush.encode", "flush.scatter", "flush.tail",
          "flush.sync", "flush.emit")


def _two_session_flush(zoo_models, *, ragged, tracer, tail_outs=None,
                       host=False):
    """s0 sends text and vitals, s1 text; one flush takes all three.
    Where ``tail_outs`` is a list, every tail call's whole output is
    appended to it. ``host`` sends the payloads as numpy arrays, as a
    deployment's network front end hands them over."""
    cfg, splits, shared, params, payloads = zoo_models
    if host:
        payloads = {m: np.asarray(x) for m, x in payloads.items()}
    if tail_outs is not None:
        def spy(tail):
            def call(p, feats):
                out = tail(p, feats)
                tail_outs.append(out)
                return out
            return call
        splits = {k: dataclasses.replace(sm, tail=spy(sm.tail))
                  for k, sm in splits.items()}
    eng = build_engine(splits, params, "batch+stream", share_encoders=True,
                       ragged=ragged, deadline_s=None, max_history=None,
                       tracer=tracer, time_fn=_ticks())
    eng.submit("s0", Event(0, "text", 0.0), payloads["text"])
    eng.submit("s0", Event(1, "vitals", 0.0), payloads["vitals"])
    eng.submit("s1", Event(0, "text", 0.0), payloads["text"])
    return eng, eng.drain()


@pytest.mark.parametrize("ragged", [False, True])
def test_flush_phases_are_disjoint_inside_the_flush_span(zoo_models,
                                                          ragged):
    eng, rep = _two_session_flush(zoo_models, ragged=ragged,
                                  tracer=Tracer())
    evs = eng.tracer.events
    (flush,) = [e for e in evs if e.name == "flush"]
    phases = sorted((e for e in evs if e.name in PHASES),
                    key=lambda e: e.ts)
    assert {e.name for e in phases} == set(PHASES)
    assert all(e.cat == "flush" and e.track == "engine"
               and e.args["flush_id"] == rep.flush_id == 0
               for e in phases)
    t0, t1 = flush.ts, flush.ts + flush.dur
    assert flush.dur == pytest.approx(rep.wall_s)
    for a, b in zip(phases, phases[1:]):
        assert a.ts + a.dur <= b.ts
    *inside, emit = phases
    assert emit.name == "flush.emit" and emit.ts == t1
    assert all(t0 <= e.ts and e.ts + e.dur <= t1 for e in inside)
    waits = [e for e in evs if e.name == "queue.wait"]
    assert len(waits) == 3
    assert all(e.args["flush_id"] == rep.flush_id for e in waits)


@pytest.mark.parametrize("host", [False, True], ids=["device", "host"])
@pytest.mark.parametrize("ragged", [False, True])
def test_flush_phase_calls_match_a_hand_count(zoo_models, ragged, host):
    eng, rep = _two_session_flush(zoo_models, ragged=ragged,
                                  tracer=Tracer(), host=host)
    leaves = len(jax.tree_util.tree_leaves(rep.predictions[0].outputs))
    calls = {}
    prep = [e for e in eng.tracer.events if e.name == "flush.prep"]
    for e in eng.tracer.events:
        if e.name in PHASES:
            calls[e.name] = calls.get(e.name, 0) + e.args["calls"]
    # scatter: a feature row per text (2) and vitals (1) input, then
    # each tail call's output leaves fetched once, not a slice per row
    if ragged:
        # one pack per text and vitals chunk, then ONE grouped tail
        # over both sessions: a stack per modality of the full model
        want = {"flush.prep": 2 + 3, "flush.encode": 2, "flush.tail": 1,
                "flush.scatter": 3 + rep.n_tail_calls * leaves}
        built_on_host = 0
    elif host:
        # fits and encoder batches are numpy work; only the stacks of
        # device feature rows issue a program: the text+vitals tail (2)
        # and the text tail (1)
        want = {"flush.prep": 2 + 1, "flush.encode": 2, "flush.tail": 2,
                "flush.scatter": 3 + rep.n_tail_calls * leaves}
        built_on_host = 2
    else:
        # 3 bucketer pads, a stack per encoder chunk (text, vitals), then
        # the text+vitals tail (2 stacks) and the text tail (1 stack)
        want = {"flush.prep": 3 + 2 + 2 + 1, "flush.encode": 2,
                "flush.tail": 2,
                "flush.scatter": 3 + rep.n_tail_calls * leaves}
        built_on_host = 0
    assert calls == dict(want, **{"flush.sync": 0, "flush.emit": 0})
    assert calls["flush.encode"] == rep.n_encoder_calls
    assert calls["flush.tail"] == rep.n_tail_calls
    assert sum(e.args["host"] for e in prep) == built_on_host


@pytest.mark.parametrize("ragged", [False, True])
def test_host_payload_flush_equals_device_payload_flush(zoo_models, ragged):
    """The same flush over numpy payloads (batches built on the host) and
    over device payloads (pads and stacks on the device) runs the same
    programs on the same values: bit-identical predictions. The batch
    counters split the batches by where they were built."""
    dev_eng, dev = _two_session_flush(zoo_models, ragged=ragged,
                                      tracer=None)
    host_eng, host = _two_session_flush(zoo_models, ragged=ragged,
                                        tracer=None, host=True)
    assert [(p.sid, p.model, p.step) for p in host.predictions] == \
        [(p.sid, p.model, p.step) for p in dev.predictions]
    for a, b in zip(host.predictions, dev.predictions):
        assert set(a.outputs) == set(b.outputs)
        for k in a.outputs:
            np.testing.assert_array_equal(a.outputs[k], b.outputs[k])
    # bucketed: text and vitals encoder batches, then 3 tail stacks;
    # ragged: packs instead of encoder batches, then 3 grouped-tail stacks
    enc, tails = (0, 3) if ragged else (2, 3)
    counts = lambda eng: (eng.metrics.get("engine.batches_host"),
                          eng.metrics.get("engine.batches_device"))
    assert counts(host_eng) == (enc, tails)
    assert counts(dev_eng) == (0, enc + tails)


@pytest.mark.parametrize("ragged", [False, True])
def test_flush_predictions_are_host_rows_of_the_whole_tail_output(
        zoo_models, ragged):
    outs = []
    eng, rep = _two_session_flush(zoo_models, ragged=ragged, tracer=None,
                                  tail_outs=outs)
    assert len(outs) == rep.n_tail_calls
    # (tail call, row) of s0 and s1: two per-model calls of one row each,
    # or the ONE grouped call over both rows
    where = [(0, 0), (0, 1)] if ragged else [(0, 0), (1, 0)]
    assert [p.sid for p in rep.predictions] == ["s0", "s1"]
    for p, (call, i) in zip(rep.predictions, where):
        assert rep.recommendations[p.sid] is p.outputs
        assert set(p.outputs) == set(outs[call])
        for k, v in p.outputs.items():
            whole = np.asarray(outs[call][k])
            assert type(v) is np.ndarray
            assert v.shape == (1,) + whole.shape[1:]
            np.testing.assert_allclose(v, whole[i:i + 1], rtol=0, atol=0)
    assert eng.metrics.get("engine.rows_host") == len(rep.predictions)


@pytest.mark.parametrize("ragged", [False, True])
def test_output_fetch_follows_the_sync_inside_the_flush(zoo_models, ragged):
    eng, rep = _two_session_flush(zoo_models, ragged=ragged,
                                  tracer=Tracer())
    evs = eng.tracer.events
    (flush,) = [e for e in evs if e.name == "flush"]
    (sync,) = [e for e in evs if e.name == "flush.sync"]
    (fetch,) = [e for e in evs
                if e.name == "flush.scatter" and "fetched" in e.args]
    leaves = len(jax.tree_util.tree_leaves(rep.predictions[0].outputs))
    assert fetch.args["fetched"] == fetch.args["calls"] \
        == rep.n_tail_calls * leaves
    assert sync.ts + sync.dur <= fetch.ts
    assert fetch.ts + fetch.dur <= flush.ts + flush.dur
    assert all(p.t_emit == flush.ts + flush.dur for p in rep.predictions)


@pytest.mark.parametrize("ragged", [False, True])
def test_flush_phases_change_no_output_and_cost_nothing_off(zoo_models,
                                                            ragged):
    _, on = _two_session_flush(zoo_models, ragged=ragged, tracer=Tracer())
    eng, off = _two_session_flush(zoo_models, ragged=ragged, tracer=None)
    assert eng.tracer is Tracer.disabled and Tracer.disabled.events == []
    assert [(p.sid, p.model, p.step) for p in on.predictions] == \
        [(p.sid, p.model, p.step) for p in off.predictions]
    for a, b in zip(on.predictions, off.predictions):
        for k in a.outputs:
            np.testing.assert_array_equal(a.outputs[k], b.outputs[k])


@pytest.mark.parametrize("ragged", [False, True])
def test_trace_with_flush_phases_passes_the_audit(zoo_models, ragged,
                                                  tmp_path):
    eng, _ = _two_session_flush(zoo_models, ragged=ragged, tracer=Tracer())
    p = tmp_path / "flush.json"
    eng.tracer.export(p)
    assert validate_chrome(json.loads(p.read_text())) == []
    rep = audit_file(p)
    assert rep.ok, rep.violations
    assert rep.checks["fuses"] == rep.checks["emits"] == 2

"""Fleet-scale serving: workload generation, routing, admission, region sim.

Four claims under test:

  * **seeded open-loop workloads are deterministic** — the same
    ``(rate, horizon, seed)`` yields the identical session stream event
    for event, the empirical arrival rate matches the offered rate, the
    diurnal envelope is respected exactly, and ``time_scale`` compresses
    intra-session times only (session start instants untouched);
  * **routing is stable and load-aware** — the consistent-hash ring
    moves only ~1/N of sessions when a replica is added, and the
    least-loaded spill fires exactly when the home replica's backlog
    exceeds the fleet minimum by the spill margin;
  * **admission control is a hysteresis state machine** — sheds above
    ``enter_frac * deadline``, keeps shedding until strictly below
    ``exit_frac * deadline`` (burst recovery), honors the queue cap,
    and accounts for every decision;
  * **fleet scale never buys drift or loss** — a ``RegionSim`` run, each
    replica on a device of its own, conserves sessions
    (offered == admitted + shed),
    finalizes every admitted session at bit-parity (atol 0) with a
    per-event reference engine built with the same batch bucket, and
    shed sessions emit ONLY ``degraded``-tagged partials — counted,
    never dropped.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ProfileTable, emsnet_zoo, split
from repro.fleet import (AdmissionController, AdmissionPolicy, AdmitAll,
                         ConsistentHashRouter, RegionSim, diurnal_rate,
                         diurnal_times, generate_workload, merge_sessions,
                         place_replica_params, poisson_times)
from repro.obs import StreamingTracer, audit_file
from repro.serving.api import build_engine

# the fixed batch bucket used on BOTH sides of every parity comparison:
# XLA CPU picks different kernels for different batch-row counts
# (GEMV vs GEMM), so atol-0 parity is only honest when the sim flushes
# and the per-event reference hit the same padded program shape
ENGINE_KW = dict(batch_bucket_min=2, max_coalesce=2)

GLASS_PROFILE = ProfileTable(base={"enc:text": 0.08, "enc:vitals": 0.01,
                                   "enc:scene": 0.05, "tail": 0.005,
                                   "full": 0.15})


# ------------------------------------------------------------------ fixtures

@pytest.fixture(scope="module")
def fleet_models(tiny_emsnet_cfg):
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


def _flatten(sessions):
    return [(s.sid, s.t_start, s.scenario,
             tuple((e.index, e.modality, e.arrival_time)
                   for e in s.events))
            for s in sessions]


# ------------------------------------------------------------------ workload

def test_workload_seeded_determinism():
    a = generate_workload(5.0, 3.0, seed=7)
    b = generate_workload(5.0, 3.0, seed=7)
    assert _flatten(a) == _flatten(b)
    c = generate_workload(5.0, 3.0, seed=8)
    assert _flatten(a) != _flatten(c)


def test_poisson_empirical_rate_and_bounds():
    ts = poisson_times(20.0, 200.0, seed=1)
    assert ts == sorted(ts)
    assert all(0.0 <= t < 200.0 for t in ts)
    rate = len(ts) / 200.0
    assert rate == pytest.approx(20.0, rel=0.15)


def test_poisson_validation():
    with pytest.raises(ValueError, match="rate"):
        poisson_times(0.0, 10.0)
    assert poisson_times(5.0, 0.0) == []
    assert poisson_times(5.0, -1.0) == []


def test_diurnal_rate_envelope():
    base, amp = 10.0, 0.6
    vals = [diurnal_rate(t, base, amp=amp, period=60.0)
            for t in np.linspace(0.0, 120.0, 97)]
    assert min(vals) >= base * (1 - amp) - 1e-9
    assert max(vals) <= base * (1 + amp) + 1e-9
    assert max(vals) == pytest.approx(base * (1 + amp), rel=1e-3)
    with pytest.raises(ValueError, match="amp"):
        diurnal_rate(0.0, base, amp=1.0)


def test_diurnal_times_rate_and_validation():
    # two whole periods: the sinusoid integrates out, so the mean
    # arrival rate should match the base rate
    ts = diurnal_times(20.0, 120.0, seed=2, amp=0.6, period=60.0)
    assert ts == sorted(ts)
    assert all(0.0 <= t < 120.0 for t in ts)
    assert len(ts) / 120.0 == pytest.approx(20.0, rel=0.25)
    with pytest.raises(ValueError, match="base_rate"):
        diurnal_times(-1.0, 10.0)
    with pytest.raises(ValueError, match="amp"):
        diurnal_times(10.0, 10.0, amp=1.5)
    with pytest.raises(ValueError, match="process"):
        generate_workload(1.0, 1.0, process="weekly")


def test_time_scale_compresses_sessions_only():
    w1 = generate_workload(2.0, 5.0, seed=3, time_scale=1.0)
    w2 = generate_workload(2.0, 5.0, seed=3, time_scale=0.5)
    assert len(w1) == len(w2) > 0
    for s1, s2 in zip(w1, w2):
        assert s2.t_start == s1.t_start          # arrivals untouched
        assert s2.scenario == s1.scenario
        assert len(s2.events) == len(s1.events)
        for e1, e2 in zip(s1.events, s2.events):
            assert e2.modality == e1.modality
            assert e2.arrival_time == pytest.approx(
                0.5 * e1.arrival_time, abs=1e-12)
    with pytest.raises(ValueError, match="time_scale"):
        generate_workload(1.0, 1.0, time_scale=0.0)


def test_merge_sessions_global_order():
    sessions = generate_workload(4.0, 4.0, seed=5)
    arrivals = merge_sessions(sessions)
    assert len(arrivals) == sum(len(s.events) for s in sessions)
    keys = [(t, sid) for t, sid, _ in arrivals]
    assert keys == sorted(keys)
    # absolute_events agrees with the merged view
    s = sessions[0]
    for e_rel, e_abs in zip(s.events, s.absolute_events()):
        assert e_abs.arrival_time == s.t_start + e_rel.arrival_time


# ------------------------------------------------------------------- router

def test_router_ring_stability_on_resize():
    sids = [f"s{i}" for i in range(400)]
    r4 = ConsistentHashRouter(4)
    r5 = ConsistentHashRouter(5)
    assert all(0 <= r4.home(s) < 4 for s in sids)
    # deterministic across instances with the same seed
    assert [r4.home(s) for s in sids] == \
        [ConsistentHashRouter(4).home(s) for s in sids]
    moved = sum(r4.home(s) != r5.home(s) for s in sids) / len(sids)
    # consistent hashing moves ~1/5 of keys, never a wholesale reshuffle
    assert 0.0 < moved < 0.45


def test_router_least_loaded_spill():
    r = ConsistentHashRouter(2, spill_s=0.05)
    sid = next(s for s in (f"s{i}" for i in range(100)) if r.home(s) == 0)
    assert r.route(sid) == 0                       # no loads: pure hash
    assert r.route(sid, loads=[0.0, 0.0]) == 0     # balanced: stay home
    assert r.spills == 0
    assert r.route(sid, loads=[1.0, 0.0]) == 1     # overloaded: spill
    assert r.spills == 1
    assert r.route(sid, loads=[0.04, 0.0]) == 0    # inside the margin
    assert r.spills == 1
    with pytest.raises(ValueError, match="loads"):
        r.route(sid, loads=[0.0])
    with pytest.raises(ValueError, match="n_replicas"):
        ConsistentHashRouter(0)


# ---------------------------------------------------------------- admission

def test_admission_policy_validation():
    with pytest.raises(ValueError, match="deadline_s"):
        AdmissionPolicy(deadline_s=0.0)
    with pytest.raises(ValueError, match="hysteresis"):
        AdmissionPolicy(deadline_s=1.0, enter_frac=0.5, exit_frac=0.5)
    with pytest.raises(ValueError, match="hysteresis"):
        AdmissionPolicy(deadline_s=1.0, enter_frac=1.0, exit_frac=0.0)


def test_admission_hysteresis_and_burst_recovery():
    c = AdmissionController(
        AdmissionPolicy(deadline_s=1.0, enter_frac=1.0, exit_frac=0.5), 2)
    assert c.admit(0, 0.0, 0.2)            # calm: admit
    assert not c.admit(0, 1.0, 1.5)        # burst: enter shedding
    assert not c.admit(0, 2.0, 0.7)        # inside band: KEEP shedding
    assert c.admit(0, 3.0, 0.4)            # drained below exit: recover
    assert c.transitions == [(1.0, 0, "enter"), (3.0, 0, "exit")]
    # replica 1 has independent state
    assert c.admit(1, 4.0, 0.9)
    assert c.stats() == {"admitted": 3, "shed": 2, "transitions": 2,
                         "shedding_now": 0}
    with pytest.raises(ValueError, match="n_replicas"):
        AdmissionController(AdmissionPolicy(deadline_s=1.0), 0)


def test_admission_queue_cap():
    c = AdmissionController(
        AdmissionPolicy(deadline_s=10.0, max_queue=2), 1)
    assert c.admit(0, 0.0, 0.0, queue_depth=2)       # at cap: fine
    assert not c.admit(0, 1.0, 0.0, queue_depth=3)   # over cap: shed
    assert not c.admit(0, 2.0, 0.0, queue_depth=3)   # cap holds recovery
    assert c.admit(0, 3.0, 0.0, queue_depth=0)       # drained: recover
    assert [k for _, _, k in c.transitions] == ["enter", "exit"]


def test_admit_all_never_sheds():
    c = AdmitAll()
    assert all(c.admit(0, float(i), 1e9) for i in range(5))
    assert c.stats() == {"admitted": 5, "shed": 0, "transitions": 0,
                         "shedding_now": 0}


# ---------------------------------------------------------------- placement

def test_place_replica_params_identity(fleet_models):
    _, _, shared, params, _ = fleet_models
    dev = jax.devices()[-1]
    placed = place_replica_params(params, dev)
    assert set(placed) == set(params)
    # one shared pytree in -> one committed pytree out, identity kept
    # across zoo keys (the share_encoders grouped-tail check needs it)
    assert len({id(v) for v in placed.values()}) == 1
    ref = jax.tree.leaves(shared)
    got = jax.tree.leaves(next(iter(placed.values())))
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        assert b.committed and b.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------- region sim

def test_region_sim_conservation_and_bit_parity(fleet_models):
    _, splits, _, params, payloads = fleet_models
    sessions = generate_workload(3.0, 2.0, seed=0, time_scale=0.2)
    assert len(sessions) >= 2
    sim = RegionSim(splits, params, n_replicas=2,
                    engine_kw=dict(ENGINE_KW))
    rep = sim.run(sessions, lambda sid, ev: payloads[ev.modality])

    n = len(sessions)
    assert rep["sessions_offered"] == n
    assert rep["sessions_admitted"] == n and rep["sessions_shed"] == 0
    assert rep["sessions_finalized"] == n
    assert rep["events_admitted"] == sum(len(s.events) for s in sessions)
    assert sim.makespan() >= sessions[-1].t_start
    assert len(sim.ttfp) == n and len(sim.ttfinal) == n
    assert all(sim.ttfp[s.sid] <= sim.ttfinal[s.sid] for s in sessions)

    # every admitted session's finals match a per-event reference engine
    # built with the SAME fixed batch bucket, at atol 0
    for s in sessions:
        ref = build_engine(splits, params, "batch+stream",
                           share_encoders=True, deadline_s=None,
                           **ENGINE_KW)
        preds = []
        for ev in s.events:
            ref.submit(s.sid, ev, payloads[ev.modality])
            preds.extend(ref.flush().predictions)
        want = next(p.outputs for p in reversed(preds)
                    if p.kind == "final")
        got = sim.final_outputs(s.sid)
        assert got is not None
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))

    # fleet-wide registry is the exact union of sim + replica counters
    mx = sim.fleet_metrics()
    assert mx.get("fleet.sessions_offered") == n
    assert mx.get("fleet.flushes") == len(sim.flush_log)
    prom = mx.to_prometheus()
    assert "# TYPE emsserve_fleet_sessions_offered counter" in prom
    assert "# TYPE emsserve_fleet_ttfp_s summary" in prom
    assert f"emsserve_fleet_ttfp_s_count {n}" in prom


def test_region_sim_replica_devices(fleet_models):
    """Replica ``r`` holds its own committed copy of the params on
    ``jax.devices()[r % n_devices]`` (one pytree per replica, shared
    across its zoo keys), and 3 replicas give the finals of 1."""
    _, splits, _, params, payloads = fleet_models
    sessions = generate_workload(3.0, 2.0, seed=0, time_scale=0.2)
    devs = jax.devices()
    sim = RegionSim(splits, params, n_replicas=3,
                    engine_kw=dict(ENGINE_KW))
    assert sim.devices == [devs[r % len(devs)] for r in range(3)]
    per_replica = [e.params for e in sim.replicas]
    for p, dev in zip(per_replica, sim.devices):
        assert len({id(v) for v in p.values()}) == 1
        for leaf in jax.tree.leaves(next(iter(p.values()))):
            assert leaf.committed and leaf.devices() == {dev}
    assert len({id(p["text"]) for p in per_replica}) == 3
    one = RegionSim(splits, params, n_replicas=1,
                    engine_kw=dict(ENGINE_KW))
    payload_fn = lambda sid, ev: payloads[ev.modality]  # noqa: E731
    sim.run(sessions, payload_fn)
    one.run(sessions, payload_fn)
    for s in sessions:
        got, want = sim.final_outputs(s.sid), one.final_outputs(s.sid)
        assert got is not None and set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))


def test_region_sim_shed_sessions_degrade_only(fleet_models, tmp_path):
    _, splits, _, params, payloads = fleet_models
    sessions = generate_workload(3.0, 2.0, seed=1, time_scale=0.2)
    # deadline far below the svc prior: every session sheds to glass
    ctrl = AdmissionController(
        AdmissionPolicy(deadline_s=1e-4, enter_frac=1.0, exit_frac=0.5), 2)
    path = tmp_path / "fleet.jsonl"
    tracer = StreamingTracer(path, buffer=16)
    sim = RegionSim(splits, params, n_replicas=2, admission=ctrl,
                    profile=GLASS_PROFILE, tracer=tracer,
                    engine_kw=dict(ENGINE_KW))
    rep = sim.run(sessions, lambda sid, ev: payloads[ev.modality])

    n = len(sessions)
    assert rep["sessions_offered"] == n
    assert rep["sessions_admitted"] + rep["sessions_shed"] == n
    assert rep["sessions_shed"] == n == ctrl.shed
    # shed sessions: ONLY tagged partials, counted, never finalized
    assert len(sim.glass.records) == sum(len(s.events) for s in sessions)
    assert all(r.kind == "partial" and r.degraded
               for r in sim.glass.records)
    assert rep["degraded_partials"] == sum(
        1 for r in sim.glass.records if r.outputs is not None) > 0
    assert all(sim.final_outputs(s.sid) is None for s in sessions)
    assert sim.metrics.get("fleet.degraded_events") == \
        len(sim.glass.records)
    # degraded sessions still get a time-to-first-prediction
    assert set(sim.glass.ttfp) == {s.sid for s in sessions}

    # the streamed trace is auditable offline
    tracer.close(other_data={"metrics": sim.fleet_metrics().snapshot()})
    report = audit_file(path)
    assert report.ok, report.violations

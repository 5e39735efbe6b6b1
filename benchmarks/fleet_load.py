"""Fleet-scale open-loop load: latency knee, replica scaling, shed A/B.

Everything before this benchmark measured a handful of closed-loop
sessions on one host. Here the region simulator (``repro.fleet``)
drives N engine replicas — built from ONE ``build_engine`` spec, each
on a device of its own — with an open-loop Poisson session-arrival
process: arrivals do not wait for the system, so when the offered rate
exceeds fleet capacity the backlog (and every new session's
time-to-first-prediction) grows without bound. Three measurements:

  * **Load curve / knee** — offered rate swept as a fraction of the
    calibrated per-replica capacity; p50/p95/p99 TTFP per point. The
    *knee* is the highest offered rate whose p99 stays within
    ``KNEE_FACTOR`` x the lowest-rate p99 — beyond it open-loop
    queueing takes off.
  * **Replica scaling** — weak scaling: offered rate proportional to
    replica count (1/2/4/8) with total sessions held constant;
    sessions/s = finalized / makespan. Engine replicas are simulated
    serially on this host, each flush costing its own measured wall
    seconds, so the scaling read is per-replica-has-its-own-device.
  * **Shed-vs-queue A/B at 2x knee** — the same overload twice: once
    admitting everything (queue-to-death baseline), once with the
    deadline admission controller shedding new sessions to the
    on-glass degraded path. Shedding holds the ADMITTED p99 near the
    at-knee service level; without it the p99 blows past the knee.

Bit-parity is spot-checked every run: finalized fleet sessions must
match a per-event reference engine (same spec, same parameter
pytree, same fixed batch bucket, one flush per event) at atol 0 —
fleet scale never buys drift. The fixed bucket (``ENGINE_KW``) is what
makes atol 0 honest: it pins every XLA call to one program shape, the
standard batch-invariance discipline.

Acceptance (checked by ``--smoke``):
  * ``passed_fleet_knee`` — with shedding at 2x knee, admitted-session
    p99 TTFP <= 1.5x the at-knee p99, while the admit-all baseline
    exceeds that bound;
  * ``passed_fleet_scaling`` — sessions/s grows >= 1.6x from 1 to 4
    replicas;
  * ``passed_fleet_parity`` — the atol-0 reference check above;
  * conservation — offered == admitted + shed, and degraded sessions
    emit ONLY ``degraded``-tagged partials.

-> artifacts/BENCH_fleet.json
"""
from __future__ import annotations

import os

# must precede any jax import: emulate a multi-device host so each
# fleet replica has a device of its own (CI overrides with its own
# XLA_FLAGS; a pre-set value is respected)
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import json                                    # noqa: E402
from pathlib import Path                       # noqa: E402

import numpy as np                             # noqa: E402

from . import common as C                      # noqa: E402

ART = Path(__file__).resolve().parent / "artifacts"

KNEE_FACTOR = 3.0          # p99 blowup that defines "past the knee"
AB_BOUND = 1.5             # shed admitted p99 must stay <= this x knee p99
EVENTS_PER_SESSION = 6     # 1 text + 3 vitals + 2 scene (workload default)
TIME_SCALE = 0.01          # intra-session time compression: real incidents
                           # unfold over ~10 s (LAG_SCENARIOS onsets), which
                           # would make every makespan session-duration-bound;
                           # compressed, the fleet is serving-limited and the
                           # knee/scaling reads measure the engines

# Fixed batch bucket: every flush pads each modality group to exactly 8
# rows and coalesces at most 8 events, so EVERY XLA call in the sweep
# runs the one identical program shape. That is what makes the atol-0
# parity gate achievable at all: row-1 (GEMV) and row-N (GEMM) kernels
# legitimately differ at ~1e-6 on CPU, so a variably-shaped fleet could
# never bit-match a per-event reference. The padding FLOPs this buys
# parity with are real and show up in the capacity calibration — the
# benchmark measures the determinism-configured engine, not a free lunch.
ENGINE_KW = dict(batch_bucket_min=8, max_coalesce=8)


def _build(quick, seed=0):
    import jax
    from repro.configs.emsnet import tiny
    from repro.core import emsnet_zoo, split

    # Always the tiny config, even in full mode: this benchmark reads
    # SERVING dynamics (queueing, coalescing, admission), where the
    # model only sets the service-time unit. The quick config's text
    # encoder costs ~0.3 s per padded flush on a 1/8th-host device,
    # which would price a single load-curve point at minutes; tiny
    # buys ~10x more offered sessions per wall second at identical
    # queueing behavior.
    cfg = tiny()
    zoo = emsnet_zoo(cfg)
    splits = {k: split(m) for k, m in zoo.items()}
    shared = zoo["text+vitals+scene"].init_fn(jax.random.PRNGKey(seed))
    params = {k: shared for k in zoo}
    payloads = C.sample_payloads(cfg, seed=seed + 1)
    payloads["vitals"] = payloads["vitals"][:, :5]
    C.warmup_engine_models(splits, params, payloads)
    placement = {"devices": len(jax.devices()),
                 "param_bytes": int(sum(x.nbytes
                                        for x in jax.tree.leaves(shared)))}
    return cfg, splits, params, payloads, placement


def _profile():
    from repro.core import ProfileTable
    return ProfileTable(base={"enc:text": 0.08, "enc:vitals": 0.01,
                              "enc:scene": 0.05, "tail": 0.02, "full": 0.16})


def _simulate(splits, params, payloads, *, rate, n_sessions, n_replicas,
              admission=None, seed=0, profile=None):
    """One open-loop run at ``rate`` sessions/s, horizon sized so the
    offered-session count stays ~constant across rates (the wall cost
    of a point is bounded by its event count, not its rate).

    ``admission`` may be a zero-arg factory — stateful controllers must
    be rebuilt per run (warm + measured passes share one call site)."""
    from repro.fleet import RegionSim, generate_workload
    horizon = n_sessions / rate
    sessions = generate_workload(rate, horizon, seed=seed,
                                 time_scale=TIME_SCALE)
    if callable(admission):
        admission = admission()
    sim = RegionSim(splits, params, n_replicas=n_replicas,
                    admission=admission, profile=profile,
                    engine_kw=dict(ENGINE_KW))
    sim.run(sessions, lambda sid, ev: payloads[ev.modality])
    return sim, sessions


def _point(splits, params, payloads, **kw):
    """Warm-then-measure: the first pass compiles every bucketed batch
    shape this exact workload hits (pow2 row padding means intermediate
    coalesce sizes are distinct XLA programs — a compile landing inside
    a measured flush would poison that point's p99); the second pass
    replays the byte-identical workload on warm engines and is the one
    reported."""
    _simulate(splits, params, payloads, **kw)
    return _simulate(splits, params, payloads, **kw)


def _ttfp_stats(sim):
    xs = np.asarray(sorted(sim.ttfp.values()), float)
    if xs.size == 0:
        return {"n": 0}
    return {"n": int(xs.size),
            "p50_s": float(np.percentile(xs, 50)),
            "p95_s": float(np.percentile(xs, 95)),
            "p99_s": float(np.percentile(xs, 99))}


def _measure_mu(splits, params, payloads, *, rate, n_sessions, seed):
    sim, _ = _simulate(splits, params, payloads, rate=rate,
                       n_sessions=n_sessions, n_replicas=1, seed=seed)
    busy = sum(done - start for _, start, done, _ in sim.flush_log)
    events = sum(n for _, _, _, n in sim.flush_log)
    return (events / busy if busy > 0 else 1.0), sim._svc_est


def _calibrate(splits, params, payloads, *, n_sessions, seed):
    """Per-replica capacity in sessions/s, measured twice on one
    replica as admitted events over summed flush wall seconds:

    * **light** — arrivals spaced out, flushes mostly single-event, so
      the per-event cost carries the full per-flush overhead. A
      conservative capacity: offered load below it is stable no matter
      how the batches fall. The scaling sweep runs here.
    * **saturated** — the whole workload arrives as a burst, backlog
      forces maximal coalescing, per-event cost amortizes to its floor.
      The true sustainable ceiling: offered load above it grows the
      backlog regardless of batching. The knee sweep is calibrated
      against THIS rate — coalescing is self-balancing (more backlog ->
      bigger batches -> higher throughput), so only rates above the
      saturated ceiling queue to death."""
    mu_light, svc_light = _measure_mu(splits, params, payloads, rate=4.0,
                                      n_sessions=n_sessions, seed=seed)
    mu_sat, _ = _measure_mu(splits, params, payloads, rate=200.0,
                            n_sessions=n_sessions, seed=seed)
    return {"service_rate_light_events_per_s": mu_light,
            "service_rate_saturated_events_per_s": mu_sat,
            "svc_est_s": svc_light,
            "capacity_light_sessions_per_s": mu_light / EVENTS_PER_SESSION,
            "capacity_saturated_sessions_per_s": mu_sat / EVENTS_PER_SESSION}


def _parity_check(sim, sessions, splits, params, payloads, *, limit=4):
    """Finalized fleet sessions vs a per-event reference engine — same
    spec and the same FIXED batch bucket (``ENGINE_KW``), driven one
    flush per event — at atol 0. Equal bucket on both sides is load-
    bearing: it pins both to the identical padded program shape, so a
    session's row cannot depend on what else coalesced around it.
    Returns the number of sessions checked (raises on mismatch)."""
    from repro.serving.api import build_engine
    checked = 0
    for s in sessions:
        if checked >= limit:
            break
        got = sim.final_outputs(s.sid)
        if got is None:
            continue
        ref = build_engine(splits, params, "batch+stream",
                           share_encoders=True, deadline_s=None,
                           **ENGINE_KW)
        preds = []
        for ev in s.events:
            ref.submit(s.sid, ev, payloads[ev.modality])
            preds += ref.flush().predictions
        want = next(p.outputs for p in reversed(preds)
                    if p.kind == "final")
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))
        checked += 1
    return checked


def run(quick=True, *, smoke=False, seed=0):
    from repro.fleet import AdmissionController, AdmissionPolicy

    cfg, splits, params, payloads, placement = _build(quick or smoke,
                                                      seed=seed)
    # offered sessions per sweep point: the overload points must offer
    # enough work for the backlog to integrate well past the light-load
    # p99 (excess-drain ~ n_point x events x (1/mu_sat - 1/offered)),
    # or the curve never shows a knee at any rate
    n_point = 40 if smoke else 64

    # ---- warmup passes: compile every engine path the sims will hit,
    # spaced arrivals (single-event flushes) AND a burst (coalesced
    # bucketed batch shapes)
    _simulate(splits, params, payloads, rate=2.0, n_sessions=6,
              n_replicas=1, seed=seed)
    _simulate(splits, params, payloads, rate=200.0, n_sessions=n_point,
              n_replicas=1, seed=seed)

    # ---- capacity calibration ---------------------------------------
    cal = _calibrate(splits, params, payloads, n_sessions=n_point,
                     seed=seed)
    cap = cal["capacity_saturated_sessions_per_s"]

    # ---- load curve over 2 replicas: offered = frac x the saturated
    # fleet capacity
    # fracs are of the SATURATED ceiling; the low end must sit well
    # below the LIGHT (single-event-flush) capacity too, or the whole
    # curve is queue-dominated and flat — with the fixed 8-row bucket
    # light capacity is roughly a third of saturated
    n_curve = 2
    fracs = ((0.15, 0.5, 1.0, 2.0) if smoke
             else (0.15, 0.3, 0.6, 1.0, 1.5, 2.0))
    curve = []
    for frac in fracs:
        rate = frac * cap * n_curve
        # above-capacity points offer proportionally MORE sessions:
        # open-loop blowup is backlog integrated over the arrival
        # window, so a constant session count would cap the excess
        # drain at one horizon and flatten the knee away; the extra
        # sessions are nearly free there (max coalescing)
        n_sess = int(round(n_point * max(1.0, frac)))
        sim, _ = _point(splits, params, payloads, rate=rate,
                        n_sessions=n_sess, n_replicas=n_curve,
                        seed=seed + 1)
        st = _ttfp_stats(sim)
        rep = sim.report()
        curve.append({"offered_x_capacity": frac,
                      "offered_sessions_per_s": rate,
                      "sessions": rep["sessions_offered"],
                      "residual_drain_s": (rep["makespan_s"]
                                           - sim._last_arrival),
                      "ttfp": st})
    base_p99 = min(c["ttfp"]["p99_s"] for c in curve if c["ttfp"]["n"])
    knee = None
    for c in curve:
        if c["ttfp"].get("p99_s", np.inf) <= KNEE_FACTOR * base_p99:
            knee = c
    knee_rate = knee["offered_sessions_per_s"]
    knee_p99 = knee["ttfp"]["p99_s"]

    # ---- shed-vs-queue A/B at 2x knee -------------------------------
    over_rate = 2.0 * knee_rate
    n_over = 2 * n_point       # sustained overload: see the curve note
    sim_q, _ = _point(splits, params, payloads, rate=over_rate,
                      n_sessions=n_over, n_replicas=n_curve,
                      seed=seed + 2)
    q_stats = _ttfp_stats(sim_q)

    deadline = knee_p99
    ctrl = lambda: AdmissionController(  # noqa: E731 - rebuilt per pass
        AdmissionPolicy(deadline_s=deadline, enter_frac=1.0, exit_frac=0.5),
        n_curve)
    sim_s, sess_s = _point(splits, params, payloads, rate=over_rate,
                           n_sessions=n_over, n_replicas=n_curve,
                           admission=ctrl, seed=seed + 2,
                           profile=_profile())
    s_stats = _ttfp_stats(sim_s)
    s_report = sim_s.report()
    # conservation + degraded-only-partials invariants
    assert (s_report["sessions_offered"]
            == s_report["sessions_admitted"] + s_report["sessions_shed"])
    assert all(r.kind == "partial" and r.degraded
               for r in sim_s.glass.records)
    shed_ok = (s_stats.get("p99_s", np.inf) <= AB_BOUND * knee_p99)
    queue_blows = (q_stats.get("p99_s", 0.0) > AB_BOUND * knee_p99)
    passed_knee = bool(shed_ok and queue_blows)

    # ---- parity spot-check (atol 0) ---------------------------------
    parity_n = _parity_check(sim_s, sess_s, splits, params, payloads)

    # ---- weak scaling: offered ~ replicas, constant total sessions --
    replica_counts = (1, 2, 4) if smoke else (1, 2, 4, 8)
    scale_frac = 0.7
    cap_light = cal["capacity_light_sessions_per_s"]
    scaling = []
    for r in replica_counts:
        # weak scaling: offered ~ replicas, below the CONSERVATIVE
        # (light-load) per-replica capacity so every config is stable
        # and the read is arrival-limited throughput
        rate = scale_frac * cap_light * r
        sim, _ = _point(splits, params, payloads, rate=rate,
                        n_sessions=2 * n_point,  # constant total work
                        n_replicas=r, seed=seed + 3)
        rep = sim.report()
        sps = (rep["sessions_finalized"] / rep["makespan_s"]
               if rep["makespan_s"] > 0 else 0.0)
        scaling.append({"replicas": r,
                        "offered_sessions_per_s": rate,
                        "sessions_finalized": rep["sessions_finalized"],
                        "makespan_s": rep["makespan_s"],
                        "sessions_per_s": sps})
    by_r = {s["replicas"]: s["sessions_per_s"] for s in scaling}
    ratio_1_4 = by_r[4] / by_r[1] if by_r.get(1) else 0.0
    passed_scaling = bool(ratio_1_4 >= 1.6)

    result = {
        "config": {"quick": bool(quick or smoke), "smoke": bool(smoke),
                   "sessions_per_point": n_point, "seed": seed,
                   "events_per_session": EVENTS_PER_SESSION,
                   "knee_factor": KNEE_FACTOR, "ab_bound": AB_BOUND},
        "placement": placement,
        "calibration": cal,
        "load_curve": curve,
        "knee": {"offered_sessions_per_s": knee_rate,
                 "offered_x_capacity": knee["offered_x_capacity"],
                 "p99_ttfp_s": knee_p99},
        "ab_at_2x_knee": {
            "offered_sessions_per_s": over_rate,
            "deadline_s": deadline,
            "admit_all": {"ttfp": q_stats,
                          "report": sim_q.report()},
            "shed": {"ttfp_admitted": s_stats,
                     "report": s_report},
        },
        "scaling": scaling,
        "scaling_ratio_1_to_4": ratio_1_4,
        "parity_checked_sessions": parity_n,
        "passed_fleet_knee": passed_knee,
        "passed_fleet_scaling": passed_scaling,
        "passed_fleet_parity": bool(parity_n > 0),
        "fleet_metrics": sim_s.fleet_metrics().snapshot(),
    }

    ART.mkdir(parents=True, exist_ok=True)
    name = "BENCH_fleet.smoke.json" if smoke else "BENCH_fleet.json"
    (ART / name).write_text(json.dumps(result, indent=2))

    C.csv_row("fleet_knee_p99_ttfp", knee_p99 * 1e6,
              f"knee_rate={knee_rate:.2f}/s;"
              f"shed_p99={s_stats.get('p99_s', float('nan')):.3f}s;"
              f"queue_p99={q_stats.get('p99_s', float('nan')):.3f}s")
    C.csv_row("fleet_sessions_per_s_4r", by_r[4] * 1e6,
              f"ratio_1_to_4={ratio_1_4:.2f}x;"
              f"shed_sessions={s_report['sessions_shed']}")

    if smoke:
        if not passed_knee:
            raise SystemExit(
                "fleet knee gate failed: shed p99 "
                f"{s_stats.get('p99_s')} vs bound {AB_BOUND * knee_p99:.3f} "
                f"(queue p99 {q_stats.get('p99_s')})")
        if not passed_scaling:
            raise SystemExit(
                f"fleet scaling gate failed: 1->4 replicas gives "
                f"{ratio_1_4:.2f}x sessions/s (need >= 1.6x)")
    return result


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="small run + assert knee/scaling/parity gates")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    r = run(quick=not args.full, smoke=args.smoke)
    print(json.dumps({k: r[k] for k in
                      ("knee", "scaling_ratio_1_to_4",
                       "passed_fleet_knee", "passed_fleet_scaling",
                       "passed_fleet_parity")}, indent=2))

"""EMSServe serving launcher.

Default mode runs Table-6 episodes through the per-event reference
engine (``core.engine.EMSServe``) with adaptive offloading, feature
caching, and (optionally) an edge crash, printing the per-event trace.

``--engine SPEC`` serves ``--sessions N`` concurrent sessions through
the unified session engine (``serving.api.build_engine``), where SPEC
is a '+'-joined subset of ``batch`` / ``stream`` / ``tiered`` —
composable, not mutually exclusive:

  PYTHONPATH=src python -m repro.launch.serve --episode 1 --mobility
  PYTHONPATH=src python -m repro.launch.serve --episode 2 --no-cache
  PYTHONPATH=src python -m repro.launch.serve --engine batch --sessions 8
  PYTHONPATH=src python -m repro.launch.serve --engine batch+stream \
      --sessions 4 --scenario mix
  PYTHONPATH=src python -m repro.launch.serve --engine stream \
      --sessions 4 --wall-clock --deadline-ms 50 --speed 10
  PYTHONPATH=src python -m repro.launch.serve --engine tiered \
      --sessions 4 --mobility
  PYTHONPATH=src python -m repro.launch.serve --engine stream+tiered \
      --sessions 2 --outage-at 4

``batch`` coalesces cross-session work into shape-bucketed batched XLA
calls; ``stream`` adds progressive partial->final predictions,
deadlines, and eviction; ``tiered`` hosts the split pieces on
glass/edge simulated-clock tiers (live offload decisions, byte-
accounted transport, ``--outage-at`` edge-crash failover).
``stream+tiered`` additionally serves on-glass provisional partials
while the edge computes each offloaded refresh. ``--wall-clock`` pumps
deadline flushes from a monotonic clock
(``serving.event_loop.WallClockDriver``); ``--speed`` fast-forwards.

``--fleet RATE --replicas N`` runs the region simulator instead
(``repro.fleet``): N engine replicas from one spec, each with its
parameters on a device of its own where there are enough, open-loop
Poisson incident arrivals at RATE sessions/s, consistent-hash routing,
deadline admission control with on-glass shedding. ``--metrics-out``
writes Prometheus text; ``--trace x.jsonl`` streams a bounded-memory
audit trace:

  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      PYTHONPATH=src python -m repro.launch.serve --fleet 4 \
      --replicas 2 --sessions 12 --trace fleet.jsonl --metrics-out m.prom

The pre-unification flags ``--batched/--stream/--tiered N`` still work
as deprecation shims that map onto the equivalent ``--engine`` spec.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def build_models(cfg):
    from repro.core import emsnet_module, split
    mods = {
        "m1": emsnet_module(cfg, ("text",)),
        "m2": emsnet_module(cfg, ("text", "vitals")),
        "m3": emsnet_module(cfg, ("text", "vitals", "scene")),
    }
    splits = {k: split(m) for k, m in mods.items()}
    key = jax.random.PRNGKey(0)
    params = {k: m.init_fn(jax.random.fold_in(key, i))
              for i, (k, m) in enumerate(mods.items())}
    return splits, params


def sample_payloads(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "text": jnp.asarray(rng.integers(1, cfg.vocab_size,
                                         (1, cfg.max_text_len)), jnp.int32),
        "vitals": jnp.asarray(rng.normal(size=(1, cfg.vitals_len,
                                               cfg.n_vitals)), jnp.float32),
        "scene": jnp.asarray(rng.integers(0, 2, (1, cfg.scene_dim)),
                             jnp.float32),
    }


def build_zoo(cfg, seed=0):
    """Subset-model zoo over ONE shared parameter pytree (streaming /
    tiered specs)."""
    from repro.core import emsnet_zoo, split
    zoo = emsnet_zoo(cfg)
    splits = {k: split(m) for k, m in zoo.items()}
    shared = zoo["text+vitals+scene"].init_fn(jax.random.PRNGKey(seed))
    return splits, {k: shared for k in zoo}


def scenario_episodes(n_sessions, scenario, *, n_vitals=4, n_scene=2):
    from repro.core import async_episode
    names = (["text_first", "vitals_first", "scene_late"]
             if scenario == "mix" else [scenario])
    return {f"s{i}": async_episode(names[i % len(names)], seed=i,
                                   n_vitals=n_vitals, n_scene=n_scene)
            for i in range(n_sessions)}


def _mobility_trace(mobility: bool):
    from repro.core import BandwidthTrace, nlos_bandwidth
    if mobility:
        dist = list(np.linspace(0, 30, 11)) + list(np.linspace(30, 0, 11))
        return BandwidthTrace.walk(dist, nlos_bandwidth, period=1.0)
    return BandwidthTrace.static(nlos_bandwidth(5.0))


def _print_tiered(eng, n_sessions):
    for r in eng.records:
        fb = " !! failover" if r.fallback else ""
        gp = (f" (glass partial @{r.glass_partial.t_emit:6.2f}s)"
              if r.glass_partial is not None else "")
        split = (f" tail={r.tail_tier}" if r.tail_tier is not None
                 and r.tail_tier != r.enc_tier else "")
        qz = f" [{r.precision}]" if r.precision != "fp32" else ""
        print(f"[{r.sid:4s} {r.index:2d}] {r.modality:6s} "
              f"tier={r.tier:7s}{qz} {r.kind:7s} "
              f"up={r.uplink_s*1e3:6.1f}ms "
              f"compute={r.compute_s*1e3:7.1f}ms "
              f"down={r.downlink_s*1e3:6.1f}ms "
              f"latency={r.latency_s*1e3:8.1f}ms{fb}{split}{gp}")
    pc = eng.placement_counts()
    fallbacks = pc.pop("fallbacks")
    placed = " / ".join(f"{n} {tier}" for tier, n in pc.items())
    print(f"\n{n_sessions} sessions, {eng.events_total} arrivals: "
          f"{placed} / {fallbacks} crash failovers / "
          f"{eng.rejoin_count} rejoins")
    for link, s in eng.transport_stats()["links"].items():
        print(f"  link {link:18s} {s['bytes']/1e6:8.2f} MB in "
              f"{s['msgs']:3d} msgs")
    print(f"cumulative serving latency {eng.total_latency_s()*1e3:.1f} ms")


def _print_stream(eng, eps):
    for f in eng.flushes:
        for p in f.predictions:
            proto = int(jnp.argmax(p.outputs["protocol_logits"]))
            print(f"flush[{f.flush_id:3d}] {p.sid:4s} "
                  f"{p.kind:7s} over {'+'.join(p.modalities):24s} "
                  f"-> protocol={proto}")
    print(f"\n{len(eps)} sessions, {eng.events_total} arrivals, "
          f"{eng.flushes_total} flushes, "
          f"{eng.encoder_calls_total()} encoder calls, "
          f"XLA compiles {eng.compile_count()}")
    for sid in sorted(eps):
        ttfp = eng.time_to_first_prediction(sid)
        ttf = eng.time_to_final_prediction(sid)
        print(f"  {sid}: time-to-first {ttfp*1e3:7.1f} ms | "
              f"time-to-final "
              f"{'n/a' if ttf is None else f'{ttf*1e3:7.1f} ms'}")


def _print_batch(eng, n_sessions):
    for f in eng.flushes:
        print(f"flush[{f.flush_id:2d}] events={f.n_events:3d} "
              f"enc_calls={f.n_encoder_calls} tail_calls={f.n_tail_calls} "
              f"wall={f.wall_s*1e3:7.2f}ms")
    lats = sorted(eng.event_latencies())
    print(f"\n{n_sessions} sessions, {eng.events_total} events in "
          f"{eng.total_wall_s()*1e3:.1f} ms compute "
          f"(p50 latency {lats[len(lats)//2]*1e3:.1f} ms, "
          f"XLA compiles {eng.compile_count()}, "
          f"cache entries {len(eng.cache)})")


def serve_unified(args):
    """One path for every --engine spec: build the zoo/models, assemble
    the engine from composable policies, drive it, print the trace."""
    from repro.configs.emsnet import config as emsnet_config
    from repro.core import Bucketer, ProfileTable, profile, table6
    from repro.serving.api import build_engine

    cfg = emsnet_config(text_encoder=args.text_encoder, vocab_size=2048)
    spec = parse_spec_tokens(args.engine)
    n = args.sessions
    tiered = "tiered" in spec
    stream = "stream" in spec

    # flag/spec mismatches fail loudly, not silently
    if args.outage_at >= 0 and not tiered:
        raise SystemExit("--outage-at requires a tiered spec "
                         "(e.g. --engine stream+tiered)")
    if args.rejoin_at >= 0 and args.outage_at < 0:
        raise SystemExit("--rejoin-at requires --outage-at")
    if args.tiers and not tiered:
        raise SystemExit("--tiers requires a tiered spec")
    if args.deadline_ms and not stream:
        raise SystemExit("--deadline-ms requires a stream spec")
    if args.wall_clock and not (stream or tiered):
        raise SystemExit("--wall-clock requires a stream or tiered spec")
    if (args.speculate or args.redispatch) and not tiered:
        raise SystemExit("--speculate/--redispatch require a tiered spec")
    if args.precision and not tiered:
        raise SystemExit("--precision requires a tiered spec")
    if args.chaos_seed >= 0 and not tiered:
        raise SystemExit("--chaos-seed requires a tiered spec")
    if args.chaos_seed >= 0 and args.outage_at >= 0:
        raise SystemExit("--chaos-seed conflicts with --outage-at; "
                         "pick one fault schedule")
    if args.ragged and tiered:
        raise SystemExit("--ragged requires a flush-mode spec (batch "
                         "and/or stream); tiered places each arrival "
                         "individually and never coalesces a flush")

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()

    # the fault schedule is validated against the EPISODES (cheap to
    # build) before any model/profiling work happens
    eps = (scenario_episodes(n, args.scenario) if tiered or stream
           else None)
    chaos = None
    if tiered:
        from repro.core import horizon
        span = horizon(eps)
        if args.outage_at >= 0:
            if args.outage_at > span:
                raise SystemExit(
                    f"--outage-at {args.outage_at:g} is beyond the "
                    f"episode horizon ({span:.2f}s): the crash would "
                    f"never be observed")
            if args.rejoin_at >= 0 and args.rejoin_at <= args.outage_at:
                raise SystemExit(
                    f"--rejoin-at {args.rejoin_at:g} must be strictly "
                    f"after --outage-at {args.outage_at:g}")
        if args.chaos_seed >= 0:
            if not args.tiers:
                raise SystemExit("--chaos-seed needs --tiers (the "
                                 "schedule spans the remote tiers)")
            from repro.serving.chaos import chaos_schedule
            remote = tuple(t.strip() for t in args.tiers.split(",")
                           if t.strip())[1:]
            chaos = chaos_schedule(args.chaos_seed, horizon=span,
                                   tiers=remote)

    kw = {}
    if tiered and args.speculate:
        from repro.core.offload import SpeculationPolicy
        kw["speculation"] = SpeculationPolicy(
            deadline_s=args.spec_deadline_ms / 1e3,
            margin_s=args.spec_margin_ms / 1e3)
    if tiered and args.redispatch:
        kw["redispatch"] = True
    if tiered and args.precision:
        prec = {}
        for part in filter(None, (p.strip()
                                  for p in args.precision.split(","))):
            host, sep, p = part.partition("=")
            if not sep or not host.strip() or not p.strip():
                raise SystemExit(
                    f"--precision: malformed entry {part!r} "
                    "(expected HOST=fp32|int8, comma-separated)")
            prec[host.strip()] = p.strip()
        kw["precision"] = prec
    if tiered or stream:
        splits, params = build_zoo(cfg)          # one shared pytree
        kw["share_encoders"] = True
    else:
        splits, params = build_models(cfg)       # independent m1/m2/m3
    payloads = sample_payloads(cfg)
    payload_fn = lambda sid, ev: payloads[ev.modality]  # noqa: E731

    if tiered:
        full = splits["text+vitals+scene"]
        base = profile(full, params["text+vitals+scene"], payloads, iters=3)
        kw["profile"] = ProfileTable(base=base)
        kw["trace"] = _mobility_trace(args.mobility)
        if args.tiers:
            from repro.core import TIER_FACTORS
            tiers = tuple(t.strip() for t in args.tiers.split(",")
                          if t.strip())
            unknown = [t for t in tiers if t not in TIER_FACTORS]
            if unknown or len(tiers) < 2:
                raise SystemExit(
                    f"--tiers: unknown tier(s) {unknown} or too few; "
                    f"pick >= 2 of {sorted(TIER_FACTORS)} (local first)")
            kw["tiers"] = tiers
            # the EMT's phone rides in a pocket: a near-field tether,
            # unlike the distance-degraded glass<->edge WiFi
            from repro.core import BandwidthTrace, nlos_bandwidth
            kw["tier_traces"] = {t: BandwidthTrace.static(nlos_bandwidth(0.0))
                                 for t in tiers[1:] if t.startswith("ph")}
    if stream:
        kw["deadline_s"] = (args.deadline_ms / 1e3 if args.wall_clock
                            else None)
    if "batch" in spec or stream:
        kw["bucketer"] = Bucketer(max_buckets={"vitals": cfg.vitals_len,
                                               "text": cfg.max_text_len})
        kw["batch_bucket_min"] = min(8, n)
        if args.ragged:
            kw["ragged"] = True

    eng = build_engine(splits, params, "+".join(spec), max_history=None,
                       tracer=tracer, **kw)

    if tiered:
        if args.outage_at >= 0:
            eng.inject_crash(args.outage_at,
                             rejoin_at=(args.rejoin_at
                                        if args.rejoin_at >= 0 else None))
            print(f"fault schedule: crash {eng._primary} "
                  f"@{args.outage_at:.2f}s, detect @{eng.detect_at:.2f}s"
                  + (f", rejoin @{args.rejoin_at:.2f}s"
                     if args.rejoin_at >= 0 else " (no restart)"))
        if chaos is not None:
            eng.inject_schedule(chaos)
            print(f"fault schedule: chaos seed {args.chaos_seed}, "
                  f"{len(chaos)} crash/rejoin cycles")
            for e in chaos:
                rj = (f"rejoin @{e.rejoin_at:6.2f}s"
                      if e.rejoin_at is not None else "no restart")
                print(f"  crash {e.tier:8s} @{e.crash_at:6.2f}s, {rj}")
        if args.wall_clock:
            from repro.serving.event_loop import WallClockDriver
            WallClockDriver(eng, speed=args.speed).run(eps, payload_fn)
        else:
            eng.run_arrivals(eps, payload_fn)
        _print_tiered(eng, n)
        if args.speculate or args.redispatch:
            ss = eng.speculation_stats()
            wins = " / ".join(f"{v} {t}" for t, v in ss["wins"].items()
                              if v)
            print(f"speculation: {ss['races']} races "
                  f"({wins or 'no wins'}), "
                  f"{ss['crash_saves']} crash saves, "
                  f"{ss['redispatches']} re-dispatches, "
                  f"{ss['cancelled_msgs']} cancelled transfers, "
                  f"{ss['duplicate_commits']} duplicate commits")
    elif stream:
        if args.wall_clock:
            from repro.serving.event_loop import WallClockDriver
            WallClockDriver(eng, speed=args.speed).run(eps, payload_fn)
        else:
            eng.run_arrivals(eps, payload_fn,
                             sim_window=args.deadline_ms / 1e3)
        _print_stream(eng, eps)
    else:
        eps = {f"s{i}": table6()[1 + i % 3] for i in range(n)}
        eng.run_episodes(eps, payload_fn)
        _print_batch(eng, n)
    if args.ragged:
        pf = [f.padded_flop_frac for f in eng.flushes]
        print(f"ragged flush: {eng.ragged.n_shapes()} packed shapes, "
              f"mean padded-FLOP fraction "
              f"{float(np.mean(pf)) if pf else 0.0:.3f}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(eng.metrics.to_prometheus())
        print(f"metrics: prometheus text -> {args.metrics_out}")
    if tracer is not None:
        other = {"metrics": eng.metrics_snapshot()}
        if tiered:
            other["transport"] = eng.fabric.stats()
        n_ev = tracer.export(args.trace, other_data=other)
        print(f"trace: {n_ev} events -> {args.trace} "
              f"(load in Perfetto: ui.perfetto.dev; audit: "
              f"python -m repro.obs.audit {args.trace})")


def serve_fleet(args):
    """``--fleet RATE``: open-loop region simulation — ``--replicas N``
    engine replicas built from ONE spec, replica r on device
    ``r % len(jax.devices())``,
    Poisson session arrivals at RATE sessions/s, consistent-hash +
    least-loaded routing, deadline admission control, and the on-glass
    degraded shed path for what the region turns away."""
    from repro.configs.emsnet import config as emsnet_config
    from repro.core import ProfileTable, profile
    from repro.fleet import (AdmissionController, AdmissionPolicy,
                             RegionSim, generate_workload)

    cfg = emsnet_config(text_encoder=args.text_encoder, vocab_size=2048)
    splits, params = build_zoo(cfg)
    payloads = sample_payloads(cfg)
    payload_fn = lambda sid, ev: payloads[ev.modality]  # noqa: E731

    tracer = None
    if args.trace:
        from repro.obs import StreamingTracer, Tracer
        tracer = (StreamingTracer(args.trace, buffer=512)
                  if args.trace.endswith(".jsonl") else Tracer())

    full = splits["text+vitals+scene"]
    base = profile(full, params["text+vitals+scene"], payloads, iters=2)
    deadline = (args.deadline_ms / 1e3) if args.deadline_ms else 0.5
    ctrl = AdmissionController(AdmissionPolicy(deadline_s=deadline),
                              args.replicas)
    sim = RegionSim(splits, params, n_replicas=args.replicas,
                    admission=ctrl, profile=ProfileTable(base=base),
                    tracer=tracer)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(
        params["text+vitals+scene"]))
    print(f"fleet: {args.replicas} replicas over {len(set(sim.devices))} "
          f"device(s), {nbytes / 1e6:.1f} MB of params per replica, "
          f"admission deadline {deadline * 1e3:.0f} ms")

    horizon = args.sessions / args.fleet
    sessions = generate_workload(args.fleet, horizon, seed=0)
    rep = sim.run(sessions, payload_fn)

    ttfp = sorted(sim.ttfp.values())
    p = lambda q: ttfp[min(len(ttfp) - 1,  # noqa: E731
                           int(q * len(ttfp)))] if ttfp else float("nan")
    print(f"\n{rep['sessions_offered']} sessions offered @ "
          f"{args.fleet:g}/s: {rep['sessions_admitted']} admitted "
          f"({rep['sessions_finalized']} finalized), "
          f"{rep['sessions_shed']} shed to glass "
          f"({rep['degraded_partials']} degraded partials)")
    print(f"admitted TTFP p50 {p(0.50) * 1e3:7.1f} ms | "
          f"p95 {p(0.95) * 1e3:7.1f} ms | "
          f"makespan {rep['makespan_s']:.2f}s | "
          f"{rep['sessions_finalized'] / rep['makespan_s']:.2f} "
          f"finalized sessions/s")
    for r, pr in enumerate(rep["per_replica"]):
        print(f"  replica {r}: {pr['sessions']:3d} sessions "
              f"{pr['flushes']:4d} flushes "
              f"idle-at {pr['final_clock_s']:.2f}s")

    mx = sim.fleet_metrics()
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(mx.to_prometheus())
        print(f"metrics: prometheus text -> {args.metrics_out}")
    if tracer is not None:
        n_ev = tracer.export(args.trace,
                             other_data={"metrics": mx.snapshot()})
        print(f"trace: {n_ev} events -> {args.trace} "
              f"(audit: python -m repro.obs.audit {args.trace})")


def parse_spec_tokens(engine_arg: str):
    """Canonical token tuple for an --engine spec string (validation is
    re-done by api.parse_spec; this is just for mode branching)."""
    from repro.serving.api import _SPEC_TOKENS
    toks = []
    for t in filter(None, (t.strip() for t in engine_arg.split("+"))):
        canon = _SPEC_TOKENS.get(t.lower())
        if canon is None:
            raise SystemExit(f"--engine: unknown token {t!r} "
                             f"(use +-joined batch/stream/tiered)")
        if canon not in toks:
            toks.append(canon)
    if not toks:
        raise SystemExit("--engine: empty spec")
    return tuple(toks)


def _apply_legacy_shims(args):
    """Map the pre-unification mode flags onto --engine specs, with a
    one-line pointer to the replacement."""
    for flag, count, spec in (("--batched", args.batched, "batch"),
                              ("--stream", args.stream, "stream"),
                              ("--tiered", args.tiered, "tiered")):
        if count:
            if args.engine:
                raise SystemExit(f"{flag} conflicts with --engine; "
                                 f"use --engine alone")
            args.engine = spec
            args.sessions = count
            print(f"note: {flag} N is deprecated — use "
                  f"`--engine {spec} --sessions {count}` "
                  f"(specs compose, e.g. --engine stream+tiered)")
    return args


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--episode", type=int, default=1, choices=[1, 2, 3])
    ap.add_argument("--text-encoder", default="tinybert")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--mobility", action="store_true",
                    help="walk 0->30->0 m during the episode (scenario 3)")
    ap.add_argument("--crash-edge-at", type=int, default=-1)
    ap.add_argument("--engine", default="", metavar="SPEC",
                    help="unified session engine: '+'-joined subset of "
                         "batch/stream/tiered (e.g. batch+stream, "
                         "stream+tiered)")
    ap.add_argument("--sessions", type=int, default=4, metavar="N",
                    help="--engine: number of concurrent sessions")
    ap.add_argument("--scenario", default="mix",
                    choices=["mix", "text_first", "vitals_first",
                             "scene_late"],
                    help="stream/tiered specs: inter-modality lag scenario")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="stream spec: coalesce arrivals within this "
                         "window before flushing (0 = flush per arrival)")
    ap.add_argument("--ragged", action="store_true",
                    help="batch/stream specs: pack the pending rows of "
                         "each variable-length modality into ONE "
                         "concatenated ragged kernel call per flush and "
                         "fuse all pending tails into ONE grouped call")
    ap.add_argument("--outage-at", type=float, default=-1.0, metavar="S",
                    help="tiered spec: kill the (fastest) remote tier at "
                         "episode second S (heartbeat-detected on-glass "
                         "failover)")
    ap.add_argument("--rejoin-at", type=float, default=-1.0, metavar="S",
                    help="tiered spec: restart the crashed tier at episode "
                         "second S (replica re-warm from the glass cache, "
                         "placement-eligible again)")
    ap.add_argument("--tiers", default="", metavar="LIST",
                    help="tiered spec: comma-separated ordered tier list "
                         "from core.offload.TIER_FACTORS, local first "
                         "(e.g. glass,ph1,edge64x); enables contention-"
                         "aware decisions and per-submodule tail placement")
    ap.add_argument("--speculate", action="store_true",
                    help="tiered spec: arm speculative dual placement — "
                         "deadline-pressured arrivals race glass against "
                         "the best remote (cancel-on-commit)")
    ap.add_argument("--spec-deadline-ms", type=float, default=350.0,
                    help="--speculate: per-arrival serving deadline")
    ap.add_argument("--spec-margin-ms", type=float, default=50.0,
                    help="--speculate: race when the estimated slack "
                         "before the deadline dips below this")
    ap.add_argument("--redispatch", action="store_true",
                    help="tiered spec: re-aim a flight lost to a tier "
                         "crash at the next-best surviving remote "
                         "instead of always re-running on glass")
    ap.add_argument("--precision", default="", metavar="MAP",
                    help="tiered spec: comma-separated HOST=fp32|int8 "
                         "map (e.g. ph1=int8,edge64x=int8) arming the "
                         "joint precision+placement co-decision: int8-"
                         "capable hosts may run the sidecar-quantized "
                         "encoders and ship ~4x-smaller packed features "
                         "when the link is the bottleneck")
    ap.add_argument("--chaos-seed", type=int, default=-1, metavar="SEED",
                    help="tiered spec with --tiers: seeded random "
                         "crash/rejoin schedule over the remote tiers "
                         "(repeated crash->re-dispatch->rejoin cycles)")
    ap.add_argument("--fleet", type=float, default=0.0, metavar="RATE",
                    help="region simulation: offer whole incident "
                         "sessions at RATE sessions/s (open-loop "
                         "Poisson) to --replicas engine replicas with "
                         "admission control; --sessions N is the total "
                         "offered count")
    ap.add_argument("--replicas", type=int, default=2, metavar="N",
                    help="--fleet: engine replicas, replica r on jax "
                         "device r mod the device count (emulate "
                         "devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="write the run's metrics registry as "
                         "Prometheus text exposition to PATH (fleet "
                         "mode: exact fleet-wide merge across replicas)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="--engine/--fleet: record every event's "
                         "serving lifecycle with repro.obs.Tracer and "
                         "export a Chrome trace-event JSON (Perfetto-"
                         "loadable, auditable via python -m "
                         "repro.obs.audit); a .jsonl PATH in fleet mode "
                         "streams through the bounded-memory "
                         "StreamingTracer instead")
    ap.add_argument("--wall-clock", action="store_true",
                    help="stream/tiered specs: replay arrivals and pump "
                         "deadline flushes from a monotonic clock")
    ap.add_argument("--speed", type=float, default=1.0,
                    help="--wall-clock: episode seconds per wall second")
    # ---- deprecated mode flags (shims onto --engine)
    ap.add_argument("--batched", type=int, default=0, metavar="N",
                    help="deprecated: --engine batch --sessions N")
    ap.add_argument("--stream", type=int, default=0, metavar="N",
                    help="deprecated: --engine stream --sessions N")
    ap.add_argument("--tiered", type=int, default=0, metavar="N",
                    help="deprecated: --engine tiered --sessions N")
    args = _apply_legacy_shims(ap.parse_args())

    if args.fleet < 0.0:
        raise SystemExit("--fleet RATE must be > 0 (sessions/s)")
    if args.fleet and args.engine:
        raise SystemExit("--fleet conflicts with --engine: the region "
                         "simulator builds its own replica engines "
                         "from one spec")
    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    if args.trace and not (args.engine or args.fleet):
        raise SystemExit("--trace requires --engine or --fleet (the "
                         "reference per-event engine predates the "
                         "traced serving stack)")
    if args.metrics_out and not (args.engine or args.fleet):
        raise SystemExit("--metrics-out requires --engine or --fleet")
    if args.fleet:
        serve_fleet(args)
        return
    if args.engine:
        serve_unified(args)
        return

    # ---- default: the per-event reference engine on a Table-6 episode
    from repro.configs.emsnet import config as emsnet_config
    from repro.core import (AdaptiveOffloadPolicy, EMSServe,
                            HeartbeatMonitor, ProfileTable, profile, table6)

    cfg = emsnet_config(text_encoder=args.text_encoder, vocab_size=2048)
    splits, params = build_models(cfg)
    payloads = sample_payloads(cfg)

    base = profile(splits["m3"], params["m3"], payloads)
    table = ProfileTable(base=base)
    policy = AdaptiveOffloadPolicy(table,
                                   HeartbeatMonitor(
                                       _mobility_trace(args.mobility)))

    engine = EMSServe(splits, params, policy=policy,
                      cached=not args.no_cache)
    events = table6()[args.episode]
    for i, ev in enumerate(events):
        if i == args.crash_edge_at:
            print("!! edge server crash — failing over to on-glass inference")
            engine.crash_edge()
        rec = engine.on_event(ev, payloads[ev.modality])
        top = ""
        if rec.recommendation is not None:
            p = int(jnp.argmax(rec.recommendation["protocol_logits"]))
            m = int(jnp.argmax(rec.recommendation["medicine_logits"]))
            q = float(rec.recommendation["quantity"][0])
            top = f" -> protocol={p} medicine={m} qty={q:+.2f}"
        print(f"[{ev.index:2d}] {ev.modality:6s} tier={rec.tier:5s} "
              f"dt={rec.delta_t*1e3:7.2f}ms compute={rec.compute_s*1e3:7.2f}ms "
              f"cum={rec.cumulative_s*1e3:8.2f}ms{top}")
    print(f"\ncumulative serving time: {engine.cumulative_time()*1e3:.1f} ms "
          f"(cache hits: {engine.cache.hits})")


if __name__ == "__main__":
    # the entry point owns the compile cache; tests call main() without it
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()

"""Multi-session serving throughput: the unified engine's batch
construction (``serving.api.build_engine(..., "batch")``) vs looping
the per-event EMSServe (the paper's single-responder engine) over N
concurrent sessions.

Workload: every session streams an EMS episode — symptom text first
(paper Episode 1 ordering), then a vitals/scene mix whose vitals GROW
one timestep per vitals event (aggregate=concat). Growing streams are
the key property: the unbucketed per-event baseline meets a NEW input
shape (and takes a fresh XLA compile) every few events for the entire
life of an incident, while the bucketed engine's shape set is finite.

Protocol (production-faithful): both engines run the first
``warmup_ticks`` of every episode untimed — steady-state submodule
programs and every (modality, bucket, batch) shape get compiled there.
The timed window is the episode's continuation, where every vitals
stream is longer than anything in history: the batched engine must add
ZERO compiles there (the plateau criterion), while the baseline keeps
recompiling — exactly what it would do in deployment.

Reports (-> artifacts/BENCH_serving.json and CSV rows): sessions/sec
and events/sec for both engines + speedup, p50/p99 per-event latency
under the batched engine, XLA compile counts and the per-tick compile
trace over the timed window.

Ragged section (``result["ragged"]``, gated by ``passed_ragged``): the
concatenated ragged flush path over a shared-parameter zoo with the
segment-flash parity config on both sides. Three deterministic gates:
(a) bit parity — ragged predictions at the reference's own cadence are
``np.array_equal`` to the per-event unbucketed ``core.engine.EMSServe``;
(b) kernel calls — every coalesced flush issues at most one packed
encoder call per live modality plus ONE grouped tail (O(modalities)+1,
vs O(modalities x buckets)+O(subsets) bucketed); (c) padded-FLOP
fraction strictly below the bucketed baseline's on the same session
mix. The legacy baseline/batched sections run exactly as before
(ragged stays OFF there).

Observability section (``result["obs_overhead"]``, gated by
``passed_obs_overhead``): the same flush workload untraced (the
disabled-tracer default — i.e. the legacy engine, byte-identical
numbers) vs with a live ``repro.obs.Tracer`` recording every event's
lifecycle; enabled tracing must cost < 5% wall regression and the
resulting trace must replay cleanly through the invariant auditor.
``result["metrics"]`` embeds the batched engine's full metrics
registry snapshot.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from . import common as C

ART = Path(__file__).resolve().parent / "artifacts"

TEXT_LENS = (6, 12, 24, 31)       # per-session utterance lengths -> buckets


def _episodes(n_sessions, n_ticks, cfg, seed=0):
    """Deterministic prefix [text, vitals, scene] (so every modality,
    model, and bucket is exercised during warmup), then a seeded
    vitals/scene mix."""
    from repro.core.episodes import Event
    rng = np.random.default_rng(seed)
    eps, payloads = {}, {}
    for i in range(n_sessions):
        sid = f"s{i}"
        kinds = ["text", "vitals", "scene"] + rng.choice(
            ["vitals", "scene"], size=max(0, n_ticks - 3), p=(0.55, 0.45)
        ).tolist()
        eps[sid] = [Event(t, k, float(t)) for t, k in enumerate(kinds[:n_ticks])]
        text_len = min(TEXT_LENS[i % len(TEXT_LENS)], cfg.max_text_len)
        p = C.sample_payloads(cfg, seed=seed + i)
        payloads[sid] = {
            "text": p["text"][:, :text_len],
            "vitals": p["vitals"][:, :1],          # ONE new timestep per event
            "scene": p["scene"],
        }
    return eps, payloads


def _aggregate(old, new):
    """Vitals extend the time series; other modalities replace."""
    import jax.numpy as jnp
    if old is not None and new.ndim == 3:
        return jnp.concatenate([old, new], axis=1)
    return new


def _pctl(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else 0.0


def _ragged_section(n_sessions, n_ticks, seed=1):
    """The ragged-vs-bucketed comparison on its own shared-parameter
    zoo (the grouped tail requires one parameter pytree) with the
    segment-flash bit-parity config on BOTH sides."""
    import jax

    from repro.core import EMSServe, emsnet_zoo, split
    from repro.serving.api import build_engine

    cfg = C.emsnet_cfg(True, text_encoder="microbert", vocab_size=512,
                       max_text_len=16, vitals_hidden=32,
                       use_flash_text=True, flash_segments=True,
                       flash_block=8)
    zoo = emsnet_zoo(cfg)
    splits = {k: split(m) for k, m in zoo.items()}
    shared = zoo["text+vitals+scene"].init_fn(jax.random.PRNGKey(0))
    params = {k: shared for k in zoo}
    eps, payloads = _episodes(n_sessions, n_ticks, cfg, seed=seed)

    def payload_fn(sid, ev):
        return payloads[sid][ev.modality]

    # --- gate (a): bit parity at the reference's own per-event cadence
    refs = {sid: EMSServe(splits, params, cached=True, real_time=True,
                          session=sid) for sid in eps}
    eng = build_engine(splits, params, "batch+stream",
                       share_encoders=True, ragged=True,
                       deadline_s=0.0, max_history=None)
    bitwise, max_diff, n_compared = True, 0.0, 0
    for t in range(n_ticks):
        for sid, events in eps.items():
            if t >= len(events):
                continue
            ev = events[t]
            rec = refs[sid].on_event(ev, payload_fn(sid, ev),
                                     aggregate=_aggregate)
            rep = eng.submit(sid, ev, payload_fn(sid, ev),
                             aggregate=_aggregate)
            if rec.recommendation is None:
                continue
            (pred,) = rep.predictions
            for k, want in rec.recommendation.items():
                got = np.asarray(pred.outputs[k])
                if not np.array_equal(got, np.asarray(want)):
                    bitwise = False
                    max_diff = max(max_diff,
                                   float(np.abs(got - np.asarray(want)).max()))
            n_compared += 1

    # --- gates (b)+(c): coalesced ragged vs bucketed, same session mix
    def coalesced(ragged):
        e = build_engine(splits, params, "batch+stream",
                         share_encoders=True, ragged=ragged,
                         deadline_s=None, batch_bucket_min=2,
                         max_history=None)
        for t in range(n_ticks):
            for sid, events in eps.items():
                if t < len(events):
                    e.submit(sid, events[t], payload_fn(sid, events[t]),
                             aggregate=_aggregate)
            e.flush()
        return e

    reng = coalesced(True)
    beng = coalesced(False)
    r_calls = [(f.n_encoder_calls, f.n_tail_calls) for f in reng.flushes]
    b_calls = [(f.n_encoder_calls, f.n_tail_calls) for f in beng.flushes]
    r_frac = float(np.mean([f.padded_flop_frac for f in reng.flushes]))
    b_frac = float(np.mean([f.padded_flop_frac for f in beng.flushes]))
    n_modalities = 3
    gates = {
        "passed_ragged_bit_parity": bool(bitwise and n_compared > 0),
        "passed_ragged_kernel_calls": bool(
            all(e <= n_modalities and tl <= 1 for e, tl in r_calls)
            and sum(e + tl for e, tl in r_calls)
            < sum(e + tl for e, tl in b_calls)),
        "passed_ragged_padded_flops": bool(r_frac < b_frac),
    }
    return {
        "config": {"text_encoder": cfg.text_encoder,
                   "use_flash_text": True, "flash_segments": True,
                   "n_sessions": n_sessions, "n_ticks": n_ticks},
        "bit_parity_vs_unbucketed_reference": {
            "predictions_compared": n_compared,
            "bitwise_equal_atol0": bool(bitwise),
            "max_abs_diff": max_diff,
        },
        "kernel_calls_per_flush": {
            "ragged": [list(c) for c in r_calls],
            "bucketed": [list(c) for c in b_calls],
            "ragged_total": sum(e + tl for e, tl in r_calls),
            "bucketed_total": sum(e + tl for e, tl in b_calls),
        },
        "padded_flop_frac": {"ragged": r_frac, "bucketed": b_frac},
        "packed_shapes": reng.ragged.n_shapes(),
        **gates,
        "passed_ragged": all(gates.values()),
    }


def _obs_overhead_section(n_sessions, n_ticks, warmup_ticks, repeats=5):
    """Traced-vs-untraced wall time on the SAME flush workload.

    The disabled tracer (the default every legacy path runs with) is a
    falsy no-op, so the untraced engine here IS the legacy engine. The
    traced engine records the full per-event lifecycle; its in-memory
    trace is replayed through the invariant auditor before timing is
    even considered a pass. Min-of-N repeats on each side, plus a
    small absolute slack so sub-100ms walls don't flap on scheduler
    noise.
    """
    from repro.core import Bucketer
    from repro.obs import Tracer, audit_tracer
    from repro.serving.api import build_engine

    cfg = C.emsnet_cfg(True)
    splits, params = C.build_split_models(cfg)
    eps, payloads = _episodes(n_sessions, n_ticks, cfg)
    max_buckets = {"vitals": 8, "text": cfg.max_text_len}

    def payload_fn(sid, ev):
        return payloads[sid][ev.modality]

    def one_run(tracer):
        eng = build_engine(splits, params, "batch",
                           bucketer=Bucketer(max_buckets=max_buckets),
                           batch_bucket_min=min(8, n_sessions),
                           max_history=None, tracer=tracer)

        def tick(t):
            for sid, events in eps.items():
                if t < len(events):
                    eng.submit(sid, events[t], payload_fn(sid, events[t]),
                               aggregate=_aggregate)
            eng.flush()

        for t in range(warmup_ticks):
            tick(t)
        t0 = time.perf_counter()
        for t in range(warmup_ticks, n_ticks):
            tick(t)
        return time.perf_counter() - t0, eng

    one_run(None)                   # shared-XLA-cache warmup pass
    untraced = min(one_run(None)[0] for _ in range(repeats))
    traced, eng = min((one_run(Tracer()) for _ in range(repeats)),
                      key=lambda we: we[0])
    audit = audit_tracer(eng.tracer)
    overhead = traced / untraced - 1.0
    passed_wall = bool(traced <= untraced * 1.05 + 0.02)
    return {
        "repeats": repeats,
        "untraced_wall_s": untraced,
        "traced_wall_s": traced,
        "overhead_frac": overhead,
        "trace_events": len(eng.tracer.events),
        "audit": {"ok": audit.ok, "violations": audit.violations[:5],
                  "checks": audit.checks},
        "passed_obs_wall": passed_wall,
        "passed_obs_audit": bool(audit.ok),
        "passed_obs_overhead": bool(passed_wall and audit.ok),
    }


def run(quick=True, *, n_sessions=None, n_ticks=None, warmup_ticks=4):
    from repro.core import Bucketer, EMSServe
    from repro.serving.api import build_engine

    n_sessions = n_sessions or (8 if quick else 32)
    n_ticks = n_ticks or (16 if quick else 48)
    if n_ticks <= warmup_ticks:
        raise SystemExit(f"--ticks must exceed the warmup window "
                         f"({warmup_ticks}); got {n_ticks}")
    cfg = C.emsnet_cfg(quick)
    # separate split sets so each engine has its own jit caches and the
    # reported compile counts are per-engine, not shared
    splits, params = C.build_split_models(cfg)
    splits_b, params_b = C.build_split_models(cfg)
    eps, payloads = _episodes(n_sessions, n_ticks, cfg)
    # vitals: sliding window of the 8 most recent samples (bounded
    # memory for an unbounded stream); text: padded to its bucket
    max_buckets = {"vitals": 8, "text": cfg.max_text_len}

    def payload_fn(sid, ev):
        return payloads[sid][ev.modality]

    # ------- baseline: loop the per-event engine, one session at a time
    base_wall = 0.0
    base_compiles_start = base_compiles_end = 0
    engines = {sid: EMSServe(splits, params, cached=True, real_time=True)
               for sid in eps}
    for sid, events in eps.items():                      # warmup window
        for ev in events[:warmup_ticks]:
            engines[sid].on_event(ev, payload_fn(sid, ev),
                                  aggregate=_aggregate)
    base_compiles_start = next(iter(engines.values())).compile_count()
    t0 = time.perf_counter()
    for sid, events in eps.items():                      # timed window
        for ev in events[warmup_ticks:]:
            engines[sid].on_event(ev, payload_fn(sid, ev),
                                  aggregate=_aggregate)
    base_wall = time.perf_counter() - t0
    base_compiles_end = next(iter(engines.values())).compile_count()
    n_timed_events = sum(len(ev) - warmup_ticks for ev in eps.values())

    # ------- batched, bucketed, dispatch-async engine (unified API)
    beng = build_engine(splits_b, params_b, "batch",
                        bucketer=Bucketer(max_buckets=max_buckets),
                        batch_bucket_min=min(8, n_sessions),
                        max_history=None)

    def tick(t):
        for sid, events in eps.items():
            if t < len(events):
                beng.submit(sid, events[t], payload_fn(sid, events[t]),
                            aggregate=_aggregate)
        beng.flush()

    for t in range(warmup_ticks):                        # warmup window
        tick(t)
    warm_flushes = len(beng.flushes)
    compile_trace = [beng.compile_count()]
    t0 = time.perf_counter()
    for t in range(warmup_ticks, n_ticks):               # timed window
        tick(t)
        compile_trace.append(beng.compile_count())
    batch_wall = time.perf_counter() - t0

    lats = [lat for f in beng.flushes[warm_flushes:]
            for lat in f.latencies.values()]
    result = {
        "n_sessions": n_sessions,
        "n_ticks": n_ticks,
        "warmup_ticks": warmup_ticks,
        "timed_events": n_timed_events,
        "baseline": {
            "wall_s": base_wall,
            "sessions_per_s": n_sessions / base_wall,
            "events_per_s": n_timed_events / base_wall,
            "xla_compiles_during_timed": base_compiles_end - base_compiles_start,
            "xla_compiles_total": base_compiles_end,
        },
        "batched": {
            "wall_s": batch_wall,
            "sessions_per_s": n_sessions / batch_wall,
            "events_per_s": n_timed_events / batch_wall,
            "xla_compiles_during_timed": compile_trace[-1] - compile_trace[0],
            "xla_compiles_total": compile_trace[-1],
            "p50_event_latency_ms": _pctl(lats, 50) * 1e3,
            "p99_event_latency_ms": _pctl(lats, 99) * 1e3,
            "encoder_calls": sum(f.n_encoder_calls for f in beng.flushes),
            "tail_calls": sum(f.n_tail_calls for f in beng.flushes),
        },
        "speedup": base_wall / batch_wall,
        "compile_trace_timed": compile_trace,
        "buckets": {f"{m}:{b}": n
                    for (m, b), n in sorted(beng.bucketer.histogram.items())},
    }

    # ------- ragged grouped flush path (own zoo; legacy paths above
    # ran with ragged OFF and are byte-for-byte what they always were)
    result["ragged"] = _ragged_section(n_sessions, n_ticks)

    # ------- observability: registry snapshot + tracing overhead gate
    result["metrics"] = beng.metrics_snapshot()
    result["obs_overhead"] = _obs_overhead_section(
        n_sessions, n_ticks, warmup_ticks)

    ART.mkdir(parents=True, exist_ok=True)
    (ART / "BENCH_serving.json").write_text(json.dumps(result, indent=2))

    C.csv_row("serve_batched_per_session", batch_wall / n_sessions * 1e6,
              f"sessions_per_s={result['batched']['sessions_per_s']:.2f};"
              f"speedup={result['speedup']:.2f}x;"
              f"compiles_timed={result['batched']['xla_compiles_during_timed']}")
    C.csv_row("serve_baseline_per_session", base_wall / n_sessions * 1e6,
              f"sessions_per_s={result['baseline']['sessions_per_s']:.2f};"
              f"compiles_timed={result['baseline']['xla_compiles_during_timed']}")
    C.csv_row("serve_event_latency_p99",
              result["batched"]["p99_event_latency_ms"] * 1e3,
              f"p50_ms={result['batched']['p50_event_latency_ms']:.2f}")
    rg = result["ragged"]
    C.csv_row("serve_ragged_padded_flop_frac",
              rg["padded_flop_frac"]["ragged"] * 1e6,
              f"bucketed={rg['padded_flop_frac']['bucketed']:.3f};"
              f"kernel_calls={rg['kernel_calls_per_flush']['ragged_total']}"
              f"vs{rg['kernel_calls_per_flush']['bucketed_total']};"
              f"bitwise={rg['bit_parity_vs_unbucketed_reference']['bitwise_equal_atol0']}")
    if not rg["passed_ragged"]:
        failed = [k for k, v in rg.items()
                  if k.startswith("passed_") and not v]
        raise SystemExit(f"ragged gates failed: {failed}")
    obs = result["obs_overhead"]
    C.csv_row("serve_obs_overhead", obs["overhead_frac"] * 1e6,
              f"untraced_s={obs['untraced_wall_s']:.3f};"
              f"traced_s={obs['traced_wall_s']:.3f};"
              f"events={obs['trace_events']};"
              f"audit_ok={obs['audit']['ok']}")
    if not obs["passed_obs_overhead"]:
        failed = [k for k, v in obs.items()
                  if k.startswith("passed_") and not v]
        raise SystemExit(f"obs overhead gates failed: {failed} "
                         f"(overhead {obs['overhead_frac']:+.1%}, "
                         f"audit {obs['audit']})")
    return result


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--sessions", type=int, default=None)
    ap.add_argument("--ticks", type=int, default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    r = run(quick=not args.full, n_sessions=args.sessions,
            n_ticks=args.ticks)
    print(json.dumps(r, indent=2))

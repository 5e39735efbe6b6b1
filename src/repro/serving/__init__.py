"""One EMSServe: the unified serving layer over the split-model zoo.

The heart of the package is ``api`` — canonical exchange types
(``Arrival``, ``Prediction``, ``FlushReport``, ``SessionView``,
``TieredRecord``) and the one multi-session runtime
(``EMSServeEngine``) whose behavior is assembled from orthogonal,
composable policies by the ``build_engine(models, params, spec)``
factory:

  * ``BatchPolicy`` — shape-bucketed cross-session coalescing, one
    batched XLA call per (modality, bucket) per flush, one host sync;
    ``ragged=True`` (default OFF) upgrades the flush to the
    concatenated ragged layout: ``core.bucketing.RaggedBatch`` packs
    every pending row of a variable-length modality into one buffer
    (text at ``ragged_align``-aligned offsets under the segment-masked
    flash kernel; vitals back-to-back with per-row state resets), and
    all pending fusion tails — across sessions AND modality subsets —
    run as ONE grouped call through zero-filled full-set heads (subset
    heads are row-slices of the full heads, and zero-filled K-slices
    are bitwise inert in a GEMM). A flush then issues O(modalities)+1
    kernels instead of O(modalities x buckets)+O(subsets), bit-parity
    (atol 0) pinned against the unbucketed per-event reference;
  * ``StreamPolicy`` — progressive partial->final predictions, flush
    deadlines, cross-incident session eviction;
  * ``PlacementPolicy`` — N tier hosts on simulated clocks (the legacy
    glass<->edge pair, or ``tiers=("glass", "ph1", "edge64x")``), live
    per-submodule offload decisions (encoder and fusion tail placed
    independently), contention-aware cost estimates, byte-accounted
    per-link transport (``transport.TierFabric``), heartbeat-detected
    crash failover, and tier restart/rejoin with replica re-warm.

Policies compose: ``build_engine(models, params, "stream+tiered", ...)``
streams on-glass provisional partials while the edge computes finals —
a regime none of the pre-unification sibling runtimes could express.

Cancel-on-commit speculation (``PlacementPolicy.speculation``): when a
``core.offload.SpeculationPolicy`` judges the deadline margin thin, the
engine races the arrival on glass AND the best remote simultaneously
and commits whichever finishes first — exactly once. The loser is
cancelled *at the commit instant*: an undelivered uplink is recalled
from the wire (``TransportChannel.cancel`` — a cancelled flight never
delivers, the in-order frontier rolls back, the bytes are audited), an
un-run remote booking is released from its host clock
(``TierHost.release``), and the duplicate-safe ``FeatureCache.put``
refuses any straggler commit at the same or an older step. A remote
crash mid-race is absorbed by the glass racer with no heartbeat stall.
``PlacementPolicy.redispatch`` re-aims flights lost to a tier crash at
the best surviving remote; ``chaos`` generates seeded, validated
crash/rejoin schedules that ``inject_schedule`` replays. All of it
defaults OFF — historical timelines never race.

Quantized precision tier (``PlacementPolicy.precision``, default
``None``): a ``{host: "fp32" | "int8"}`` map arms joint
(tier, precision) enumeration in ``core.offload.MultiTierPolicy`` —
an int8 candidate halves the remote encoder clock
(``int8_compute_scale``) and quarters the returned feature bytes
(``int8_bytes_scale``), so the argmin ships packed features exactly
when the uplink is the bottleneck. int8 flights run the UNMODIFIED
jitted encoders over a sidecar param pytree
(``models.quantized.quantize_emsnet_params`` — GEMM-heavy denses as
``{"w_q", "w_scale"}``, everything else fp32 shared by reference,
derived once per fp32 pytree and cached by id()), return
``{"q", "scale"}`` packed features (~4x smaller ``payload_nbytes``),
and the FeatureCache commits the packed form with staleness semantics
unchanged — consumers dequantize before fusion. Precision rides the
flight: racers run at the decided precision and crash re-dispatch
preserves it. Every model in a precision-armed spec must declare a
``quantize_fn``; an all-fp32 map disarms to the bit-identical legacy
path. The launcher flag is ``--precision ph1=int8,edge64x=int8``.

Observability (``repro.obs``, defaults OFF): every engine carries a
``Metrics`` registry — the stack's formerly ad hoc counters
(``duplicate_commits``, ``cancelled_bytes``, placement tallies, ...)
are names in its one flat namespace, the historical attributes
surviving as read-through properties, plus p50/p95/p99 latency
histograms behind ``EMSServeEngine.metrics_snapshot()``. Passing
``build_engine(..., tracer=repro.obs.Tracer())`` (or ``--trace PATH``
on the launcher) records every arrival's full lifecycle — arrival,
queue wait, encode@tier compute spans, transport flights by flight id,
fuse, cache commit, partial/final emit, and the race/cancel/crash/
redispatch/rejoin annotations — as Chrome trace-event JSON loadable in
Perfetto; the default ``Tracer.disabled`` is a falsy no-op, so untraced
runs regenerate bit-identically. ``python -m repro.obs.audit`` replays
an exported trace and re-verifies the serving invariants (exactly-one
commit, <=1-step staleness, byte conservation incl. cancelled flights,
no emit before its inputs) from the file alone.

Fleet scale lives one package up (``repro.fleet``): ``RegionSim``
replays seeded open-loop Poisson/diurnal incident arrivals against N
replicas of ONE ``build_engine`` spec, each on a device of its own, with
consistent-hash routing and deadline-hysteresis admission control that
sheds overload to on-glass ``degraded``-tagged partials (launcher:
``--fleet RATE --replicas N``; benchmark: ``benchmarks/fleet_load.py``).

Historical constructors remain as thin shims over the same engine:

  * ``batch_engine.BatchedEMSServe`` — the ``"batch"`` construction;
  * ``stream_engine.StreamingEMSServe`` — ``"batch+stream"``;
  * ``tiered_runtime.TieredEMSServe`` — ``"tiered"``;

plus the pieces the engine rides on:

  * ``transport`` — in-order byte-accounting tier links;
  * ``event_loop.WallClockDriver`` — monotonic-clock deadline pumping
    for any engine exposing ``submit``/``poll``/``drain``;
  * ``engine`` / ``kv_cache`` — LLM decode serving (KV-cache paths),
    unrelated to the EMS session engine.

(`core.engine.EMSServe` stays the single-session per-event *reference*
engine — the paper's Table-6 trace and the baseline every parity tier
and benchmark compares against.)
"""
from .api import (Arrival, BatchPolicy, EMSServeEngine,  # noqa: F401
                  EngineSpec, FlushReport, PlacementPolicy, Prediction,
                  SessionView, StreamPolicy, TieredRecord, TierHost,
                  build_engine, parse_spec)
from .batch_engine import BatchedEMSServe, SessionState  # noqa: F401
from .chaos import FaultEvent, chaos_schedule, validate_schedule  # noqa: F401
from .event_loop import LoopStats, WallClockDriver  # noqa: F401
from .stream_engine import (StreamFlushReport,  # noqa: F401
                            StreamingEMSServe, StreamSession)
from .tiered_runtime import TieredEMSServe, TierSession  # noqa: F401
from .transport import (Delivery, MinTrace, TierFabric,  # noqa: F401
                        TransportChannel, payload_nbytes)

"""One EMSServe: the unified session-engine API.

The serving layer used to be four sibling runtimes (`core.engine.EMSServe`
per-event reference, `BatchedEMSServe`, `StreamingEMSServe`,
`TieredEMSServe`) with duplicated session/flush/report machinery and
mutually exclusive launcher modes. This module replaces the three
multi-session runtimes with ONE :class:`EMSServeEngine` whose behavior is
assembled from orthogonal, composable policy objects:

  * :class:`BatchPolicy` — cross-session coalescing: shape-bucketed
    inputs (``core.bucketing``), power-of-two batch rows, chunked
    batched XLA calls, one host sync per flush;
  * :class:`StreamPolicy` — progressive partial->final predictions,
    wall-clock flush deadlines, cross-incident session eviction
    (idle timeout + LRU cap), and — under tiered placement — on-glass
    provisional partials while the edge computes the refreshed result;
  * :class:`PlacementPolicy` — N tier hosts on simulated busy-clocks
    (the legacy glass<->edge pair, or an ordered ``tiers`` list like
    ``("glass", "ph1", "edge64x")``), live per-arrival decisions
    through per-link heartbeat-quantized monitors with each host's
    queueing delay in the estimate, per-submodule placement (the
    fusion tail may run on a different tier than its encoder),
    byte-accounted in-order per-link transport, heartbeat-detected
    crash failover from the versioned feature cache, and tier
    restart/rejoin with replica re-warm.

Engines are built from a config spec by :func:`build_engine` (xFormers
factory idiom: the spec is data, the factory types it):

    eng = build_engine(models, params, "batch+stream")
    eng = build_engine(models, params, "stream+tiered",
                       profile=table, trace=trace, share_encoders=True)
    eng = build_engine(models, params, {"batch": {"max_coalesce": 32},
                                        "stream": {"deadline_s": 0.05}})

The canonical exchange types — :class:`Arrival` in, :class:`Prediction` /
:class:`FlushReport` / :class:`TieredRecord` out, :class:`SessionView`
for per-session state — are shared by every composition, so batching,
streaming, and tiering can be enabled *together*: the legacy engines are
thin constructor shims over this class (``serving.batch_engine``,
``serving.stream_engine``, ``serving.tiered_runtime``).

`core.engine.EMSServe` remains the single-session per-event *reference*
engine (the paper's Table-6 trace and every benchmark's baseline); the
parity tiers assert this engine agrees with it output-for-output.

Semantics of composition:

  * ``batch`` alone — caller-driven flushes (``deadline_s=None``), one
    batched encoder call per (modality, bucket) per consumer model, one
    batched tail per selected model, ``FlushReport.recommendations``
    per touched session (the BatchedEMSServe contract);
  * ``stream`` adds deadline-driven flushing, ``partial``/``final``
    tagging on every emitted :class:`Prediction`, and eviction;
  * ``tiered`` switches intake to per-arrival placement on the
    simulated tier clocks (offload decisions are per-event by
    construction, so batch coalescing degrades to shape bucketing
    there — the bucketer still bounds compile counts);
  * ``stream+tiered`` — the composition none of the siblings could
    express: when an arrival offloads, the glasses immediately re-fuse
    the cached (<=1-step stale, asserted live) features into an
    on-glass provisional partial while the edge computes the refreshed
    prediction, so the EMT always has the freshest answer the glass can
    produce *now* and the refined one the moment the downlink lands.
"""
from __future__ import annotations

import math
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.bucketing import (Bucketer, RaggedBatch, next_pow2,
                                  stack_bucketed)
from repro.core.episodes import Event, merge_arrivals
from repro.core.feature_cache import FeatureCache
from repro.core.offload import (BandwidthTrace, HeartbeatMonitor,
                                MultiTierPolicy, ProfileTable, TierDecision,
                                SpeculationPolicy)
from repro.core.splitter import SplitModel, select_model
from repro.models.quantized import dequantize_feature, quantize_feature
from repro.obs import Metrics, Tracer
from repro.serving.transport import TierFabric, payload_nbytes

__all__ = [
    "Arrival", "Prediction", "FlushReport", "SessionView", "TieredRecord",
    "TierHost", "BatchPolicy", "StreamPolicy", "PlacementPolicy",
    "SpeculationPolicy", "EngineSpec", "EMSServeEngine", "build_engine",
    "parse_spec",
]


# ======================================================================
# Canonical exchange types
# ======================================================================

@dataclass(frozen=True)
class Arrival:
    """One datum entering the engine: which session, which event, what
    payload. ``EMSServeEngine.ingest`` consumes these; ``submit`` is the
    unpacked form the drivers and legacy callers use."""
    sid: str
    event: Event
    payload: Any = None

    @property
    def modality(self) -> str:
        return self.event.modality

    @property
    def arrival_time(self) -> float:
        return self.event.arrival_time

    @property
    def index(self) -> int:
        return self.event.index


@dataclass
class Prediction:
    """One progressive prediction emitted for a session.

    Flush-mode predictions carry the flush that produced them in
    ``flush_id``, and ``outputs`` as host ``numpy`` rows of shape
    ``(1, k)``: the flush fetches each tail call's whole output once and
    cuts the rows on the host. Tiered-mode (per-arrival) predictions
    carry ``-1`` there, keep their outputs as the tier's arrays, and
    stamp ``t_emit`` on the simulated tier clock instead of the engine's
    ``time_fn``."""
    sid: str
    step: int                       # session step it reflects
    model: str                      # selected model name
    modalities: Tuple[str, ...]     # fused subset, canonical order
    kind: str                       # "partial" | "final"
    outputs: dict                   # head outputs (the sid's row)
    flush_id: int
    t_emit: float


@dataclass
class FlushReport:
    """What one flush did: arrivals drained, XLA dispatches, the single
    host sync's wall time, per-arrival latencies, and the emissions —
    ``predictions`` (tagged partial/final) and the last fused head
    outputs per touched session in ``recommendations`` (the batch-mode
    contract; identical host rows, different indexing)."""
    flush_id: int
    n_events: int
    n_encoder_calls: int
    n_tail_calls: int
    wall_s: float
    latencies: Dict[Tuple[str, int], float]     # (sid, event idx) -> s
    predictions: List[Prediction] = field(default_factory=list)
    recommendations: Dict[str, dict] = field(default_factory=dict)
    # padding-tax accounting: weighted position counts this flush's XLA
    # calls spent on real data vs bucket/batch padding (weights are each
    # submodule's parameter count — a MAC-proportional estimate, not a
    # hardware FLOP counter)
    flops_useful: float = 0.0
    flops_padded: float = 0.0

    @property
    def padded_flop_frac(self) -> float:
        total = self.flops_useful + self.flops_padded
        return self.flops_padded / total if total else 0.0


@dataclass
class SessionView:
    """Per-session state, one shape for every composition. Flush-mode
    engines use the intake/prediction fields; tiered placement adds the
    simulated-clock fields (``ready_at``, ``records``, ``t_*_emit``)."""
    sid: str
    inputs: Dict[str, object] = field(default_factory=dict)
    input_step: Dict[str, int] = field(default_factory=dict)
    step: int = 0
    dirty: set = field(default_factory=set)   # modalities changed since flush
    events_seen: int = 0
    last_recommendation: Optional[dict] = None
    predictions: List["Prediction"] = field(default_factory=list)
    finalized: bool = False                   # has emitted a final prediction
    t_first_submit: Optional[float] = None    # time_fn clock
    t_first_prediction: Optional[float] = None
    t_final_prediction: Optional[float] = None
    t_last_activity: Optional[float] = None   # last submit or emission
    # ---- tiered placement (simulated episode clock)
    ready_at: float = 0.0                     # per-session in-order processing
    records: List["TieredRecord"] = field(default_factory=list)
    t_first_arrival: Optional[float] = None   # survives record trimming
    t_first_emit: Optional[float] = None
    t_final_emit: Optional[float] = None


@dataclass
class TierHost:
    """One hardware tier with its own busy-until simulated clock."""
    name: str                   # display name ('glass' | 'edge')
    tier: str                   # key into ProfileTable.factors
    profile: ProfileTable
    free_at: float = 0.0
    busy_s: float = 0.0
    calls: int = 0
    tracer: Optional[Tracer] = None

    def __post_init__(self):
        if self.tracer is None:
            self.tracer = Tracer.disabled

    def time(self, submodule: str) -> float:
        return self.profile.time(submodule, self.tier)

    def occupy(self, duration: float, t_start: float,
               label: Optional[str] = None) -> Tuple[float, float]:
        """Book ``duration`` seconds of compute no earlier than
        ``t_start``; returns (start, done) on the simulated clock."""
        start = max(t_start, self.free_at)
        done = start + duration
        self.free_at = done
        self.busy_s += duration
        self.calls += 1
        if self.tracer:
            self.tracer.span(label or f"compute@{self.name}", "compute",
                             start, done, track=f"host:{self.name}",
                             host=self.name, queued_s=start - t_start)
        return start, done

    def release(self, start: float, done: float, t: float):
        """Unwind the un-run tail of the MOST RECENT booking: a
        speculative racer cancelled at commit instant ``t`` frees the
        host from ``max(start, t)`` on (cancel-on-commit — the loser
        stops computing the moment the winner's result lands). A no-op
        if something was booked after, so only the latest racer may be
        released."""
        if self.free_at != done:
            return
        cut = max(start, min(t, done))
        self.busy_s -= done - cut
        self.free_at = cut
        if self.tracer:
            self.tracer.instant("host.release", "speculation", t,
                                track=f"host:{self.name}", host=self.name,
                                freed_s=done - cut)


@dataclass
class _TierFault:
    """Crash / detection / restart state of one remote tier."""
    crash_at: Optional[float] = None     # when the box actually dies
    detect_at: Optional[float] = None    # first missed heartbeat after it
    rejoin_at: Optional[float] = None    # when a restarted box comes back
    dead: bool = False                   # the glasses KNOW it is gone


@dataclass
class TieredRecord:
    """Timeline of one arrival through tiered placement. The schema is
    tier-count-agnostic: ``tier`` names whichever host ran the encoder
    (any of the N configured hosts, not just 'glass'/'edge'), and
    per-submodule placement is broken out in ``enc_tier``/``tail_tier``
    (the tail may run on a third host, or nowhere when the modality
    subset is still incomplete)."""
    sid: str
    index: int
    modality: str
    model: Optional[str]
    tier: str                   # host that ran the encoder (bulk compute)
    kind: str                   # 'partial' | 'final'
    t_arrival: float
    t_start: float              # when the glasses picked the event up
    t_emit: float               # when the prediction reached the glasses
    uplink_s: float = 0.0       # payload + cache-sync transfer time
    downlink_s: float = 0.0     # feature + outputs return transfer time
    compute_s: float = 0.0
    fallback: bool = False      # a tier crashed mid-flight; re-ran on glass
    detect_s: float = 0.0       # stall waiting on missed-heartbeat detection
    decision: Optional[TierDecision] = None
    outputs: Optional[dict] = None
    # per-submodule placement (tail may differ from the encoder's host)
    enc_tier: Optional[str] = None
    tail_tier: Optional[str] = None             # None: no fusion ran
    tail_decision: Optional[TierDecision] = None
    # stream x tiered composition: the on-glass provisional prediction
    # emitted from cached features while this offload was in flight
    glass_partial: Optional[Prediction] = None
    # speculative dual placement: this arrival raced glass against the
    # best remote; the winner's timeline is the record's, the loser's
    # would-have-emitted instant is kept for the win-margin analysis
    speculative: bool = False
    race_winner: Optional[str] = None
    race_loser_emit: Optional[float] = None
    # numeric precision the encoder flight ran at ("fp32" | "int8") —
    # int8 means the sidecar-quantized encoder computed the feature and
    # the cache/wire carry its packed {"q", "scale"} form
    precision: str = "fp32"

    @property
    def latency_s(self) -> float:
        return self.t_emit - self.t_arrival


# ======================================================================
# Composable policies
# ======================================================================

_AUTO = "auto"      # BatchPolicy.bucketer sentinel: derive from the models


@dataclass
class BatchPolicy:
    """Cross-session coalescing knobs.

    ``bucketer="auto"`` derives per-modality length caps from the
    models' declared ``max_lengths`` (so padding never exceeds e.g. a
    positional table); pass an explicit :class:`Bucketer` to control the
    grid, or ``None`` to disable shape bucketing (tiered default).
    ``batch_bucket_min`` floors the coalesced batch axis so a steady
    session count compiles ONE batch shape.

    ``ragged=True`` switches variable-length modalities (text, vitals)
    from per-bucket stacked calls to the concatenated ragged layout
    (``core.bucketing.RaggedBatch``): ONE encoder call per modality per
    flush regardless of live length buckets, and ONE grouped fusion-tail
    call across all pending sessions and modality subsets (possible
    when the zoo shares one parameter pytree — ``share_encoders`` zoos;
    engines with per-model parameters keep the per-model tail loop).
    ``ragged_align`` is the packed rows' start alignment. Any alignment
    gives the same masked attention; XLA-CPU bit parity against the
    unbucketed reference needs it equal to the model's text
    ``flash_block`` (rows start on flash-block boundaries) and the
    model config run with ``use_flash_text=True, flash_segments=True``
    on both sides. Defaults OFF: the bucketed path stays the default
    fast path."""
    bucketer: Union[Bucketer, None, str] = _AUTO
    max_coalesce: int = 64
    batch_bucket_min: int = 1
    ragged: bool = False
    ragged_align: int = 8


@dataclass
class StreamPolicy:
    """Progressive-prediction and liveness knobs.

    ``deadline_s``: 0 flushes on every submit, > 0 buffers arrivals until
    the oldest pending one is that old, None leaves flushing entirely to
    the caller. ``idle_timeout_s``/``max_sessions`` drive cross-incident
    eviction — swept after every flush and ``poll()`` (wall clock), or
    after every arrival under tiered placement (simulated clock, where
    the wall-clock ``poll()`` must not sweep). ``glass_partials``
    (tiered composition only): emit an on-glass provisional partial
    from cached features while an offloaded arrival is in flight."""
    deadline_s: Optional[float] = 0.0
    idle_timeout_s: Optional[float] = None
    max_sessions: Optional[int] = None
    glass_partials: bool = True


@dataclass
class PlacementPolicy:
    """Tier placement knobs — two named tiers by default (the historical
    glass<->edge pair), or an ordered N-tier list.

    ``profile`` is the one-time offline profiling result; ``trace``
    drives both the heartbeat monitors (decisions) and the transport
    links (true wire bandwidth). ``tiers`` generalizes: an ordered list
    of ``ProfileTable.factors`` keys (e.g. ``("glass", "ph1",
    "edge64x")``) whose FIRST entry is the local host (the glasses);
    each remote's radio link defaults to ``trace`` and can be overridden
    per host via ``tier_traces``. With ``tiers`` set the engine also
    turns on the two N-tier capabilities by default:

      * ``contention_aware`` — the decision rule adds each host's
        current work-queue delay to its estimate, so concurrent
        sessions spread across tiers instead of stampeding the fastest;
      * ``tail_placement`` — the fusion tail is placed separately from
        the encoder that feeds it (a scene encoder can run on the edge
        box while its tail runs on the phone), paying the feature
        transfer between the two placements.

    Both default to the paper-verbatim contention-blind, co-located
    behavior when ``tiers`` is None (the legacy pair), keeping every
    historical timeline bit-reproducible; pass True/False to override
    either way. ``force`` pins placement for ablations: a host name
    pins everything, a ``{submodule: host}`` dict pins per submodule.
    ``adaptive=False`` always offloads to the cheapest remote.

    The two robustness rungs (both OFF by default so every historical
    timeline stays bit-reproducible):

      * ``speculation`` — a :class:`SpeculationPolicy` arming
        speculative dual placement: an arrival whose estimated
        completion leaves less than the configured margin before the
        deadline races glass against the best remote, commits whichever
        returns first, and cancels the loser (cancel-on-commit);
      * ``redispatch`` — when a tier dies with a flight outstanding,
        re-dispatch the lost flight to the next-best SURVIVING remote
        (falling back to glass only when none exists) instead of
        always re-running on glass.

    ``precision`` arms the quantized tier rung (OFF by default —
    ``None`` keeps every timeline bit-identical to the precision-less
    engine): a ``{host: "int8"}`` dict declares which hosts may run the
    int8 sidecar-quantized encoders. The placement argmin then
    enumerates (tier, precision) candidates JOINTLY — an int8 candidate
    scales a tier's encoder compute by ``int8_compute_scale`` and its
    feature-return bytes by ``int8_bytes_scale`` (the estimate; real
    flights ship the real packed bytes) — so the engine sends quantized
    features exactly when the uplink is the bottleneck and raw ones
    when it isn't. int8 flights commit the packed ``{"q", "scale"}``
    feature form to the cache (staleness semantics unchanged); consuming
    tails dequantize at gather time. Every model in the zoo must
    declare a ``quantize_fn`` or the engine refuses to build."""
    profile: ProfileTable
    trace: BandwidthTrace
    tiers: Optional[Tuple[str, ...]] = None
    tier_traces: Optional[Dict[str, BandwidthTrace]] = None
    glass_tier: str = "glass"
    edge_tier: str = "edge4c"
    hb_period: float = 1.0
    link_latency_s: float = 0.005
    adaptive: bool = True
    force: Optional[Union[str, Dict[str, str]]] = None
    contention_aware: Optional[bool] = None     # None = on iff N-tier
    tail_placement: Optional[bool] = None       # None = on iff N-tier
    speculation: Optional[SpeculationPolicy] = None
    redispatch: bool = False
    precision: Optional[Dict[str, str]] = None  # host -> "fp32" | "int8"
    int8_compute_scale: float = 0.5
    int8_bytes_scale: float = 0.25


@dataclass
class EngineSpec:
    """A fully-typed engine recipe: which policies are on, plus the
    engine-wide options. Produced from strings/dicts by
    :func:`parse_spec`; consumed by :func:`build_engine`."""
    batch: Optional[BatchPolicy] = None
    stream: Optional[StreamPolicy] = None
    placement: Optional[PlacementPolicy] = None
    share_encoders: bool = False
    max_history: Optional[int] = 256

    def enabled(self) -> Tuple[str, ...]:
        out = []
        if self.batch is not None:
            out.append("batch")
        if self.stream is not None:
            out.append("stream")
        if self.placement is not None:
            out.append("tiered")
        return tuple(out)


# ======================================================================
# The unified engine
# ======================================================================

class EMSServeEngine:
    """The one multi-session serving runtime over a ``SplitModel`` zoo.

    ``models``/``params`` are shared across sessions (one weight copy).
    Behavior composes from the policy objects — see the module docstring
    for the composition semantics. All public surface of the three
    legacy engines is preserved: ``submit``/``flush``/``poll``/``drain``
    /``run_episodes``/``run_arrivals``, the stats accessors, and the
    per-session views under ``sessions``.

    ``share_encoders=True`` is for zoos built by ``core.modular
    .emsnet_zoo`` whose subset models share one parameter pytree: a
    feature is encoded once *total* (cache keys are session-level)
    instead of once per consuming model (``"{sid}:{model}"`` keys, the
    per-event engine's discipline). ``time_fn`` is injectable so tests
    drive a fake wall clock; tiered placement runs on the simulated
    episode clock instead.
    """

    def __init__(self, models: Dict[str, SplitModel],
                 params: Dict[str, dict], *,
                 batch: Optional[BatchPolicy] = None,
                 stream: Optional[StreamPolicy] = None,
                 placement: Optional[PlacementPolicy] = None,
                 share_encoders: bool = False,
                 max_history: Optional[int] = 256,
                 time_fn: Callable[[], float] = time.perf_counter,
                 tracer: Optional[Tracer] = None):
        self.models = models
        self.params = params
        self.batch_policy = batch or BatchPolicy()
        self.stream_policy = stream
        self.placement_policy = placement
        self.share_encoders = share_encoders
        self.max_history = max_history
        self.time_fn = time_fn

        # ---- observability: one metrics registry for the whole stack
        # (engine + cache + transport), and a span tracer defaulting to
        # the falsy no-op so historical timelines replay bit-identically
        self.metrics = Metrics()
        self.tracer = tracer if tracer is not None else Tracer.disabled
        if self.tracer and placement is None and self.tracer.clock is None:
            # flush-mode engines run on the injected wall clock; tiered
            # engines call set_time() at each simulated-clock arrival
            self.tracer.clock = self.time_fn
        self.metrics.gauge_fn("engine.sessions_live",
                              lambda: len(self.sessions))
        self.metrics.gauge_fn("cache.entries", lambda: len(self.cache))
        # source-step metadata of the most recent _gather, consumed by
        # the fuse trace point (tracer-gated; {} when tracing is off)
        self._last_consumed: dict = {}

        # ---- batch policy -> coalescing state
        bucketer = self.batch_policy.bucketer
        if bucketer == _AUTO:
            # default grid only for flush-mode engines; tiered placement
            # historically runs unbucketed unless explicitly configured
            bucketer = (self._derive_bucketer(models)
                        if placement is None else None)
        self.bucketer: Optional[Bucketer] = bucketer
        self.max_coalesce = self.batch_policy.max_coalesce
        self.batch_bucket_min = self.batch_policy.batch_bucket_min
        self.ragged: Optional[RaggedBatch] = None
        if self.batch_policy.ragged:
            limits: Dict[str, int] = {}
            for sm in models.values():
                for m, n in sm.module.max_lengths.items():
                    limits[m] = min(limits.get(m, n), n)
            self.ragged = RaggedBatch(
                align=self.batch_policy.ragged_align,
                min_rows=self.batch_policy.batch_bucket_min,
                max_lengths=limits)
        # per-(model, subtree) parameter counts, the flop-estimate
        # weights for FlushReport's padding-tax accounting
        self._flop_w: Dict[Tuple[str, str], float] = {}
        # width -> the grouped tail's zero row for a missing modality
        self._zero_rows: Dict[int, jax.Array] = {}

        # ---- stream policy -> deadline / eviction state
        sp = stream
        self.deadline_s = sp.deadline_s if sp is not None else None
        self.idle_timeout_s = sp.idle_timeout_s if sp is not None else None
        self.max_sessions = sp.max_sessions if sp is not None else None
        self.glass_partials = bool(sp is not None and sp.glass_partials
                                   and placement is not None)

        # ---- shared session/cache state
        self.cache = FeatureCache(max_staleness=1, metrics=self.metrics,
                                  tracer=self.tracer)
        self.sessions: Dict[str, SessionView] = {}
        # every modality ANY model consumes: a prediction fusing all of
        # them cannot be refined further -> tagged "final"
        self.full_set = frozenset(m for sm in models.values()
                                  for m in sm.modalities())
        self._pending: List[Tuple[str, int, float]] = []  # (sid, idx, t_submit)
        self.flushes: List[FlushReport] = []              # bounded window
        self.events_total = 0
        self.flushes_total = 0
        self._enc_calls_total = 0
        self._tail_calls_total = 0

        # ---- placement policy -> tier hosts, link fabric, fault state
        self.records: List[TieredRecord] = []
        if placement is not None:
            pp = placement
            self.profile = pp.profile
            multi = pp.tiers is not None
            # host names double as ProfileTable factor keys in N-tier
            # mode; the legacy pair keeps its historical display names
            names = list(pp.tiers) if multi else ["glass", "edge"]
            keys = names if multi else [pp.glass_tier, pp.edge_tier]
            if len(names) < 2:
                raise ValueError("tiered placement needs the local host "
                                 "plus at least one remote tier")
            self.local_name = names[0]
            self.hosts: Dict[str, TierHost] = {
                n: TierHost(n, k, pp.profile, tracer=self.tracer)
                for n, k in zip(names, keys)}
            self.remote_names = names[1:]
            traces = {n: (pp.tier_traces or {}).get(n, pp.trace)
                      for n in self.remote_names}
            self.monitors = {n: HeartbeatMonitor(traces[n],
                                                 period=pp.hb_period)
                             for n in self.remote_names}
            self.fabric = TierFabric(self.local_name, traces,
                                     latency_s=pp.link_latency_s,
                                     metrics=self.metrics,
                                     tracer=self.tracer)
            # ---- quantized tier rung: validate the precision map up
            # front (a bad host name or a zoo without quantize_fn is a
            # configuration error, not a first-decision surprise), then
            # arm the policy's joint (tier, precision) enumeration only
            # when some host actually serves int8 — an all-fp32 map is
            # the legacy bit-identical rule
            prec_cfg = dict(pp.precision or {})
            for h, p in prec_cfg.items():
                if h not in names or p not in ("fp32", "int8"):
                    raise ValueError(
                        f"precision[{h!r}]={p!r}: unknown host or "
                        f"precision (hosts {sorted(names)}, "
                        "precisions fp32/int8)")
            int8_hosts = sorted(h for h, p in prec_cfg.items()
                                if p == "int8")
            if int8_hosts:
                for mname, sm in models.items():
                    if sm.module.quantize_fn is None:
                        raise ValueError(
                            f"precision={prec_cfg} needs an int8 variant "
                            f"of every model; {mname!r} declares no "
                            "quantize_fn")
            self.int8_compute_scale = pp.int8_compute_scale
            # fp32 pytree id() -> derived int8 sidecar pytree: derived
            # ONCE per distinct parameter pytree, so share_encoders zoos
            # (one pytree for the whole zoo) quantize exactly once
            self._qparams_cache: Dict[int, dict] = {}
            self.policy = MultiTierPolicy(
                pp.profile, self.monitors, local=self.local_name,
                tier_of={n: h.tier for n, h in self.hosts.items()},
                adaptive=pp.adaptive, force=pp.force,
                speculation=pp.speculation,
                precisions=({h: ("fp32", "int8") for h in int8_hosts}
                            if int8_hosts else None),
                int8_compute_scale=pp.int8_compute_scale,
                int8_bytes_scale=pp.int8_bytes_scale)
            self.redispatch = pp.redispatch
            # the fastest remote is the legacy 'edge' for the 2-tier
            # accessor surface (uplink/downlink/crash_at/...)
            self._primary = min(
                self.remote_names,
                key=lambda n: pp.profile.factors[self.hosts[n].tier])
            self.monitor = self.monitors[self._primary]
            # the two N-tier capabilities default on exactly when the
            # N-tier surface is used, so legacy timelines stay
            # bit-reproducible
            self.contention_aware = (multi if pp.contention_aware is None
                                     else pp.contention_aware)
            self.tail_placement = (multi if pp.tail_placement is None
                                   else pp.tail_placement)
            # per-tier replica freshness: (cache key, modality) ->
            # feature VERSION that host holds (versions only bump on
            # real re-encodes; steps get re-stamped by every touch,
            # which would force spurious re-ships)
            self._replica_versions: Dict[str, Dict[Tuple[str, str], int]] \
                = {n: {} for n in self.remote_names}
            # fault injection / detection / restart, per remote tier;
            # _schedule holds the not-yet-armed chaos cycles per tier
            self._faults: Dict[str, _TierFault] = {
                n: _TierFault() for n in self.remote_names}
            self._schedule: Dict[str, deque] = {}
            # placement / speculation tallies live on the metrics
            # registry; the historical attributes are read-through
            # properties (below) keyed off the host-name list
            self._host_names = list(names)
            self._total_latency = 0.0

    # ---- legacy counter attributes (read-through to the registry)
    @property
    def evicted_count(self) -> int:
        return int(self.metrics.get("engine.evicted_sessions"))

    @property
    def fallback_count(self) -> int:
        return int(self.metrics.get("placement.fallbacks"))

    @property
    def rejoin_count(self) -> int:
        return int(self.metrics.get("placement.rejoins"))

    @property
    def offloaded_count(self) -> int:
        return int(self.metrics.get("placement.offloaded"))

    @property
    def on_glass_count(self) -> int:
        return int(self.metrics.get("placement.on_glass"))

    @property
    def place_counts(self) -> Dict[str, int]:
        return {n: int(self.metrics.get(f"placement.enc.{n}"))
                for n in self._host_names}

    @property
    def tail_counts(self) -> Dict[str, int]:
        return {n: int(self.metrics.get(f"placement.tail.{n}"))
                for n in self._host_names}

    @property
    def spec_count(self) -> int:
        return int(self.metrics.get("speculation.races"))

    @property
    def spec_wins(self) -> Dict[str, int]:
        return {n: int(self.metrics.get(f"speculation.wins.{n}"))
                for n in self._host_names}

    @property
    def spec_crash_saves(self) -> int:
        return int(self.metrics.get("speculation.crash_saves"))

    @property
    def redispatch_count(self) -> int:
        return int(self.metrics.get("placement.redispatches"))

    def metrics_snapshot(self) -> dict:
        """One JSON-serializable snapshot of every counter, gauge, and
        latency histogram (p50/p95/p99) the stack accumulated."""
        return self.metrics.snapshot()

    # ------------------------------------------------------------ setup

    @staticmethod
    def _derive_bucketer(models: Dict[str, SplitModel]) -> Bucketer:
        """Hard caps from the models (e.g. the text positional table) so
        the default grid never pads past what they accept."""
        limits: Dict[str, int] = {}
        for sm in models.values():
            for m, n in sm.module.max_lengths.items():
                limits[m] = min(limits.get(m, n), n)
        return Bucketer(max_buckets=limits)

    @property
    def tiered(self) -> bool:
        return self.placement_policy is not None

    # ------------------------------------------------------------ intake

    def session(self, sid: str) -> SessionView:
        st = self.sessions.get(sid)
        if st is None:
            st = self.sessions[sid] = SessionView(sid)
        return st

    def ingest(self, arrival: Arrival, *, aggregate=None):
        """Canonical-typed intake: unpacks an :class:`Arrival`."""
        return self.submit(arrival.sid, arrival.event, arrival.payload,
                           aggregate=aggregate)

    def submit(self, sid: str, event: Event, payload, *, aggregate=None):
        """Record one arriving datum. ``aggregate(old, new) -> input``
        merges it into the modality's aggregated input (default:
        replace).

        Flush-mode (no placement): buffers the arrival and flushes if
        the deadline policy says so — returns the :class:`FlushReport`
        when one ran, else None. Tiered placement: processes the arrival
        end to end on the decided tier and returns its
        :class:`TieredRecord`."""
        if self.tiered:
            return self._submit_tiered(sid, event, payload,
                                       aggregate=aggregate)
        now = self.time_fn()
        st = self._intake(sid, event, payload, aggregate)
        st.t_last_activity = now
        if st.t_first_submit is None:
            st.t_first_submit = now
        if self.tracer:
            self.tracer.instant("arrival", "arrival", now,
                                track=f"session:{sid}", sid=sid,
                                index=event.index,
                                modality=event.modality, step=st.step)
        self._pending.append((sid, event.index, now))
        if self.deadline_s is None:
            return None
        if self.deadline_s <= 0.0:
            return self.flush()
        if now - self._pending[0][2] >= self.deadline_s:
            return self.flush()
        return None

    def _intake(self, sid: str, event: Event, payload,
                aggregate) -> SessionView:
        """Shared input-aggregation bookkeeping for both modes."""
        st = self.session(sid)
        st.step += 1
        m = event.modality
        old = st.inputs.get(m)
        st.inputs[m] = aggregate(old, payload) if aggregate else payload
        st.input_step[m] = st.step
        st.dirty.add(m)
        st.events_seen += 1
        self.events_total += 1
        return st

    def poll(self, now: Optional[float] = None) -> Optional[FlushReport]:
        """Flush if the oldest pending arrival has exceeded the
        deadline; also the idle hook where session eviction runs. No-op
        under tiered placement (nothing buffers there)."""
        if self.tiered:
            return None
        now = self.time_fn() if now is None else now
        if self._pending and self.deadline_s is not None \
                and now - self._pending[0][2] >= self.deadline_s:
            return self.flush()
        self.evict_sessions(now)
        return None

    def drain(self) -> Optional[FlushReport]:
        """Flush whatever is pending, deadline or not."""
        if self.tiered:
            return None
        return self.flush() if self._pending else None

    def pending_count(self) -> int:
        """Arrivals buffered but not yet flushed (the event-loop driver
        pumps poll() until this reaches zero)."""
        return len(self._pending)

    # ------------------------------------------------------------- flush

    def _cache_key(self, sid: str, model_name: str) -> str:
        return sid if self.share_encoders else f"{sid}:{model_name}"

    def _bucket_rows(self, n: int) -> int:
        return max(self.batch_bucket_min, next_pow2(n))

    def _consumers(self, m: str):
        return [(n, sm) for n, sm in self.models.items()
                if m in sm.modalities()]

    def _bucketed(self, m: str, x):
        return self.bucketer.fit(m, x) if self.bucketer else x

    def _encode_groups(self, sids):
        """Dirty (session, modality) work grouped by identical
        post-bucket shape: each group is one stacked encoder call.
        Modalities no model consumes are skipped BEFORE the bucketer
        sees them, so bucket/compile statistics count served groups
        only (an unconsumed modality used to inflate the histogram the
        bench reports). Returns the groups and the device operations
        the bucketer's pads and crops issued."""
        groups = defaultdict(list)  # (modality, shape) -> [(sid, payload, nat)]
        ops = 0
        for sid in sids:
            st = self.sessions[sid]
            for m in sorted(st.dirty):
                if not self._consumers(m):
                    continue
                x = st.inputs[m]
                p = self._bucketed(m, x)
                ops += self._issued(p, x)
                shape = (tuple(p["x"].shape) if isinstance(p, dict)
                         else tuple(p.shape))
                groups[(m, shape)].append((st.sid, p, self._nat_len(x)))
        return groups, ops

    @staticmethod
    def _issued(out, src=None) -> int:
        """Device operations that made ``out``: one per device array in
        it other than ``src`` (an input passed through unpadded). A host
        (numpy) array took none."""
        return sum(isinstance(a, jax.Array) and a is not src
                   for a in jax.tree_util.tree_leaves(out))

    def _stack(self, rows):
        """``stack_bucketed`` at the rows' batch bucket. Returns the batch
        and the device programs it issued (one per device leaf; none for
        a batch built in numpy), and counts the batch in
        ``engine.batches_host`` or ``engine.batches_device``."""
        stacked = stack_bucketed(rows, self._bucket_rows(len(rows)))
        ops = self._issued(stacked)
        self.metrics.inc("engine.batches_device" if ops
                         else "engine.batches_host")
        return stacked, ops

    @staticmethod
    def _nat_len(x) -> int:
        """Real (pre-padding) sequence length of a raw modality input:
        axis 1 for (B, S, ...) payloads, 1 for fixed-size vectors."""
        return int(x.shape[1]) if getattr(x, "ndim", 0) >= 2 else 1

    def _weight(self, name: str, key: str) -> float:
        """Parameter count of ``params[name][key]`` — the per-position
        weight of the padding-tax estimate in :class:`FlushReport`
        (1.0 when the subtree is not addressable)."""
        k = (name, key)
        w = self._flop_w.get(k)
        if w is None:
            p = self.params.get(name)
            sub = p.get(key) if isinstance(p, dict) else None
            w = float(sum(getattr(leaf, "size", 0)
                          for leaf in jax.tree_util.tree_leaves(sub))) or 1.0
            self._flop_w[k] = w
        return w

    def _run_encoder_chunk(self, m, sids, batch, upos, total_pos,
                           sync_targets, flush_id):
        """Run every consuming model's encoder over one prepared batch
        (stacked or packed), scatter rows into the feature cache, and
        account the padding tax. Returns (n_calls, useful, padded)."""
        runners = (self._consumers(m)[:1] if self.share_encoders
                   else self._consumers(m))
        useful, padded, outs = 0.0, 0.0, []
        with self.tracer.scope("flush.encode", "flush", flush_id=flush_id,
                               calls=len(runners)):
            for name, sm in runners:
                feats = sm.encoders[m](self.params[name], batch)
                outs.append((name, feats))
                w = self._weight(name, m)
                useful += w * upos
                padded += w * (total_pos - upos)
                sync_targets.append(feats)
        with self.tracer.scope("flush.scatter", "flush", flush_id=flush_id,
                               calls=len(runners) * len(sids)):
            for name, feats in outs:
                for i, sid in enumerate(sids):
                    st = self.sessions[sid]
                    self.cache.put(self._cache_key(sid, name), m,
                                   feats[i:i + 1], step=st.step,
                                   tier="glass")
        return len(runners), useful, padded

    def _flush_encode(self, touched, sync_targets, flush_id):
        """Bucketed encode: one stacked call per (modality, bucket[,
        chunk]) per consuming model."""
        with self.tracer.scope("flush.prep", "flush", flush_id=flush_id,
                               host=0) as phase:
            groups, ops = self._encode_groups(touched)
            phase.set(calls=ops)
        n_enc, useful, padded = 0, 0.0, 0.0
        for (m, _shape), items in groups.items():
            for c0 in range(0, len(items), self.max_coalesce):
                chunk = items[c0:c0 + self.max_coalesce]
                with self.tracer.scope("flush.prep", "flush",
                                       flush_id=flush_id) as phase:
                    stacked, ops = self._stack([p for _, p, _ in chunk])
                    phase.set(calls=ops, host=int(ops == 0))
                    lead = (stacked["x"] if isinstance(stacked, dict)
                            else stacked)
                    plen = lead.shape[1] if lead.ndim >= 2 else 1
                    upos = sum(min(nat, plen) for _, _, nat in chunk)
                c, u, pd = self._run_encoder_chunk(
                    m, [sid for sid, _, _ in chunk], stacked, upos,
                    lead.shape[0] * plen, sync_targets, flush_id)
                n_enc += c
                useful += u
                padded += pd
        return n_enc, useful, padded

    def _flush_encode_ragged(self, touched, sync_targets, flush_id):
        """Ragged encode: ONE packed call per variable-length modality
        (per chunk, per consuming model) regardless of how many length
        buckets are live; fixed-size modalities keep the stacked path."""
        n_enc, useful, padded = 0, 0.0, 0.0
        ragged_mods = defaultdict(list)      # m -> [(sid, raw, nat)]
        fixed = defaultdict(list)            # (m, shape) -> [(sid, raw, nat)]
        with self.tracer.scope("flush.prep", "flush", flush_id=flush_id,
                               calls=0, host=0):
            for sid in touched:
                st = self.sessions[sid]
                for m in sorted(st.dirty):
                    if not self._consumers(m):
                        continue
                    x = st.inputs[m]
                    if m in ("text", "vitals"):
                        ragged_mods[m].append((st.sid, x, self._nat_len(x)))
                    else:
                        fixed[(m, tuple(x.shape))].append(
                            (st.sid, x, self._nat_len(x)))
        for m, items in sorted(ragged_mods.items()):
            cap = self.ragged.max_lengths.get(m)
            for c0 in range(0, len(items), self.max_coalesce):
                chunk = items[c0:c0 + self.max_coalesce]
                with self.tracer.scope("flush.prep", "flush",
                                       flush_id=flush_id, calls=1, host=0):
                    packed = self.ragged.pack(m, [x for _, x, _ in chunk])
                    total = (packed["tokens"] if m == "text"
                             else packed["x"]).shape[1]
                    upos = sum(nat if cap is None else min(nat, cap)
                               for _, _, nat in chunk)
                c, u, pd = self._run_encoder_chunk(
                    m, [sid for sid, _, _ in chunk], packed, upos, total,
                    sync_targets, flush_id)
                n_enc += c
                useful += u
                padded += pd
        for (m, _shape), items in sorted(fixed.items()):
            for c0 in range(0, len(items), self.max_coalesce):
                chunk = items[c0:c0 + self.max_coalesce]
                with self.tracer.scope("flush.prep", "flush",
                                       flush_id=flush_id) as phase:
                    stacked, ops = self._stack([x for _, x, _ in chunk])
                    phase.set(calls=ops, host=int(ops == 0))
                    rows = (stacked["x"] if isinstance(stacked, dict)
                            else stacked).shape[0]
                c, u, pd = self._run_encoder_chunk(
                    m, [sid for sid, _, _ in chunk], stacked, len(chunk),
                    rows, sync_targets, flush_id)
                n_enc += c
                useful += u
                padded += pd
        return n_enc, useful, padded

    def _flush_tails(self, tail_groups, sync_targets, flush_id):
        """One batched tail call per selected model (per chunk). Returns
        the calls' outputs, the rows to emit from them, and the padding
        tax."""
        useful, padded = 0.0, 0.0
        tail_outs, to_emit = [], []
        for name, items in tail_groups.items():
            sm = self.models[name]
            mods = sm.modalities()
            w = self._weight(name, "heads")
            for c0 in range(0, len(items), self.max_coalesce):
                chunk = items[c0:c0 + self.max_coalesce]
                with self.tracer.scope("flush.prep", "flush",
                                       flush_id=flush_id) as phase:
                    stacked = self._stack_tail(
                        {mm: [f[mm] for _, f in chunk] for mm in mods},
                        phase)
                with self.tracer.scope("flush.tail", "flush",
                                       flush_id=flush_id, calls=1):
                    outs = sm.tail(self.params[name], stacked)
                    rows = next(iter(stacked.values())).shape[0]
                    useful += w * len(chunk)
                    padded += w * (rows - len(chunk))
                    sync_targets.append(outs)
                    tail_outs.append(outs)
                to_emit += self._scatter_tail_rows(
                    [(sid, name) for sid, _ in chunk], len(tail_outs) - 1,
                    flush_id)
        return tail_outs, to_emit, useful, padded

    def _stack_tail(self, columns, phase):
        """A tail call's input: one ``_stack`` per modality's column of
        feature rows. Sets ``calls`` and ``host`` on the ``phase`` span."""
        stacked, ops, host = {}, 0, 0
        for m, rows in columns.items():
            stacked[m], n = self._stack(rows)
            ops += n
            host += n == 0
        phase.set(calls=ops, host=host)
        return stacked

    def _zero_row(self, width: int):
        """The zero feature row a grouped tail puts in a missing
        modality's slice: made on the device once per width."""
        row = self._zero_rows.get(width)
        if row is None:
            row = self._zero_rows[width] = jnp.zeros((1, width), jnp.float32)
        return row

    def _scatter_tail_rows(self, chunk, call, flush_id):
        """Re-stamp the cache entries each row of tail call ``call``
        consumed. ``chunk`` lists (sid, model name) per row; returns the
        rows to emit as (sid, name, modalities, call, row index, step).
        The rows' outputs are cut on the host after the sync."""
        to_emit = []
        with self.tracer.scope("flush.scatter", "flush", flush_id=flush_id,
                               calls=0):
            for i, (sid, name) in enumerate(chunk):
                st = self.sessions[sid]
                mods = self.models[name].modalities()
                to_emit.append((sid, name, tuple(mods), call, i, st.step))
                for mm in mods:   # the result carries the cache back
                    self.cache.touch(self._cache_key(sid, name), mm,
                                     st.step)
        return to_emit

    def _grouped_tail_target(self, tail_groups) -> Optional[str]:
        """The ONE grouped tail is legal when a full-fusion model exists,
        declares its feature widths, and every pending model shares its
        parameter pytree (``share_encoders`` zoos): subset heads are
        then row-slices of the full heads, so a zero-filled slice for a
        missing modality contributes exactly zero to the fusion GEMM and
        the full tail reproduces every subset tail bit-for-bit. Returns
        the full model's name, or None to keep the per-model loop."""
        full_name = next((n for n, sm in self.models.items()
                          if frozenset(sm.modalities()) == self.full_set),
                         None)
        if full_name is None:
            return None
        dims = self.models[full_name].module.feature_dims
        if not all(m in dims for m in self.full_set):
            return None
        if not all(self.params[n] is self.params[full_name]
                   for n in tail_groups):
            return None
        return full_name

    def _flush_tails_grouped(self, tail_groups, full_name, sync_targets,
                             flush_id):
        """ONE stacked tail call for every pending (session, subset) —
        flush then issues O(modalities) + 1 kernels instead of
        O(modalities x buckets) + O(subsets). Each row is the full-width
        F_C with zeros in the slices of modalities outside that row's
        subset; the padding-tax account charges those zero slices as
        padding."""
        full_sm = self.models[full_name]
        full_mods = full_sm.modalities()
        dims = full_sm.module.feature_dims
        fullw = float(sum(dims[m] for m in full_mods))
        w = self._weight(full_name, "heads")
        rows = [(sid, name, f)
                for name, items in tail_groups.items()
                for sid, f in items]
        useful, padded = 0.0, 0.0
        tail_outs, to_emit = [], []
        for c0 in range(0, len(rows), self.max_coalesce):
            chunk = rows[c0:c0 + self.max_coalesce]
            nb = self._bucket_rows(len(chunk))
            with self.tracer.scope("flush.prep", "flush",
                                   flush_id=flush_id) as phase:
                stacked = self._stack_tail(
                    {m: [f.get(m, self._zero_row(dims[m]))
                         for _, _, f in chunk] for m in full_mods}, phase)
            with self.tracer.scope("flush.tail", "flush", flush_id=flush_id,
                                   calls=1):
                outs = full_sm.tail(self.params[full_name], stacked)
                sync_targets.append(outs)
                tail_outs.append(outs)
                subw = sum(sum(dims[m]
                               for m in self.models[name].modalities())
                           for _, name, _ in chunk) / fullw
                useful += w * subw
                padded += w * (nb - subw)
            to_emit += self._scatter_tail_rows(
                [(sid, name) for sid, name, _ in chunk], len(tail_outs) - 1,
                flush_id)
        return tail_outs, to_emit, useful, padded

    def flush(self) -> FlushReport:
        """Run all pending work: one batched encoder call per
        (modality, bucket[, chunk]) per consuming model (ONE total with
        ``share_encoders``), scatter rows into the feature cache, one
        batched tail per selected model, emit progressive predictions,
        sync the host ONCE.

        With a tracer, the ``flush`` span ``[t0, t1]`` holds disjoint
        phase spans (cat ``flush``, each with ``flush_id`` and ``calls``,
        the device array operations it issued: each pad or crop of a
        device input, stacked device leaf, pack, feature row slice,
        encoder and tail call, and tail output leaf fetched once; a fit
        or batch built in numpy on the host issues none): ``flush.prep``
        (model selection, cache reads, pads, grouping, stacks and packs;
        ``host`` counts its batches built in numpy),
        ``flush.encode`` and ``flush.tail`` (the program calls),
        ``flush.scatter`` (row slices into the cache, the rows'
        bookkeeping, and, after the sync, the one fetch of every tail
        output to the host with ``fetched`` leaves, where the emitted rows
        are cut) and ``flush.sync`` (the one host sync); ``flush.emit``
        runs from ``t1`` to the return (predictions, bookkeeping, metrics,
        eviction). ``t1`` is when the host holds the emitted numbers."""
        if self.tiered:
            raise RuntimeError(
                "flush() is a flush-mode operation; tiered placement "
                "processes each arrival in submit()")
        t0 = self.time_fn()
        flush_id = self.flushes_total
        tr = self.tracer
        sync_targets = []
        # every dirty marking comes with a _pending entry, so only the
        # pending sessions can have work — never scan the whole (ever-
        # growing) session table on the latency-critical path
        touched = sorted({sid for sid, _, _ in self._pending})

        # ---- batched encode + scatter rows into the feature cache
        if self.ragged is not None:
            n_enc, enc_u, enc_p = self._flush_encode_ragged(
                touched, sync_targets, flush_id)
        else:
            n_enc, enc_u, enc_p = self._flush_encode(touched, sync_targets,
                                                     flush_id)

        # ---- progressive re-fusion: batched tails per selected model
        with tr.scope("flush.prep", "flush", flush_id=flush_id, calls=0,
                      host=0):
            tail_groups = defaultdict(list)    # model -> [(sid, feats)]
            consumed_meta: Dict[Tuple[str, str], dict] = {}
            for sid in touched:
                st = self.sessions[sid]
                if not st.dirty:
                    continue
                st.dirty.clear()
                name = select_model(self.models, st.inputs)
                if name is None:
                    continue
                sm = self.models[name]
                feats = self.cache.features(self._cache_key(st.sid, name),
                                            sm.modalities(),
                                            input_steps=st.input_step)
                if feats is not None:
                    tail_groups[name].append((st.sid, feats))
                    if tr:
                        # snapshot source steps BEFORE the tail path
                        # re-stamps them via cache.touch
                        key = self._cache_key(st.sid, name)
                        consumed_meta[(st.sid, name)] = {
                            m: [self.cache.peek(key, m).step,
                                st.input_step.get(m, 0)]
                            for m in sm.modalities()}
            full_name = (self._grouped_tail_target(tail_groups)
                         if self.ragged is not None and tail_groups
                         else None)
        if full_name is not None:
            tail_outs, to_emit, tail_u, tail_p = self._flush_tails_grouped(
                tail_groups, full_name, sync_targets, flush_id)
        else:
            tail_outs, to_emit, tail_u, tail_p = self._flush_tails(
                tail_groups, sync_targets, flush_id)
        n_tail = len(tail_outs)

        # ---- the ONE host sync of this flush
        with tr.scope("flush.sync", "flush", flush_id=flush_id, calls=0):
            jax.block_until_ready(sync_targets)
        # ---- every tail output to the host in one fetch; each emitted
        # row is a numpy view of its call's output
        fetched = len(jax.tree_util.tree_leaves(tail_outs))
        with tr.scope("flush.scatter", "flush", flush_id=flush_id,
                      calls=fetched, fetched=fetched):
            host = jax.device_get(tail_outs)
            emitted = [(sid, name, mods,
                        jax.tree.map(lambda a: a[i:i + 1], host[call]), step)
                       for sid, name, mods, call, i, step in to_emit]
        t1 = self.time_fn()

        with tr.scope("flush.emit", "flush", at=t1, flush_id=flush_id,
                      calls=0):
            predictions, recommendations = [], {}
            for sid, name, mods, row, step in emitted:
                kind = ("final" if frozenset(mods) == self.full_set
                        else "partial")
                pred = Prediction(sid=sid, step=step, model=name,
                                  modalities=mods, kind=kind, outputs=row,
                                  flush_id=flush_id, t_emit=t1)
                st = self.sessions[sid]
                self._record_prediction(st, pred)
                predictions.append(pred)
                recommendations[sid] = row
                if tr:
                    key = self._cache_key(sid, name)
                    tr.instant(
                        "fuse", "fusion", t1, track=f"session:{sid}",
                        sid=sid, key=key, model=name, step=step,
                        consumed=consumed_meta.get((sid, name), {}))
                    tr.instant(
                        "emit", "predict", t1, track=f"session:{sid}",
                        sid=sid, key=key, model=name, step=step, kind=kind,
                        modalities=sorted(mods))

            # keyed by arrival with the EARLIEST submit kept: a duplicate
            # submission of the same (sid, idx) used to overwrite the
            # first latency entry and double-count n_events
            arrived: Dict[Tuple[str, int], float] = {}
            for sid, idx, ts in self._pending:
                arrived.setdefault((sid, idx), ts)
            latencies = {key: t1 - ts for key, ts in arrived.items()}
            report = FlushReport(
                flush_id=flush_id, n_events=len(arrived),
                n_encoder_calls=n_enc, n_tail_calls=n_tail,
                wall_s=t1 - t0, latencies=latencies,
                predictions=predictions, recommendations=recommendations,
                flops_useful=enc_u + tail_u, flops_padded=enc_p + tail_p)
            if tr:
                for (sid, idx), ts in arrived.items():
                    tr.span("queue.wait", "queue", ts, t0,
                            track=f"session:{sid}", sid=sid, index=idx,
                            flush_id=flush_id)
                tr.span("flush", "flush", t0, t1, track="engine",
                        flush_id=flush_id, n_events=len(arrived),
                        n_encoder_calls=n_enc, n_tail_calls=n_tail)
            self.metrics.inc("engine.flushes")
            self.metrics.inc("engine.flush_events", len(arrived))
            self.metrics.inc("engine.rows_host", len(emitted))
            self.metrics.observe("flush.wall_s", t1 - t0)
            for lat in latencies.values():
                self.metrics.observe("serve.latency_s", lat)
            self._pending.clear()
            self.flushes.append(report)
            if self.max_history is not None:
                del self.flushes[:-self.max_history]
            self.flushes_total += 1
            self._enc_calls_total += n_enc
            self._tail_calls_total += n_tail
            self.evict_sessions(t1)
            return report

    def _record_prediction(self, st: SessionView, pred: Prediction):
        """Session-side bookkeeping shared by flush- and tiered-mode
        emissions."""
        st.predictions.append(pred)
        if self.max_history is not None:
            del st.predictions[:-self.max_history]
        st.last_recommendation = pred.outputs
        st.t_last_activity = pred.t_emit if self.tiered else self.time_fn()
        if pred.kind == "final":
            st.finalized = True
            if st.t_final_prediction is None:
                st.t_final_prediction = pred.t_emit
        if st.t_first_prediction is None:
            st.t_first_prediction = pred.t_emit
            if not self.tiered and st.t_first_submit is not None:
                self.metrics.observe("serve.ttfp_s",
                                     pred.t_emit - st.t_first_submit)

    # ---------------------------------------------------------- eviction

    def _evict(self, sid: str):
        keys = ([sid] if self.share_encoders
                else [f"{sid}:{n}" for n in self.models])
        for key in keys:
            self.cache.drop_session(key)
        if self.tiered:
            # forget every tier replica's versions too: a re-created
            # session restarts its version counters at 0, and a stale
            # high-water mark would wrongly skip re-shipping features
            dropped = set(keys)
            for versions in self._replica_versions.values():
                for k in [k for k in versions if k[0] in dropped]:
                    del versions[k]
        del self.sessions[sid]
        self.metrics.inc("engine.evicted_sessions")
        if self.tracer:
            self.tracer.instant("evict", "session", track="engine",
                                sid=sid, keys=keys)

    def evict_sessions(self, now: Optional[float] = None) -> int:
        """Cross-incident eviction sweep; returns how many sessions
        left. A session is evictable only when it has no pending
        arrivals and no un-flushed dirty modalities — eviction never
        drops work. Idle timeout first, then LRU down to
        ``max_sessions``: least-recently-active leaves first, so a
        finalized incident that is still streaming updates outlives an
        abandoned partial one (finalized only breaks activity ties)."""
        if self.idle_timeout_s is None and self.max_sessions is None:
            return 0
        now = self.time_fn() if now is None else now
        pending_sids = {sid for sid, _, _ in self._pending}
        evictable = [st for sid, st in self.sessions.items()
                     if sid not in pending_sids and not st.dirty]
        n0 = self.evicted_count
        if self.idle_timeout_s is not None:
            for st in list(evictable):
                last = (st.t_last_activity if st.t_last_activity is not None
                        else st.t_first_submit)
                if last is not None and now - last >= self.idle_timeout_s:
                    self._evict(st.sid)
                    evictable.remove(st)
        if self.max_sessions is not None \
                and len(self.sessions) > self.max_sessions:
            evictable.sort(key=lambda st: (st.t_last_activity or 0.0,
                                           not st.finalized))
            excess = len(self.sessions) - self.max_sessions
            for st in evictable[:excess]:
                self._evict(st.sid)
        return self.evicted_count - n0

    # ==================================================================
    # Tiered placement path (per-arrival on the simulated tier clocks)
    # ==================================================================

    # ----- legacy 2-tier accessor surface (maps onto the fastest remote)

    @property
    def glass(self) -> TierHost:
        return self.hosts[self.local_name]

    @property
    def edge(self) -> TierHost:
        return self.hosts[self._primary]

    @property
    def uplink(self):
        return self.fabric.channel(self.local_name, self._primary)

    @property
    def downlink(self):
        return self.fabric.channel(self._primary, self.local_name)

    @property
    def crash_at(self) -> Optional[float]:
        return self._faults[self._primary].crash_at

    @property
    def detect_at(self) -> Optional[float]:
        return self._faults[self._primary].detect_at

    @property
    def edge_known_dead(self) -> bool:
        return self._faults[self._primary].dead

    @property
    def _edge_versions(self) -> Dict[Tuple[str, str], int]:
        return self._replica_versions[self._primary]

    # ----- fault injection / detection / rejoin

    def inject_crash(self, t: float, tier: Optional[str] = None, *,
                     rejoin_at: Optional[float] = None):
        """Tier ``tier`` (default: the fastest remote) dies at simulated
        time ``t``. The glasses learn of it at the first missed
        heartbeat strictly after ``t``. With ``rejoin_at``, a restarted
        box comes back at that time: it re-warms its feature-cache
        replica from the glass-side versioned cache and becomes eligible
        for placement again."""
        tier = self._primary if tier is None else tier
        f = self._faults[tier]
        f.crash_at = t
        period = self.monitors[tier].period
        f.detect_at = (math.floor(t / period) + 1) * period
        if self.tracer:
            self.tracer.instant("crash.inject", "fault", t,
                                track=f"host:{tier}", tier=tier,
                                detect_at=f.detect_at,
                                rejoin_at=rejoin_at)
        if rejoin_at is not None:
            self.schedule_rejoin(rejoin_at, tier)

    def inject_edge_crash(self, t: float):
        self.inject_crash(t)

    def inject_schedule(self, schedule):
        """Install a multi-cycle crash/rejoin schedule (an iterable of
        :class:`repro.serving.chaos.FaultEvent`, e.g. from
        ``chaos_schedule``). The first cycle of each tier arms
        immediately; each subsequent cycle arms when the previous one's
        rejoin completes, so repeated crash -> re-dispatch/fallback ->
        rejoin -> re-warm rounds replay on the simulated clock."""
        from repro.serving.chaos import validate_schedule
        entries = validate_schedule(list(schedule))
        unknown = {e.tier for e in entries} - set(self.remote_names)
        if unknown:
            raise ValueError(f"schedule names unknown tier(s) "
                             f"{sorted(unknown)}; remotes are "
                             f"{self.remote_names}")
        for e in entries:
            self._schedule.setdefault(e.tier, deque()).append(e)
        for n in list(self._schedule):
            if self._faults[n].crash_at is None:
                self._install_next_fault(n)

    def _install_next_fault(self, tier: str):
        q = self._schedule.get(tier)
        if q:
            e = q.popleft()
            self.inject_crash(e.crash_at, tier, rejoin_at=e.rejoin_at)

    def schedule_rejoin(self, t: float, tier: Optional[str] = None):
        tier = self._primary if tier is None else tier
        f = self._faults[tier]
        if f.crash_at is not None and t <= f.crash_at:
            raise ValueError(f"rejoin at {t} precedes the crash at "
                             f"{f.crash_at}")
        f.rejoin_at = t

    def _mark_dead(self, tier: str):
        self._faults[tier].dead = True
        self._replica_versions[tier].clear()   # that replica is gone
        self.metrics.inc("fault.crashes_detected")
        if self.tracer:
            f = self._faults[tier]
            self.tracer.instant(
                "crash.detect", "fault",
                f.detect_at if f.detect_at is not None else self.tracer.now(),
                track=f"host:{tier}", tier=tier, crash_at=f.crash_at)

    def _rejoin(self, tier: str, t: float):
        """A restarted tier comes back: fresh fault state, fresh busy
        clock, and a replica re-warm shipped from the glass-side
        versioned cache (one bulk message on its link at the rejoin
        instant), after which it is placement-eligible again."""
        self._faults[tier] = _TierFault()
        host = self.hosts[tier]
        # a restarted box boots idle: anything still on its clock is
        # phantom occupancy from flights the crash already lost
        host.free_at = t
        versions = self._replica_versions[tier]
        warm_b = 0
        for (key, m), e in self.cache.entries():
            if versions.get((key, m), -1) < e.version:
                warm_b += payload_nbytes(e.feature)
                versions[(key, m)] = e.version
        if warm_b:
            self.fabric.channel(self.local_name, tier).send(warm_b, t)
        self.metrics.inc("placement.rejoins")
        if self.tracer:
            self.tracer.instant("rejoin", "fault", t,
                                track=f"host:{tier}", tier=tier,
                                warm_bytes=warm_b)

    def _usable_remotes(self, now: float) -> List[str]:
        """Remote tiers a decision made at ``now`` may target, applying
        any heartbeat detection or restart the clock has crossed. Under
        a chaos schedule, a rejoin arms the tier's NEXT scheduled cycle
        and the loop re-checks — several crash/rejoin rounds may have
        elapsed between two arrivals."""
        out = []
        for n in self.remote_names:
            while True:
                f = self._faults[n]
                if not f.dead and f.detect_at is not None \
                        and now >= f.detect_at:
                    self._mark_dead(n)
                if f.dead and f.rejoin_at is not None \
                        and now >= f.rejoin_at:
                    self._rejoin(n, f.rejoin_at)
                    self._install_next_fault(n)
                    continue
                break
            if not self._faults[n].dead:
                out.append(n)
        return out

    def _dies_before(self, tier: str, t: float) -> bool:
        """Does ``tier`` crash before simulated time ``t``? (A sender
        must survive through the END of its own transmission.)"""
        f = self._faults.get(tier)
        return (f is not None and f.crash_at is not None
                and f.crash_at < t)

    def _queues(self, now: float) -> Optional[Dict[str, float]]:
        """Per-host queueing delay feeding contention-aware decisions
        (None = the contention-blind paper rule)."""
        if not self.contention_aware:
            return None
        return {n: max(0.0, h.free_at - now)
                for n, h in self.hosts.items()}

    def _payload_bytes(self, m: str, payload) -> int:
        """Raw sensor bytes for the uplink: the module's declared size
        (audio clip / camera frame, not the tokenized tensor) when
        available, else the actual array bytes."""
        for _n, sm in self._consumers(m):
            b = sm.module.payload_bytes.get(m)
            if b:
                return b
        return payload_nbytes(payload)

    def _enc_duration(self, m: str, n_runners: int, host: TierHost,
                      precision: str = "fp32") -> float:
        """Simulated seconds the tier spends encoding modality ``m`` for
        ``n_runners`` consuming models: expensive text encoders run in
        parallel, cheap ones serially (paper Fig. 8-right — matching
        ``core.engine.EMSServe``). int8 flights scale by the SAME
        ``int8_compute_scale`` the placement estimate used, so the
        decision and the booking agree."""
        per = host.time(f"enc:{m}")
        if precision == "int8":
            per *= self.int8_compute_scale
        return per if m == "text" else per * n_runners

    def _feat_bytes_est(self, m: str) -> int:
        """A-priori fp32 size of modality ``m``'s encoded feature (the
        declared feature width x 4 bytes) — what the joint precision
        enumeration scales by ``int8_bytes_scale`` BEFORE the encoder
        has run. Real flights then ship the real packed bytes."""
        for _n, sm in self._consumers(m):
            d = sm.module.feature_dims.get(m)
            if d:
                return 4 * int(d)
        return 0

    # ----------------------------------------------------- real numerics
    #
    # The numerics are split into run / commit phases so the edge fault
    # path can execute the real jitted calls (placement never changes
    # the math) yet leave the glass-side cache untouched when the edge
    # dies before its result makes it back.

    def _quantized_params(self, name: str) -> dict:
        """The int8 sidecar pytree for model ``name``, derived lazily
        and cached per DISTINCT fp32 pytree (id()-keyed): a
        share_encoders zoo whose subsets all alias one parameter pytree
        quantizes once total. The sidecar's fp32 leaves are shared by
        reference with the source, so nothing doubles in memory but the
        int8 weights themselves."""
        src = self.params[name]
        qp = self._qparams_cache.get(id(src))
        if qp is None:
            qp = self._qparams_cache[id(src)] = \
                self.models[name].quantize_params(src)
        return qp

    def _run_encoders(self, st: SessionView, m: str,
                      precision: str = "fp32") -> Dict[str, object]:
        """Real jitted encoder run(s) for the arriving modality; returns
        ``{model_name: feature}`` WITHOUT touching the cache. An int8
        flight runs the SAME jitted encoder over the sidecar pytree
        (``layers.dense`` dispatches on the leaf form) and returns the
        packed ``{"q", "scale"}`` wire form — what the cache commits
        and the downlink sizes."""
        consumers = self._consumers(m)
        if not consumers:
            return {}
        runners = consumers[:1] if self.share_encoders else consumers
        enc_in = self._bucketed(m, st.inputs[m])
        if precision == "int8":
            return {name: quantize_feature(
                        sm.encoders[m](self._quantized_params(name), enc_in))
                    for name, sm in runners}
        return {name: sm.encoders[m](self.params[name], enc_in)
                for name, sm in runners}

    def _commit_features(self, st: SessionView, m: str, feats, tier: str):
        for name, feat in feats.items():
            self.cache.put(self._cache_key(st.sid, name), m, feat,
                           step=st.step, tier=tier)

    def _gather(self, st: SessionView, model_name: str, m: str, feats):
        """The selected model's input features — the arriving modality
        from the fresh (possibly uncommitted) ``feats``, everything else
        from the glass cache with the <=1-step staleness invariant
        asserted on every read. None while the subset is incomplete."""
        sm = self.models[model_name]
        key = self._cache_key(st.sid, model_name)
        fresh = (next(iter(feats.values()), None) if self.share_encoders
                 else feats.get(model_name))
        out = {}
        consumed = {}
        # packed int8 features (fresh or cached) unpack here, at the
        # consuming tier, right before fusion; raw features pass through
        # untouched (dequantize_feature is the identity on them)
        for mm in sm.modalities():
            if mm == m and fresh is not None:
                out[mm] = dequantize_feature(fresh)
                # the fresh feature carries this very step; its commit
                # lands before the fuse is recorded
                consumed[mm] = [st.step, st.input_step.get(mm, st.step)]
                continue
            e = self.cache.get(key, mm, input_step=st.input_step.get(mm))
            if e is None:
                return None
            out[mm] = dequantize_feature(e.feature)
            consumed[mm] = [e.step, st.input_step.get(mm, e.step)]
        if self.tracer:
            self._last_consumed = consumed
        return out

    def _touch_consumed(self, st: SessionView, model_name: str):
        """The result carries the cache back (paper fault tolerance):
        re-stamp every consumed entry at this step."""
        key = self._cache_key(st.sid, model_name)
        for mm in self.models[model_name].modalities():
            self.cache.touch(key, mm, st.step)

    # ------------------------------------------------------------- event

    def _submit_tiered(self, sid: str, event: Event, payload, *,
                       aggregate=None) -> TieredRecord:
        """Process one arriving datum end to end: decide a tier per
        submodule, encode there, transport, re-fuse, emit on glass. With
        the stream policy's ``glass_partials``, an offloaded arrival
        also yields an immediate on-glass provisional partial from
        cached features."""
        prev_observed = set(self.session(sid).inputs)
        st = self._intake(sid, event, payload, aggregate)
        st.dirty.clear()        # per-arrival mode: nothing buffers

        t_a = event.arrival_time
        if st.t_first_arrival is None:
            st.t_first_arrival = t_a
        now = max(t_a, st.ready_at)
        sess = f"session:{sid}"
        if self.tracer:
            self.tracer.set_time(now)
            self.tracer.instant("arrival", "arrival", t_a, track=sess,
                                sid=sid, index=event.index,
                                modality=event.modality, step=st.step)
            if now > t_a:
                # per-session in-order processing: this arrival waits
                # for the previous record's emit
                self.tracer.span("queue.wait", "queue", t_a, now,
                                 track=sess, sid=sid, index=event.index)
        model_name = select_model(self.models, st.inputs)
        payload_b = self._payload_bytes(event.modality, st.inputs[event.modality])
        avail = self._usable_remotes(now)
        queues = self._queues(now)
        dec = self.policy.decide(f"enc:{event.modality}", payload_b, now,
                                 queues=queues, available=avail,
                                 lateness_s=max(0.0, now - t_a),
                                 feat_bytes=self._feat_bytes_est(
                                     event.modality))
        if self.tracer:
            # the precision attr only appears when the joint rung is
            # armed, so precision-less traces stay byte-identical
            extra = ({"precision": dec.precision}
                     if self.policy.precisions is not None else {})
            self.tracer.instant("decide", "placement", now, track=sess,
                                sid=sid, submodule=f"enc:{event.modality}",
                                tier=dec.tier, speculate=dec.speculate,
                                best_remote=dec.best_remote, **extra)

        partial = None
        if dec.speculate and dec.best_remote is not None:
            # deadline margin too thin to trust the estimate: race glass
            # against the best remote, commit the first result, cancel
            # the loser. The glass racer IS the immediate answer, so no
            # separate provisional partial; the race also supersedes
            # tail splitting (both racers run encoder+tail co-located).
            rec = self._race_event(st, event, model_name, payload_b,
                                   now, dec, dec.best_remote)
        else:
            if dec.tier != self.local_name and self.glass_partials:
                partial = self._glass_provisional(st, prev_observed, now)
            if self.tail_placement:
                rec = self._placed_event(st, event, model_name, payload_b,
                                         now, dec, avail, queues,
                                         prev_observed)
            elif dec.tier != self.local_name:
                rec = self._remote_event(st, event, model_name, payload_b,
                                         now, dec, dec.tier)
            else:
                rec = self._glass_event(st, event, model_name, now, dec)
        if partial is not None:
            rec.glass_partial = partial

        st.ready_at = rec.t_emit
        st.t_last_activity = rec.t_emit        # simulated clock
        st.records.append(rec)
        self.records.append(rec)
        if self.max_history is not None:
            del st.records[:-self.max_history]
            del self.records[:-self.max_history]
        self._total_latency += rec.latency_s
        self.metrics.observe("serve.latency_s", rec.latency_s)
        if self.tracer:
            self.tracer.span(
                f"{rec.modality}#{rec.index}", "lifecycle",
                rec.t_arrival, rec.t_emit, track=sess, sid=sid,
                index=rec.index, modality=rec.modality,
                enc_tier=rec.enc_tier, tail_tier=rec.tail_tier,
                kind=rec.kind, fallback=rec.fallback,
                speculative=rec.speculative, detect_s=rec.detect_s)
        if rec.outputs is not None:
            if st.t_first_emit is None:
                st.t_first_emit = rec.t_emit
                if st.t_first_arrival is not None:
                    self.metrics.observe("serve.ttfp_s",
                                         rec.t_emit - st.t_first_arrival)
            if rec.kind == "final" and st.t_final_emit is None:
                st.t_final_emit = rec.t_emit
            if self.tracer:
                key = self._cache_key(sid, rec.model)
                self.tracer.instant(
                    "fuse", "fusion", rec.t_emit, track=sess, sid=sid,
                    key=key, model=rec.model, step=st.step,
                    consumed=self._last_consumed)
                self.tracer.instant(
                    "emit", "predict", rec.t_emit, track=sess, sid=sid,
                    key=key, model=rec.model, step=st.step, kind=rec.kind,
                    modalities=sorted(self.models[rec.model].modalities()))
            if self.stream_policy is not None:
                self._record_prediction(st, Prediction(
                    sid=st.sid, step=st.step, model=rec.model,
                    modalities=tuple(self.models[rec.model].modalities()),
                    kind=rec.kind, outputs=rec.outputs, flush_id=-1,
                    t_emit=rec.t_emit))
        # cross-incident eviction on the SIMULATED clock (every activity
        # timestamp in this mode is a t_emit, so wall-clock poll() must
        # not sweep here — the per-arrival hook is the only safe one)
        self.evict_sessions(rec.t_emit)
        return rec

    def _glass_provisional(self, st: SessionView, prev_observed: set,
                           now: float) -> Optional[Prediction]:
        """Stream x tiered composition: while the edge refreshes the
        arriving modality, the glasses immediately re-fuse what they
        already hold — every feature read from the cache with the
        <=1-step staleness invariant asserted (the arriving modality's
        cached feature is exactly one step behind its input now, the
        paper's tolerated bound). Tagged ``partial`` always: it never
        reflects the newest datum. No cache touch — provisional serving
        must not mask real staleness from later reads."""
        name = select_model(self.models, prev_observed)
        if name is None:
            return None
        sm = self.models[name]
        feats = self.cache.features(self._cache_key(st.sid, name),
                                    sm.modalities(),
                                    input_steps=st.input_step)
        if feats is None:
            return None
        feats = {mm: dequantize_feature(f) for mm, f in feats.items()}
        outputs = sm.tail(self.params[name], feats)
        _start, done = self.glass.occupy(self.glass.time("tail"), now,
                                         label="tail@glass:provisional")
        pred = Prediction(sid=st.sid, step=st.step, model=name,
                          modalities=tuple(sm.modalities()), kind="partial",
                          outputs=outputs, flush_id=-1, t_emit=done)
        self._record_prediction(st, pred)
        if self.tracer:
            key = self._cache_key(st.sid, name)
            sess = f"session:{st.sid}"
            consumed = {mm: [self.cache.peek(key, mm).step,
                             st.input_step.get(mm, 0)]
                        for mm in sm.modalities()}
            self.tracer.instant("fuse", "fusion", done, track=sess,
                                sid=st.sid, key=key, model=name,
                                step=st.step, consumed=consumed,
                                provisional=True)
            self.tracer.instant("emit", "predict", done, track=sess,
                                sid=st.sid, key=key, model=name,
                                step=st.step, kind="partial",
                                modalities=sorted(sm.modalities()),
                                provisional=True)
        if st.t_first_emit is None or done < st.t_first_emit:
            if st.t_first_emit is None and st.t_first_arrival is not None:
                self.metrics.observe("serve.ttfp_s",
                                     done - st.t_first_arrival)
            st.t_first_emit = done
        return pred

    def _kind(self, model_name: Optional[str]) -> str:
        if model_name is None:
            return "partial"
        mods = frozenset(self.models[model_name].modalities())
        return "final" if mods == self.full_set else "partial"

    def _sync_bytes(self, tier: str, st: SessionView,
                    model_name: Optional[str], *, skip: str):
        """Bytes needed to bring ``tier``'s replica up to date on every
        cached feature the selected model consumes (except ``skip``, the
        freshly arriving modality), plus the (replica key, version)
        pairs to stamp once the path succeeds."""
        sync_b, synced = 0, []
        if model_name is not None:
            versions = self._replica_versions[tier]
            key = self._cache_key(st.sid, model_name)
            for mm in self.models[model_name].modalities():
                if mm == skip:
                    continue
                e = self.cache.peek(key, mm)
                if e is not None and \
                        versions.get((key, mm), -1) < e.version:
                    sync_b += payload_nbytes(e.feature)
                    synced.append(((key, mm), e.version))
        return sync_b, synced

    def _stamp_fresh(self, tier: str, st: SessionView, m: str):
        """``tier``'s replica now holds the fresh feature(s) of ``m``."""
        versions = self._replica_versions[tier]
        for name in self.models:
            key = self._cache_key(st.sid, name)
            e = self.cache.peek(key, m)
            if e is not None:
                versions[(key, m)] = e.version

    def _crash_fallback(self, tier: str, st: SessionView, event: Event,
                        model_name: Optional[str], now: float,
                        dec: TierDecision, *, payload_b: Optional[int] = None,
                        feats=None, outputs=None) -> TieredRecord:
        """A remote participant died before its transmission completed:
        mark it dead at the first missed heartbeat, then re-dispatch the
        lost flight. With ``redispatch`` on and a surviving remote
        available, the flight goes to the next-best surviving remote
        (a fresh placement decision at the detection instant, restricted
        to survivors); otherwise — or when the policy rung is off — it
        re-runs on glass. Either way the already-computed numerics are
        reused: placement never changes the math, so the re-run's
        arrays are the in-flight ones. Cascading crashes recurse — a
        re-dispatch target that also dies falls through again until a
        survivor (ultimately glass) emits."""
        t_detect = max(now, self._faults[tier].detect_at)
        self._mark_dead(tier)
        detect_s = max(0.0, t_detect - now)
        if self.redispatch and payload_b is not None:
            survivors = self._usable_remotes(t_detect)
            if survivors:
                dec2 = self.policy.decide(
                    f"enc:{event.modality}", payload_b, t_detect,
                    queues=self._queues(t_detect), available=survivors)
                B = dec2.best_remote
                if B is not None:
                    # the re-aimed flight reuses the dead tier's
                    # already-computed arrays, so it keeps the original
                    # decision's precision whatever the survivors prefer
                    dec2 = replace(dec2, precision=dec.precision)
                    self.metrics.inc("placement.redispatches")
                    if self.tracer:
                        self.tracer.instant(
                            "redispatch", "fault", t_detect,
                            track=f"session:{st.sid}", sid=st.sid,
                            from_tier=tier, to_tier=B)
                    return self._remote_event(
                        st, event, model_name, payload_b, t_detect, dec2,
                        B, feats=feats, outputs=outputs, fallback=True,
                        detect_s=detect_s)
        return self._glass_event(st, event, model_name, t_detect, dec,
                                 fallback=True, detect_s=detect_s,
                                 feats=feats, outputs=outputs)

    def _race_event(self, st: SessionView, event: Event,
                    model_name: Optional[str], payload_b: int,
                    now: float, dec: TierDecision, A: str) -> TieredRecord:
        """Speculative dual placement (cancel-on-commit): dispatch the
        arriving submodule on glass AND remote ``A`` simultaneously,
        commit whichever result reaches the glasses first, and cancel
        the loser at the commit instant — its in-flight transfer never
        delivers, its un-run compute is released, and nothing of it
        ever commits (the cache would refuse the late duplicate
        anyway: same step, structural no-op). The numerics run ONCE —
        both racers share the same arrays, so the committed result is
        bit-equal to the monolithic reference whichever side wins. A
        remote crash mid-race is absorbed with NO detection stall: the
        glass racer is already running, so the EMT pays the glass
        latency instead of the missed-heartbeat timeout (counted in
        ``spec_crash_saves``, not as a fallback)."""
        m = event.modality
        local = self.local_name
        host = self.hosts[A]
        up_ch = self.fabric.channel(local, A)
        down_ch = self.fabric.channel(A, local)

        # ---- real numerics once; the racers share the arrays (and the
        # decision's precision — it is a property of the flight, not of
        # either host, so the committed result is identical whichever
        # side wins)
        feats = self._run_encoders(st, m, dec.precision)
        outputs = None
        if model_name is not None:
            gathered = self._gather(st, model_name, m, feats)
            if gathered is not None:
                outputs = self.models[model_name].tail(
                    self.params[model_name], gathered)

        # ---- glass racer: always booked (the hedge that cannot crash)
        g_dur = (self._enc_duration(m, len(feats), self.glass,
                                    dec.precision)
                 if feats else 0.0)
        if outputs is not None:
            g_dur += self.glass.time("tail")
        g_start, g_done = self.glass.occupy(g_dur, now)

        # ---- remote racer: the uplink truly dispatches; compute and
        # downlink are PLANNED via eta() so a loss unwinds cleanly
        sync_b, synced = self._sync_bytes(A, st, model_name, skip=m)
        up = up_ch.send(payload_b + sync_b, now)
        r_dur = (self._enc_duration(m, len(feats), host, dec.precision)
                 if feats else 0.0)
        if outputs is not None:
            r_dur += host.time("tail")
        down_b = sum(payload_nbytes(f) for f in feats.values())
        if outputs is not None:
            down_b += payload_nbytes(outputs)
        r_done = max(up.t_deliver, host.free_at) + r_dur
        r_emit = down_ch.eta(down_b, r_done)
        crashed = self._dies_before(A, r_emit)

        # tie -> local: offloading must strictly win (the legacy rule)
        glass_wins = crashed or g_done <= r_emit
        self.metrics.inc("speculation.races")
        if self.tracer:
            self.tracer.instant("race.start", "speculation", now,
                                track=f"session:{st.sid}", sid=st.sid,
                                remote=A, glass_done=g_done,
                                remote_emit=r_emit, crashed=crashed)
        stamp_fresh_remote = False

        if glass_wins:
            stop = g_done                        # the commit instant
            if up.t_deliver > stop:
                # payload still in flight at commit: the wire frees now
                # and the remote never computes
                up_ch.cancel(up.flight, t=stop)
            else:
                rs, rd = host.occupy(r_dur, up.t_deliver)
                cut = (min(stop, self._faults[A].crash_at) if crashed
                       else stop)
                host.release(rs, rd, cut)        # un-run compute freed
                if not self._dies_before(A, up.t_deliver):
                    versions = self._replica_versions[A]
                    for k, version in synced:
                        versions[k] = version
                if rd <= stop and not self._dies_before(A, rd):
                    # loser finished computing; its result transfer is
                    # recalled at commit (a dead-on-the-wire sender is
                    # recalled at its crash instant instead)
                    stamp_fresh_remote = True
                    down = down_ch.send(down_b, rd)
                    down_ch.cancel(down.flight, t=cut)
            winner, t_start, t_emit = local, g_start, g_done
            uplink_s = downlink_s = 0.0
            compute_s, loser_emit = g_dur, r_emit
            self.metrics.inc("placement.on_glass")
            if crashed:
                self.metrics.inc("speculation.crash_saves")
        else:
            _rs, rd = host.occupy(r_dur, up.t_deliver)
            down = down_ch.send(down_b, rd)
            # cancel the glass racer: free the un-run tail of its booking
            self.glass.release(g_start, g_done, down.t_deliver)
            versions = self._replica_versions[A]
            for k, version in synced:
                versions[k] = version
            stamp_fresh_remote = True
            winner, t_start, t_emit = A, up.t_send, down.t_deliver
            uplink_s = up.t_deliver - up.t_send
            downlink_s = down.t_deliver - rd
            compute_s, loser_emit = r_dur, g_done
            self.metrics.inc("placement.offloaded")

        if self.tracer:
            self.tracer.instant("race.win", "speculation", t_emit,
                                track=f"session:{st.sid}", sid=st.sid,
                                winner=winner, loser_emit=loser_emit,
                                crashed=crashed)
        # ---- commit ONCE, for the winner only
        self._commit_features(st, m, feats, tier=winner)
        if outputs is not None:
            self._touch_consumed(st, model_name)
            self.metrics.inc(f"placement.tail.{winner}")
        if stamp_fresh_remote:
            # the loser computed (or received) the same fresh feature;
            # its replica holds the committed version
            self._stamp_fresh(A, st, m)
        self.metrics.inc(f"placement.enc.{winner}")
        self.metrics.inc(f"speculation.wins.{winner}")
        return TieredRecord(
            sid=st.sid, index=event.index, modality=m, model=model_name,
            tier=winner, kind=self._kind(model_name),
            t_arrival=event.arrival_time, t_start=t_start, t_emit=t_emit,
            uplink_s=uplink_s, downlink_s=downlink_s, compute_s=compute_s,
            decision=dec, outputs=outputs, enc_tier=winner,
            tail_tier=winner if outputs is not None else None,
            speculative=True, race_winner=winner,
            race_loser_emit=loser_emit, precision=dec.precision)

    def _glass_event(self, st: SessionView, event: Event,
                     model_name: Optional[str], now: float,
                     dec: TierDecision, *, fallback: bool = False,
                     detect_s: float = 0.0, feats=None,
                     outputs=None) -> TieredRecord:
        m = event.modality
        local = self.local_name
        if feats is None:
            feats = self._run_encoders(st, m, dec.precision)
        self._commit_features(st, m, feats, tier=local)
        if outputs is None and model_name is not None:
            gathered = self._gather(st, model_name, m, feats)
            if gathered is not None:
                outputs = self.models[model_name].tail(
                    self.params[model_name], gathered)
        if outputs is not None:
            self._touch_consumed(st, model_name)
        dur = (self._enc_duration(m, len(feats), self.glass, dec.precision)
               if feats else 0.0)
        if outputs is not None:
            dur += self.glass.time("tail")
        start, done = self.glass.occupy(dur, now)
        self.metrics.inc("placement.on_glass")
        self.metrics.inc(f"placement.enc.{local}")
        if outputs is not None:
            self.metrics.inc(f"placement.tail.{local}")
        if fallback:
            self.metrics.inc("placement.fallbacks")
        return TieredRecord(
            sid=st.sid, index=event.index, modality=m, model=model_name,
            tier=local, kind=self._kind(model_name),
            t_arrival=event.arrival_time, t_start=start, t_emit=done,
            compute_s=dur, fallback=fallback, detect_s=detect_s,
            decision=dec, outputs=outputs, enc_tier=local,
            tail_tier=local if outputs is not None else None,
            precision=dec.precision)

    def _remote_event(self, st: SessionView, event: Event,
                      model_name: Optional[str], payload_b: int,
                      now: float, dec: TierDecision, A: str, *,
                      feats=None, outputs=None, fallback: bool = False,
                      detect_s: float = 0.0) -> TieredRecord:
        """Encoder AND tail on remote tier ``A`` (the co-located path —
        with ``tail_placement`` off this is the only remote shape).
        ``fallback``/``detect_s`` mark a mid-flight re-dispatch: the
        flight already died once on another tier and was re-aimed here
        at the detection instant."""
        m = event.modality
        host = self.hosts[A]
        up_ch = self.fabric.channel(self.local_name, A)
        down_ch = self.fabric.channel(A, self.local_name)
        # ---- uplink: raw payload + any features this replica lacks
        sync_b, synced = self._sync_bytes(A, st, model_name, skip=m)
        up = up_ch.send(payload_b + sync_b, now)

        # ---- real numerics (uncommitted) + simulated remote compute
        if feats is None:
            feats = self._run_encoders(st, m, dec.precision)
            if model_name is not None:
                gathered = self._gather(st, model_name, m, feats)
                if gathered is not None:
                    outputs = self.models[model_name].tail(
                        self.params[model_name], gathered)
        dur = (self._enc_duration(m, len(feats), host, dec.precision)
               if feats else 0.0)
        if outputs is not None:
            dur += host.time("tail")
        _start, t_done = host.occupy(dur, up.t_deliver)

        # ---- downlink payload: fresh feature(s) + head outputs + the
        # piggybacked cache re-stamp (an empty-feature result still
        # ships a small ack frame)
        down_b = sum(payload_nbytes(f) for f in feats.values())
        if outputs is not None:
            down_b += payload_nbytes(outputs)

        # ---- crash window: the tier must survive through the END of
        # its downlink transmission, not just its compute — a death
        # mid-transfer loses the result exactly like one mid-encode
        if self._dies_before(A, down_ch.eta(down_b, t_done)):
            return self._crash_fallback(A, st, event, model_name, now,
                                        dec, payload_b=payload_b,
                                        feats=feats, outputs=outputs)

        # ---- success: commit to the glass cache, ship the bytes
        self._commit_features(st, m, feats, tier=A)
        if outputs is not None:
            self._touch_consumed(st, model_name)
        down = down_ch.send(down_b, t_done)
        # the replica now holds everything it consumed or produced
        versions = self._replica_versions[A]
        for k, version in synced:
            versions[k] = version
        self._stamp_fresh(A, st, m)
        self.metrics.inc("placement.offloaded")
        self.metrics.inc(f"placement.enc.{A}")
        if outputs is not None:
            self.metrics.inc(f"placement.tail.{A}")
        if fallback:
            self.metrics.inc("placement.fallbacks")
        return TieredRecord(
            sid=st.sid, index=event.index, modality=m, model=model_name,
            tier=A, kind=self._kind(model_name),
            t_arrival=event.arrival_time, t_start=up.t_send,
            t_emit=down.t_deliver,
            uplink_s=up.t_deliver - up.t_send,
            downlink_s=down.t_deliver - t_done,
            compute_s=dur, fallback=fallback, detect_s=detect_s,
            decision=dec, outputs=outputs,
            enc_tier=A, tail_tier=A if outputs is not None else None,
            precision=dec.precision)

    # ------------------------------------------- per-submodule placement

    def _placed_event(self, st: SessionView, event: Event,
                      model_name: Optional[str], payload_b: int,
                      now: float, dec: TierDecision, avail, queues,
                      prev_observed=()) -> TieredRecord:
        """Per-submodule placement: the encoder goes to ``dec.tier``;
        when a fusion will run, the tail gets its OWN argmin placement
        (possibly a third host), paying the feature hop between the two
        and the head-output return to the glasses."""
        m = event.modality
        A = dec.tier
        # will a fusion actually run? (fresh feature for m, every other
        # consumed modality already cached)
        fusible = False
        if model_name is not None:
            have_fresh = bool(self._consumers(m))
            key = self._cache_key(st.sid, model_name)
            fusible = all((mm == m and have_fresh)
                          or self.cache.peek(key, mm) is not None
                          for mm in self.models[model_name].modalities())
        if not fusible:
            # nothing to place but the encoder
            if A == self.local_name:
                return self._glass_event(st, event, model_name, now, dec)
            return self._remote_event(st, event, model_name, payload_b,
                                      now, dec, A)
        # real numerics first: the tail decision weighs the ACTUAL
        # feature/output byte sizes (placement never changes the math) —
        # for an int8 flight that is the PACKED feature form, so the
        # tail placement argmin sees the ~4x smaller hop for free
        feats = self._run_encoders(st, m, dec.precision)
        gathered = self._gather(st, model_name, m, feats)
        if gathered is None:
            if A == self.local_name:
                return self._glass_event(st, event, model_name, now, dec,
                                         feats=feats)
            return self._remote_event(st, event, model_name, payload_b,
                                      now, dec, A, feats=feats)
        outputs = self.models[model_name].tail(self.params[model_name],
                                               gathered)
        feat_b = sum(payload_nbytes(f) for f in feats.values())
        out_b = payload_nbytes(outputs)
        dtail = self.policy.decide_tail(feat_b, out_b, A, now,
                                        queues=queues, available=avail)
        T = dtail.tier
        partial = None
        if A == self.local_name and T != A and self.glass_partials:
            # the split shape pays a remote round trip even though the
            # encoder stayed home — the EMT still gets an immediate
            # provisional from cached features while the tail travels
            partial = self._glass_provisional(st, prev_observed, now)
        if T == A:
            if A == self.local_name:
                rec = self._glass_event(st, event, model_name, now, dec,
                                        feats=feats, outputs=outputs)
            else:
                rec = self._remote_event(st, event, model_name, payload_b,
                                         now, dec, A, feats=feats,
                                         outputs=outputs)
        else:
            rec = self._split_event(st, event, model_name, payload_b, now,
                                    dec, A, T, feats, outputs, feat_b,
                                    out_b)
        rec.tail_decision = dtail
        if partial is not None:
            rec.glass_partial = partial
        return rec

    def _split_event(self, st: SessionView, event: Event, model_name: str,
                     payload_b: int, now: float, dec: TierDecision,
                     A: str, T: str, feats, outputs, feat_b: int,
                     out_b: int) -> TieredRecord:
        """Encoder on ``A``, tail on a different tier ``T``. The fresh
        features always flow home to the glasses with the result (the
        paper's cache-carrying discipline), whichever tier computed
        them; commit stays on-success so a mid-flight death loses the
        in-flight work, never corrupts the cache."""
        m = event.modality
        local = self.local_name

        if A == local:
            # encoder at home; only the tail travels
            enc_dur = (self._enc_duration(m, len(feats), self.glass,
                                          dec.precision)
                       if feats else 0.0)
            start, t_enc_done = self.glass.occupy(enc_dur, now)
            # glass-computed features are already safe at home
            self._commit_features(st, m, feats, tier=local)
            sync_b, synced = self._sync_bytes(T, st, model_name, skip=m)
            up = self.fabric.channel(local, T).send(feat_b + sync_b,
                                                    t_enc_done)
            tail_host = self.hosts[T]
            _s, t_tail_done = tail_host.occupy(tail_host.time("tail"),
                                               up.t_deliver)
            down_ch = self.fabric.channel(T, local)
            if self._dies_before(T, down_ch.eta(out_b, t_tail_done)):
                # tail-only fallback: features survived on glass
                t_detect = max(t_enc_done, self._faults[T].detect_at)
                self._mark_dead(T)
                _s2, done = self.glass.occupy(self.glass.time("tail"),
                                              t_detect)
                self._touch_consumed(st, model_name)
                self.metrics.inc("placement.on_glass")
                self.metrics.inc("placement.fallbacks")
                self.metrics.inc(f"placement.enc.{local}")
                self.metrics.inc(f"placement.tail.{local}")
                return TieredRecord(
                    sid=st.sid, index=event.index, modality=m,
                    model=model_name, tier=local,
                    kind=self._kind(model_name),
                    t_arrival=event.arrival_time, t_start=start,
                    t_emit=done,
                    uplink_s=up.t_deliver - up.t_send,
                    compute_s=enc_dur + self.glass.time("tail"),
                    fallback=True,
                    detect_s=max(0.0, t_detect - t_enc_done),
                    decision=dec, outputs=outputs, enc_tier=local,
                    tail_tier=local, precision=dec.precision)
            down = down_ch.send(out_b, t_tail_done)
            self._touch_consumed(st, model_name)
            versions = self._replica_versions[T]
            for k, version in synced:
                versions[k] = version
            self._stamp_fresh(T, st, m)
            self.metrics.inc("placement.on_glass")
            self.metrics.inc(f"placement.enc.{local}")
            self.metrics.inc(f"placement.tail.{T}")
            return TieredRecord(
                sid=st.sid, index=event.index, modality=m,
                model=model_name, tier=local, kind=self._kind(model_name),
                t_arrival=event.arrival_time, t_start=start,
                t_emit=down.t_deliver,
                uplink_s=up.t_deliver - up.t_send,
                downlink_s=down.t_deliver - t_tail_done,
                compute_s=enc_dur + tail_host.time("tail"),
                decision=dec, outputs=outputs, enc_tier=local,
                tail_tier=T, precision=dec.precision)

        host = self.hosts[A]
        up = self.fabric.channel(local, A).send(payload_b, now)
        enc_dur = (self._enc_duration(m, len(feats), host, dec.precision)
                   if feats else 0.0)
        _s, t_enc_done = host.occupy(enc_dur, up.t_deliver)

        if T == local:
            # features come home, fusion runs on the glasses
            down_ch = self.fabric.channel(A, local)
            if self._dies_before(A, down_ch.eta(feat_b, t_enc_done)):
                return self._crash_fallback(A, st, event, model_name, now,
                                            dec, payload_b=payload_b,
                                            feats=feats, outputs=outputs)
            down = down_ch.send(feat_b, t_enc_done)
            self._commit_features(st, m, feats, tier=A)
            self._stamp_fresh(A, st, m)
            _s2, done = self.glass.occupy(self.glass.time("tail"),
                                          down.t_deliver)
            self._touch_consumed(st, model_name)
            self.metrics.inc("placement.offloaded")
            self.metrics.inc(f"placement.enc.{A}")
            self.metrics.inc(f"placement.tail.{local}")
            return TieredRecord(
                sid=st.sid, index=event.index, modality=m,
                model=model_name, tier=A, kind=self._kind(model_name),
                t_arrival=event.arrival_time, t_start=up.t_send,
                t_emit=done,
                uplink_s=up.t_deliver - up.t_send,
                downlink_s=down.t_deliver - t_enc_done,
                compute_s=enc_dur + self.glass.time("tail"),
                decision=dec, outputs=outputs, enc_tier=A,
                tail_tier=local, precision=dec.precision)

        # encoder on A, tail on another remote B: the feature hops
        # A->B on the direct link while the glasses warm B's replica
        # in parallel; B returns features + outputs home
        B = T
        sync_b, synced = self._sync_bytes(B, st, model_name, skip=m)
        sync_d = (self.fabric.channel(local, B).send(sync_b, now)
                  if sync_b else None)
        hop_ch = self.fabric.channel(A, B)
        if self._dies_before(A, hop_ch.eta(feat_b, t_enc_done)):
            return self._crash_fallback(A, st, event, model_name, now,
                                        dec, payload_b=payload_b,
                                        feats=feats, outputs=outputs)
        hop = hop_ch.send(feat_b, t_enc_done)
        ready = max(hop.t_deliver,
                    sync_d.t_deliver if sync_d is not None else 0.0)
        tail_host = self.hosts[B]
        _s2, t_tail_done = tail_host.occupy(tail_host.time("tail"), ready)
        down_ch = self.fabric.channel(B, local)
        down_b = feat_b + out_b         # the result carries the cache home
        if self._dies_before(B, down_ch.eta(down_b, t_tail_done)):
            return self._crash_fallback(B, st, event, model_name, now,
                                        dec, payload_b=payload_b,
                                        feats=feats, outputs=outputs)
        down = down_ch.send(down_b, t_tail_done)
        self._commit_features(st, m, feats, tier=A)
        self._touch_consumed(st, model_name)
        versions = self._replica_versions[B]
        for k, version in synced:
            versions[k] = version
        self._stamp_fresh(A, st, m)
        self._stamp_fresh(B, st, m)
        self.metrics.inc("placement.offloaded")
        self.metrics.inc(f"placement.enc.{A}")
        self.metrics.inc(f"placement.tail.{B}")
        return TieredRecord(
            sid=st.sid, index=event.index, modality=m, model=model_name,
            tier=A, kind=self._kind(model_name),
            t_arrival=event.arrival_time, t_start=up.t_send,
            t_emit=down.t_deliver,
            uplink_s=up.t_deliver - up.t_send,
            downlink_s=down.t_deliver - t_tail_done,
            compute_s=enc_dur + tail_host.time("tail"),
            decision=dec, outputs=outputs, enc_tier=A, tail_tier=B,
            precision=dec.precision)

    # --------------------------------------------------------- episodes

    def run_arrivals(self, episodes: Dict[str, List[Event]], payload_fn,
                     *, aggregate=None, sim_window: Optional[float] = None,
                     crash_at: Optional[float] = None,
                     rejoin_at: Optional[float] = None,
                     schedule=None):
        """Drive sessions through their episodes in GLOBAL arrival-time
        order (the field regime: one incident, many responders, one
        interleaved stream — ``core.episodes.merge_arrivals``).
        ``payload_fn(sid, event) -> payload``.

        Tiered placement: per-arrival, optionally killing the edge at
        simulated time ``crash_at``; returns the records. Flush-mode:
        with ``sim_window=None`` the engine's wall-clock deadline policy
        applies; with ``sim_window`` set, the deadline rule runs on
        EPISODE time instead (same semantics, different clock): after
        each submit, flush iff the oldest pending arrival's episode time
        is >= ``sim_window`` seconds behind the current one — so
        ``sim_window=0`` flushes per arrival. A final ``drain`` runs
        either way; returns the flush reports."""
        arrivals = merge_arrivals(episodes)
        if self.tiered:
            if crash_at is not None:
                self.inject_crash(crash_at, rejoin_at=rejoin_at)
            elif rejoin_at is not None:
                raise ValueError("rejoin_at requires crash_at")
            if schedule is not None:
                self.inject_schedule(schedule)
            for _t, sid, ev in arrivals:
                self.submit(sid, ev, payload_fn(sid, ev),
                            aggregate=aggregate)
            return self.records
        if crash_at is not None or rejoin_at is not None \
                or schedule is not None:
            raise ValueError("crash_at/rejoin_at/schedule require tiered "
                             "placement")
        if sim_window is None:
            for _t, sid, ev in arrivals:
                self.submit(sid, ev, payload_fn(sid, ev),
                            aggregate=aggregate)
        else:
            saved, self.deadline_s = self.deadline_s, None
            try:
                oldest = None
                for t, sid, ev in arrivals:
                    self.submit(sid, ev, payload_fn(sid, ev),
                                aggregate=aggregate)
                    oldest = t if oldest is None else oldest
                    if t - oldest >= sim_window:
                        self.flush()
                        oldest = None
            finally:
                self.deadline_s = saved
        self.drain()
        return self.flushes

    def run_episodes(self, episodes: Dict[str, List[Event]], payload_fn,
                     *, aggregate=None, events_per_flush: int = 1):
        """Tick-driven batch serving: at tick t every session submits
        its t-th event; flush every ``events_per_flush`` ticks.
        ``payload_fn(sid, event) -> payload``."""
        if self.tiered:
            raise RuntimeError("run_episodes is a flush-mode driver; "
                               "tiered placement uses run_arrivals")
        horizon = max((len(ev) for ev in episodes.values()), default=0)
        for t in range(horizon):
            for sid, evs in episodes.items():
                if t < len(evs):
                    self.submit(sid, evs[t], payload_fn(sid, evs[t]),
                                aggregate=aggregate)
            if (t + 1) % events_per_flush == 0:
                self.flush()
        if self._pending:
            self.flush()
        return self.flushes

    # ------------------------------------------------------------- stats

    def compile_count(self) -> int:
        return sum(sm.compile_count() for sm in self.models.values())

    def encoder_calls_total(self) -> int:
        return self._enc_calls_total

    def tail_calls_total(self) -> int:
        return self._tail_calls_total

    def event_latencies(self) -> List[float]:
        return [lat for f in self.flushes for lat in f.latencies.values()]

    def total_wall_s(self) -> float:
        return sum(f.wall_s for f in self.flushes)

    def time_to_first_prediction(self, sid: str) -> Optional[float]:
        """Flush-mode: wall seconds from first submit to first emitted
        prediction. Tiered: simulated seconds from first arrival to the
        first emission (a glass provisional counts — it IS the first
        thing the EMT sees)."""
        st = self.sessions[sid]
        if self.tiered:
            if st.t_first_emit is None or st.t_first_arrival is None:
                return None
            return st.t_first_emit - st.t_first_arrival
        if st.t_first_prediction is None or st.t_first_submit is None:
            return None
        return st.t_first_prediction - st.t_first_submit

    def time_to_final_prediction(self, sid: str) -> Optional[float]:
        st = self.sessions[sid]
        if self.tiered:
            if st.t_final_emit is None or st.t_first_arrival is None:
                return None
            return st.t_final_emit - st.t_first_arrival
        if st.t_final_prediction is None or st.t_first_submit is None:
            return None
        return st.t_final_prediction - st.t_first_submit

    # ----- tiered stats (meaningful only with placement enabled)

    def total_latency_s(self) -> float:
        """Cumulative serving latency (sum of per-arrival t_emit -
        t_arrival) — the Fig. 15 comparison metric."""
        return self._total_latency

    def makespan_s(self) -> float:
        return max((r.t_emit for r in self.records), default=0.0)

    def transport_stats(self) -> dict:
        """Per-link byte accounting. ``uplink``/``downlink`` keep the
        historical 2-tier view (the glass<->fastest-remote pair);
        ``links`` breaks out every (src, dst) channel the fabric
        actually used."""
        return {"uplink": self.uplink.stats(),
                "downlink": self.downlink.stats(),
                "links": self.fabric.stats()}

    def placement_counts(self) -> dict:
        """Events placed per host (by ENCODER tier — the bulk compute),
        plus crash fallbacks. Tier-count-agnostic: one key per
        configured host ('glass'/'edge' in the legacy pair)."""
        return {**self.place_counts, "fallbacks": self.fallback_count}

    def tail_placement_counts(self) -> dict:
        """Fusions run per host — diverges from ``placement_counts``
        exactly when per-submodule tail placement split a tail from its
        encoder."""
        return dict(self.tail_counts)

    def speculation_stats(self) -> dict:
        """Speculative-dual-placement and re-dispatch accounting:
        how many arrivals raced, which host won each race, how many
        remote crashes the race absorbed without a detection stall,
        how many lost flights re-aimed at a surviving remote, plus the
        commit-protocol audit trail (cancelled transfers, refused
        duplicate/stale cache commits — both must stay refusals, never
        visible state)."""
        return {"races": self.spec_count,
                "wins": dict(self.spec_wins),
                "crash_saves": self.spec_crash_saves,
                "redispatches": self.redispatch_count,
                "cancelled_msgs": self.fabric.cancelled_msgs(),
                "duplicate_commits": self.cache.duplicate_commits,
                "stale_commits": self.cache.stale_commits}


# ======================================================================
# Spec parsing + factory
# ======================================================================

_SPEC_TOKENS = {
    "batch": "batch", "batched": "batch",
    "stream": "stream", "streaming": "stream",
    "tiered": "tiered", "tier": "tiered", "placement": "tiered",
}

# canonical sections -> (policy class, EngineSpec field); section names
# are pre-canonicalized through _SPEC_TOKENS
_SECTIONS = {
    "batch": (BatchPolicy, "batch"),
    "stream": (StreamPolicy, "stream"),
    "tiered": (PlacementPolicy, "placement"),
}


def parse_spec(spec, **overrides) -> EngineSpec:
    """Normalize an engine spec into a typed :class:`EngineSpec`.

    ``spec`` may be:
      * a string of '+'-joined policy tokens: ``"batch"``, ``"stream"``,
        ``"batch+stream"``, ``"stream+tiered"``, ``"batch+stream+tiered"``
        (aliases: batched/streaming/tier/placement);
      * a dict with sections ``batch`` / ``stream`` / ``tiered`` (each
        True or a kwargs dict) plus engine-wide keys ``share_encoders``
        and ``max_history``;
      * an :class:`EngineSpec` (returned as-is, overrides applied to
        copies of its policies is NOT supported — pass a fresh spec).

    ``overrides`` are routed by name: policy-constructor fields go to
    their policy (e.g. ``deadline_s`` -> StreamPolicy, ``profile``/
    ``trace`` -> PlacementPolicy, ``bucketer`` -> BatchPolicy), and
    ``share_encoders``/``max_history`` to the engine; an override beats
    the same key in a dict-spec section. Tiered specs REQUIRE
    ``profile`` and ``trace`` (there is no meaningful default
    hardware)."""
    if isinstance(spec, EngineSpec):
        if overrides:
            raise ValueError("overrides are not applied to a pre-built "
                             "EngineSpec; pass tokens or a dict instead")
        return spec

    sections: Dict[str, dict] = {}
    engine_kw: Dict[str, Any] = {}
    if isinstance(spec, str):
        for tok in filter(None, (t.strip() for t in spec.split("+"))):
            canon = _SPEC_TOKENS.get(tok.lower())
            if canon is None:
                raise ValueError(
                    f"unknown engine spec token {tok!r}; expected "
                    f"'+'-joined subset of batch/stream/tiered")
            sections[canon] = {}
    elif isinstance(spec, dict):
        for key, val in spec.items():
            if key in ("share_encoders", "max_history"):
                engine_kw[key] = val
                continue
            canon = _SPEC_TOKENS.get(str(key).lower())
            if canon is None:
                raise ValueError(f"unknown engine spec section {key!r}")
            if val is False or val is None:
                continue
            sections[canon] = {} if val is True else dict(val)
    else:
        raise TypeError(f"engine spec must be str, dict, or EngineSpec; "
                        f"got {type(spec).__name__}")

    if not sections:
        raise ValueError("empty engine spec: enable at least one of "
                         "batch/stream/tiered")

    # route the keyword overrides to their policy (or the engine)
    fields_of = {
        "batch": set(BatchPolicy.__dataclass_fields__),
        "stream": set(StreamPolicy.__dataclass_fields__),
        "tiered": set(PlacementPolicy.__dataclass_fields__),
    }
    for k, v in overrides.items():
        if k in ("share_encoders", "max_history"):
            engine_kw[k] = v
            continue
        owner = next((sec for sec in sections if k in fields_of[sec]), None)
        if owner is None and k in fields_of["batch"]:
            # the coalescing machinery exists in every flush-mode engine,
            # so its knobs (bucketer, batch_bucket_min, ...) are always
            # addressable — an explicit "batch" token is only needed to
            # *enable* coalescing semantics in the spec's own vocabulary
            owner = "batch"
            sections.setdefault("batch", {})
        if owner is None:
            enabled = "+".join(sections) or "(none)"
            raise ValueError(f"override {k!r} does not match any enabled "
                             f"policy ({enabled})")
        sections[owner][k] = v        # overrides WIN over dict-spec values

    policies: Dict[str, Any] = {}
    for sec, kw in sections.items():
        cls, target = _SECTIONS[sec]
        unknown = set(kw) - fields_of[sec]
        if unknown:
            raise ValueError(f"unknown {sec} policy option(s): "
                             f"{sorted(unknown)}")
        if cls is PlacementPolicy and not {"profile", "trace"} <= set(kw):
            raise ValueError("tiered placement requires 'profile' "
                             "(ProfileTable) and 'trace' (BandwidthTrace)")
        policies[target] = cls(**kw)
    return EngineSpec(**policies, **engine_kw)


def build_engine(models: Dict[str, SplitModel], params: Dict[str, dict],
                 spec, *, time_fn: Callable[[], float] = time.perf_counter,
                 tracer: Optional[Tracer] = None,
                 **overrides) -> EMSServeEngine:
    """THE factory: assemble an :class:`EMSServeEngine` from a spec.

    ``build_engine(models, params, "batch")`` is the batched
    fast path; ``"stream"`` the progressive-prediction runtime;
    ``"stream+tiered"`` streams partials on-glass while the edge
    computes finals. See :func:`parse_spec` for the spec grammar and
    override routing. ``tracer`` (a :class:`repro.obs.Tracer`) turns on
    full-lifecycle span tracing; it defaults to the no-op."""
    es = parse_spec(spec, **overrides)
    return EMSServeEngine(models, params, batch=es.batch, stream=es.stream,
                          placement=es.placement,
                          share_encoders=es.share_encoders,
                          max_history=es.max_history, time_fn=time_fn,
                          tracer=tracer)

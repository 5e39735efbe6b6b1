"""Open-loop incident traffic, made from a seed and a traffic file.

Adapted from the program's ``fleet/workload.py`` (``poisson_times``,
``generate_workload``), kept here so that the yardstick does not move
with the program. Two changes against that original, both so that runs
with different seeds measure the same work:

* Session starts are a Poisson process whose exponential gaps are a
  fixed, stratified set (the quantiles of Exp(rate)) put in an order
  drawn from the seed. Every seed then offers the same number of
  sessions over the same span, in a different pattern of bursts.
* Text and vitals lengths are likewise stratified quantiles of their
  distributions, shuffled by the seed.

Within a session the modality onsets and gaps are drawn as in the
original: scenario onsets from ``LAG_SCENARIOS``, then exponential gaps
around the scenario's periods, all times multiplied by ``time_scale``.
Every session sends one narrative. A traffic file may set ``n_vitals``
or ``n_scene`` to 0 to leave a modality out, and ``bursts``
(``factor``, ``length_s``, ``every_s``, optional ``offset_s``) for
mass-casualty bursts over the base rate.

Payloads are host arrays made before the window opens, as a radio link
delivers them: text ``(1, n)`` int32 ids in ``[1, vocab)``, vitals
``(1, n, n_vitals)`` float32 readings (the monitor's recent trend),
scene ``(1, scene_dim)`` float32 detections.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

# seconds until a modality FIRST arrives, N(mu, sigma) clipped at 0, per
# scenario (the program's core/episodes.py LAG_SCENARIOS, copied)
LAG_SCENARIOS = {
    "text_first": {"text": (0.0, 0.05), "vitals": (2.0, 0.8),
                   "scene": (5.0, 1.5)},
    "vitals_first": {"vitals": (0.0, 0.1), "text": (3.0, 1.0),
                     "scene": (4.0, 1.5)},
    "scene_late": {"text": (0.5, 0.3), "vitals": (1.0, 0.4),
                   "scene": (8.0, 2.0)},
}


@dataclass(frozen=True)
class Arrival:
    """One datum of one session: ``index`` is its place in the session
    (0-based, time order), ``t`` its due time in seconds from the start
    of the schedule."""
    sid: str
    index: int
    modality: str
    t: float


@dataclass
class Schedule:
    sessions: Dict[str, List[Arrival]]       # sid -> arrivals, time order
    payloads: Dict[Tuple[str, int], np.ndarray]
    text_len: Dict[Tuple[str, int], int]      # natural token counts
    vitals_len: Dict[Tuple[str, int], int]    # natural reading counts
    lead_in_s: float
    window_s: float

    @property
    def window(self) -> Tuple[float, float]:
        return self.lead_in_s, self.lead_in_s + self.window_s

    def arrivals(self) -> List[Arrival]:
        """Every arrival in global due order (ties by sid, then index)."""
        return sorted((a for arr in self.sessions.values() for a in arr),
                      key=lambda a: (a.t, a.sid, a.index))


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), *tags])


def _stratified(n: int, ppf, rng) -> np.ndarray:
    """``n`` values at the mid-quantiles of a distribution, shuffled."""
    vals = np.array([ppf((i + 0.5) / n) for i in range(n)])
    return rng.permutation(vals)


def session_starts(rate: float, horizon: float, seed: int) -> np.ndarray:
    """``round(rate * horizon)`` Poisson session starts in ``[0, horizon)``:
    the stratified exponential gaps, in the seed's order, scaled so that
    they span the horizon exactly."""
    if rate <= 0.0 or horizon <= 0.0:
        raise ValueError(f"need rate > 0 and horizon > 0, got {rate}, "
                         f"{horizon}")
    n = max(1, round(rate * horizon))
    gaps = _stratified(n, lambda q: -math.log1p(-q) / rate,
                       _rng(seed, 0x9015))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (horizon / float(np.sum(gaps)))


def burst_starts(rate: float, horizon: float, seed: int,
                 bursts: dict) -> np.ndarray:
    """Extra session starts during bursts: ``factor`` times the base rate
    for ``length_s`` seconds every ``every_s`` seconds (the base starts
    go on underneath), the same stratified way."""
    windows = [(t, min(t + bursts["length_s"], horizon))
               for t in np.arange(bursts.get("offset_s", 0.0), horizon,
                                  bursts["every_s"])]
    span = sum(e - s for s, e in windows)
    extra = session_starts((bursts["factor"] - 1.0) * rate, span,
                           seed + 0xB0B5)
    out, base = [], 0.0
    for s, e in windows:
        out += [s + (t - base) for t in extra if base <= t < base + e - s]
        base += e - s
    return np.array(out)


def _session_times(scenario: str, rng, *, n_vitals: int, n_scene: int,
                   vitals_period: float, scene_period: float):
    spec = LAG_SCENARIOS[scenario]

    def onset(m):
        mu, sigma = spec[m]
        return float(max(0.0, rng.normal(mu, sigma)))

    def stream(m, n, mean_gap):
        if n <= 0:
            return []
        t = onset(m)
        ts = [t]
        for _ in range(n - 1):
            t += float(rng.exponential(mean_gap))
            ts.append(t)
        return ts

    return {
        "text": [onset("text")],
        "vitals": stream("vitals", n_vitals, vitals_period),
        "scene": stream("scene", n_scene, scene_period),
    }


def _length_ppf(spec: dict):
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        nd = NormalDist(math.log(spec["median"]), spec["sigma"])
        return lambda q: int(min(hi, max(lo, round(math.exp(nd.inv_cdf(q))))))
    if spec["dist"] == "uniform":
        return lambda q: int(min(hi, lo + math.floor(q * (hi - lo + 1))))
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def build_schedule(traffic: dict, model: dict, *, seed: int,
                   window_s: float) -> Schedule:
    """The whole run's arrivals and payloads: a lead-in of
    ``traffic["lead_in_s"]`` seconds, then the measured window. Arrivals
    due after the window are not part of the run."""
    lead = float(traffic["lead_in_s"])
    horizon = lead + float(window_s)
    rate = float(traffic["rate_sessions_per_s"])
    starts = session_starts(rate, horizon, seed)
    if traffic.get("bursts"):
        starts = np.sort(np.concatenate(
            [starts, burst_starts(rate, horizon, seed, traffic["bursts"])]))
    scale = float(traffic["time_scale"])
    scenarios = traffic["scenarios"]
    sessions: Dict[str, List[Arrival]] = {}
    for i, t0 in enumerate(starts):
        scen = scenarios[i % len(scenarios)]
        times = _session_times(scen, _rng(seed, 0x5E55, i),
                               n_vitals=int(traffic["n_vitals"]),
                               n_scene=int(traffic["n_scene"]),
                               vitals_period=float(traffic["vitals_period"]),
                               scene_period=float(traffic["scene_period"]))
        evs = sorted(((t * scale, m) for m, ts in times.items() for t in ts),
                     key=lambda tm: (tm[0], tm[1]))
        sid = f"s{i}"
        arr = [Arrival(sid, k, m, float(t0) + t)
               for k, (t, m) in enumerate(evs)]
        # a session's arrivals after the window are never sent; its
        # indices stay contiguous because the cut keeps a time prefix
        arr = [a for a in arr if a.t < horizon]
        if arr:
            sessions[sid] = arr

    texts = [(a.sid, a.index) for arr in sessions.values() for a in arr
             if a.modality == "text"]
    vitals = [(a.sid, a.index) for arr in sessions.values() for a in arr
              if a.modality == "vitals"]
    tl = _stratified(len(texts), _length_ppf(traffic["text_len"]),
                     _rng(seed, 0x7E47))
    vl = _stratified(len(vitals), _length_ppf(traffic["vitals_len"]),
                     _rng(seed, 0x0B17))
    text_len = {k: int(n) for k, n in zip(texts, tl)}
    vitals_len = {k: int(n) for k, n in zip(vitals, vl)}

    rng = _rng(seed, 0xDA7A)
    vocab, nv, sd = (int(model["vocab_size"]), int(model["n_vitals"]),
                     int(model["scene_dim"]))
    payloads: Dict[Tuple[str, int], np.ndarray] = {}
    for arr in sessions.values():
        for a in arr:
            key = (a.sid, a.index)
            if a.modality == "text":
                payloads[key] = rng.integers(1, vocab, (1, text_len[key]),
                                             dtype=np.int32)
            elif a.modality == "vitals":
                payloads[key] = rng.normal(
                    size=(1, vitals_len[key], nv)).astype(np.float32)
            else:
                payloads[key] = rng.integers(0, 2, (1, sd)).astype(
                    np.float32)
    return Schedule(sessions=sessions, payloads=payloads, text_len=text_len,
                    vitals_len=vitals_len, lead_in_s=lead,
                    window_s=float(window_s))


def inputs_at(schedule: Schedule, sid: str, step: int) -> Dict[str, tuple]:
    """What session ``sid`` held after its first ``step`` arrivals: the
    newest payload of each modality, keyed by modality, as
    ``(arrival index, payload)``."""
    held: Dict[str, tuple] = {}
    for a in schedule.sessions[sid][:step]:
        held[a.modality] = (a.index, schedule.payloads[(a.sid, a.index)])
    return held

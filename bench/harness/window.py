"""From one replay to per-arrival results and the end-to-end metrics.

Every time is on the engine's clock and is measured from when an
arrival was due under the schedule, not from when it was submitted:
an arrival that a blocking flush made late carries that lateness.

* update: an arrival's result is the session's first prediction whose
  step covers it (the session's ``step`` counts its arrivals, so the
  prediction reflects arrival ``index`` once ``step > index``).
* ttfp: a session's first prediction, from its first arrival's due time.

A missing prediction counts as ``math.inf``.
"""
from __future__ import annotations

import bisect
import math
from dataclasses import dataclass
from typing import Dict, List, Tuple

from . import stats
from .gen import Schedule
from .serve import Drive


@dataclass
class Window:
    schedule: Schedule
    drive: Drive
    due: Dict[Tuple[str, int], float]           # arrival -> due time
    update: Dict[Tuple[str, int], float]        # arrival -> emit time / inf
    first_emit: Dict[str, float]                 # sid -> first emit / inf
    predictions: List[dict]                      # every prediction, in order

    @property
    def bounds(self) -> Tuple[float, float]:
        return self.drive.window

    @property
    def seconds(self) -> float:
        w0, w1 = self.bounds
        return w1 - w0

    def inside(self, t: float) -> bool:
        w0, w1 = self.bounds
        return w0 <= t < w1

    def window_arrivals(self) -> List[Tuple[str, int]]:
        return [k for k, t in self.due.items() if self.inside(t)]

    def window_sessions(self) -> List[str]:
        return [sid for sid, arr in self.schedule.sessions.items()
                if self.inside(self.due[(sid, arr[0].index)])]

    def update_latencies(self) -> List[float]:
        return [self.update[k] - self.due[k] for k in self.window_arrivals()]

    def ttfp_latencies(self) -> List[float]:
        return [self.first_emit[sid]
                - self.due[(sid, self.schedule.sessions[sid][0].index)]
                for sid in self.window_sessions()]

    def lateness(self) -> List[float]:
        """Submit minus due, for the window's arrivals that were sent."""
        sub = self.drive.submit
        return [sub[k] - self.due[k] for k in self.window_arrivals()
                if k in sub]


def analyse(schedule: Schedule, drive: Drive) -> Window:
    t0 = drive.t_start
    due = {(a.sid, a.index): t0 + a.t
           for arr in schedule.sessions.values() for a in arr}
    preds: List[dict] = []
    by_sid: Dict[str, List[dict]] = {}
    for _, report in drive.flushes:
        for p in report.predictions:
            rec = {"sid": p.sid, "step": p.step, "t_emit": p.t_emit,
                   "kind": p.kind, "modalities": tuple(p.modalities),
                   "outputs": p.outputs, "flush_id": p.flush_id}
            preds.append(rec)
            by_sid.setdefault(p.sid, []).append(rec)
    update: Dict[Tuple[str, int], float] = {}
    first_emit: Dict[str, float] = {}
    for sid, arr in schedule.sessions.items():
        ps = sorted(by_sid.get(sid, []), key=lambda r: r["t_emit"])
        first_emit[sid] = ps[0]["t_emit"] if ps else math.inf
        # the earliest emit whose step covers arrival k: a running max of
        # steps over emits in time order, searched per arrival
        reach, best = [], 0
        for r in ps:
            best = max(best, r["step"])
            reach.append(best)
        for a in arr:
            i = bisect.bisect_right(reach, a.index)
            update[(sid, a.index)] = ps[i]["t_emit"] if i < len(ps) \
                else math.inf
    return Window(schedule=schedule, drive=drive, due=due, update=update,
                  first_emit=first_emit, predictions=preds)


def end_to_end(win: Window) -> Dict[str, float]:
    """Every end-to-end metric this harness can give; ``setup_s`` is added
    by the caller. Latencies in ms; None where a tail is a miss."""
    ms = lambda v: None if v is None or math.isinf(v) else v * 1e3
    upd = win.update_latencies()
    done = sum(1 for k, t in win.update.items() if win.inside(t))
    return {
        "ttfp_p95_ms": ms(stats.percentile(win.ttfp_latencies(), 95)),
        "update_p95_ms": ms(stats.percentile(upd, 95)),
        "update_p50_ms": ms(stats.percentile(upd, 50)),
        "events_per_s": stats.rate(done, win.seconds),
    }


def failed(win: Window) -> int:
    """Arrivals of the window with no prediction by the end of the drain."""
    return sum(1 for k in win.window_arrivals() if math.isinf(win.update[k]))

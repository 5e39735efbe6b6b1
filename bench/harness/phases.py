"""The program's flush phases: per window flush, and on the device
trace's clock.

The engine records, per flush, a ``flush`` span ``[t0, t1]`` and disjoint
phase spans (cat ``flush``): ``flush.prep``, ``flush.encode``,
``flush.scatter``, ``flush.tail`` and ``flush.sync`` inside it, and
``flush.emit`` from ``t1`` to the return. Each carries the ``flush_id``
of its flush and ``calls``, the device array operations it issued. A
program without the phases gives no reading (None), and no error.

The program's spans are on its own clock (``time.perf_counter``), the
profiler's on another. The traced run wraps every call of the program's
``flush()`` in a ``bench.flush`` annotation that opens a few microseconds
before the program reads ``t0``. So the k-th ``bench.flush``, by start,
anchors the flush with ``flush_id`` k: each of that flush's spans is
placed at ``bench_start + (span.ts - t0)``. Anchoring every flush leaves
no drift between the two clocks. Counts that differ, or a placed
``flush`` span that does not fit inside its ``bench.flush`` within
``SLACK_NS``, raise: the alignment is broken, and a reading from it
would be wrong.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from . import trace

PHASES = ("flush.prep", "flush.encode", "flush.scatter", "flush.tail",
          "flush.sync", "flush.emit")
SLACK_NS = 50_000
ANCHOR = "bench.flush"


def _window_flush_ids(ctx) -> List[int]:
    """``flush_id`` of each flush span that started in the window."""
    return [e.args["flush_id"] for e in ctx.spans
            if e.name == "flush" and ctx.win.inside(e.ts)]


def per_flush(ctx) -> Optional[Dict[int, Dict[str, float]]]:
    """Per window flush: seconds summed by phase, and ``calls`` summed
    over its phases. None where the program records no phases."""
    phases = [e for e in ctx.spans if e.cat == "flush" and e.name in PHASES]
    ids = _window_flush_ids(ctx)
    if not phases or not ids:
        return None
    rows = {i: dict.fromkeys(PHASES + ("calls",), 0.0) for i in ids}
    for e in phases:
        row = rows.get(e.args.get("flush_id"))
        if row is not None:
            row[e.name] += e.dur
            row["calls"] += e.args.get("calls", 0)
    return rows


def _mean(ctx, keys, scale) -> Optional[float]:
    rows = per_flush(ctx)
    if rows is None:
        return None
    return sum(r[k] for r in rows.values() for k in keys) / len(rows) * scale


def flush_prep_ms(ctx) -> Optional[float]:
    return _mean(ctx, ("flush.prep",), 1e3)


def flush_dispatch_ms(ctx) -> Optional[float]:
    return _mean(ctx, ("flush.encode", "flush.tail"), 1e3)


def flush_scatter_ms(ctx) -> Optional[float]:
    return _mean(ctx, ("flush.scatter",), 1e3)


def flush_sync_ms(ctx) -> Optional[float]:
    return _mean(ctx, ("flush.sync",), 1e3)


def flush_emit_ms(ctx) -> Optional[float]:
    return _mean(ctx, ("flush.emit",), 1e3)


def flush_calls(ctx) -> Optional[float]:
    return _mean(ctx, ("calls",), 1.0)


def anchors(ctx) -> Dict[int, float]:
    """flush_id -> offset (ns) from the program's clock to the trace's:
    ``trace_ns = span.ts * 1e9 + offset``. Raises where the program's
    flushes and the trace's ``bench.flush`` annotations do not pair."""
    flushes = sorted((e for e in ctx.spans if e.name == "flush"),
                     key=lambda e: e.args["flush_id"])
    marks = sorted((s, e) for name, s, e in ctx.trace.host if name == ANCHOR)
    if len(flushes) != len(marks):
        raise ValueError(f"{len(flushes)} program flush spans, {len(marks)} "
                         f"{ANCHOR} annotations in the trace")
    out = {}
    for k, (f, (s, e)) in enumerate(zip(flushes, marks)):
        fid = f.args["flush_id"]
        if fid != k:
            raise ValueError(f"flush ids are not 0..{len(flushes) - 1}: "
                             f"{fid} in place {k}")
        over = s + f.dur * 1e9 - e
        if over > SLACK_NS:
            raise ValueError(f"flush {fid} placed at its {ANCHOR} ends "
                             f"{over / 1e3:.1f} us after it")
        out[fid] = s - f.ts * 1e9
    return out


def placed(ctx, name: str) -> Optional[List[Tuple[float, float]]]:
    """Intervals (ns, trace clock) of the window flushes' ``name``
    spans; None where the program records none."""
    spans = [e for e in ctx.spans if e.name == name]
    if not spans:
        return None
    off = anchors(ctx)
    ids = set(_window_flush_ids(ctx))
    return [(e.ts * 1e9 + off[e.args["flush_id"]],
             (e.ts + e.dur) * 1e9 + off[e.args["flush_id"]])
            for e in spans if e.args["flush_id"] in ids]


def overlap_ns(intervals, busy) -> float:
    """Length of the intersection of ``intervals`` with ``busy`` (sorted,
    disjoint)."""
    starts = [s for s, _ in busy]
    total = 0.0
    for s, e in intervals:
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(busy) and busy[i][0] < e:
            total += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
            i += 1
    return total


def sync_idle_share(ctx) -> Optional[float]:
    """Share of the window flushes' ``flush.sync`` time in which the
    device ran no operation (%), averaged over the devices."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    syncs = placed(ctx, "flush.sync")
    if not syncs:
        return None
    total = sum(e - s for s, e in syncs)
    if total <= 0:
        return None
    inf = float("inf")
    busy = [overlap_ns(syncs, trace.merged([(s, e) for _, s, e in ops],
                                           -inf, inf))
            for ops in ctx.trace.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / total)

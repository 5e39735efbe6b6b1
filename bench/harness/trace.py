"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps.

The traced run puts two host markers into the trace,
``bench.window_open`` and ``bench.window_close``; the measured window is
the time between them. Device operations are the events of the "XLA Ops"
line of each TPU device plane. On a TPU v5e each such event is named by
its whole HLO instruction (``%flash_attention.5 = f32[...]
custom-call(...), custom_call_target="tpu_custom_call", ...``); an
operation whose name or statistics name ``tpu_custom_call`` is a Pallas
kernel (the compiler's target for every Pallas call), and an operation
is reported by its instruction's name without the ``%`` and the numeric
suffix (``flash_attention``, ``fusion``, ``copy-done``). Host activity
is read from the harness's own annotations (``bench.flush``,
``bench.sleep``) on the host plane, which shares the device planes'
clock.

The arithmetic works on plain ``(name, start_ns, end_ns)`` tuples, so
that it can be checked on a synthetic trace.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
OPEN, CLOSE = "bench.window_open", "bench.window_close"
HOST_SPANS = ("bench.flush", "bench.sleep")
KERNEL_TARGET = "tpu_custom_call"


@dataclass
class Trace:
    window: Tuple[float, float]                   # ns
    devices: List[List[Event]]                    # ops per device plane
    host: List[Event] = field(default_factory=list)
    kernels: List[List[Event]] = field(default_factory=list)  # Pallas ops

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def merged(intervals: Sequence[Tuple[float, float]], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    lo, hi = tr.window
    per = [sum(e - s for s, e in merged([(s, e) for _, s, e in ops], lo, hi))
           for ops in tr.devices]
    return (sum(per) / len(per)) * 1e-9 if per else 0.0


def op_seconds(tr: Trace) -> Dict[str, float]:
    """Device seconds per operation name inside the window, summed over
    devices."""
    lo, hi = tr.window
    out: Dict[str, float] = {}
    for ops in tr.devices:
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def kernel_seconds(tr: Trace, name: str) -> float:
    """Device seconds of the Pallas kernel whose op name is ``name``
    inside the window, summed over devices."""
    lo, hi = tr.window
    return sum(max(0.0, min(e, hi) - max(s, lo)) * 1e-9
               for ops in tr.kernels for op, s, e in ops if op == name)


def _is_kernel(ev) -> bool:
    return KERNEL_TARGET in ev.name or any(
        isinstance(v, str) and KERNEL_TARGET in v for _, v in ev.stats)


def op_name(hlo: str) -> str:
    """``%fusion.244 = (f32[8,256]...) fusion(...)`` -> ``fusion``."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def idle_gaps(tr: Trace) -> Dict[str, float]:
    """Idle device seconds inside the window, by what the host was doing
    at the middle of each gap: one of ``HOST_SPANS`` (prefix dropped), or
    ``other`` (the serving loop's own work, submits, the generator). The
    host spans do not nest. Averaged over the devices."""
    lo, hi = tr.window
    host = sorted((s, e, name) for name, s, e in tr.host)
    starts = [s for s, _, _ in host]
    out: Dict[str, float] = {}
    for ops in tr.devices:
        busy = merged([(s, e) for _, s, e in ops], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            i = bisect.bisect_right(starts, mid) - 1
            what = (host[i][2].split(".", 1)[-1]
                    if i >= 0 and host[i][1] >= mid else "other")
            out[what] = out.get(what, 0.0) + (g1 - g0) * 1e-9 / len(tr.devices)
    return out


def find_xplane(log_dir: Path) -> Path:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(log_dir: Path) -> Trace:
    """Read the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(find_xplane(log_dir)))
    devices, kernels, host, marks = [], [], [], {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = [ev for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            devices.append([(op_name(ev.name), ev.start_ns, ev.end_ns)
                            for ev in evs])
            kernels.append([(op_name(ev.name), ev.start_ns, ev.end_ns)
                            for ev in evs if _is_kernel(ev)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (OPEN, CLOSE):
                        marks[ev.name] = ev.start_ns
                    elif ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns, ev.end_ns))
    if OPEN not in marks or CLOSE not in marks:
        raise ValueError(f"the trace lacks its window markers: "
                         f"found {sorted(marks)}")
    return Trace(window=(marks[OPEN], marks[CLOSE]), devices=devices,
                 host=host, kernels=kernels)


def summary(log_dir: Path, top: int = 40) -> dict:
    """Planes, lines and the event names that took most time, each with
    the statistics of its first event: what a reader needs to find a
    kernel by hand."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(find_xplane(log_dir)))
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            counts: Dict[str, list] = {}
            for ev in line.events:
                c = counts.setdefault(ev.name, [0, 0.0, None])
                c[0] += 1
                c[1] += ev.duration_ns * 1e-9
                if c[2] is None:
                    c[2] = {k: str(v)[:300] for k, v in ev.stats}
            lines[line.name] = sorted(
                ([n, *c] for n, c in counts.items()),
                key=lambda r: -r[2])[:top]
        out[plane.name] = lines
    return out

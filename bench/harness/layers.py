"""Per-layer quantities shared by the reducers in ``bench/metrics``.

Each takes the run's ``Context`` and returns a number, or None where the
run gave nothing to read (the harness then leaves the metric out).
A flush belongs to the window when it started inside it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List, Optional

from . import stats, trace
from .window import Window, end_to_end


@dataclass
class Context:
    win: Window
    model: dict
    peaks: dict
    family: Optional[ModuleType] = None            # bench/families/<name>
    spans: List = field(default_factory=list)      # the program's Tracer
    trace: Optional[trace.Trace] = None


def window_flushes(ctx: Context) -> List:
    """FlushReports of the flushes that started in the window."""
    return [r for t, r in ctx.win.drive.flushes if ctx.win.inside(t)]


def queue_wait_p95_ms(ctx: Context) -> Optional[float]:
    """Due time to the start of the flush that took the arrival, from the
    program's ``queue.wait`` spans (which end where the flush starts)."""
    ends = {(e.args.get("sid"), e.args.get("index")): e.ts + e.dur
            for e in ctx.spans if e.name == "queue.wait"}
    if not ends:
        return None
    waits = [ends.get(k, math.inf) - ctx.win.due[k]
             for k in ctx.win.window_arrivals()]
    v = stats.percentile(waits, 95)
    return None if v is None or math.isinf(v) else v * 1e3


def flush_ms(ctx: Context) -> Optional[float]:
    """Mean duration of the program's ``flush`` spans that started in the
    window."""
    durs = [e.dur for e in ctx.spans
            if e.name == "flush" and ctx.win.inside(e.ts)]
    return sum(durs) / len(durs) * 1e3 if durs else None


def pad_share(ctx: Context) -> Optional[float]:
    """Padded share of the positions the window's flushes computed, from
    ``FlushReport``: positions weighted by each submodule's parameter
    count, the program's own estimate (not a FLOP count)."""
    fl = window_flushes(ctx)
    pad = sum(r.flops_padded for r in fl)
    tot = pad + sum(r.flops_useful for r in fl)
    return 100.0 * pad / tot if tot > 0 else None


def encoded_inputs(ctx: Context, reports) -> List[tuple]:
    """(modality, natural length) of each input the flushes encoded: per
    flush and session, the newest arrival of each modality it took."""
    sched = ctx.win.schedule
    out = []
    for r in reports:
        newest: Dict[tuple, int] = {}
        for sid, idx in r.latencies:
            mod = sched.sessions[sid][idx].modality
            newest[(sid, mod)] = max(newest.get((sid, mod), -1), idx)
        for (sid, mod), idx in newest.items():
            if mod == "text":
                n = sched.text_len[(sid, idx)]
            elif mod == "vitals":
                n = sched.vitals_len[(sid, idx)]
            else:
                n = 1
            out.append((mod, n))
    return out


FLASH = "flash_attention"       # the flash kernel's op name in the trace


def flash_roofline(ctx: Context) -> Optional[float]:
    """Least time the chip needs for the window's useful attention work
    (natural lengths; the larger of its FLOPs over the bf16 peak and its
    bytes over HBM bandwidth, as the family's ``kernel_work`` counts
    them), over the device time of the Pallas ops named ``FLASH`` in the
    window. A window that encoded text while the trace shows no such op
    is an error: the kernel went unseen."""
    if ctx.trace is None:
        return None
    pf, pb = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    work = (ctx.family.kernel_work(FLASH, n, ctx.model)
            for mod, n in encoded_inputs(ctx, window_flushes(ctx))
            if mod == "text")
    least = sum(max(f / pf, b / pb) for f, b in work)
    if least <= 0:
        return None
    kernel_s = trace.kernel_seconds(ctx.trace, FLASH)
    if kernel_s <= 0:
        raise ValueError("the window encoded text, but the trace holds no "
                         f"{FLASH} {trace.KERNEL_TARGET} operation")
    return 100.0 * least / kernel_s


def idle_share(ctx: Context) -> Optional[float]:
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / ctx.trace.window_s)


def flush_mfu(ctx: Context) -> Optional[float]:
    """Useful model FLOPs of the window's flushes (encoders at natural
    lengths, heads per prediction, as the family counts them) over their
    summed wall time at the bf16 peak."""
    fl = window_flushes(ctx)
    wall = sum(r.wall_s for r in fl)
    if wall <= 0:
        return None
    work = sum(ctx.family.encoder_flops(mod, n, ctx.model)
               for mod, n in encoded_inputs(ctx, fl))
    work += sum(ctx.family.head_flops(p.modalities, ctx.model)
                for r in fl for p in r.predictions)
    return 100.0 * work / (wall * ctx.peaks["bf16_flops_per_s"])


def ttfp_tail_ms(ctx: Context) -> Optional[float]:
    """``ttfp_p95_ms`` of the traced run, for a cell whose runs spread
    too widely to bound it end to end."""
    return end_to_end(ctx.win)["ttfp_p95_ms"]


def update_tail_ms(ctx: Context) -> Optional[float]:
    """``update_p95_ms`` of the traced run, for a cell whose runs spread
    too widely to bound it end to end."""
    return end_to_end(ctx.win)["update_p95_ms"]

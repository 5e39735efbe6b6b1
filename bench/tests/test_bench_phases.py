"""The readers of the program's flush phases, on synthetic spans and a
synthetic trace: per-flush means, and the sync's device idle share once
the program's clock is anchored on the trace's."""
from types import SimpleNamespace

import pytest

import _setup  # noqa: F401
from harness import layers, phases, trace
from repro.obs import Tracer

MS = 1_000_000  # ns
# phase -> (start, end) in ms after the flush's t0, and calls; the flush
# span ends at the sync's end, emit runs after it
PLAN = {"flush.prep": (1, 11, 5), "flush.encode": (11, 13, 1),
        "flush.scatter": (13, 20, 4), "flush.tail": (20, 21, 1),
        "flush.sync": (21, 31, 0), "flush.emit": (31, 35, 0)}
T0 = [100.0, 101.0, 102.0]      # program clock (s) of flushes 0, 1, 2
WINDOW = (100.5, 103.0)          # flushes 1 and 2 start inside


def program_spans(*, with_phases=True):
    tr = Tracer()
    for fid, t0 in enumerate(T0):
        if with_phases:
            for name, (a, b, calls) in PLAN.items():
                tr.span(name, "flush", t0 + a / 1e3, t0 + b / 1e3,
                        flush_id=fid, calls=calls)
            # flush 2 stacks one more chunk: 2 ms more prep, 3 calls
            if fid == 2:
                tr.span("flush.prep", "flush", t0 + 0.0002, t0 + 0.0022,
                        flush_id=fid, calls=3)
        tr.span("flush", "flush", t0, t0 + 0.031, flush_id=fid)
    return tr.events


def anchored_trace(offset_ns, *, drop_last=False, short_ns=0):
    """``bench.flush`` opens 3 us before each t0 on a clock ``offset_ns``
    away; the device runs the first 4 ms of each sync, the whole sync
    of flush 0 (outside the window), and once during prep."""
    host, ops = [], []
    for fid, t0 in enumerate(T0):
        s = offset_ns + (t0 - 100.0) * 1e9 - 3_000
        host.append(("bench.flush", s, s + 35 * MS + 5_000 - short_ns))
        sync = (s + 21 * MS, s + (31 if fid == 0 else 25) * MS)
        ops += [("fusion", *sync), ("copy", s + 2 * MS, s + 3 * MS)]
    if drop_last:
        host.pop()
    lo = offset_ns - 1e9
    return trace.Trace(window=(lo, lo + 5e9), devices=[ops], host=host)


def ctx(spans, tr=None):
    w0, w1 = WINDOW
    win = SimpleNamespace(inside=lambda t: w0 <= t < w1)
    return layers.Context(win=win, model={}, peaks={}, spans=spans, trace=tr)


def test_per_flush_means_over_the_window_flushes():
    c = ctx(program_spans())
    # flushes 1 and 2: prep 10 and 12 ms, calls 11 and 14
    assert phases.flush_prep_ms(c) == pytest.approx(11.0)
    assert phases.flush_dispatch_ms(c) == pytest.approx(3.0)
    assert phases.flush_scatter_ms(c) == pytest.approx(7.0)
    assert phases.flush_sync_ms(c) == pytest.approx(10.0)
    assert phases.flush_emit_ms(c) == pytest.approx(4.0)
    assert phases.flush_calls(c) == pytest.approx(12.5)


@pytest.mark.parametrize("offset_ns", [0.0, 7.3e9, 1.7e18])
def test_sync_idle_share_after_anchoring(offset_ns):
    c = ctx(program_spans(), anchored_trace(offset_ns))
    # 4 ms of each window flush's 10 ms sync is busy
    assert phases.sync_idle_share(c) == pytest.approx(60.0, abs=1e-3)


def test_a_count_mismatch_raises():
    c = ctx(program_spans(), anchored_trace(0.0, drop_last=True))
    with pytest.raises(ValueError, match="annotations"):
        phases.sync_idle_share(c)


def test_a_flush_that_overruns_its_anchor_raises():
    c = ctx(program_spans(), anchored_trace(0.0, short_ns=4 * MS + 60_000))
    with pytest.raises(ValueError, match="after it"):
        phases.sync_idle_share(c)


def test_a_program_without_phases_reads_nothing():
    c = ctx(program_spans(with_phases=False), anchored_trace(0.0))
    for read in (phases.flush_prep_ms, phases.flush_dispatch_ms,
                 phases.flush_scatter_ms, phases.flush_sync_ms,
                 phases.flush_emit_ms, phases.flush_calls,
                 phases.sync_idle_share):
        assert read(c) is None
    assert phases.sync_idle_share(ctx(program_spans())) is None  # untraced


def test_overlap_with_sorted_busy_intervals():
    busy = [(0, 10), (20, 30), (40, 50)]
    assert phases.overlap_ns([(5, 25), (45, 60)], busy) == 5 + 5 + 5
    assert phases.overlap_ns([(10, 20), (60, 70)], busy) == 0

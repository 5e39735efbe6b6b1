"""Percentiles, rates and the per-arrival results, with missed arrivals
counted as misses."""
import math
from types import SimpleNamespace

import pytest

import _setup  # noqa: F401
from harness import gen, stats, window
from harness.serve import Drive


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None


def test_missing_counts_as_a_miss():
    xs = [1.0] * 94 + [math.inf] * 6
    assert math.isinf(stats.percentile(xs, 95))
    assert stats.percentile(xs, 50) == 1.0


def test_rate_refuses_empty_window():
    assert stats.rate(30, 10.0) == 3.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def _schedule():
    A = gen.Arrival
    sessions = {
        "a": [A("a", 0, "text", 1.0), A("a", 1, "vitals", 1.5),
              A("a", 2, "scene", 2.5)],
        "b": [A("b", 0, "vitals", 1.2), A("b", 1, "text", 1.3)],
        "c": [A("c", 0, "scene", 0.5)],              # in the lead-in
    }
    return gen.Schedule(sessions=sessions, payloads={}, text_len={},
                        vitals_len={}, lead_in_s=1.0, window_s=2.0)


def _report(*preds):
    return SimpleNamespace(predictions=[
        SimpleNamespace(sid=s, step=k, t_emit=t, kind="partial",
                        modalities=(), outputs={}, flush_id=0)
        for s, k, t in preds])


def test_window_results_from_due_time():
    t0 = 100.0
    drive = Drive(t_start=t0, window=(t0 + 1.0, t0 + 3.0))
    drive.flushes = [
        (t0 + 0.55, _report(("c", 1, t0 + 0.6))),
        (t0 + 1.35, _report(("a", 1, t0 + 1.4), ("b", 2, t0 + 1.4))),
        # a's second arrival is covered here; its third never is
        (t0 + 1.9, _report(("a", 2, t0 + 2.0))),
    ]
    win = window.analyse(_schedule(), drive)
    assert sorted(win.window_arrivals()) == [("a", 0), ("a", 1), ("a", 2),
                                             ("b", 0), ("b", 1)]
    lat = dict(zip(win.window_arrivals(), win.update_latencies()))
    assert lat[("a", 0)] == pytest.approx(0.4)
    assert lat[("a", 1)] == pytest.approx(0.5)
    assert math.isinf(lat[("a", 2)])
    assert lat[("b", 0)] == pytest.approx(0.2)
    assert lat[("b", 1)] == pytest.approx(0.1)
    assert window.failed(win) == 1
    assert sorted(win.ttfp_latencies()) == pytest.approx([0.2, 0.4])
    e2e = window.end_to_end(win)
    assert e2e["update_p95_ms"] is None          # the miss is the tail
    assert e2e["update_p50_ms"] == pytest.approx(400.0)
    # emits inside the window: a0, a1 (both at 1.4 / 2.0), b0, b1; c's
    # lead-in arrival was emitted before the window opened
    assert e2e["events_per_s"] == pytest.approx(4 / 2.0)


def test_tail_readers_give_the_window_tails():
    from harness import layers
    t0 = 100.0
    drive = Drive(t_start=t0, window=(t0 + 1.0, t0 + 3.0))
    drive.flushes = [
        (t0 + 1.35, _report(("a", 3, t0 + 1.4), ("b", 2, t0 + 1.4))),
    ]
    win = window.analyse(_schedule(), drive)
    ctx = layers.Context(win=win, model={}, peaks={})
    e2e = window.end_to_end(win)
    assert layers.update_tail_ms(ctx) == e2e["update_p95_ms"]
    assert layers.ttfp_tail_ms(ctx) == pytest.approx(400.0)

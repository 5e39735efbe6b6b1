"""The traffic generator: the same seed gives the same run, and every
seed offers the same work in another order."""
import json

import numpy as np
import pytest

import _setup  # noqa: F401
from harness import gen

TRAFFIC = json.loads((_setup.BENCH / "traffic" / "steady.tinybert.json")
                     .read_text())
MODEL = json.loads((_setup.BENCH / "configs" / "emsnet-tinybert-gru.json")
                   .read_text())["model"]


def schedule(seed, **kw):
    return gen.build_schedule(dict(TRAFFIC, **kw), MODEL, seed=seed,
                              window_s=4.0)


def test_same_seed_same_schedule():
    a, b = schedule(2**33 + 7), schedule(2**33 + 7)
    assert [x for x in a.arrivals()] == [x for x in b.arrivals()]
    assert a.payloads.keys() == b.payloads.keys()
    for k in a.payloads:
        np.testing.assert_array_equal(a.payloads[k], b.payloads[k])


def test_other_seed_same_work_in_other_order():
    rate, horizon = 12.0, 10.0
    s1 = gen.session_starts(rate, horizon, 1)
    s2 = gen.session_starts(rate, horizon, 2)
    assert len(s1) == len(s2) == round(rate * horizon)
    # the gaps between starts come from one set (the last gap of each
    # order falls after the horizon)
    a = set(np.round(np.diff(s1), 9))
    b = set(np.round(np.diff(s2), 9))
    assert len(a & b) >= len(s1) - 2
    assert not np.allclose(s1, s2)
    assert s1[0] == 0.0 and s1[-1] < horizon


@pytest.mark.parametrize("seed", [0, 5, 2**40 + 3])
def test_lengths_within_traffic_bounds(seed):
    s = schedule(seed)
    tl = np.array(list(s.text_len.values()))
    assert tl.min() >= 8 and tl.max() <= 256
    assert abs(np.median(tl) - 64) <= 16
    vl = np.array(list(s.vitals_len.values()))
    assert vl.min() >= 1 and vl.max() <= 30
    for (sid, idx), p in s.payloads.items():
        mod = s.sessions[sid][idx].modality
        if mod == "text":
            assert p.shape == (1, s.text_len[(sid, idx)]) and p.min() >= 1
            assert p.max() < MODEL["vocab_size"]


def test_session_arrivals_are_time_ordered_and_cut_at_window_end():
    s = schedule(3)
    end = s.window[1]
    for arr in s.sessions.values():
        assert [a.index for a in arr] == list(range(len(arr)))
        assert all(a.t < end for a in arr)
        assert all(x.t <= y.t for x, y in zip(arr, arr[1:]))


def test_inputs_at_holds_newest_payload_per_modality():
    s = schedule(4)
    sid, arr = next((k, v) for k, v in s.sessions.items() if len(v) >= 4)
    held = gen.inputs_at(s, sid, len(arr))
    for mod, (idx, payload) in held.items():
        newest = max(a.index for a in arr if a.modality == mod)
        assert idx == newest
        assert payload is s.payloads[(sid, idx)]
    assert gen.inputs_at(s, sid, 0) == {}


def test_bursts_add_starts_only_inside_burst_windows():
    rate, horizon = 4.0, 30.0
    b = {"factor": 3.0, "length_s": 2.0, "every_s": 10.0}
    extra = gen.burst_starts(rate, horizon, 9, b)
    assert len(extra) == round(2.0 * rate * 6.0)
    assert all((t % 10.0) < 2.0 for t in extra)
    s = schedule(9, bursts=b)
    assert len(s.sessions) > len(schedule(9).sessions)


def test_a_modality_can_be_left_out():
    s = schedule(6, n_vitals=0, n_scene=0)
    mods = {a.modality for arr in s.sessions.values() for a in arr}
    assert mods == {"text"}

"""A whole run of a cell on the CPU, past the look for a chip, at tiny
widths: sound, it is correct; with the timed path broken underneath, in
each way a serving cell can be broken, it is not."""
import dataclasses

import pytest

import _setup
import run as bench
from harness import serve

CELL = "tinybert.steady"
# the tiny model stands in for the cell's configuration; on the CPU the
# program computes in float32, far inside any limit the chip needs
TINY_LIMIT = {"output_gap": 1e-4, "mean_gap": 1e-5}


@pytest.fixture(scope="module")
def splits():
    return serve.build_zoo(bench.load_family("emsnet_bert_gru"),
                           _setup.TINY_MODEL)


def run_with(monkeypatch, splits, *, zoo=None, rate=None):
    monkeypatch.setattr(serve, "build_zoo",
                        lambda family, model: zoo or splits)
    traffic = dict(_setup.TINY_TRAFFIC)
    if rate is not None:
        traffic["rate_sessions_per_s"] = rate
    return bench.run(CELL, 2**35 + 11, 2.0, False, chips_check=False,
                     model_override=_setup.TINY_MODEL,
                     serving_override=_setup.TINY_SERVING,
                     traffic_override=traffic, limits=TINY_LIMIT)


def rewrap(splits, *, encoder=None, tail=None):
    out = {}
    for name, sm in splits.items():
        enc = {m: (encoder(f) if encoder else f) for m, f in sm.encoders.items()}
        out[name] = dataclasses.replace(sm, encoders=enc,
                                        tail=tail(sm.tail) if tail else sm.tail)
    return out


def test_sound_run_is_correct(monkeypatch, splits):
    res = run_with(monkeypatch, splits)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # the cell's latency tails spread too widely to bound: per layer
    assert set(res["metrics"]) == {"setup_s", "update_p50_ms"}
    assert list(res)[-1] == "checks"


def test_altered_answer_is_not_correct(monkeypatch, splits):
    def tail(fn):
        def altered(params, feats):
            out = fn(params, feats)
            return dict(out, protocol_logits=out["protocol_logits"]
                        .at[0, 0].add(0.5))
        return altered
    res = run_with(monkeypatch, splits, zoo=rewrap(splits, tail=tail))
    assert not res["correct"]
    assert res["checks"]["output_gap"]["value"] > 0.1


def test_half_the_batch_left_out_is_not_correct(monkeypatch, splits):
    def encoder(fn):
        def half(params, batch):
            feats = fn(params, batch)
            return feats.at[1::2].set(0.0)
        return half
    # every other row of each stacked batch is left out; at this rate
    # most flushes stack more than one session per modality
    res = run_with(monkeypatch, splits, zoo=rewrap(splits, encoder=encoder),
                   rate=12.0)
    assert not res["correct"]


def test_state_left_unchanged_is_not_correct(monkeypatch, splits):
    from repro.core.feature_cache import FeatureCache
    put = FeatureCache.put

    def stale_put(self, session, modality, feature, *, step, tier="glass"):
        prev = self._store.get((session, modality))
        if prev is not None:      # keep the old feature, move the step on
            feature = prev.feature
        return put(self, session, modality, feature, step=step, tier=tier)
    monkeypatch.setattr(FeatureCache, "put", stale_put)
    res = run_with(monkeypatch, splits)
    assert not res["correct"]

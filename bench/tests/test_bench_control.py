"""The control against each configuration's own limits, at the
configuration's widths: the reference computed in bfloat16, put in the
program's place on rows drawn as a run draws them, has to fail the
comparison that decides ``correct``."""
import json

import jax.numpy as jnp
import pytest

import _setup
from harness import check, gen, reference
from harness.weights import make_params

SPEC = json.loads((_setup.BENCH.parent / "BENCHMARK.json").read_text())
# each configuration's first cell
CELLS = {}
for w in SPEC["workloads"]:
    CELLS.setdefault(w["config"], w)
ROWS = 24


def load(config):
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    model_file = json.loads((_setup.BENCH.parent / entry["file"]).read_text())
    traffic = json.loads((_setup.BENCH / "traffic"
                          / f"{CELLS[config]['traffic']}.json").read_text())
    return model_file, traffic


def window_rows(model, traffic, seed):
    """The inputs that ``ROWS`` sessions held after their last arrival,
    longest narratives first, as the run's sample keeps them."""
    sched = gen.build_schedule(traffic, model, seed=seed, window_s=4.0)
    held = [gen.inputs_at(sched, sid, len(arr))
            for sid, arr in sched.sessions.items()]
    held.sort(key=lambda h: -h["text"][1].shape[1] if "text" in h else 0)
    return [{m: p for m, (_, p) in h.items()} for h in held[:ROWS]]


@pytest.mark.parametrize("config", sorted(CELLS))
def test_configuration_names_known_numbers(config):
    rule = load(config)[0]["correct"]
    assert rule["reference_precision"] in ("highest", "high", "default")
    assert rule["limits"] and set(rule["limits"]) <= set(check.NUMBERS)


@pytest.mark.parametrize("config", sorted(CELLS))
def test_bfloat16_control_fails_the_configured_limits(config):
    model_file, traffic = load(config)
    m, rule = model_file["model"], model_file["correct"]
    params = make_params(m, seed=2**34 + 5)
    rows = window_rows(m, traffic, seed=2**34 + 5)
    ref = reference.forward(params, m, rows, block=ROWS,
                            precision=rule["reference_precision"])
    low = reference.forward(params, m, rows, block=ROWS, dtype=jnp.bfloat16,
                            precision=rule["reference_precision"])
    checks = check.judge(low, ref, rule["limits"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks

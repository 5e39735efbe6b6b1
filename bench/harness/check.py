"""The comparison that decides ``correct``.

The sample is drawn from the seed among the predictions the window
emitted, partial and final, and always holds the predictions whose
text was longest. For each one the reference is run once over the
inputs the session held when the prediction was made (``gen.inputs_at``
at the prediction's step), on the modality subset that those inputs
cover. Two numbers are compared, over every logit and quantity of the
sample: the widest gap between a served output and the reference's
(``output_gap``), which a single wrong answer fails, and the mean gap
(``mean_gap``). Which numbers a configuration compares, their limits
and the reference's matmul precision are in its file under
``bench/configs``, key ``correct``, with the readings they were set
from in PERF.md.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import reference
from .gen import Schedule, inputs_at

SAMPLE = 256          # predictions compared per run
LONGEST = 16          # of which the ones with the longest text


def draw_sample(preds: List[dict], schedule: Schedule, seed: int) -> List[dict]:
    """``preds``: dicts with sid, step, ... of the window's predictions."""
    def text_len(p):
        held = inputs_at(schedule, p["sid"], p["step"])
        return held["text"][1].shape[1] if "text" in held else 0
    order = sorted(range(len(preds)), key=lambda i: -text_len(preds[i]))
    keep = order[:LONGEST]
    rest = np.array(order[LONGEST:], dtype=np.int64)
    rng = np.random.default_rng([int(seed) % (1 << 63), 0xC4EC])
    take = min(len(rest), SAMPLE - len(keep))
    if take:
        keep += rng.choice(rest, size=take, replace=False).tolist()
    return [preds[i] for i in sorted(keep)]


def expected_rows(sample: List[dict], schedule: Schedule):
    """Reference input rows, and the modality subset each prediction has
    to cover."""
    rows, subsets = [], []
    for p in sample:
        held = inputs_at(schedule, p["sid"], p["step"])
        rows.append({mod: payload for mod, (_, payload) in held.items()})
        subsets.append(tuple(x for x in reference.MODALITIES if x in held))
    return rows, subsets


def output_gap(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> float:
    """Widest |got - ref| over every output of every row."""
    return max(float(np.max(np.abs(np.asarray(got[k], np.float64)
                                   - np.asarray(ref[k], np.float64))))
               for k in reference.OUTPUTS)


def mean_gap(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> float:
    """Mean |got - ref| over every output of every row."""
    d = np.concatenate([np.abs(np.asarray(got[k], np.float64)
                               - np.asarray(ref[k], np.float64)).ravel()
                        for k in reference.OUTPUTS])
    return float(d.mean())


def stack_outputs(sample: List[dict]) -> Dict[str, np.ndarray]:
    return {k: np.stack([np.asarray(p["outputs"][k], np.float32).reshape(-1)
                         for p in sample]).reshape(len(sample), -1)
            if k != "quantity" else
            np.array([float(np.asarray(p["outputs"][k]).reshape(-1)[0])
                      for p in sample], np.float32)
            for k in reference.OUTPUTS}


NUMBERS = {"output_gap": output_gap, "mean_gap": mean_gap}


def judge(got, ref, limits: Dict[str, float]) -> Dict[str, dict]:
    """Each number that ``limits`` names, beside its limit; a value of
    None (nothing to compare) fails."""
    return {name: {"value": None if got is None else NUMBERS[name](got, ref),
                   "limit": lim} for name, lim in limits.items()}

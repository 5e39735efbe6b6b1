"""The emsnet_bert_gru family's plain reference against the program's
model at tiny widths, and the bfloat16 control against the reference."""
import jax.numpy as jnp
import numpy as np
import pytest

import _setup
import run as bench
from harness import check, reference

FAMILY = bench.load_family("emsnet_bert_gru")

M = dict(_setup.TINY_MODEL, max_text_len=16, vitals_len=8)
SUBSETS = [("text",), ("vitals",), ("scene",), ("text", "vitals"),
           ("text", "scene"), ("vitals", "scene"),
           ("text", "vitals", "scene")]


def rows(seed, n):
    r = np.random.default_rng(seed)
    out = []
    for i in range(n):
        sub = SUBSETS[i % len(SUBSETS)]
        row = {}
        if "text" in sub:
            L = int(r.integers(1, M["max_text_len"] + 1))
            row["text"] = r.integers(1, M["vocab_size"], (1, L)).astype(
                np.int32)
        if "vitals" in sub:
            T = int(r.integers(1, M["vitals_len"] + 1))
            row["vitals"] = r.normal(size=(1, T, 6)).astype(np.float32)
        if "scene" in sub:
            row["scene"] = r.integers(0, 2, (1, 3)).astype(np.float32)
        out.append(row)
    return out


@pytest.fixture(scope="module")
def params():
    return FAMILY.make_params(M, seed=3)


def program_outputs(params, rs, use_flash):
    from repro.models import emsnet as E
    cfg = FAMILY.program_config(dict(M, use_flash_text=use_flash))
    outs = {k: [] for k in reference.OUTPUTS}
    for row in rs:
        sub = tuple(m for m in reference.MODALITIES if m in row)
        o = E.partial_forward(params, cfg, row, sub)
        for k in reference.OUTPUTS:
            outs[k].append(np.asarray(o[k]).reshape(-1))
    return {k: np.stack(v) if k != "quantity" else np.concatenate(v)
            for k, v in outs.items()}


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["einsum", "flash"])
def test_reference_matches_program_on_every_subset(params, use_flash):
    rs = rows(0, 14)
    ref = reference.forward(FAMILY, params, M, rs, block=8)
    got = program_outputs(params, rs, use_flash)
    # both are float32 on the CPU backend: only the order of the sums
    # differs, a few units of float32 rounding on outputs of size ~1
    assert check.output_gap(got, ref) < 2e-5
    assert max(float(np.abs(v).max()) for v in ref.values()) > 0.5


def test_bfloat16_control_is_far_from_reference(params):
    rs = rows(1, 14)
    ref = reference.forward(FAMILY, params, M, rs, block=8)
    low = reference.forward(FAMILY, params, M, rs, block=8, dtype=jnp.bfloat16)
    # one bfloat16 rounding alone is 2^-9 relative; the control's gap is
    # many times the float32 program's
    assert check.output_gap(low, ref) > 100 * 2e-5


def test_a_wrong_head_row_is_seen(params):
    rs = rows(2, 7)
    ref = reference.forward(FAMILY, params, M, rs, block=8)
    got = {k: v.copy() for k, v in ref.items()}
    got["medicine_logits"][3, 5] += 0.05
    assert check.output_gap(got, ref) == pytest.approx(0.05, rel=1e-3)

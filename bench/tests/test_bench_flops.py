"""FLOP and byte counts against values worked out by hand at tiny
widths."""
import pytest

import _setup  # noqa: F401
from harness import flops

M = {"text_layers": 2, "text_hidden": 4, "text_heads": 2, "text_ffn": 8,
     "n_vitals": 3, "vitals_hidden": 2, "scene_dim": 3, "scene_hidden": 5,
     "n_protocols": 4, "n_medicines": 2}


def test_attention():
    # QK^T and PV: 2 x (2 L^2 d) per layer -> 2 layers x 4 x 9 x 4
    assert flops.attention_flops(3, M) == 288
    # Q, K, V, O: 4 tensors x 3 x 4 floats x 4 bytes x 2 layers
    assert flops.attention_bytes(3, M) == 384


def test_text_encoder():
    # per token and layer: qkv 2*4*12 + wo 2*4*4 + ffn 2*(2*4*8) = 96+32+128
    assert flops.text_flops(3, M) == 2 * 256 * 3 + 288


def test_gru():
    # per step: 3 gates x (2*3*2 input + 2*2*2 hidden) = 3 x 20
    assert flops.gru_flops(5, M) == 5 * 60


def test_scene_and_heads():
    assert flops.scene_flops(M) == 30
    # fused width text 4 + scene 5, outputs 4 + 2 + 1
    assert flops.head_flops(("text", "scene"), M) == 2 * 9 * 7
    assert flops.encoder_flops("scene", 1, M) == 30


@pytest.mark.parametrize("mod,n,want", [("text", 3, 2 * 256 * 3 + 288),
                                        ("vitals", 5, 300)])
def test_encoder_dispatch(mod, n, want):
    assert flops.encoder_flops(mod, n, M) == want

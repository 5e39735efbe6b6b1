"""Operations and bytes of EMSNet's work, from natural input lengths.

Counted from what was served (token and reading counts before any
bucketing or packing) and the configuration's widths, never from padded
shapes, so that a change to bucketing cannot make the count stale. A
multiply-add is two operations.
"""
from __future__ import annotations


def attention_flops(L: int, m: dict) -> float:
    """QK^T and PV of one text input of ``L`` tokens, all layers."""
    return m["text_layers"] * 4.0 * L * L * m["text_hidden"]


def attention_bytes(L: int, m: dict) -> float:
    """Q, K, V read and O written once per layer, float32."""
    return m["text_layers"] * 4.0 * L * m["text_hidden"] * 4.0


def text_flops(L: int, m: dict) -> float:
    """The text encoder on one input: projections, FFN and attention."""
    d, ff = m["text_hidden"], m["text_ffn"]
    per_token = 2.0 * (d * 3 * d + d * d + 2 * d * ff)
    return m["text_layers"] * per_token * L + attention_flops(L, m)


def gru_flops(n: int, m: dict) -> float:
    """A 3-gate GRU over ``n`` readings: input and hidden matmuls."""
    h = m["vitals_hidden"]
    return n * 3 * (2.0 * m["n_vitals"] * h + 2.0 * h * h)


def scene_flops(m: dict) -> float:
    return 2.0 * m["scene_dim"] * m["scene_hidden"]


FEATURE_WIDTH = {"text": "text_hidden", "vitals": "vitals_hidden",
                 "scene": "scene_hidden"}


def head_flops(modalities, m: dict) -> float:
    """The three heads over the fused features of ``modalities``."""
    fc = sum(m[FEATURE_WIDTH[x]] for x in modalities)
    return 2.0 * fc * (m["n_protocols"] + m["n_medicines"] + 1)


def encoder_flops(modality: str, n: int, m: dict) -> float:
    """One input of ``modality`` with natural length ``n``."""
    if modality == "text":
        return text_flops(n, m)
    if modality == "vitals":
        return gru_flops(n, m)
    return scene_flops(m)

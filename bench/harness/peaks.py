"""Published per-chip peaks, keyed by ``jax.Device.device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = json.loads((Path(__file__).with_name("peaks.json")).read_text())


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; a kind that is not in the table is an error.
    Float32 matmuls at default precision take one bf16 pass on the chip,
    so the bf16 peak is the compute roof of every metric here."""
    if device_kind == "source" or device_kind not in _TABLE:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: "
                         f"{sorted(k for k in _TABLE if k != 'source')}")
    return _TABLE[device_kind]

"""Shared set-up of the benchmark's tests: the CPU backend, the paths to
the harness and the program, and a tiny configuration a CPU can run."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# microbert widths (the program's CPU tier), a 256-token vocabulary
TINY_MODEL = {
    "text_encoder": "microbert", "text_layers": 2, "text_hidden": 64,
    "text_heads": 4, "text_ffn": 128, "vocab_size": 256, "max_text_len": 16,
    "vitals_encoder": "gru", "n_vitals": 6, "vitals_len": 8,
    "vitals_hidden": 16, "scene_dim": 3, "scene_hidden": 8,
    "n_protocols": 46, "n_medicines": 18, "dtype": "float32",
    "use_flash_text": True, "flash_block": 8,
}
TINY_SERVING = {"max_coalesce": 4, "bucket_max": {"text": 16, "vitals": 8}}
TINY_TRAFFIC = {
    "rate_sessions_per_s": 4.0, "lead_in_s": 0.5,
    "text_len": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                 "min": 2, "max": 16},
    "vitals_len": {"dist": "uniform", "min": 1, "max": 8},
}

"""Useful model FLOPs of the window's flushes over their wall time at
the chip's bf16 peak (%)."""
from harness.layers import flush_mfu as read  # noqa: F401

"""Padded share of the positions the window's flushes computed (%), from
FlushReport's parameter-weighted position counts."""
from harness.layers import pad_share as read  # noqa: F401

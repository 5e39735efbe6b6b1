"""p95 of first-prediction latency over the window's sessions (ms), in
a cell whose runs spread too widely to bound it end to end."""
from harness.layers import ttfp_tail_ms as read  # noqa: F401

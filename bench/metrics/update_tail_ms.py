"""p95 of update latency over the window's arrivals (ms), in a cell
whose runs spread too widely to bound it end to end."""
from harness.layers import update_tail_ms as read  # noqa: F401

"""p95 over the window's arrivals of due time to the start of the flush
that took them (ms), from the program's queue.wait spans."""
from harness.layers import queue_wait_p95_ms as read  # noqa: F401

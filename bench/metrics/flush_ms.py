"""Mean duration of the flushes that started in the window (ms), from
the program's flush spans."""
from harness.layers import flush_ms as read  # noqa: F401

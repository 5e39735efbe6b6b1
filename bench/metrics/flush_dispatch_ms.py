"""Mean over the window's flushes of their summed flush.encode and
flush.tail spans (ms): the encoder and tail program calls."""
from harness.phases import flush_dispatch_ms as read  # noqa: F401

"""Mean over the window's flushes of their flush.sync span (ms): the
one host sync."""
from harness.phases import flush_sync_ms as read  # noqa: F401

"""Mean over the window's flushes of their flush.emit span (ms): from
the end of the flush span to the return of flush()."""
from harness.phases import flush_emit_ms as read  # noqa: F401

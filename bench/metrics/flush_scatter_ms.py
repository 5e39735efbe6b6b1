"""Mean over the window's flushes of their summed flush.scatter spans
(ms): row slices into the feature cache and the emitted rows."""
from harness.phases import flush_scatter_ms as read  # noqa: F401

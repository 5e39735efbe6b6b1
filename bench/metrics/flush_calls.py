"""Mean over the window's flushes of the device array operations their
phases issued (the phase spans' summed calls)."""
from harness.phases import flush_calls as read  # noqa: F401

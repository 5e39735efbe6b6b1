"""Share of the window flushes' flush.sync time in which the device ran
no operation (%), with the program's spans anchored on the trace's clock."""
from harness.phases import sync_idle_share as read  # noqa: F401

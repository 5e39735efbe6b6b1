"""Share of the traced window in which no operation ran on the device
(%)."""
from harness.layers import idle_share as read  # noqa: F401

"""The flash kernel's share of its roofline (%): least time for the useful
attention work over the kernel's device time in the trace."""
from harness.layers import flash_roofline as read  # noqa: F401

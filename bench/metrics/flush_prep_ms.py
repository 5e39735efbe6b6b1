"""Mean over the window's flushes of their summed flush.prep spans (ms):
model selection, cache reads, bucketer pads, grouping, stacks and packs."""
from harness.phases import flush_prep_ms as read  # noqa: F401

"""engine: summed ``serve/engine.fetch`` over the window: the share of wall time
the scheduler thread is blocked on the device (lib/span_tree.py). 100 minus
it is what overlapping host and device work could win back."""

from benchmarks.lib.span_tree import device_wait_share as read  # noqa: F401

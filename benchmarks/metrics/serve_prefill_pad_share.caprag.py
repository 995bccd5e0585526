"""engine: padding in the window's prefill calls, 1 - prompt tokens over
buckets (1,536 / 2,560 / 4,096 / 6,144), from the prefill calls'
``serve/engine.stage`` counters (lib/span_tree.py)."""

from benchmarks.lib.span_tree import prefill_pad_share as read  # noqa: F401

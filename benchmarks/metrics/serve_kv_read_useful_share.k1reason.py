"""engine (paged latent pool): positions the decode rows attend over
positions of the latent pool the decode program gathers, from the decode
calls' ``serve/engine.stage`` counters (lib/span_tree.py)."""

from benchmarks.lib.span_tree import kv_read_useful_share as read  # noqa: F401

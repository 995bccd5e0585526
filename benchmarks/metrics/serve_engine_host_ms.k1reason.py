"""engine: median over decode calls of the host's part of the call: stage +
dispatch + what of ``serve/decode`` no engine span covers (lib/span_tree.py);
with a small vocabulary's sort out of the way, what the engine costs a tick."""

from benchmarks.lib.span_tree import engine_host_ms as read  # noqa: F401

"""engine: median over decode calls of the host's part of the call: stage +
dispatch + what of ``serve/decode`` no engine span covers (lib/span_tree.py);
a 64-row call with block tables of 416 entries a row to stage."""

from benchmarks.lib.span_tree import engine_host_ms as read  # noqa: F401

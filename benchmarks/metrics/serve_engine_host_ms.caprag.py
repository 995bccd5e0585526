"""engine: median over decode calls of the host's part of the call: stage +
dispatch + what of ``serve/decode`` no engine span covers (lib/span_tree.py);
a 32-row call with a block table of 512 entries and a ring of 257 a row to
stage."""

from benchmarks.lib.span_tree import engine_host_ms as read  # noqa: F401

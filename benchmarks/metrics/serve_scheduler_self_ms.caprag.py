"""serving scheduler: median over working ticks of the ``serve/tick`` span minus what its
``serve/prefill`` and ``serve/decode`` spans cover (lib/span_tree.py): admission, the rows'
two tables, emitting, retiring and publishing, with the device idle meanwhile."""

from benchmarks.lib.span_tree import scheduler_self_ms as read  # noqa: F401

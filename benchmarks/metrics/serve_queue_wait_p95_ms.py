"""serving scheduler: 95th percentile of the scheduler's own
``serve/queue_wait`` spans (submit -> admission) in the window."""

from benchmarks.lib.loadgen import percentile
from benchmarks.lib.readers import span_ms


def read(run):
    return percentile(span_ms(run, "serve/queue_wait"), 95)

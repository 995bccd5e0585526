"""trainer, input pipeline: mean per step of the time the step loop spent
blocked on the prefetcher (the ``data_wait`` spans around its ``get``)."""

from benchmarks.lib.readers import span_ms


def read(run):
    waits = span_ms(run, "data_wait")
    return sum(waits) / len(waits) if waits else None

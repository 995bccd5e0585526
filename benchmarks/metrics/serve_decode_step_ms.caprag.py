"""engine / model step: median duration of the scheduler's ``serve/decode``
spans (one batched decode step of 32 rows: three window layers that gather a
ring of 257 blocks a row, one global layer that gathers the whole table, the
held and the shared experts; host clock around the engine call)."""

from benchmarks.lib.readers import median_span_ms


def read(run):
    return median_span_ms(run, "serve/decode")

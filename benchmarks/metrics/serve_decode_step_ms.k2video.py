"""engine / model step: median duration of the scheduler's ``serve/decode``
spans (one batched decode step of 64 rows: index scores over each row's
table, the top-k, the gather of the chosen K/V rows, the held experts; host
clock around the engine call)."""

from benchmarks.lib.readers import median_span_ms


def read(run):
    return median_span_ms(run, "serve/decode")

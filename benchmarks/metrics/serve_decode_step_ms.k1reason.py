"""engine / model step: median duration of the scheduler's ``serve/decode``
spans (one batched decode step of 96 rows over the latent pool and the held
experts, host clock around the engine call)."""

from benchmarks.lib.readers import median_span_ms


def read(run):
    return median_span_ms(run, "serve/decode")

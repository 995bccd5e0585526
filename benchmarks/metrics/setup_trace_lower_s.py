"""start-up: ``startup/trace`` + ``startup/lower`` before the ramp: JAX tracing the
programs and lowering them to MLIR, paid again on every warm start."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "trace_lower_s")

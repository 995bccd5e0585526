"""start-up: ``setup_wall_s`` less the ramp, less ``before_program``, less every named phase:
what no span covers (weights made from the seed, warm-up's decode steps, ...)."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "unnamed_s")

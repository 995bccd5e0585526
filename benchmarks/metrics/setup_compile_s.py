"""start-up: ``startup/compile`` before the ramp: XLA's backend compile, less the cache
read it encloses on a hit (what is left of a hit is the cache key's hashing)."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "compile_s")

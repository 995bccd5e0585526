"""start-up: the ``cache_misses`` counter at the window's start: compiles that had to be
written to the persistent cache (0 on a warm start)."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "cache_misses")

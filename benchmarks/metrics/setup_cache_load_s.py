"""start-up: ``startup/cache_load`` before the ramp: the persistent cache's reads (file,
decompression, the runtime loading the executable)."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "cache_load_s")

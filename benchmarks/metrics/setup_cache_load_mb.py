"""start-up: size of what the cache loads read: ``bytes`` of the spans where the program can
say it, else the cache directory's entries this process read (else all of them)."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "cache_load_mb")

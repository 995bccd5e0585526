"""start-up: process start -> window start on the host's clock, the persistent cache's loads
included: what ``setup_s`` read until PR 40. ``setup_s`` is this less ``setup_cache_load_s``."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "wall_s")

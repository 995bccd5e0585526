"""start-up: the persistent cache's reads before the ramp (file, decompression, the runtime
loading the executable), by the harness's own listener on JAX's ``cache_retrieval_time_sec``
event: the seconds ``setup_s`` leaves out of ``setup_wall_s`` (PR 40)."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "cache_load_s")

"""start-up: summed ``host/stall`` before the window: the whole process stood still (inside
the other parts, not added to them)."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "stall_s")

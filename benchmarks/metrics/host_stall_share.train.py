"""start-up: ``host/stall`` seconds inside the measured window over the window: how much of
it the whole process stood still (the sandbox's pauses, PERF.md section 6)."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "stall_share")

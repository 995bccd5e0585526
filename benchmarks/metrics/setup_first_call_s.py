"""start-up: ``startup/first_call`` spans less the compile-family spans inside them: the first
execution of each program itself (device time, allocation, the read-back)."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "first_call_s")

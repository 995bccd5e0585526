"""start-up: ``startup/build`` spans (engine with its pool, scheduler, trainer with its data
set-up and state) less the compile-family spans inside them."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "build_s")

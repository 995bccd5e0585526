"""start-up: process start -> the stamp on the package's first line: the interpreter, the
harness's imports, ``import jax`` and the TPU runtime's start in ``require_devices``."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "before_program_s")

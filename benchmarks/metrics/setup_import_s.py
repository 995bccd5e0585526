"""start-up: ``startup/import``: the package's first line -> ``configure_compilation_cache``,
the imports an entry point makes."""

from benchmarks.lib.startup import read as read_startup


def read(run):
    return read_startup(run, "import_s")

"""trainer: median optimizer-step time between log boundaries (host clock;
each boundary ends in a ``device_get`` of the loss), untraced intervals."""

import statistics


def read(run):
    steps = run["records"].get("step_seconds")
    return statistics.median(steps) * 1e3 if steps else None

"""Arithmetic that several per-layer metric readers share. A reader that
finds nothing to read returns ``None`` and the harness leaves it out."""

from __future__ import annotations

import statistics


def idle_share(run) -> float | None:
    """1 - union of device-op intervals over the traced window, in %."""
    trace = run.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def span_ms(run, name: str) -> list[float]:
    """Durations (ms) of the window's spans called ``name``."""
    return [(t1 - t0) * 1e3 for n, t0, t1 in run["records"].get("spans", ()) if n == name]


def median_span_ms(run, name: str) -> float | None:
    values = span_ms(run, name)
    return statistics.median(values) if values else None

"""Where set-up went: the readers of the fourteen ``start-up`` metrics.

The program records set-up as spans of its one timeline, on
``time.perf_counter`` (``llmtrain_tpu/telemetry/timeline.py``): ``startup/import``,
``startup/build``, JAX's own compile events as ``startup/trace`` / ``lower`` /
``compile`` / ``cache_load``, ``startup/first_call`` around the first run of
each program, and ``host/stall`` whenever the whole process stood still. Both
runners keep only the spans of the window, so a reader takes the program's
process buffer itself: ``records["startup"]`` where a runner (or a test)
supplies it, otherwise ``process_spans()`` of the program that ran the cell,
which is this process. A program without the buffer and a run off the chip
read ``None``, never an error.

``setup_s`` is ``window start - _T_PROCESS`` (``benchmarks/run.py``). It is cut
here into parts that make it up exactly: the ramp (the traffic file's
``ramp_seconds``, which both serving runners add to the clock they mark),
``before_program`` (``_T_PROCESS`` -> the stamp on the package's first line:
the interpreter, the harness's imports, ``import jax``, the TPU runtime's
start), the six phases, each instant booked once, to the first phase of
``PHASES`` that covers it (a cache load inside a compile inside a first call
counts as cache load), and ``unnamed``, what no span covers.
"""

from __future__ import annotations

import sys

PHASES = ("cache_load", "compile", "trace_lower", "first_call", "build", "import")
PHASE_OF = {
    "startup/cache_load": "cache_load",
    "startup/compile": "compile",
    "startup/trace": "trace_lower",
    "startup/lower": "trace_lower",
    "startup/first_call": "first_call",
    "startup/build": "build",
    "startup/import": "import",
}
PARTS = ("ramp", "before_program") + PHASES


def exclusive_seconds(intervals, order, lo, hi) -> dict[str, float]:
    """Seconds of ``[lo, hi]`` under ``(part, t0, t1)`` intervals, each
    instant booked to the FIRST part of ``order`` that covers it."""
    edges = []
    for part, t0, t1 in intervals:
        t0, t1 = max(t0, lo), min(t1, hi)
        if t1 > t0:
            edges += [(t0, 1, part), (t1, -1, part)]
    edges.sort(key=lambda edge: edge[0])
    covering = dict.fromkeys(order, 0)
    out = dict.fromkeys(order, 0.0)
    prev = lo
    for t, delta, part in edges:
        top = next((p for p in order if covering[p]), None)
        if top is not None:
            out[top] += t - prev
        covering[part] += delta
        prev = t
    return out


def startup_records(run) -> dict | None:
    given = run["records"].get("startup")
    if given is not None:
        return given
    if (run.get("device") or {}).get("platform") != "tpu":
        return None  # a CPU rehearsal's seconds are nobody's set-up
    try:
        from llmtrain_tpu.telemetry.timeline import process_spans
    except ImportError:  # the program as it was before the buffer
        return None
    return process_spans()


def _stall_seconds(spans, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` inside ``host/stall`` spans (a span runs from
    when the watch was due to wake to when it woke: its length IS ``late_ms``)."""
    return sum(
        max(0.0, min(s["t1"], hi) - max(s["t0"], lo)) for s in spans if s["name"] == "host/stall"
    )


def _compute(run) -> dict | None:
    rec = startup_records(run)
    t_process = getattr(sys.modules.get("__main__"), "_T_PROCESS", None)
    setup_s = run["end_to_end"].get("setup_s")
    if rec is None or t_process is None or setup_s is None:
        return None
    spans = rec["spans"]
    t_open = t_process + setup_s  # the window's start, as ``setup_s`` has it
    ramp = float(run["traffic"].get("ramp_seconds", 0.0))
    parts = [(PHASE_OF[s["name"]], s["t0"], s["t1"]) for s in spans if s["name"] in PHASE_OF]
    parts += [("ramp", t_open - ramp, t_open), ("before_program", t_process, rec["t_package"])]
    out = {f"{k}_s": v for k, v in exclusive_seconds(parts, PARTS, t_process, t_open).items()}
    out["unnamed_s"] = setup_s - sum(out.values())
    out["stall_s"] = _stall_seconds(spans, t_process, t_open)
    out["cache_misses"] = sum(1 for t in rec.get("counters", {}).get("cache_misses", ()) if t <= t_open)
    # The size of what the cache loads read: on the spans where the program
    # can say it, else what its one ``startup/summary`` counted in the cache
    # directory (the entries this process read, else all of them).
    loads = [s for s in spans if s["name"] == "startup/cache_load" and s["t1"] <= t_open - ramp]
    summary = next((s["args"] for s in spans if s["name"] == "startup/summary"), {})
    if any("bytes" in s["args"] for s in loads):
        read = sum(s["args"].get("bytes", 0) for s in loads)
    else:
        read = summary.get("cache_read_bytes", summary.get("cache_dir_bytes"))
    out["cache_load_mb"] = None if read is None else read / 1e6
    window = run["records"].get("window")
    if window:
        w0, w1 = window
        out["stall_share"] = 100.0 * _stall_seconds(spans, w0, w1) / (w1 - w0) if w1 > w0 else None
    return out


def read(run, key: str) -> float | None:
    """One number of the run's set-up; ``None`` where there is none to read."""
    if "_startup" not in run:
        run["_startup"] = _compute(run)
    found = run["_startup"]
    return None if found is None else found.get(key)

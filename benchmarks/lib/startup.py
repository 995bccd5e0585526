"""Where set-up went: ``setup_s`` as the harness reports it since PR 40, and
the readers of the fifteen ``start-up`` metrics.

The program records set-up as spans of its one timeline, on
``time.perf_counter`` (``llmtrain_tpu/telemetry/timeline.py``): ``startup/import``,
``startup/build``, JAX's own compile events as ``startup/trace`` / ``lower`` /
``compile`` / ``cache_load``, ``startup/first_call`` around the first run of
each program, and ``host/stall`` whenever the whole process stood still. Both
runners keep only the spans of the window, so a reader takes the program's
process buffer itself: ``records["startup"]`` where a runner (or a test)
supplies it, otherwise ``process_spans()`` of the program that ran the cell,
which is this process. A program without the buffer reads ``None`` for every
part its spans would give, and a run off the chip for all, never an error.

The wall clock of set-up is ``window start - _T_PROCESS`` (``benchmarks/run.py``:
``setup_wall_s``). It is cut here into parts that make it up exactly: the ramp
(the traffic file's ``ramp_seconds``, which both serving runners add to the
clock they mark), ``before_program`` (``_T_PROCESS`` -> the stamp on the
package's first line: the interpreter, the harness's imports, ``import jax``,
the TPU runtime's start), the six phases, each instant booked once, to the
first phase of ``PHASES`` that covers it (a cache load inside a compile inside
a first call counts as cache load), and ``unnamed``, what no span covers.

``setup_s``, the end-to-end metric, is that wall clock LESS the seconds booked
to ``cache_load``: how long the TPU runtime takes to load a cached executable
is drawn by the machine (6 or 20 s for the same bytes: PERF.md, PR 39), every
other part is decided by the program's code. The loads are the HARNESS'S OWN
reading (``run["cache_loads"]``: ``run.py``'s listener on JAX's
``cache_retrieval_time_sec`` event, stamped on ``perf_counter``), not the
program's ``startup/cache_load`` spans: what is subtracted from an end-to-end
metric is nothing a change to the program could enlarge. So it is also read
for a program without the buffer; off the chip nothing is read at all.
"""

from __future__ import annotations

PHASES = ("cache_load", "compile", "trace_lower", "first_call", "build", "import")
PHASE_OF = {  # ``cache_load`` is not here: its intervals are the harness's own
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
    t_process, loads = run.get("t_process"), run.get("cache_loads")
    wall = run["end_to_end"].get("setup_wall_s")
    if t_process is None or loads is None or wall is None:
        return None
    if (run.get("device") or {}).get("platform") != "tpu":
        return None  # a CPU rehearsal's seconds are nobody's set-up
    t_open = t_process + wall  # the window's start, as the harness marked it
    ramp = float(run["traffic"].get("ramp_seconds", 0.0))
    rec = startup_records(run)
    parts = [("cache_load", t0, t1) for t0, t1 in loads] + [("ramp", t_open - ramp, t_open)]
    if rec is not None:
        parts += [(PHASE_OF[s["name"]], s["t0"], s["t1"]) for s in rec["spans"] if s["name"] in PHASE_OF]
        parts.append(("before_program", t_process, rec["t_package"]))
    booked = exclusive_seconds(parts, PARTS, t_process, t_open)
    known = PARTS if rec is not None else ("ramp", "cache_load")  # no buffer: the harness's own readings only
    out = {"wall_s": wall} | {f"{k}_s": booked[k] for k in known}
    if rec is None:
        return out
    spans = rec["spans"]
    out["unnamed_s"] = wall - sum(booked.values())
    out["stall_s"] = _stall_seconds(spans, t_process, t_open)
    out["cache_misses"] = sum(1 for t in rec.get("counters", {}).get("cache_misses", ()) if t <= t_open)
    # The size of what the cache loads read: on the program's spans where it
    # can say it, else what its one ``startup/summary`` counted in the cache
    # directory (the entries this process read, else all of them).
    sized = [s for s in spans if s["name"] == "startup/cache_load" and s["t1"] <= t_open - ramp]
    summary = next((s["args"] for s in spans if s["name"] == "startup/summary"), {})
    if any("bytes" in s["args"] for s in sized):
        read = sum(s["args"].get("bytes", 0) for s in sized)
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

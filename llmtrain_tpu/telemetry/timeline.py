"""Structured per-step event timeline: spans + instants, JSONL + Perfetto.

The framework now has fast paths (async prefetch) and failure paths
(watchdog, rollback, fault injection) but, before this module, no single
record of *when* each of them happened relative to the step loop. The
timeline is that record: every span (data_wait, host_dispatch, checkpoint
save/wait, eval, rollback restore, prefetch assembly) and every instant
event (rollback, fault injection, straggler warning, HBM headroom,
hang detection) lands in one ordered stream that is

* appended to ``{run_dir}/telemetry/timeline.jsonl`` at each flush point
  (one JSON object per line — greppable mid-run, tail-able on a pod), and
* exported at end of run as ``{run_dir}/telemetry/trace.json`` in the
  Chrome/Perfetto trace-event format, so ``ui.perfetto.dev`` renders the
  whole run as a track-per-thread timeline.

Alignment with XLA profiles: ``span`` optionally enters a
``jax.profiler.TraceAnnotation`` of the same name, and the trainer wraps
each step in :func:`step_annotation` — so when a ``jax.profiler`` window
is active, the framework spans appear as named regions inside the XPlane
trace and line up 1:1 with the device timeline.

Rollback semantics (docs/robustness.md): events recorded during a window
that is later rolled back are NOT dropped — :meth:`EventTimeline.tag_rollback`
marks them ``rolled_back: true`` so a post-mortem can still see what the
poisoned window did. Tagging happens before the boundary flush, so the
JSONL on disk carries the tags too.

Thread safety: the prefetch producer and the step loop record
concurrently; all mutation is under one lock (the hot-path cost is a
dict append, far below the numpy work inside any span).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
import weakref
from contextlib import contextmanager, nullcontext
from pathlib import Path
from typing import Any, Callable, Iterator

from .. import _T_IMPORT
from ..utils.logging import get_logger

logger = get_logger()

# Spans open on each thread, innermost last, as (name, timeline or None):
# ONE stack for every timeline and for the process buffer below, so a span
# recorded by either names the span that was open around it as ``parent``.
_OPEN = threading.local()


def _open_stack() -> list[tuple[str, Any]]:
    try:
        return _OPEN.stack
    except AttributeError:
        stack = _OPEN.stack = []
        return stack


def step_annotation(step: int, *, enabled: bool = True):
    """``jax.profiler.StepTraceAnnotation`` for optimizer step ``step``."""
    if not enabled:
        return nullcontext()
    import jax

    return jax.profiler.StepTraceAnnotation("train", step_num=step)


def _trace_annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class EventTimeline:
    """Append-only event stream with bounded memory and JSONL persistence.

    ``jsonl_path`` None keeps the timeline memory-only (non-main ranks,
    eval-only runs). ``max_events`` bounds the retained list; overflow
    drops the OLDEST flushed events (the JSONL already has them) and
    counts the drop so the Perfetto export can say it is partial.
    """

    def __init__(
        self,
        jsonl_path: str | Path | None = None,
        *,
        process_index: int = 0,
        max_events: int = 200_000,
        xprof_annotations: bool = True,
        enabled: bool = True,
    ) -> None:
        # enabled=False makes every recording call a true no-op (no lock,
        # no retained dicts, no TraceAnnotation) so the master telemetry
        # switch removes the subsystem from the hot path entirely.
        self._enabled = enabled
        self._jsonl_path = Path(jsonl_path) if jsonl_path is not None else None
        self._process_index = process_index
        self._max_events = max(1000, int(max_events))
        self._xprof = xprof_annotations
        self._lock = threading.Lock()
        self._events: list[dict[str, Any]] = []
        self._flushed = 0  # events [0, _flushed) are already on disk
        self._dropped = 0
        # Event timestamps are perf_counter-relative microseconds; the
        # wall-clock anchor lets post-processing map them to real time.
        self._t0 = time.perf_counter()
        self._wall0 = time.time()
        # Segment identity (telemetry/goodput.py): the JSONL is opened in
        # append mode, so successive resume segments of one run share ONE
        # file — an EAGERLY written header line per process delimits them,
        # and segment_id = number of headers already on disk gives the
        # ledger a monotonic ordering with no reliance on file mtimes.
        # Written at construction (not first flush) so even a segment
        # SIGKILLed before its first flush leaves its start time behind.
        self._segment_id = 0
        self._segment_ended = False
        if self._enabled and self._jsonl_path is not None:
            self._segment_id = self._write_segment_header()
        if self._enabled:
            # What the process recorded before any timeline existed
            # (start-up spans, host stalls) becomes this timeline's.
            _PROCESS.adopt(self)

    # ------------------------------------------------------------- recording

    @property
    def origin_unix_time(self) -> float:
        return self._wall0

    @property
    def segment_id(self) -> int:
        """This process's 0-based position in the run's segment sequence."""
        return self._segment_id

    def _write_segment_header(self) -> int:
        """Append this process's segment-start record; returns its id.

        Best-effort like every other persistence path: an unwritable disk
        degrades to a memory-only segment (id from whatever was readable),
        never an exception in the constructor."""
        marker = '"name": "segment_start"'
        segment_id = 0
        try:
            if self._jsonl_path.is_file():
                segment_id = self._jsonl_path.read_text(
                    encoding="utf-8"
                ).count(marker)
        except OSError:
            pass
        header = {
            "name": "segment_start",
            "ph": "seg",
            "segment_id": segment_id,
            "start_unix_time": self._wall0,
            "process_index": self._process_index,
            "pid": os.getpid(),
        }
        try:
            self._jsonl_path.parent.mkdir(parents=True, exist_ok=True)
            with self._jsonl_path.open("a", encoding="utf-8") as fh:
                fh.write(json.dumps(header, sort_keys=True) + "\n")
        except OSError as exc:
            logger.warning(
                "timeline segment header to %s failed (%s); continuing",
                self._jsonl_path,
                exc,
            )
        return segment_id

    def end_segment(self) -> None:
        """Append the clean-exit footer (idempotent). Crashed segments
        never reach this; the goodput ledger then infers the end from the
        newest event timestamp and the heartbeat mtime instead. Also hands
        the process buffer back and stops its stall watch (memory-only
        timelines too)."""
        if self._enabled:
            _PROCESS.release(self)
        if not self._enabled or self._jsonl_path is None or self._segment_ended:
            return
        self._segment_ended = True
        footer = {
            "name": "segment_end",
            "ph": "seg",
            "segment_id": self._segment_id,
            "end_unix_time": time.time(),
        }
        try:
            with self._jsonl_path.open("a", encoding="utf-8") as fh:
                fh.write(json.dumps(footer, sort_keys=True) + "\n")
        except OSError as exc:
            logger.warning(
                "timeline segment footer to %s failed (%s); continuing",
                self._jsonl_path,
                exc,
            )

    def _now_us(self) -> int:
        return int((time.perf_counter() - self._t0) * 1e6)

    def _append(self, event: dict[str, Any]) -> None:
        if _PROCESS.armed:
            _PROCESS.settle()
        with self._lock:
            self._events.append(event)
            if len(self._events) > self._max_events:
                # Drop the oldest FLUSHED prefix first: those lines are
                # already durable in the JSONL. Unflushed events are only
                # dropped when flushing has no sink at all (memory-only).
                drop = len(self._events) - self._max_events
                drop = min(drop, self._flushed) if self._jsonl_path else drop
                if drop > 0:
                    del self._events[:drop]
                    self._flushed = max(0, self._flushed - drop)
                    self._dropped += drop

    @contextmanager
    def span(
        self, name: str, *, cat: str = "train", step: int | None = None, **args: Any
    ) -> Iterator[dict[str, Any]]:
        """Record a duration event around the body; never raises from the
        recording itself (the body's exceptions propagate untouched).

        Yields the event's ``args``: the body adds what it counted there
        (``with tl.span("x") as args: args["rows"] = n``). A span opened
        inside another span of the same thread carries that span's name as
        ``args["parent"]``, so each span says which span caused it."""
        if not self._enabled:
            yield args
            return
        stack = _open_stack()
        if stack:
            args.setdefault("parent", stack[-1][0])
        stack.append((name, self))
        start = self._now_us()
        cm = _trace_annotation(name) if self._xprof else nullcontext()
        try:
            with cm:
                yield args
        finally:
            end = self._now_us()
            stack.pop()
            event: dict[str, Any] = {
                "name": name,
                "cat": cat,
                "ph": "X",
                "ts_us": start,
                "dur_us": max(0, end - start),
                "thread": threading.current_thread().name,
            }
            if step is not None:
                event["step"] = int(step)
            if args:
                event["args"] = args
            self._append(event)

    def record(
        self,
        name: str,
        *,
        t0: float,
        t1: float,
        cat: str = "train",
        step: int | None = None,
        **args: Any,
    ) -> None:
        """Record a duration event from perf_counter stamps the caller
        already took — the hot loop's path: its interval accumulators and
        the timeline share ONE set of clock reads, so the span record and
        the `train/data_wait_ms` family can never drift apart."""
        if self._enabled:
            self._record_event(name, t0, t1, cat, threading.current_thread().name, args, step)

    def _record_event(
        self, name: str, t0: float, t1: float, cat: str, thread: str,
        args: dict[str, Any], step: int | None = None,
    ) -> None:
        event: dict[str, Any] = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts_us": int((t0 - self._t0) * 1e6),
            "dur_us": max(0, int((t1 - t0) * 1e6)),
            "thread": thread,
        }
        if step is not None:
            event["step"] = int(step)
        if args:
            event["args"] = args
        self._append(event)

    @contextmanager
    def opened(self, name: str) -> Iterator[None]:
        """Declare ``name`` open on this thread WITHOUT recording it: the
        hot loop records its spans from stamps afterwards (:meth:`record`),
        and what is recorded meanwhile (a recompile inside a step) still
        names it as ``parent``."""
        if not self._enabled:
            yield
            return
        stack = _open_stack()
        stack.append((name, self))
        try:
            yield
        finally:
            stack.pop()

    def instant(
        self,
        name: str,
        *,
        cat: str = "event",
        step: int | None = None,
        t: float | None = None,
        **args: Any,
    ) -> None:
        """Point event; ``t`` (a perf_counter stamp the caller already
        took) backdates it — the trace flush path records marks at their
        TRUE time, not the flush time."""
        if not self._enabled:
            return
        event: dict[str, Any] = {
            "name": name,
            "cat": cat,
            "ph": "i",
            "ts_us": self._now_us() if t is None else int((t - self._t0) * 1e6),
            "dur_us": 0,
            "thread": threading.current_thread().name,
        }
        if step is not None:
            event["step"] = int(step)
        if args:
            event["args"] = args
        self._append(event)

    def tag_rollback(self, first_step: int, last_step: int) -> None:
        """Mark every retained event of steps [first_step, last_step] as
        belonging to a rolled-back window. Runs BEFORE the boundary flush,
        so unflushed events carry the tag into the JSONL; events of the
        window flushed in earlier intervals keep their lines but the
        paired ``rollback`` instant (recorded by the trainer) gives
        post-processing the window to re-tag them."""
        with self._lock:
            for event in self._events:
                step = event.get("step")
                if step is not None and first_step <= step <= last_step:
                    event["rolled_back"] = True

    # ----------------------------------------------------------- persistence

    def flush(self) -> None:
        """Append every not-yet-persisted event to the JSONL (no-op when
        memory-only). Never raises: a full disk must not kill the step loop."""
        if self._jsonl_path is None:
            return
        with self._lock:
            pending = self._events[self._flushed :]
            self._flushed = len(self._events)
        if not pending:
            return
        try:
            self._jsonl_path.parent.mkdir(parents=True, exist_ok=True)
            with self._jsonl_path.open("a", encoding="utf-8") as fh:
                for event in pending:
                    fh.write(json.dumps(event, sort_keys=True) + "\n")
        except OSError as exc:
            logger.warning("timeline flush to %s failed (%s); continuing", self._jsonl_path, exc)

    def events(self) -> list[dict[str, Any]]:
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        return self._dropped

    # -------------------------------------------------------------- analysis

    def span_totals(self) -> dict[str, dict[str, float]]:
        """Wall-clock breakdown: {span name: {count, total_ms, max_ms}} over
        retained duration events — the report's and bench's summary input."""
        totals: dict[str, dict[str, float]] = {}
        for event in self.events():
            if event.get("ph") != "X":
                continue
            entry = totals.setdefault(
                event["name"], {"count": 0, "total_ms": 0.0, "max_ms": 0.0}
            )
            ms = event["dur_us"] / 1e3
            entry["count"] += 1
            entry["total_ms"] += ms
            entry["max_ms"] = max(entry["max_ms"], ms)
        for entry in totals.values():
            entry["total_ms"] = round(entry["total_ms"], 3)
            entry["max_ms"] = round(entry["max_ms"], 3)
        return totals

    def event_counts(self) -> dict[str, int]:
        """{instant-event name: occurrences} — rollbacks, faults, warnings."""
        counts: dict[str, int] = {}
        for event in self.events():
            if event.get("ph") == "i":
                counts[event["name"]] = counts.get(event["name"], 0) + 1
        return counts

    # ------------------------------------------------------------- exporters

    def export_perfetto(self, path: str | Path) -> Path | None:
        """Write the retained events as a Chrome/Perfetto trace-event JSON.

        ``pid`` is the JAX process index, ``tid`` a stable small int per
        recording thread (with ``thread_name`` metadata so Perfetto shows
        real names). Returns the path, or None when the write failed
        (logged — exporting must not fail the run it describes)."""
        target = Path(path)
        events = self.events()
        tids: dict[str, int] = {}
        trace_events: list[dict[str, Any]] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": self._process_index,
                "tid": 0,
                "args": {"name": f"llmtrain host {self._process_index}"},
            }
        ]
        for event in events:
            thread = event.get("thread", "MainThread")
            if thread not in tids:
                tids[thread] = len(tids) + 1
                trace_events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": self._process_index,
                        "tid": tids[thread],
                        "args": {"name": thread},
                    }
                )
            out: dict[str, Any] = {
                "name": event["name"],
                "cat": event.get("cat", "train"),
                "ph": event.get("ph", "X"),
                "ts": event["ts_us"],
                "pid": self._process_index,
                "tid": tids[thread],
            }
            if out["ph"] == "X":
                out["dur"] = event.get("dur_us", 0)
            if out["ph"] == "i":
                out["s"] = "t"  # thread-scoped instant marker
            args = dict(event.get("args") or {})
            if "step" in event:
                args["step"] = event["step"]
            if event.get("rolled_back"):
                args["rolled_back"] = True
            if args:
                out["args"] = args
            trace_events.append(out)
        payload = {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "otherData": {
                "origin_unix_time": self._wall0,
                "dropped_events": self._dropped,
            },
        }
        try:
            target.parent.mkdir(parents=True, exist_ok=True)
            target.write_text(json.dumps(payload), encoding="utf-8")
            return target
        except OSError as exc:
            logger.warning("perfetto export to %s failed (%s)", target, exc)
            return None


# ------------------------------------------------------- the process buffer
#
# Start-up begins before any EventTimeline exists (the serving CLI builds
# its timeline after the engine; a library caller may build none). What is
# recorded meanwhile waits here, on the timelines' own clock
# (``perf_counter``), and the first timeline built adopts it. Everything
# recorded through the buffer also STAYS in it, bounded, keeping the
# earliest: :func:`process_spans` hands it to a reader that has no timeline.
# docs/observability.md, "Start-up", has the span tree.

_MAX_PROCESS_SPANS = 8192

# Booked to the first phase of this order that covers an instant, so nested
# spans (a cache load inside a compile inside a first call inside a build)
# never count a second twice.
STARTUP_PHASES = ("cache_load", "compile", "trace_lower", "first_call", "build", "import")
_PHASE_OF = {
    "startup/cache_load": "cache_load",
    "startup/compile": "compile",
    "startup/trace": "trace_lower",
    "startup/lower": "trace_lower",
    "startup/first_call": "first_call",
    "startup/build": "build",
    "startup/import": "import",
}


def exclusive_seconds(
    intervals: list[tuple[str, float, float]],
    order: tuple[str, ...],
    lo: float = float("-inf"),
    hi: float = float("inf"),
) -> dict[str, float]:
    """Wall seconds of ``[lo, hi]`` under ``(phase, t0, t1)`` intervals,
    each instant booked ONCE: to the first phase of ``order`` covering it."""
    edges = []
    for phase, t0, t1 in intervals:
        t0, t1 = max(t0, lo), min(t1, hi)
        if t1 > t0:
            edges += [(t0, 1, phase), (t1, -1, phase)]
    edges.sort(key=lambda edge: edge[0])
    covering = dict.fromkeys(order, 0)
    out = dict.fromkeys(order, 0.0)
    prev = lo
    for t, delta, phase in edges:
        top = next((p for p in order if covering[p]), None)
        if top is not None:
            out[top] += t - prev
        covering[phase] += delta
        prev = t
    return out


def startup_phase_seconds(
    spans: list[tuple[str, float, float]], lo: float = float("-inf"), hi: float = float("inf")
) -> dict[str, float]:
    """:data:`STARTUP_PHASES` -> exclusive seconds, from ``(span name, t0,
    t1)`` on any one clock; children of ``startup/build`` count as build."""
    named = [(_PHASE_OF[n], a, b) for n, a, b in spans if n in _PHASE_OF]
    return exclusive_seconds(named, STARTUP_PHASES, lo, hi)


class _StallWatch(threading.Thread):
    """Sleeps ``PERIOD_S`` over and over and records ``host/stall`` when it
    wakes more than ``LATE_S`` late: the whole process stood still (a
    sandbox's pause, a GIL held through a long C call, a swapped-out host).
    A pause shorter than the period is seen only when it covers a wake-up.

    It does NOT run while the process starts up (:meth:`_ProcessBuffer.settle`
    starts it once start-up has been quiet). On the chip machine a Python
    thread that wakes now and then beside the thread that loads the cached
    programs made the runtime load them three times slower, most runs (5.7 s
    -> 20 s; at 50 wake-ups a second, at 5, and at 1 through the loads
    themselves: PERF.md section 6, PR 39), and tracing up to 30% slower at
    50 a second. Five a second is what a served token or a train step can
    be shown to afford."""

    PERIOD_S = 0.200
    LATE_S = 0.050

    def __init__(self, clock: Callable[[], float] = time.perf_counter) -> None:
        super().__init__(name="host-stall-watch", daemon=True)
        self._clock = clock
        self._halt = threading.Event()

    def tick(self, due: float) -> float:
        """One wake-up that was due at ``due``; returns the time it woke."""
        now = self._clock()
        if now - due > self.LATE_S:
            record_process_span(
                "host/stall", due, now, cat="host", thread=self.name,
                late_ms=round((now - due) * 1e3, 3),
            )
        return now

    def run(self) -> None:
        _PROCESS.summarise()
        woke = self._clock()
        while not self._halt.wait(self.PERIOD_S):
            woke = self.tick(woke + self.PERIOD_S)

    def halt(self) -> None:
        self._halt.set()


class _ProcessBuffer:
    QUIET_S = 5.0  # start-up is over when nothing of it was recorded for this long

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.on = True
        self.watch: _StallWatch | None = None
        self.reset()

    def reset(self) -> None:
        with self.lock:
            self.spans: list[dict[str, Any]] = []
            self.dropped = 0
            self.counters: dict[str, list[float]] = {}
            self.adopter: weakref.ref | None = None
            self.open_spans = 0
            # Start-up recorded and the stall watch not yet running: the hot
            # paths look at this ONE flag and call settle() while it is up.
            self.armed = False
            self.last_startup_end: float | None = None
            self.first_call_seen = False
            self.summarised = False
        watch, self.watch = self.watch, None
        if watch is not None:
            watch.halt()

    # ----------------------------------------------------------- recording

    def _sink(self, stack: list[tuple[str, Any]]) -> "EventTimeline | None":
        """The timeline a span recorded on this thread belongs to: the one
        whose span is open around it, else the one that adopted the buffer."""
        for _, owner in reversed(stack):
            if owner is not None:
                return owner
        return self.adopter() if self.adopter is not None else None

    def record(
        self, name: str, t0: float, t1: float, cat: str, thread: str | None, args: dict[str, Any]
    ) -> None:
        stack = _open_stack()
        if stack:
            args.setdefault("parent", stack[-1][0])
        thread = thread or threading.current_thread().name
        with self.lock:
            sink = self._sink(stack)
            entry = {"name": name, "cat": cat, "t0": t0, "t1": t1, "thread": thread,
                     "args": args, "adopted": sink is not None}
            if len(self.spans) < _MAX_PROCESS_SPANS:
                self.spans.append(entry)
            else:
                self.dropped += 1
            if cat == "startup":
                self.last_startup_end = max(t1, self.last_startup_end or t1)
                self.first_call_seen |= name == "startup/first_call"
                self.armed = self.watch is None and name != "startup/summary"
        if sink is not None and sink._enabled:
            sink._record_event(name, t0, t1, cat, thread, dict(args))

    def count(self, name: str) -> None:
        with self.lock:
            stamps = self.counters.setdefault(name, [])
            if len(stamps) < _MAX_PROCESS_SPANS:
                stamps.append(time.perf_counter())

    # ------------------------------------------------------------ adoption

    def adopt(self, timeline: "EventTimeline") -> None:
        """``timeline`` takes every span no timeline holds yet and, until
        its segment ends, every later one recorded outside another
        timeline's spans. A second timeline beside a live adopter gets
        nothing: a span is adopted once."""
        with self.lock:
            holder = self.adopter() if self.adopter is not None else None
            if holder is not None or not self.on:
                return
            self.adopter = weakref.ref(timeline)
            waiting = [e for e in self.spans if not e["adopted"]]
            for entry in waiting:
                entry["adopted"] = True
        for e in waiting:
            timeline._record_event(
                e["name"], e["t0"], e["t1"], e["cat"], e["thread"], dict(e["args"])
            )

    def release(self, timeline: "EventTimeline") -> None:
        if self.adopter is None or self.adopter() is not timeline:
            return
        self.summarise()  # a run too short to have settled still says where its start-up went
        with self.lock:
            self.adopter = None
            self.armed = False
            watch, self.watch = self.watch, None
        if watch is not None:
            watch.halt()

    def settle(self) -> None:
        """Called from the hot paths while ``armed``: once a first call has
        been seen and nothing of start-up was recorded (or is open) for
        ``QUIET_S``, start-up is over: the stall watch starts and logs the
        start-up table."""
        last = self.last_startup_end
        if not self.first_call_seen or self.open_spans or last is None:
            return
        if time.perf_counter() - last < self.QUIET_S:
            return
        with self.lock:
            if not self.armed or not self.on:
                return
            self.armed = False
            self.watch = _StallWatch()
        self.watch.start()

    def summarise(self) -> None:
        """Log the start-up table, ONCE a process buffer's life."""
        with self.lock:
            if self.summarised or not self.first_call_seen:
                return
            self.summarised = True
        summary = startup_summary()
        logger.info("start-up: %s", json.dumps(summary, sort_keys=True))
        t = self.last_startup_end or time.perf_counter()
        self.record("startup/summary", t, t, "startup", None, summary)


_PROCESS = _ProcessBuffer()


def record_process_span(
    name: str, t0: float, t1: float, *, cat: str = "startup", thread: str | None = None, **args: Any
) -> None:
    """A span from ``perf_counter`` stamps, into the process buffer and the
    timeline it belongs to (``parent`` = the span open on this thread)."""
    if _PROCESS.on:
        _PROCESS.record(name, t0, t1, cat, thread, args)


@contextmanager
def process_span(name: str, *, cat: str = "startup", **args: Any) -> Iterator[dict[str, Any]]:
    """:meth:`EventTimeline.span` for code that may run before any timeline
    exists; yields the span's ``args``. As a decorator it spans the whole
    call (``@process_span("startup/build", kind="engine")``)."""
    if not _PROCESS.on:
        yield args
        return
    stack = _open_stack()
    if stack:
        args.setdefault("parent", stack[-1][0])
    stack.append((name, None))
    with _PROCESS.lock:
        _PROCESS.open_spans += 1
    t0 = time.perf_counter()
    try:
        yield args
    finally:
        t1 = time.perf_counter()
        stack.pop()
        with _PROCESS.lock:
            _PROCESS.open_spans -= 1
        _PROCESS.record(name, t0, t1, cat, None, args)


def first_call_span(fn: Callable, **args: Any) -> Callable:
    """``fn`` with its FIRST call under ``startup/first_call``: the call
    that traces, lowers and compiles (or loads) a jitted program."""
    pending = [True]

    @functools.wraps(fn)
    def call(*a: Any, **kw: Any) -> Any:
        if not pending:
            if _PROCESS.armed:  # a caller with no timeline's events: the step loop itself
                _PROCESS.settle()
            return fn(*a, **kw)
        pending.clear()
        with process_span("startup/first_call", **args):
            return fn(*a, **kw)

    return call


def process_spans() -> dict[str, Any]:
    """Everything recorded through the process buffer so far, adopted or
    not, on ``perf_counter``: ``t_package`` (the stamp on the package's
    first line), ``spans`` (``name``, ``cat``, ``t0``, ``t1``, ``thread``,
    ``args``), ``counters`` (name -> the stamp of each count) and how many
    spans the bound ``dropped``."""
    with _PROCESS.lock:
        keys = ("name", "cat", "t0", "t1", "thread", "args")
        return {
            "t_package": _T_IMPORT,
            "spans": [{k: e[k] for k in keys} for e in _PROCESS.spans],
            "counters": {k: list(v) for k, v in _PROCESS.counters.items()},
            "dropped": _PROCESS.dropped,
        }


def startup_summary() -> dict[str, Any]:
    """Where start-up went, from the package's first line to the end of
    the last ``startup/*`` span: exclusive seconds a phase, what no span
    names, the stalls inside, the compile cache's counts and sizes."""
    buffered = process_spans()
    spans = buffered["spans"]
    startup = [(s["name"], s["t0"], s["t1"]) for s in spans if s["cat"] == "startup"]
    end = max((t1 for _, _, t1 in startup), default=_T_IMPORT)
    phases = startup_phase_seconds(startup, _T_IMPORT, end)
    out: dict[str, Any] = {f"{p}_s": round(v, 3) for p, v in phases.items()}
    out["span_s"] = round(end - _T_IMPORT, 3)
    out["unnamed_s"] = round(end - _T_IMPORT - sum(phases.values()), 3)
    stalls = [s for s in spans if s["name"] == "host/stall" and s["t1"] <= end]
    out["stall_s"] = round(sum(s["args"].get("late_ms", 0.0) for s in stalls) / 1e3, 3)
    out["first_calls"] = sum(1 for n, _, _ in startup if n == "startup/first_call")
    for name, stamps in buffered["counters"].items():
        out[name] = sum(1 for t in stamps if t <= end)
    out.update(_cache_dir_sizes())
    return out


def _cache_dir_sizes() -> dict[str, int]:
    """Bytes of the persistent compile cache: ``cache_dir_bytes``, every
    entry of the directory, and, where JAX keeps the cache under a size
    limit (it then stamps an ``-atime`` twin at each read),
    ``cache_read_bytes``, the entries THIS process read. A cache_load span
    cannot carry its entry's size: JAX's event has the time and no key."""
    jax = sys.modules.get("jax")
    path = getattr(jax.config, "jax_compilation_cache_dir", None) if jax is not None else None
    if not path or "://" in str(path) or not os.path.isdir(path):
        return {}
    started_ns = time.time_ns() - int((time.perf_counter() - _T_IMPORT) * 1e9)
    total, read, stamped = 0, 0, False
    try:
        for entry in os.scandir(path):
            if not entry.name.endswith("-cache"):
                continue
            size = entry.stat().st_size
            total += size
            try:
                with open(entry.path[: -len("-cache")] + "-atime", "rb") as fh:
                    stamped = True
                    if int.from_bytes(fh.read(8), "little") >= started_ns:
                        read += size
            except OSError:
                pass
    except OSError:
        return {}
    return {"cache_dir_bytes": total, **({"cache_read_bytes": read} if stamped else {})}


# JAX's own monitoring events (jax 0.9: dispatch.py, pxla.py, compiler.py)
# -> the span each becomes. A listener fires at an event's END with its
# duration, on the thread that did the work.
_JAX_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "startup/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "startup/lower",
    "/jax/core/compile/backend_compile_duration": "startup/compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "startup/cache_load",
}
_JAX_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_COMPILING = threading.local()  # what the compile now ending on this thread met in the cache


def _on_jax_duration(event: str, duration: float, **kwargs: Any) -> None:
    name = _JAX_DURATION_SPANS.get(event)
    if name == "startup/trace":
        # A jitted function called while another is traced is traced inside
        # it (thousands of them in a model's step): the outermost trace
        # covers them all and is the one recorded. Counted whether or not
        # recording is on, as the starts are: the two must stay in step.
        _COMPILING.depth = depth = max(0, getattr(_COMPILING, "depth", 1) - 1)
        if depth:
            return
    if name is None or not _PROCESS.on:
        return
    t1 = time.perf_counter()
    t0 = t1 - float(duration)
    args: dict[str, Any] = {}
    if kwargs.get("fun_name"):
        args["fun"] = str(kwargs["fun_name"])
    if name == "startup/cache_load":
        _COMPILING.load_t0 = t0
    elif name == "startup/compile":
        # ``backend_compile_duration`` encloses the cache's read on a hit:
        # the compile span then ends where the cache_load span began (what
        # is left is the cache key's hashing), and nothing counts twice.
        load_t0 = getattr(_COMPILING, "load_t0", None)
        wrote = getattr(_COMPILING, "wrote", False)
        _COMPILING.load_t0, _COMPILING.wrote = None, False
        if load_t0 is not None and load_t0 >= t0:
            args["cache"], t1 = "hit", load_t0
        else:
            args["cache"] = "miss" if wrote else "none"
    _PROCESS.record(name, t0, t1, "startup", None, args)


def _on_jax_scalar(event: str, value: float, **kwargs: Any) -> None:
    # JAX records an event's START as a scalar of the same name.
    if event == "/jax/core/compile/jaxpr_trace_duration":
        _COMPILING.depth = getattr(_COMPILING, "depth", 0) + 1


def _on_jax_event(event: str, **kwargs: Any) -> None:
    name = _JAX_COUNTERS.get(event)
    if name is None or not _PROCESS.on:
        return
    if name == "cache_misses":
        _COMPILING.wrote = True
    _PROCESS.count(name)


_LISTENING = False


def watch_startup() -> None:
    """Idempotent; called where every entry point passes before its first
    compile (``distributed.configure_compilation_cache``). The first call
    closes ``startup/import`` (the package's first line -> here) and
    registers the ``jax.monitoring`` listeners. The stall watch starts
    later, when start-up is over (:meth:`_ProcessBuffer.settle`)."""
    global _LISTENING
    if not _PROCESS.on:
        return
    if not _LISTENING:
        _LISTENING = True
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        jax.monitoring.register_scalar_listener(_on_jax_scalar)
        jax.monitoring.register_event_listener(_on_jax_event)
        record_process_span("startup/import", _T_IMPORT, time.perf_counter())


def process_recording(on: bool) -> None:
    """Switch the process buffer (and with it the listeners and the stall
    watch) off or on, emptied: the test suite's, whose hundreds of
    timelines would each adopt the spans of the tests before."""
    _PROCESS.on = bool(on)
    _PROCESS.reset()


__all__ = [
    "EventTimeline",
    "exclusive_seconds",
    "first_call_span",
    "process_recording",
    "process_span",
    "process_spans",
    "record_process_span",
    "startup_phase_seconds",
    "startup_summary",
    "step_annotation",
    "watch_startup",
]

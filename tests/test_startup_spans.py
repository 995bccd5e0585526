"""Start-up recording (telemetry/timeline.py): the process buffer and its
adoption, JAX's compile and cache events as spans, ``startup/first_call``,
the stall watch, and the goodput row that lists the measured parts.

The suite runs with the buffer OFF (tests/conftest.py); every test here
switches it on, emptied, and off again.
"""

from __future__ import annotations

import json
import threading

import jax
import jax.numpy as jnp
import pytest

from llmtrain_tpu.telemetry import timeline as tlmod
from llmtrain_tpu.telemetry.goodput import compute_goodput, render_goodput_md
from llmtrain_tpu.telemetry.timeline import (
    EventTimeline,
    exclusive_seconds,
    first_call_span,
    process_span,
    process_spans,
    record_process_span,
    startup_phase_seconds,
    startup_summary,
)


@pytest.fixture
def recording():
    tlmod.process_recording(True)
    yield
    tlmod.process_recording(False)


def _names(timeline: EventTimeline) -> list[str]:
    return [e["name"] for e in timeline.events()]


# ------------------------------------------------------------- the buffer


class TestProcessBuffer:
    def test_off_for_the_suite_and_a_timeline_stays_empty(self):
        with process_span("startup/build"):
            record_process_span("startup/compile", 1.0, 2.0)
        assert process_spans()["spans"] == [] and EventTimeline().events() == []

    def test_a_timeline_built_later_adopts_once_with_parents_kept(self, recording):
        with process_span("startup/build", kind="engine"), process_span("startup/pool", num_blocks=3):
            record_process_span("startup/compile", 10.0, 12.5, fun="zeros")
        first = EventTimeline()
        by_name = {e["name"]: e for e in first.events()}
        assert set(by_name) == {"startup/build", "startup/pool", "startup/compile"}
        assert by_name["startup/pool"]["args"] == {"num_blocks": 3, "parent": "startup/build"}
        assert by_name["startup/compile"]["args"] == {"fun": "zeros", "parent": "startup/pool"}
        assert by_name["startup/compile"]["dur_us"] == 2_500_000 and by_name["startup/build"]["cat"] == "startup"
        # shifted to the timeline's own origin: recorded before it was built
        assert by_name["startup/build"]["ts_us"] < 0
        # a second timeline beside the first gets nothing: adopted once
        assert EventTimeline().events() == []
        # ... and the buffer still hands everything to a reader with no timeline
        assert [s["name"] for s in process_spans()["spans"]] == ["startup/compile", "startup/pool", "startup/build"]

    def test_what_is_recorded_after_adoption_reaches_the_adopter(self, recording):
        adopter = EventTimeline()
        record_process_span("startup/cache_load", 1.0, 2.0)
        assert _names(adopter) == ["startup/cache_load"]
        adopter.end_segment()  # hands the buffer back ...
        record_process_span("startup/cache_load", 3.0, 4.0)
        assert _names(adopter) == ["startup/cache_load"]
        later = EventTimeline()  # ... and the next timeline takes what came since
        assert [e["ts_us"] for e in later.events()] == [int((3.0 - later._t0) * 1e6)]

    def test_a_span_under_another_timelines_span_goes_to_that_timeline(self, recording):
        adopter, replica = EventTimeline(), EventTimeline()
        with replica.span("serve/prefill", cat="serve"):
            record_process_span("startup/compile", 1.0, 2.0)
        assert _names(adopter) == []
        compile_event = next(e for e in replica.events() if e["name"] == "startup/compile")
        assert compile_event["args"]["parent"] == "serve/prefill"

    def test_opened_names_the_parent_without_recording_a_span(self, recording):
        timeline = EventTimeline()
        with timeline.opened("host_dispatch"):
            record_process_span("startup/compile", 1.0, 2.0)
        (event,) = timeline.events()
        assert event["name"] == "startup/compile" and event["args"]["parent"] == "host_dispatch"

    def test_the_bound_keeps_the_earliest_and_counts_the_rest(self, recording, monkeypatch):
        monkeypatch.setattr(tlmod, "_MAX_PROCESS_SPANS", 3)
        for i in range(5):
            record_process_span("startup/trace", float(i), float(i) + 0.5)
        got = process_spans()
        assert [s["t0"] for s in got["spans"]] == [0.0, 1.0, 2.0] and got["dropped"] == 2

    def test_exported_to_perfetto_like_any_other_span(self, recording, tmp_path):
        with process_span("startup/build", kind="engine"):
            pass
        timeline = EventTimeline(tmp_path / "timeline.jsonl")
        with timeline.span("serve/prefill", cat="serve"):
            record_process_span("startup/cache_load", 5.0, 6.0)
        record_process_span("host/stall", 7.0, 7.2, cat="host", thread="host-stall-watch", late_ms=200.0)
        timeline.flush()
        trace = json.loads(timeline.export_perfetto(tmp_path / "trace.json").read_text())
        events = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "X"}
        assert events["startup/cache_load"]["args"]["parent"] == "serve/prefill"
        assert events["host/stall"]["args"] == {"late_ms": 200.0} and events["host/stall"]["dur"] == 200_000
        assert events["startup/build"]["cat"] == "startup"
        lines = [json.loads(ln) for ln in (tmp_path / "timeline.jsonl").read_text().splitlines()]
        assert {"startup/build", "startup/cache_load", "host/stall", "serve/prefill"} <= {ln["name"] for ln in lines}


# ----------------------------------------------- exclusive seconds, by hand


class TestExclusiveSeconds:
    def test_each_instant_is_booked_once_to_the_first_phase_covering_it(self):
        spans = [
            ("startup/import", 0.0, 1.0),
            ("startup/build", 1.0, 4.0),
            ("startup/trace", 1.5, 2.5),
            ("startup/trace", 1.6, 1.8),  # nested: covered already
            ("startup/lower", 2.5, 3.0),
            ("startup/first_call", 5.0, 9.0),
            ("startup/compile", 5.5, 6.0),
            ("startup/cache_load", 6.0, 8.0),
            ("startup/summary", 9.0, 9.0),  # no phase
        ]
        got = startup_phase_seconds(spans)
        assert got == pytest.approx(
            {"import": 1.0, "build": 1.5, "trace_lower": 1.5, "first_call": 1.5, "compile": 0.5, "cache_load": 2.0}
        )
        # clipped to a window: 2.0 .. 7.0
        clipped = startup_phase_seconds(spans, 2.0, 7.0)
        assert clipped == pytest.approx(
            {"import": 0.0, "build": 1.0, "trace_lower": 1.0, "first_call": 0.5, "compile": 0.5, "cache_load": 1.0}
        )

    def test_overlap_across_threads_counts_wall_time_once(self):
        got = exclusive_seconds([("a", 0.0, 3.0), ("b", 1.0, 5.0), ("a", 2.0, 4.0)], ("a", "b"))
        assert got == {"a": 4.0, "b": 1.0}


# -------------------------------------------------- JAX's events as spans


@pytest.fixture
def temp_cache(tmp_path, recording):
    """A persistent cache of its own that takes every program, however small."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    tlmod.watch_startup()
    yield tmp_path / "cache"
    cc.reset_cache()
    for key, value in keep.items():
        jax.config.update(key, value)


def _program(x):
    return jnp.tanh(x @ x).sum() * 39.0


class TestCompileAndCacheSpans:
    def test_a_compile_and_a_cache_hit_each_leave_their_span_and_move_their_counter(self, temp_cache):
        x = jnp.ones((32, 32))  # made before: its own small programs stay out of the spans below
        tlmod.process_recording(True)
        timeline = EventTimeline()
        with timeline.span("serve/prefill", cat="serve"):
            jax.jit(_program)(x).block_until_ready()
        cold = {s["name"]: s for s in process_spans()["spans"] if s["args"].get("fun", "").endswith("_program)")
                or s["args"].get("fun") == "_program"}
        assert set(cold) == {"startup/trace", "startup/lower", "startup/compile"}
        assert cold["startup/compile"]["args"]["cache"] == "miss"
        assert all(s["args"]["parent"] == "serve/prefill" for s in cold.values())
        counters = process_spans()["counters"]
        assert len(counters["cache_misses"]) == 1 and "cache_hits" not in counters
        assert not any(s["name"] == "startup/cache_load" for s in process_spans()["spans"])
        assert any(p.name.endswith("-cache") for p in temp_cache.iterdir())

        jax.clear_caches()  # the in-memory programs go, the directory stays: a warm start
        tlmod.process_recording(True)
        timeline = EventTimeline()
        with timeline.span("serve/decode", cat="serve"):
            jax.jit(_program)(x).block_until_ready()
        spans = process_spans()["spans"]
        (load,) = [s for s in spans if s["name"] == "startup/cache_load"]
        (compiled,) = [s for s in spans if s["name"] == "startup/compile"]
        assert load["args"]["parent"] == "serve/decode" and compiled["args"]["cache"] == "hit"
        # the compile span ends where the retrieval it enclosed began: no second counted twice
        assert compiled["t1"] == load["t0"] and compiled["t0"] <= load["t0"]
        phases = startup_phase_seconds([(s["name"], s["t0"], s["t1"]) for s in spans])
        assert phases["cache_load"] == pytest.approx(load["t1"] - load["t0"])
        assert phases["compile"] == pytest.approx(compiled["t1"] - compiled["t0"])
        counters = process_spans()["counters"]
        assert len(counters["cache_hits"]) == 1 and "cache_misses" not in counters
        # the spans reached the timeline too, under the span that paid for them
        in_timeline = {e["name"]: e for e in timeline.events()}
        assert in_timeline["startup/cache_load"]["args"]["parent"] == "serve/decode"

    def test_traces_inside_a_trace_are_covered_by_the_outermost(self, temp_cache):
        inner = jax.jit(lambda v: jnp.tanh(v) + 39.5)

        def outer(v):
            return inner(inner(v)).sum()

        jax.jit(outer)(jnp.ones((8,))).block_until_ready()
        traced = [s["args"]["fun"] for s in process_spans()["spans"] if s["name"] == "startup/trace"]
        assert "outer" in traced and "<lambda>" not in traced

    def test_the_summary_counts_the_cache_directory(self, temp_cache):
        with process_span("startup/first_call", kind="prefill", bucket=8):
            jax.jit(_program)(jnp.ones((16, 16))).block_until_ready()
        summary = startup_summary()
        assert summary["first_calls"] == 1 and summary["cache_misses"] >= 1
        assert summary["cache_dir_bytes"] == sum(
            p.stat().st_size for p in temp_cache.iterdir() if p.name.endswith("-cache"))
        assert summary["compile_s"] > 0 and summary["span_s"] >= summary["compile_s"] + summary["trace_lower_s"]


# ------------------------------------------------------ startup/first_call


class TestFirstCall:
    def test_a_function_is_spanned_on_its_first_call_only(self, recording):
        calls = []
        spanned = first_call_span(lambda v: calls.append(v) or v * 2, kind="train_step")
        assert [spanned(1), spanned(2), spanned(3)] == [2, 4, 6] and calls == [1, 2, 3]
        (span,) = process_spans()["spans"]
        assert span["name"] == "startup/first_call" and span["args"] == {"kind": "train_step"}

    def test_the_engine_spans_the_first_call_of_each_bucket_once(self, recording):
        import numpy as np

        from llmtrain_tpu.serving import ContinuousBatchingScheduler, ServeRequest
        from tests.test_serving_engine import LAYOUT_MODELS, _drain, _engine, _unboxed_params

        model = LAYOUT_MODELS["gpt-row32-fold4"]()
        engine = _engine(model, _unboxed_params(model))
        tlmod.process_recording(True)  # the parameters' own small programs stay out
        engine = _engine(model, engine.params)
        built = [s for s in process_spans()["spans"] if s["name"] in ("startup/build", "startup/pool")]
        assert [s["name"] for s in built] == ["startup/pool", "startup/build"]
        assert built[0]["args"]["parent"] == "startup/build" and built[0]["args"]["bytes"] > 0
        assert built[1]["args"] == {"kind": "engine"}
        timeline = EventTimeline()
        scheduler = ContinuousBatchingScheduler(engine, timeline=timeline)
        requests = [ServeRequest(prompt_ids=np.arange(1, 1 + n, dtype=np.int32), max_new_tokens=4, seed=0)
                    for n in (3, 5, 12, 4)]  # prompt buckets 8, 8, 16, 8: four prefill calls, two programs
        for req in requests:
            scheduler.submit(req)
        _drain(scheduler, requests)
        firsts = [s for s in process_spans()["spans"] if s["name"] == "startup/first_call"]
        want = [("prefill", b) for b in engine._prefill_shapes] + [("decode", b) for b in engine._decode_shapes]
        assert ("prefill", 8) in want and ("prefill", 16) in want and len(want) >= 3
        assert sorted((s["args"]["kind"], s["args"]["bucket"]) for s in firsts) == sorted(want)  # once a bucket
        assert {s["args"]["parent"] for s in firsts} == {"serve/prefill", "serve/decode"}
        assert not hasattr(engine, "on_compile")
        assert "serve/compile" not in _names(timeline) and "startup/first_call" in _names(timeline)
        # each compile-family span lies under the engine span that paid for it
        family = [s for s in process_spans()["spans"] if s["name"] in ("startup/trace", "startup/lower", "startup/compile")
                  and s["t0"] >= min(f["t0"] for f in firsts)]  # the engine's construction traced too, under build
        assert family and all(s["args"]["parent"].startswith("serve/engine.") for s in family)
        # self time: a first call less the compile-family spans inside it
        spans = [(s["name"], s["t0"], s["t1"]) for s in process_spans()["spans"]]
        for first in firsts:
            phases = startup_phase_seconds(spans, first["t0"], first["t1"])
            inside = phases["trace_lower"] + phases["compile"] + phases["cache_load"]
            assert inside > 0 and phases["first_call"] == pytest.approx(first["t1"] - first["t0"] - inside)


# ------------------------------------------------------------ host/stall


class TestStallWatch:
    def test_a_late_wake_up_is_a_span_and_a_timely_one_is_not(self, recording):
        now = [100.0]
        watch = tlmod._StallWatch(clock=lambda: now[0])
        now[0] = 100.03  # due at 100.02, woke 10 ms late: nothing
        assert watch.tick(100.02) == 100.03
        assert process_spans()["spans"] == []
        now[0] = 101.5  # due at 100.05, woke 1.45 s late
        watch.tick(100.05)
        (stall,) = process_spans()["spans"]
        assert stall["name"] == "host/stall" and stall["cat"] == "host" and stall["thread"] == "host-stall-watch"
        assert (stall["t0"], stall["t1"]) == (100.05, 101.5) and stall["args"] == {"late_ms": 1450.0}
        assert not watch.is_alive()  # ticked by hand: no thread ran, nothing slept

    def test_the_watch_starts_when_start_up_is_over_and_stops_at_end_segment(self, recording, monkeypatch):
        monkeypatch.setattr(tlmod._StallWatch, "PERIOD_S", 0.001)
        t = tlmod._T_IMPORT
        timeline = EventTimeline()
        record_process_span("startup/import", t, t + 1.0)
        timeline.instant("tick")  # no first call yet: start-up has not even begun to end
        assert tlmod._PROCESS.armed and tlmod._PROCESS.watch is None
        now = [t + 12.0 + tlmod._ProcessBuffer.QUIET_S - 0.1]
        monkeypatch.setattr(tlmod.time, "perf_counter", lambda: now[0])
        record_process_span("startup/first_call", t + 10.0, t + 12.0, kind="decode", bucket=4)
        timeline.instant("tick")  # the last first call ended under QUIET_S ago: loads may follow
        assert tlmod._PROCESS.watch is None
        with process_span("startup/first_call", kind="decode", bucket=8):
            now[0] += 60.0
            timeline.instant("tick")  # a start-up span is open: not over
            assert tlmod._PROCESS.watch is None
        now[0] += tlmod._ProcessBuffer.QUIET_S + 0.1
        timeline.instant("tick")  # quiet for long enough: the hot path's one flag starts the watch
        watch = tlmod._PROCESS.watch
        assert watch.is_alive() and watch.daemon and watch.name == "host-stall-watch"
        assert not tlmod._PROCESS.armed
        timeline.end_segment()
        watch.join(timeout=5.0)
        assert not watch.is_alive() and tlmod._PROCESS.watch is None
        assert "host-stall-watch" not in {th.name for th in threading.enumerate()}

    def test_a_step_loop_with_no_timeline_events_settles_through_its_own_calls(self, recording, monkeypatch):
        step = first_call_span(lambda: None, kind="train_step")
        step()
        assert tlmod._PROCESS.armed and tlmod._PROCESS.watch is None
        real = tlmod.time.perf_counter
        monkeypatch.setattr(tlmod.time, "perf_counter", lambda: real() + tlmod._ProcessBuffer.QUIET_S + 1.0)
        step()
        watch = tlmod._PROCESS.watch
        assert watch is not None and not tlmod._PROCESS.armed
        watch.halt()
        watch.join(timeout=5.0)

    def test_the_start_up_table_is_logged_once(self, recording, caplog):
        t = tlmod._T_IMPORT  # the table runs from the package's first line
        with caplog.at_level("INFO", logger="llmtrain"):
            tlmod._PROCESS.summarise()  # nothing was called yet: nothing to say
            record_process_span("startup/first_call", t + 10.0, t + 12.0, kind="decode", bucket=4)
            record_process_span("startup/cache_load", t + 10.5, t + 11.5)
            tlmod._PROCESS.summarise()
            tlmod._PROCESS.summarise()  # once
        (line,) = [r.getMessage() for r in caplog.records if r.getMessage().startswith("start-up: ")]
        table = json.loads(line[len("start-up: "):])
        assert table["cache_load_s"] == 1.0 and table["first_call_s"] == 1.0 and table["first_calls"] == 1
        assert table["span_s"] == 12.0 and table["unnamed_s"] == 10.0
        summary = [s for s in process_spans()["spans"] if s["name"] == "startup/summary"]
        assert len(summary) == 1 and summary[0]["args"]["cache_load_s"] == 1.0
        assert not tlmod._PROCESS.armed  # the table itself does not re-arm the watch

    def test_end_segment_logs_the_table_of_a_run_too_short_to_settle(self, recording, caplog):
        timeline = EventTimeline()
        with process_span("startup/first_call", kind="train_step"):
            pass
        with caplog.at_level("INFO", logger="llmtrain"):
            timeline.end_segment()
        assert [r for r in caplog.records if r.getMessage().startswith("start-up: ")]
        assert "startup/summary" in _names(timeline) and tlmod._PROCESS.watch is None


# ------------------------------------------------- goodput: compile's parts


def _write_timeline(path, events):
    header = {"name": "segment_start", "ph": "seg", "segment_id": 0, "start_unix_time": 1000.0}
    footer = {"name": "segment_end", "ph": "seg", "segment_id": 0, "end_unix_time": 1030.0}
    path.parent.mkdir(parents=True)
    path.write_text("\n".join(json.dumps(e) for e in [header, *events, footer]) + "\n")


def _x(name, ts_s, dur_s, **extra):
    return {"name": name, "ph": "X", "ts_us": int(ts_s * 1e6), "dur_us": int(dur_s * 1e6), **extra}


class TestGoodputCompileParts:
    STEPS = [_x("data_wait", 10.0, 0.5, step=1), _x("host_dispatch", 10.5, 4.0, step=1),
             _x("host_dispatch", 14.5, 15.5, step=2)]

    def test_the_compile_row_lists_its_measured_parts_and_the_sum_stays(self, tmp_path):
        startup = [
            _x("startup/import", -3.0, 2.0),  # before the segment: outside the ledger's wall clock
            _x("startup/build", 0.5, 9.0),
            _x("startup/trace", 1.0, 2.0), _x("startup/lower", 3.0, 1.0),
            _x("startup/compile", 4.0, 0.5), _x("startup/cache_load", 4.5, 3.0),
            _x("startup/first_call", 10.5, 3.5),  # inside step 1: past the compile window
            _x("startup/compile", 10.6, 3.0),
        ]
        _write_timeline(tmp_path / "telemetry" / "timeline.jsonl", startup + self.STEPS)
        ledger = compute_goodput(tmp_path)
        assert ledger["categories"]["compile"] == 10.0 and ledger["balance_error_sec"] == 0.0
        assert ledger["compile_parts"] == {"trace + lower": 3.0, "compile": 0.5, "cache load": 3.0, "first call": 0.0}
        assert sum(ledger["compile_parts"].values()) <= ledger["categories"]["compile"]
        table = render_goodput_md(ledger)
        rows = [ln for ln in table.splitlines() if ln.startswith("| compile") or ln.startswith("| - of which")]
        assert rows[0].startswith("| compile | 10.0 |") and len(rows) == 5
        assert rows[1].startswith("| - of which trace + lower | 3.0 |") and rows[3].startswith("| - of which cache load | 3.0 |")

    def test_a_timeline_without_startup_spans_gives_the_ledger_it_always_gave(self, tmp_path):
        _write_timeline(tmp_path / "telemetry" / "timeline.jsonl", self.STEPS)
        ledger = compute_goodput(tmp_path)
        assert "compile_parts" not in ledger and ledger["categories"]["compile"] == 10.0
        assert "of which" not in render_goodput_md(ledger)

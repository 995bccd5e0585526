"""Unified telemetry subsystem tests (llmtrain_tpu/telemetry/).

Covers the ISSUE-4 contract end to end:

* EventTimeline — span/instant recording, monotonic timestamps, JSONL
  persistence, Perfetto export format (loadable JSON, pid/tid mapping,
  thread-name metadata), rollback tagging (events TAGGED, never dropped),
  bounded retention.
* MemoryMonitor — hbm metrics from memory_stats, the live-array fallback
  when the backend reports None (CPU PJRT — the tier-1 environment), and
  the headroom warning channel.
* MetricsRegistry — publish/flush to the tracker, the degrade-to-warning
  path for failing backends (regression: backend exceptions used to
  propagate out of log_metrics into the step loop), flush ordering under
  rollback.
* Prometheus — naming convention, exposition rendering, the stdlib HTTP
  endpoint, the textfile snapshot.
* Report — aggregation fields + markdown rendering.
* Trainer integration smoke (`make verify-telemetry` acceptance): a real
  fit produces report.json / report.md / Perfetto-loadable trace.json;
  train/mfu, mem/hbm_peak and span metrics land in the tracker AND in one
  live Prometheus scrape.
"""

from __future__ import annotations

import json
import threading
import urllib.request
from pathlib import Path

import pytest

from llmtrain_tpu.config.schemas import RunConfig
from llmtrain_tpu.telemetry.memory import MemoryMonitor
from llmtrain_tpu.telemetry.prometheus import (
    PrometheusEndpoint,
    prometheus_name,
    render_prometheus,
    write_textfile,
)
from llmtrain_tpu.telemetry.registry import MetricsRegistry
from llmtrain_tpu.telemetry.report import build_report, render_markdown, write_reports
from llmtrain_tpu.telemetry.timeline import EventTimeline


# ---------------------------------------------------------------- timeline


class TestEventTimeline:
    def test_span_records_duration_event(self):
        tl = EventTimeline()
        with tl.span("work", cat="test", step=3, detail="x"):
            pass
        (event,) = tl.events()
        assert event["name"] == "work"
        assert event["ph"] == "X"
        assert event["step"] == 3
        assert event["dur_us"] >= 0
        assert event["args"] == {"detail": "x"}

    def test_nested_spans_record_their_parent_per_thread(self):
        """A span opened inside another says which span caused it; the
        stack is per thread, so a span another thread opens meanwhile is
        nobody's child; the body adds what it counted to the yielded args."""
        tl = EventTimeline()
        inside = threading.Event()
        release = threading.Event()

        def other():
            inside.wait(timeout=10.0)
            with tl.span("other_thread"):
                with tl.span("other_child"):
                    pass
            release.set()

        worker = threading.Thread(target=other, name="other")
        worker.start()
        with tl.span("outer", rows=2) as args:
            with tl.span("middle"):
                inside.set()
                assert release.wait(timeout=10.0)
                with tl.span("inner") as inner_args:
                    inner_args["counted"] = 5
            with tl.span("sibling"):
                pass
            args["rows_padded"] = 8
        with tl.span("after"):
            pass
        worker.join(timeout=10.0)
        assert not worker.is_alive()
        args_of = {e["name"]: e.get("args", {}) for e in tl.events()}
        assert args_of["outer"] == {"rows": 2, "rows_padded": 8}
        assert args_of["middle"] == {"parent": "outer"}
        assert args_of["inner"] == {"parent": "middle", "counted": 5}
        assert args_of["sibling"] == {"parent": "outer"}
        assert args_of["after"] == {}
        assert args_of["other_thread"] == {}
        assert args_of["other_child"] == {"parent": "other_thread"}

    def test_span_stack_unwinds_when_the_body_raises(self):
        tl = EventTimeline()
        with pytest.raises(ValueError):
            with tl.span("outer"):
                with tl.span("failing"):
                    raise ValueError("boom")
        with tl.span("next"):
            pass
        args_of = {e["name"]: e.get("args", {}) for e in tl.events()}
        assert args_of["failing"] == {"parent": "outer"}
        assert args_of["next"] == {}

    def test_disabled_timeline_still_yields_args(self):
        tl = EventTimeline(enabled=False)
        with tl.span("outer", rows=1) as args:
            args["more"] = 2
        assert tl.events() == []

    def test_span_propagates_body_exception_but_still_records(self):
        tl = EventTimeline()
        with pytest.raises(ValueError):
            with tl.span("boom"):
                raise ValueError("body")
        assert [e["name"] for e in tl.events()] == ["boom"]

    def test_timestamps_monotonic_nondecreasing(self):
        tl = EventTimeline()
        for i in range(50):
            with tl.span("s", step=i):
                pass
            tl.instant("i", step=i)
        stamps = [e["ts_us"] for e in tl.events()]
        assert stamps == sorted(stamps)

    def test_jsonl_flush_appends_once_per_event(self, tmp_path):
        path = tmp_path / "t" / "timeline.jsonl"
        tl = EventTimeline(path)
        tl.instant("a")
        tl.flush()
        tl.instant("b")
        tl.flush()
        tl.flush()  # idempotent: nothing pending
        lines = path.read_text().strip().splitlines()
        # The segment_start header (goodput ledger) is written eagerly at
        # construction, before any flush; events append exactly once after.
        assert [json.loads(ln)["name"] for ln in lines] == [
            "segment_start",
            "a",
            "b",
        ]

    def test_rollback_window_tagged_not_dropped(self, tmp_path):
        """Satellite contract: events of a rolled-back window stay in the
        stream, tagged — and the tag lands in the JSONL because tagging
        happens before the boundary flush (flush ordering)."""
        path = tmp_path / "timeline.jsonl"
        tl = EventTimeline(path)
        for step in range(1, 11):
            with tl.span("host_dispatch", step=step):
                pass
        tl.tag_rollback(6, 10)
        tl.instant("rollback", step=10, restored_step=5)
        tl.flush()
        rows = [json.loads(ln) for ln in path.read_text().strip().splitlines()]
        dispatch = [r for r in rows if r["name"] == "host_dispatch"]
        assert len(dispatch) == 10  # nothing dropped
        tagged = {r["step"] for r in dispatch if r.get("rolled_back")}
        assert tagged == {6, 7, 8, 9, 10}
        assert any(r["name"] == "rollback" for r in rows)

    def test_perfetto_export_loadable_with_pid_tid_mapping(self, tmp_path):
        tl = EventTimeline(process_index=2)
        with tl.span("main_work", step=1):
            pass

        done = threading.Event()

        def worker():
            tl.instant("bg_event")
            done.set()

        threading.Thread(target=worker, name="bg-thread").start()
        assert done.wait(5)
        target = tmp_path / "trace.json"
        assert tl.export_perfetto(target) == target
        trace = json.loads(target.read_text())
        events = trace["traceEvents"]
        assert isinstance(events, list) and events
        # every event carries the process index as pid and an int tid
        real = [e for e in events if e["ph"] in ("X", "i")]
        assert real and all(e["pid"] == 2 for e in real)
        assert all(isinstance(e["tid"], int) for e in real)
        assert all(isinstance(e["ts"], int) and e["ts"] >= 0 for e in real)
        # duration events carry dur; metadata names both threads
        assert all("dur" in e for e in real if e["ph"] == "X")
        names = {
            e["args"]["name"] for e in events if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert "bg-thread" in names and len(names) == 2

    def test_max_events_cap_counts_drops(self):
        tl = EventTimeline(max_events=1000)
        for i in range(1100):
            tl.instant("e", step=i)
        assert len(tl.events()) == 1000
        assert tl.dropped == 100

    def test_span_totals_and_event_counts(self):
        tl = EventTimeline()
        for _ in range(3):
            with tl.span("a"):
                pass
        tl.instant("warned")
        totals = tl.span_totals()
        assert totals["a"]["count"] == 3
        assert totals["a"]["total_ms"] >= 0
        assert tl.event_counts() == {"warned": 1}


# ------------------------------------------------------------------ memory


class TestMemoryMonitor:
    def test_cpu_backend_falls_back_to_live_arrays(self):
        """Tier-1 environment: CPU PJRT memory_stats() is None/empty — the
        sample must still produce hbm metrics (live-array estimator) and
        host metrics, and must not raise."""
        import jax.numpy as jnp

        anchor = jnp.ones((64, 64))  # keep at least one live array around
        mon = MemoryMonitor()
        sample = mon.sample(step=1)
        assert sample["mem/hbm_used"] >= anchor.nbytes
        assert sample["mem/hbm_peak"] >= sample["mem/hbm_used"]
        assert sample["mem/live_arrays"] >= 1
        assert sample.get("mem/host_rss", 0) > 0
        assert mon.source == "live_arrays"
        del anchor

    def test_memory_stats_none_direct(self, monkeypatch):
        """Explicit fallback unit: a device whose memory_stats() returns
        None (the satellite's named failure shape)."""
        from llmtrain_tpu.telemetry import memory as mem_mod

        monkeypatch.setattr(mem_mod, "_device_memory_stats", lambda: None)
        sample = MemoryMonitor().sample()
        assert "mem/hbm_used" in sample and "mem/hbm_limit" not in sample

    def test_device_stats_and_headroom_warning(self, monkeypatch, caplog):
        from llmtrain_tpu.telemetry import memory as mem_mod

        stats = {
            "bytes_in_use": 95.0e9,
            "peak_bytes_in_use": 96.0e9,
            "bytes_limit": 100.0e9,
        }
        monkeypatch.setattr(mem_mod, "_device_memory_stats", lambda: dict(stats))
        tl = EventTimeline()
        mon = MemoryMonitor(headroom_warn_frac=0.9, timeline=tl)
        with caplog.at_level("WARNING"):
            sample = mon.sample(step=7)
            # second sample in the same excursion must NOT re-warn
            mon.sample(step=8)
        assert sample["mem/hbm_used"] == 95.0e9
        assert sample["mem/hbm_peak"] == 96.0e9
        assert sample["mem/hbm_limit"] == 100.0e9
        assert mon.source == "memory_stats"
        assert mon.headroom_warnings == 1
        assert sum("HBM headroom low" in r.message for r in caplog.records) == 1
        assert tl.event_counts().get("hbm_headroom") == 1
        # drop below threshold -> excursion resets -> warns again
        stats["bytes_in_use"] = 10.0e9
        mon.sample(step=9)
        stats["bytes_in_use"] = 95.0e9
        mon.sample(step=10)
        assert mon.headroom_warnings == 2


    def test_peak_counts_the_reserved_region_too(self, monkeypatch):
        """On the TPU ``peak_bytes_in_use`` is live arrays only; compiled
        programs' temporaries are in ``peak_bytes_reserved`` (PR 23's train
        cell, rounded). The peak sums both, like the benchmark's
        ``memory_peak_bytes``; the live-array part keeps its own key."""
        from llmtrain_tpu.telemetry import memory as mem_mod
        from llmtrain_tpu.utils.hw import peak_bytes_from_stats

        stats = {
            "bytes_in_use": 1.5e9,
            "peak_bytes_in_use": 2.17e9,
            "bytes_reserved": 10.0e9,
            "peak_bytes_reserved": 10.42e9,
            "bytes_limit": 16.0e9,
        }
        assert peak_bytes_from_stats(stats) == 2.17e9 + 10.42e9
        assert peak_bytes_from_stats({"bytes_in_use": 3.0}) == 3.0
        assert peak_bytes_from_stats({}) == 0.0
        monkeypatch.setattr(mem_mod, "_device_memory_stats", lambda: dict(stats))
        mon = MemoryMonitor()
        sample = mon.sample(step=1)
        assert sample["mem/hbm_peak"] == 2.17e9 + 10.42e9
        assert sample["mem/hbm_peak_in_use"] == 2.17e9
        assert sample["mem/hbm_used"] == 1.5e9
        peaks = mon.peaks()
        assert peaks["hbm_peak_bytes"] == 2.17e9 + 10.42e9
        assert peaks["hbm_peak_in_use_bytes"] == 2.17e9


# ---------------------------------------------------------------- registry


class _RecordingTracker:
    def __init__(self):
        self.calls: list[tuple[dict, int | None]] = []
        self.params: list[dict] = []
        self.artifacts: list[tuple[str, str | None]] = []

    def start_run(self, run_id, run_name=None):
        pass

    def log_params(self, params):
        self.params.append(params)

    def log_metrics(self, metrics, step=None):
        self.calls.append((dict(metrics), step))

    def log_artifact(self, local_path, artifact_path=None):
        self.artifacts.append((local_path, artifact_path))

    def end_run(self, status="FINISHED"):
        pass


class _FailingTracker(_RecordingTracker):
    def __init__(self, fail_times: int = 10**9):
        super().__init__()
        self.fail_times = fail_times
        self.attempts = 0

    def log_metrics(self, metrics, step=None):
        self.attempts += 1
        if self.attempts <= self.fail_times:
            raise RuntimeError("backend down")
        super().log_metrics(metrics, step)

    def log_params(self, params):
        raise RuntimeError("backend down")

    def log_artifact(self, local_path, artifact_path=None):
        raise RuntimeError("backend down")


class TestMetricsRegistry:
    def test_publish_then_flush_single_tracker_call(self):
        tracker = _RecordingTracker()
        reg = MetricsRegistry(tracker)
        reg.publish({"train/loss": 2.0}, step=5)
        reg.publish({"train/mfu": 0.3}, step=5)
        assert tracker.calls == []  # buffered until the flush point
        assert reg.flush(step=5)
        ((metrics, step),) = tracker.calls
        assert metrics == {"train/loss": 2.0, "train/mfu": 0.3}
        assert step == 5
        assert reg.latest()["train/loss"] == (2.0, 5)

    def test_failing_backend_degrades_to_warning(self, caplog):
        """Regression (satellite): a tracker backend exception must not
        escape the flush — the old direct log_metrics calls propagated it
        into the step loop and killed the run."""
        tracker = _FailingTracker()
        reg = MetricsRegistry(tracker)
        with caplog.at_level("WARNING"):
            for step in range(1, 4):
                reg.publish({"train/loss": 1.0}, step=step)
                assert reg.flush(step=step) is False  # degraded, not raised
        assert reg.tracker_errors == 3
        assert reg.counters()["telemetry/tracker_errors"] == 3
        # rate-limited: first failure warns, the streak does not spam
        warns = [r for r in caplog.records if "log_metrics failed" in r.message]
        assert len(warns) == 1
        # registry state stays queryable while the backend is down
        assert reg.latest()["train/loss"][0] == 1.0
        assert not reg.safe_log_params({"a": 1})
        assert not reg.safe_log_artifact("/nope")

    def test_recovery_resets_streak(self, caplog):
        tracker = _FailingTracker(fail_times=2)
        reg = MetricsRegistry(tracker)
        for step in range(1, 4):
            reg.publish({"m": 1.0}, step=step)
            reg.flush(step=step)
        assert len(tracker.calls) == 1  # third flush landed
        assert reg.tracker_errors == 2

    def test_counters_and_history(self):
        reg = MetricsRegistry(_RecordingTracker())
        reg.inc("resilience/rollbacks")
        reg.inc("resilience/rollbacks")
        reg.publish({"train/loss": 3.0, "other": 1.0}, step=1)
        reg.flush(step=1)
        assert reg.counters()["resilience/rollbacks"] == 2
        assert reg.history() == [(1, {"train/loss": 3.0})]

    def test_flush_ordering_under_rollback(self, tmp_path):
        """Registry flush + timeline flush at a boundary where a rollback
        fired: the tagged window must be on disk after the SAME flush that
        pushes the boundary's metrics — not an interval later."""
        tracker = _RecordingTracker()
        reg = MetricsRegistry(tracker)
        tl = EventTimeline(tmp_path / "timeline.jsonl")
        for step in range(1, 6):
            with tl.span("host_dispatch", step=step):
                pass
        # boundary at step 5: rollback to 2 detected BEFORE the flush
        tl.tag_rollback(3, 5)
        tl.instant("rollback", step=5, restored_step=2)
        reg.publish({"train/loss": 9.9}, step=5)
        reg.flush(step=5)
        tl.flush()
        rows = [
            json.loads(ln)
            for ln in (tmp_path / "timeline.jsonl").read_text().strip().splitlines()
        ]
        assert {r["step"] for r in rows if r.get("rolled_back")} == {3, 4, 5}
        assert tracker.calls == [({"train/loss": 9.9}, 5)]


# -------------------------------------------------------------- prometheus


class TestPrometheus:
    def test_name_convention(self):
        assert prometheus_name("train/loss") == "llmtrain_train_loss"
        assert prometheus_name("mem/hbm_peak") == "llmtrain_mem_hbm_peak"
        assert prometheus_name("train/loss_rank_0") == "llmtrain_train_loss_rank_0"
        # idempotent + safe on weird input
        assert prometheus_name("llmtrain_train_loss") == "llmtrain_train_loss"
        assert prometheus_name("a b/c-d") == "llmtrain_a_b_c_d"

    def test_render_format(self):
        text = render_prometheus(
            {"train/loss": (2.5, 10), "train/mfu": (float("nan"), 10)},
            {"resilience/rollbacks": 1.0},
            info={"run_name": 'he"llo'},
        )
        assert "# TYPE llmtrain_train_loss gauge" in text
        assert "llmtrain_train_loss 2.5" in text
        assert "llmtrain_train_mfu NaN" in text
        assert "llmtrain_resilience_rollbacks_total 1.0" in text
        assert 'run_name="he\\"llo"' in text
        assert text.endswith("\n")

    def test_endpoint_serves_metrics(self):
        reg = MetricsRegistry(None)
        reg.publish({"train/loss": 1.25}, step=3)
        reg.flush(step=3)
        endpoint = PrometheusEndpoint(
            lambda: render_prometheus(reg.latest(), reg.counters()),
            host="127.0.0.1",
            port=0,
        )
        try:
            url = f"http://127.0.0.1:{endpoint.port}/metrics"
            with urllib.request.urlopen(url, timeout=10) as resp:
                assert resp.status == 200
                assert "text/plain" in resp.headers["Content-Type"]
                body = resp.read().decode()
            assert "llmtrain_train_loss 1.25" in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{endpoint.port}/nope", timeout=10
                )
        finally:
            endpoint.close()

    def test_textfile_atomic_write(self, tmp_path):
        target = tmp_path / "tele" / "metrics.prom"
        assert write_textfile(target, "llmtrain_x 1\n")
        assert target.read_text() == "llmtrain_x 1\n"
        assert not target.with_name("metrics.prom.tmp").exists()


# ------------------------------------------------------------------ report


class TestReport:
    def _populated(self, tmp_path):
        reg = MetricsRegistry(_RecordingTracker())
        tl = EventTimeline()
        for step in (5, 10):
            with tl.span("host_dispatch", step=step):
                pass
            reg.publish(
                {
                    "train/loss": 3.0 - step / 10,
                    "train/tokens_per_sec": 1000.0,
                    "train/mfu": 0.21,
                },
                step=step,
            )
            reg.flush(step=step)
        reg.inc("resilience/rollbacks")
        tl.instant("rollback", step=10)
        return build_report(
            run_id="rid-1",
            run_name="unit",
            registry=reg,
            timeline=tl,
            memory=MemoryMonitor(),
            wall_time_sec=12.0,
            train_result={"final_step": 10, "final_loss": 2.0},
        )

    def test_report_fields(self, tmp_path):
        report = self._populated(tmp_path)
        assert report["schema"].startswith("llmtrain-telemetry-report/")
        assert report["run"] == {"run_id": "rid-1", "name": "unit"}
        assert report["loss"]["trajectory"] == [[5, 2.5], [10, 2.0]]
        assert report["loss"]["final"] == 2.0 and report["loss"]["min"] == 2.0
        assert report["throughput"]["mfu"] == 0.21
        assert report["spans"]["host_dispatch"]["count"] == 2
        assert 0 <= report["spans"]["host_dispatch"]["frac_of_wall"] <= 1
        assert report["events"]["instants"] == {"rollback": 1}
        assert report["events"]["counters"]["resilience/rollbacks"] == 1
        assert report["train_result"]["final_step"] == 10

    def test_markdown_survives_inf_and_nan(self, tmp_path):
        """Diverged runs put inf/nan in the result — the report must render
        anyway (int(inf) raises OverflowError)."""
        report = self._populated(tmp_path)
        report["train_result"] = {
            "final_step": 10,
            "final_loss": float("inf"),
            "final_val_loss": float("nan"),
        }
        report["memory"]["hbm_peak_bytes"] = float("inf")
        md = render_markdown(report)
        assert "inf" in md and "NaN" in md

    def test_write_and_markdown(self, tmp_path):
        report = self._populated(tmp_path)
        json_path, md_path = write_reports(tmp_path, report)
        assert json.loads(json_path.read_text())["run"]["run_id"] == "rid-1"
        md = md_path.read_text()
        assert md.startswith("# Run report — unit (rid-1)")
        assert "host_dispatch" in md and "rollback: 1" in md
        assert render_markdown(report) == md


# --------------------------------------------------- trainer integration


def _smoke_cfg(tmp_path, **telemetry):
    return RunConfig.model_validate(
        {
            "run": {"name": "tele-e2e"},
            "model": {
                "name": "dummy_gpt",
                "block_size": 8,
                "d_model": 16,
                "n_layers": 1,
                "n_heads": 2,
                "d_ff": 32,
                "dropout": 0.0,
                "vocab_size": 32,
            },
            "data": {"name": "dummy_text"},
            "trainer": {
                "max_steps": 12,
                "micro_batch_size": 2,
                "grad_accum_steps": 1,
                "log_every_steps": 5,
                "eval_every_steps": 10,
                "save_every_steps": 10,
                "warmup_steps": 0,
            },
            "telemetry": telemetry or {},
            "output": {"root_dir": str(tmp_path / "runs")},
        }
    )


def _make_run_dir(tmp_path) -> Path:
    run_dir = tmp_path / "runs" / "tele-e2e"
    (run_dir / "logs").mkdir(parents=True)
    return run_dir


class TestTrainerIntegration:
    def test_smoke_fit_produces_reports_trace_and_scrape(self, tmp_path):
        """`make verify-telemetry` acceptance: one smoke fit produces
        report.json + report.md + a Perfetto-loadable trace.json; train/mfu,
        mem/hbm_peak and the span metrics appear in the TRACKER sample and
        in one live Prometheus scrape taken during the run."""
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()
        cfg = _smoke_cfg(
            tmp_path,
            prometheus=True,
            prometheus_port=0,  # ephemeral: parallel test runs must not collide
            prometheus_host="127.0.0.1",
        )
        run_dir = _make_run_dir(tmp_path)
        tracker = _RecordingTracker()
        trainer = Trainer(cfg, run_dir, tracker)

        scraped: list[str] = []
        result_box: list = []

        def run_fit():
            result_box.append(trainer.fit())

        # fit runs in a worker so the main thread can scrape mid-run (the
        # trainer warns that SIGTERM handling is disabled — irrelevant here)
        fit_thread = threading.Thread(target=run_fit, name="fit")
        fit_thread.start()
        try:
            import time as _time

            deadline = _time.monotonic() + 120
            while _time.monotonic() < deadline and fit_thread.is_alive():
                port = trainer._telemetry.prometheus_port
                if port is not None:
                    try:
                        with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/metrics", timeout=5
                        ) as resp:
                            text = resp.read().decode()
                        if "llmtrain_train_mfu" in text:
                            scraped.append(text)
                            break
                    except OSError:
                        pass
                _time.sleep(0.05)
        finally:
            fit_thread.join(timeout=180)
        assert not fit_thread.is_alive()
        assert result_box and result_box[0].final_step == 12

        # --- tracker: train/mfu, mem/hbm_peak, span metrics in the sample
        all_keys = set()
        for metrics, _step in tracker.calls:
            all_keys.update(metrics)
        assert {"train/loss", "train/mfu", "mem/hbm_peak", "mem/hbm_used"} <= all_keys
        assert {"train/data_wait_ms", "train/host_dispatch_ms"} <= all_keys

        # --- one Prometheus scrape carried the same gauges live
        assert scraped, "no successful /metrics scrape during the run"
        scrape = scraped[0]
        for gauge in (
            "llmtrain_train_mfu",
            "llmtrain_train_loss",
            "llmtrain_mem_hbm_peak",
            "llmtrain_train_data_wait_ms",
        ):
            assert gauge in scrape, f"{gauge} missing from scrape"
        assert 'llmtrain_run_info{' in scrape

        # --- run-dir artifacts: reports + Perfetto-loadable trace + JSONL
        report = json.loads((run_dir / "report.json").read_text())
        assert report["run"]["run_id"] == "tele-e2e"
        assert report["loss"]["final"] is not None
        assert report["throughput"]["mfu"] is not None
        assert report["memory"]["hbm_peak_bytes"] > 0
        assert {"data_wait", "host_dispatch", "checkpoint_save", "eval"} <= set(
            report["spans"]
        )
        assert (run_dir / "report.md").read_text().startswith("# Run report")
        trace = json.loads((run_dir / "telemetry" / "trace.json").read_text())
        assert any(e.get("name") == "host_dispatch" for e in trace["traceEvents"])
        jsonl = (run_dir / "telemetry" / "timeline.jsonl").read_text()
        assert any(
            json.loads(ln)["name"] == "prefetch_assemble"
            for ln in jsonl.strip().splitlines()
        )
        prom_file = (run_dir / "telemetry" / "metrics.prom").read_text()
        assert "llmtrain_mem_hbm_peak" in prom_file
        # telemetry artifacts registered with the tracker (satellite)
        registered = {a for a, _ in tracker.artifacts}
        assert str(run_dir / "report.json") in registered
        assert str(run_dir / "telemetry" / "trace.json") in registered

    def test_fit_survives_failing_tracker_backend(self, tmp_path, caplog):
        """Satellite regression: a tracker whose every method raises must
        cost warnings, not the run."""
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()
        cfg = _smoke_cfg(tmp_path)
        tracker = _FailingTracker()
        with caplog.at_level("WARNING"):
            result = Trainer(cfg, None, tracker).fit()
        assert result.final_step == 12
        assert tracker.attempts > 0  # the backend WAS exercised
        assert any("log_metrics failed" in r.message for r in caplog.records)

    def test_telemetry_disabled_writes_nothing_but_tracker_still_logs(
        self, tmp_path
    ):
        """The master switch removes the telemetry extras (files, timeline
        recording, memory sampling) — NOT experiment tracking, which now
        flows through the registry."""
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()
        cfg = _smoke_cfg(tmp_path, enabled=False)
        run_dir = _make_run_dir(tmp_path)
        tracker = _RecordingTracker()
        trainer = Trainer(cfg, run_dir, tracker)
        result = trainer.fit()
        assert result.final_step == 12
        assert not (run_dir / "report.json").exists()
        assert not (run_dir / "telemetry").exists()
        # the timeline is a true no-op, not an unbounded in-memory buffer
        assert trainer._telemetry.timeline.events() == []
        # tracker logging is unaffected by the telemetry switch
        assert tracker.params, "log_params lost with telemetry disabled"
        all_keys = {k for metrics, _ in tracker.calls for k in metrics}
        assert {"train/loss", "train/mfu"} <= all_keys
        assert not any(k.startswith("mem/") for k in all_keys)

    def test_rollback_run_tags_timeline_and_counts(self, tmp_path):
        """Registry/timeline behavior under a REAL spike rollback: the
        replayed window's events are tagged in the JSONL, the rollback
        instant + counter land in the report."""
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()
        cfg = _smoke_cfg(tmp_path)
        cfg = RunConfig.model_validate(
            {
                **cfg.model_dump(),
                "trainer": {
                    **cfg.trainer.model_dump(),
                    "max_steps": 40,
                    "save_every_steps": 10,
                    "log_every_steps": 5,
                    "eval_every_steps": 40,
                },
                "resilience": {
                    "spike_detection": True,
                    "spike_factor": 4.0,
                    "spike_min_history": 5,
                    "max_rollbacks": 2,
                    "faults": {"spike_loss_at_step": 23, "spike_loss_scale": 1e4},
                },
            }
        )
        run_dir = _make_run_dir(tmp_path)
        result = Trainer(cfg, run_dir, _RecordingTracker()).fit()
        assert result.rollbacks == 1
        rows = [
            json.loads(ln)
            for ln in (run_dir / "telemetry" / "timeline.jsonl")
            .read_text()
            .strip()
            .splitlines()
        ]
        assert any(r["name"] == "rollback" for r in rows)
        assert any(r["name"] == "fault_spike_loss" for r in rows)
        tagged = [r for r in rows if r.get("rolled_back")]
        assert tagged, "rolled-back window events missing their tag"
        assert all(r["step"] > 20 for r in tagged if "step" in r)
        report = json.loads((run_dir / "report.json").read_text())
        assert report["events"]["counters"]["resilience/rollbacks"] == 1
        assert report["events"]["instants"]["rollback"] == 1

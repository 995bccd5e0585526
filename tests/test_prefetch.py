"""Async input pipeline (data/prefetch.py) + trainer integration.

The acceptance pillar is bitwise determinism: the prefetcher overlaps
batch assembly + H2D with device compute but must never change WHAT is
assembled — the loss trajectory with ``prefetch_depth: 2`` must equal the
synchronous path (``prefetch_depth: 0``) exactly, including across a
resume and an injected loss-spike rollback. The shutdown pillars: a
SIGTERM with a full queue stops cleanly, and the hang watchdog still
catches a hang injected INSIDE the prefetch thread (the consumer starves
on the queue instead of blocking in the loop).

Also covers the persistent-compilation-cache satellite: the
env-beats-config-beats-default resolution of the cache directory.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
import yaml

from llmtrain_tpu.config import RunConfig
from llmtrain_tpu.data.prefetch import BatchPrefetcher, PrefetcherClosedError
from llmtrain_tpu.distributed import resolve_compilation_cache_dir
from llmtrain_tpu.registry import initialize_registries
from llmtrain_tpu.resilience import EXIT_HANG_DETECTED
from llmtrain_tpu.tracking import NullTracker
from llmtrain_tpu.training import Trainer


@pytest.fixture(autouse=True)
def _registries():
    initialize_registries()


def _cfg(tmp_path=None, *, prefetch_depth=2, **overrides):
    base = {
        "run": {"name": "pf", "seed": 11},
        "model": {
            "name": "dummy_gpt",
            "block_size": 8,
            "vocab_size": 32,
            "dropout": 0.0,
            "d_model": 48,
            "n_heads": 2,
            "d_ff": 96,
            "n_layers": 1,
        },
        "data": {"name": "dummy_text"},
        "trainer": {
            "max_steps": 12,
            "micro_batch_size": 2,
            "grad_accum_steps": 1,
            "lr": 3e-3,
            "warmup_steps": 0,
            "log_every_steps": 2,
            "eval_every_steps": 100,
            "save_every_steps": 5,
            "prefetch_depth": prefetch_depth,
        },
        "mlflow": {"enabled": False},
    }
    if tmp_path is not None:
        base["output"] = {"root_dir": str(tmp_path)}
    for section, values in overrides.items():
        base[section] = {**base.get(section, {}), **values}
    return RunConfig.model_validate(base)


class RecordingTracker(NullTracker):
    """Capture every log_metrics call for exact trajectory comparison."""

    def __init__(self):
        self.records: list[tuple[int | None, dict]] = []

    def log_metrics(self, metrics, step=None):
        self.records.append((step, dict(metrics)))

    def series(self, key: str) -> list[tuple[int | None, float]]:
        return [(s, m[key]) for s, m in self.records if key in m]


def _no_live_prefetch_threads():
    return not any(
        t.name.startswith("batch-prefetch") and t.is_alive()
        for t in threading.enumerate()
    )


# --------------------------------------------------------------------------
# prefetcher unit behavior (no trainer, no jax arrays)
# --------------------------------------------------------------------------


class TestBatchPrefetcherUnit:
    def test_in_order_delivery(self):
        pf = BatchPrefetcher(lambda s: ("batch", s), depth=2, start_step=1)
        try:
            for step in range(1, 8):
                assert pf.get(step) == ("batch", step)
        finally:
            pf.close()
        assert _no_live_prefetch_threads()

    def test_depth_zero_is_rejected(self):
        with pytest.raises(ValueError, match="depth"):
            BatchPrefetcher(lambda s: s, depth=0, start_step=1)

    def test_reseek_invalidates_stale_batches(self):
        """Batches assembled under pre-reseek state must never reach the
        consumer — the rollback correctness invariant."""
        offset = [0]
        pf = BatchPrefetcher(lambda s: (s, offset[0]), depth=3, start_step=1)
        try:
            assert pf.get(1) == (1, 0)
            # Simulate the rollback protocol: mutate state, THEN reseek.
            offset[0] = 42
            pf.reseek(2)
            for step in (2, 3, 4):
                assert pf.get(step) == (step, 42)
        finally:
            pf.close()

    def test_error_surfaces_after_good_batches(self):
        """An assembly failure at step N must not mask batches for steps
        < N already queued: the run fails at the same step the synchronous
        path would have failed at."""
        boom = RuntimeError("bad fetch")

        def assemble(s):
            if s == 3:
                raise boom
            return s

        pf = BatchPrefetcher(assemble, depth=4, start_step=1)
        try:
            assert pf.get(1) == 1
            assert pf.get(2) == 2
            with pytest.raises(RuntimeError, match="bad fetch") as exc_info:
                pf.get(3)
            assert exc_info.value is boom  # original object, not a wrapper
        finally:
            pf.close()

    def test_reseek_revives_a_producer_killed_by_a_stale_error(self):
        """An assembly failure during look-ahead belongs to the generation
        a rollback just invalidated: reseek must clear it and restart the
        producer, so the replay runs exactly as the synchronous path
        (which would re-assemble the window and succeed) would."""
        fail_step = [3]

        def assemble(s):
            if s == fail_step[0]:
                raise RuntimeError("transient pre-rollback failure")
            return s

        pf = BatchPrefetcher(assemble, depth=2, start_step=1)
        try:
            assert pf.get(1) == 1
            assert pf.get(2) == 2
            # Rollback protocol: mutate state (here: the failure is gone,
            # as a re-assembly under the advanced offset would be), reseek.
            fail_step[0] = -1
            pf.reseek(2)
            for step in (2, 3, 4):
                assert pf.get(step) == step
        finally:
            pf.close()

    def test_close_with_full_queue_unblocks_producer(self):
        pf = BatchPrefetcher(lambda s: s, depth=1, start_step=1)
        time.sleep(0.2)  # let the producer fill the queue and block in put
        pf.close()
        assert pf.closed
        assert _no_live_prefetch_threads()
        with pytest.raises(PrefetcherClosedError):
            pf.get(1)

    def test_close_abandons_a_wedged_assembly(self):
        """A producer blocked inside a hung fetch cannot be joined; close
        must return within its bound instead of deadlocking the exit."""
        release = threading.Event()

        def assemble(s):
            if s >= 2:
                release.wait()
            return s

        pf = BatchPrefetcher(assemble, depth=2, start_step=1)
        try:
            assert pf.get(1) == 1
            start = time.monotonic()
            pf.close(timeout=0.3)
            assert time.monotonic() - start < 5.0
        finally:
            release.set()  # let the abandoned daemon thread die


# --------------------------------------------------------------------------
# bitwise determinism: prefetch on vs off
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sync_baseline(tmp_path_factory):
    """One synchronous (depth 0) full run: the reference trajectory every
    prefetch variant must reproduce bit for bit."""
    initialize_registries()
    tmp = tmp_path_factory.mktemp("sync_base")
    tracker = RecordingTracker()
    res = Trainer(_cfg(tmp, prefetch_depth=0), None, tracker, None).fit()
    return res, tracker


class TestBitwiseDeterminism:
    def test_prefetch_matches_synchronous_path(self, tmp_path, sync_baseline):
        sync_res, sync_tracker = sync_baseline
        tracker = RecordingTracker()
        res = Trainer(_cfg(tmp_path, prefetch_depth=2), None, tracker, None).fit()
        assert res.final_loss == sync_res.final_loss  # bitwise, no tolerance
        assert res.first_step_loss == sync_res.first_step_loss
        assert tracker.series("train/loss") == sync_tracker.series("train/loss")
        assert _no_live_prefetch_threads()

    def test_deep_queue_matches_too(self, tmp_path, sync_baseline):
        """Depth only bounds look-ahead memory; any depth is the same run."""
        sync_res, sync_tracker = sync_baseline
        tracker = RecordingTracker()
        res = Trainer(_cfg(tmp_path, prefetch_depth=6), None, tracker, None).fit()
        assert res.final_loss == sync_res.final_loss
        assert tracker.series("train/loss") == sync_tracker.series("train/loss")

    def test_host_overlap_metrics_are_logged(self, tmp_path):
        tracker = RecordingTracker()
        Trainer(_cfg(tmp_path, prefetch_depth=2), None, tracker, None).fit()
        waits = tracker.series("train/data_wait_ms")
        dispatch = tracker.series("train/host_dispatch_ms")
        assert waits and dispatch  # logged at every boundary
        assert all(v >= 0.0 for _, v in waits)
        assert all(v >= 0.0 for _, v in dispatch)

    def test_eval_pool_is_released_when_fit_returns(self, tmp_path):
        cfg = _cfg(tmp_path, trainer={"eval_every_steps": 4})
        trainer = Trainer(cfg, None, NullTracker(), None)
        trainer.fit()
        assert trainer._eval_pool is None
        assert not any(
            t.name.startswith("eval-data") and t.is_alive()
            for t in threading.enumerate()
        )

    def test_resume_mid_run_matches_uninterrupted(self, tmp_path, sync_baseline):
        """Stop a prefetching run at the step-5 checkpoint, resume with
        prefetching to 12: final loss and all fully-aligned log intervals
        equal the uninterrupted synchronous run."""
        sync_res, sync_tracker = sync_baseline
        run_dir = tmp_path / "part"
        (run_dir / "checkpoints").mkdir(parents=True)
        # max_steps_override, not a max_steps=5 config: dummy_text sizes
        # its dataset from trainer.max_steps, and the partial run must
        # sample the SAME data stream as the full one.
        part = Trainer(_cfg(tmp_path), run_dir, NullTracker(), None).fit(
            max_steps_override=5
        )
        assert part.final_step == 5
        tracker = RecordingTracker()
        res = Trainer(_cfg(tmp_path), None, tracker, None).fit(
            resume_from=str(run_dir / "checkpoints")
        )
        assert res.resumed_from_step == 5
        assert res.final_loss == sync_res.final_loss
        # Boundary 6 covers steps 5-6 in the full run but only step 6 in
        # the resumed one (different interval mean); 8/10/12 align exactly.
        full = dict(sync_tracker.series("train/loss"))
        resumed = dict(tracker.series("train/loss"))
        for boundary in (8, 10, 12):
            assert resumed[boundary] == full[boundary]

    def test_resume_with_different_prefetch_depth_matches(
        self, tmp_path, sync_baseline
    ):
        """The saving run's prefetch_depth is a pure performance knob: a
        checkpoint saved with depth 2 must resume bitwise-identically under
        depth 0 (prefetch on→off) and a different nonzero depth. The
        manifest records the saving depth (crash-consistency layer), and
        resume must treat the difference as a non-event."""
        from llmtrain_tpu.training.checkpoint import read_manifest

        sync_res, sync_tracker = sync_baseline
        run_dir = tmp_path / "saved_d2"
        (run_dir / "checkpoints").mkdir(parents=True)
        part = Trainer(_cfg(tmp_path, prefetch_depth=2), run_dir, NullTracker(), None).fit(
            max_steps_override=5
        )
        assert part.final_step == 5
        manifest = read_manifest(run_dir / "checkpoints" / "step_000005.ckpt")
        assert manifest["data"]["prefetch_depth"] == 2

        full = dict(sync_tracker.series("train/loss"))
        for depth in (0, 3):
            tracker = RecordingTracker()
            res = Trainer(
                _cfg(tmp_path, prefetch_depth=depth), None, tracker, None
            ).fit(resume_from=str(run_dir / "checkpoints"))
            assert res.resumed_from_step == 5
            assert res.final_loss == sync_res.final_loss  # bitwise
            resumed = dict(tracker.series("train/loss"))
            # Boundary 6 straddles the resume point (partial interval);
            # the fully-aligned intervals must match bit for bit.
            for boundary in (8, 10, 12):
                assert resumed[boundary] == full[boundary]

    def test_resume_off_to_on_matches(self, tmp_path, sync_baseline):
        """The mirror direction: saved synchronously, resumed prefetching."""
        sync_res, _ = sync_baseline
        run_dir = tmp_path / "saved_d0"
        (run_dir / "checkpoints").mkdir(parents=True)
        Trainer(_cfg(tmp_path, prefetch_depth=0), run_dir, NullTracker(), None).fit(
            max_steps_override=5
        )
        res = Trainer(_cfg(tmp_path, prefetch_depth=2), None, RecordingTracker(), None).fit(
            resume_from=str(run_dir / "checkpoints")
        )
        assert res.resumed_from_step == 5
        assert res.final_loss == sync_res.final_loss  # bitwise

    def test_spike_rollback_replay_matches_synchronous(self, tmp_path):
        """An injected spike rolls both variants back to the step-5
        checkpoint; the replayed window (advanced data offset, rollback-
        folded RNG) must be identical with prefetch on vs off."""

        def run(depth, sub):
            run_dir = tmp_path / sub
            (run_dir / "checkpoints").mkdir(parents=True)
            tracker = RecordingTracker()
            cfg = _cfg(
                tmp_path,
                prefetch_depth=depth,
                resilience={
                    "spike_detection": True,
                    "spike_factor": 4.0,
                    "spike_min_history": 4,
                    "max_rollbacks": 2,
                    "faults": {"spike_loss_at_step": 8, "spike_loss_scale": 100.0},
                },
            )
            res = Trainer(cfg, run_dir, tracker, None).fit()
            return res, tracker

        sync_res, sync_tracker = run(0, "sync")
        pf_res, pf_tracker = run(2, "prefetch")
        assert sync_res.rollbacks == pf_res.rollbacks == 1
        assert pf_res.final_loss == sync_res.final_loss
        assert pf_res.final_step == sync_res.final_step == 12
        assert pf_tracker.series("train/loss") == sync_tracker.series("train/loss")


# --------------------------------------------------------------------------
# shutdown: SIGTERM preemption with a full queue
# --------------------------------------------------------------------------


class _SigtermAtFirstInterval(NullTracker):
    """First log boundary delivers SIGTERM on the training thread — the
    deterministic in-process preemption trigger (tests/test_preemption.py)."""

    def __init__(self):
        self.fired = False

    def log_metrics(self, metrics, step=None):
        if not self.fired and step and step >= 1:
            self.fired = True
            os.kill(os.getpid(), signal.SIGTERM)


class TestPreemptionShutdown:
    def test_sigterm_with_full_queue_stops_cleanly(self, tmp_path):
        """At the preemption break the producer holds a full queue; fit
        must still save, return, and leave no live prefetch thread."""
        cfg = _cfg(
            tmp_path, prefetch_depth=4, trainer={"max_steps": 4000}
        )
        run_dir = tmp_path / "preempt"
        (run_dir / "checkpoints").mkdir(parents=True)
        before = signal.getsignal(signal.SIGTERM)
        res = Trainer(cfg, run_dir, _SigtermAtFirstInterval(), None).fit()
        assert res.preempted is True
        assert 0 < res.final_step < cfg.trainer.max_steps
        assert np.isfinite(res.final_loss)
        ckpt = run_dir / "checkpoints" / f"step_{res.final_step:06d}.ckpt"
        assert ckpt.exists()
        assert _no_live_prefetch_threads()
        assert signal.getsignal(signal.SIGTERM) == before


# --------------------------------------------------------------------------
# watchdog catches a hang inside the prefetch thread (e2e subprocess)
# --------------------------------------------------------------------------


def _cli_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    return env


class TestWatchdogCatchesPrefetcherHang:
    def test_hang_in_prefetcher_exits_retryable_with_report(self, tmp_path):
        """A wedged prefetch thread starves the consumer on the queue: no
        step dispatches, the beacon stalls, and the armed watchdog must
        end the run exactly as it would for a host-loop hang — retryable
        exit, all-thread stack report naming the blocked prefetch thread."""
        raw = _cfg().model_dump()
        raw["output"] = {"root_dir": "runs"}
        raw["resilience"] = {
            **raw["resilience"],
            "watchdog": {
                "enabled": True,
                "stall_timeout_sec": 0.8,
                "heartbeat_interval_sec": 0.0,
            },
            "faults": {"hang_at_step": 3, "hang_in_prefetcher": True},
        }
        (tmp_path / "pfhang.yaml").write_text(yaml.safe_dump(raw))
        proc = subprocess.run(
            [sys.executable, "-m", "llmtrain_tpu", "train", "--config",
             "pfhang.yaml", "--run-id", "pfhang"],
            capture_output=True,
            text=True,
            cwd=tmp_path,
            env=_cli_env(),
            timeout=420,
        )
        assert proc.returncode == EXIT_HANG_DETECTED, (
            f"expected exit {EXIT_HANG_DETECTED}, got {proc.returncode}\n"
            f"stdout: {proc.stdout}\nstderr: {proc.stderr}"
        )
        reports = list((tmp_path / "runs" / "pfhang").glob("hang_report_*.txt"))
        assert len(reports) == 1, proc.stderr
        text = reports[0].read_text()
        assert "batch-prefetch" in text  # the wedged producer's stack
        assert "maybe_hang" in text  # ... at the actual stall site
        assert "MainThread" in text  # the starved consumer's stack
        assert "HANG DETECTED" in proc.stderr


# --------------------------------------------------------------------------
# persistent compilation cache: dir resolution precedence
# --------------------------------------------------------------------------


class TestCompilationCacheResolution:
    """Precedence only; the set-nothing-in-code rule and the fixed
    in-checkout default are pinned in tests/test_platform_rules.py."""

    def test_jax_env_beats_config_beats_default(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/env")
        assert resolve_compilation_cache_dir("/from/config") == "/from/env"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert resolve_compilation_cache_dir("/from/config") == "/from/config"
        default = resolve_compilation_cache_dir(None)
        assert default.endswith(os.path.join(".cache", "jax"))

    def test_retired_private_env_var_is_ignored(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("LLMTRAIN_COMPILATION_CACHE", "/from/private")
        assert resolve_compilation_cache_dir("/from/config") == "/from/config"

    def test_empty_env_counts_as_unset(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
        assert resolve_compilation_cache_dir("/from/config") == "/from/config"

    def test_run_section_accepts_cache_dir(self):
        cfg = _cfg(run={"compilation_cache_dir": "/tmp/jaxcache"})
        assert cfg.run.compilation_cache_dir == "/tmp/jaxcache"


class TestConfigSchema:
    def test_prefetch_depth_default_and_bounds(self):
        assert _cfg().trainer.prefetch_depth == 2
        assert _cfg(prefetch_depth=0).trainer.prefetch_depth == 0
        with pytest.raises(Exception):
            _cfg(prefetch_depth=-1)

"""SLO-aware overload control (serving/overload.py + its wiring).

Tier-1 keeps to pure units — token buckets, the EWMA wait estimator, the
weighted-class queue, brownout hysteresis, the retry budget, the
per-client gate, admission verdicts — plus scheduler integration over a
FakeEngine (real PagedKVPool accounting, no jax compiles) and the HTTP /
router rejection surfaces. The seeded 10x-burst acceptance drill
(parity, shedding, brownout entry AND exit, exact pool accounting)
compiles a model and runs under ``@pytest.mark.slow`` via
``make verify-overload``.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from llmtrain_tpu.serving.overload import (
    REASON_DEADLINE_EXCEEDED,
    REASON_DEADLINE_UNMEETABLE,
    REASON_QUEUE_FULL,
    REASON_RATE_LIMITED,
    REASON_RETRY_BUDGET,
    REJECT_REASONS,
    Brownout,
    ClientRateGate,
    EwmaWaitEstimator,
    OverloadController,
    RetryBudget,
    TokenBucket,
    WeightedClassQueue,
    rejected_counter,
)
from llmtrain_tpu.serving.paged_kv import PagedKVPool
from llmtrain_tpu.serving.scheduler import (
    ContinuousBatchingScheduler,
    ServeRequest,
)


class FakeClock:
    def __init__(self, t: float = 100.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _req(prompt: int = 4, max_new: int = 4, **kw) -> ServeRequest:
    return ServeRequest(
        prompt_ids=(np.arange(prompt, dtype=np.int32) % 32),
        max_new_tokens=max_new,
        **kw,
    )


# ---------------------------------------------------------------------------
# token bucket
# ---------------------------------------------------------------------------


class TestTokenBucket:
    def test_burst_then_deny_then_refill(self):
        clock = FakeClock()
        b = TokenBucket(2.0, 3, clock=clock)
        assert all(b.try_acquire() for _ in range(3))
        assert not b.try_acquire()
        clock.advance(0.5)  # 2/s * 0.5s = 1 token back
        assert b.try_acquire()
        assert not b.try_acquire()

    def test_refill_caps_at_burst(self):
        clock = FakeClock()
        b = TokenBucket(100.0, 2, clock=clock)
        clock.advance(60.0)
        assert b.try_acquire() and b.try_acquire()
        assert not b.try_acquire()

    def test_retry_after_hint(self):
        clock = FakeClock()
        b = TokenBucket(2.0, 1, clock=clock)
        assert b.retry_after() == 0.0
        assert b.try_acquire()
        # 1 token at 2/s = 0.5s away.
        assert b.retry_after() == pytest.approx(0.5)

    def test_validation(self):
        with pytest.raises(ValueError, match="rate"):
            TokenBucket(0.0, 1)
        with pytest.raises(ValueError, match="burst"):
            TokenBucket(1.0, 0)


# ---------------------------------------------------------------------------
# EWMA wait estimator
# ---------------------------------------------------------------------------


class TestEwmaWaitEstimator:
    def test_prior_seeds_prediction(self):
        est = EwmaWaitEstimator(beta=0.8, prior_ms=40.0)
        assert est.predicted_wait_ms(0) == pytest.approx(40.0)
        assert est.predicted_wait_ms(3) == pytest.approx(160.0)

    def test_observation_moves_per_slot(self):
        est = EwmaWaitEstimator(beta=0.5, prior_ms=0.0)
        # wait 100ms at depth 1 -> per-slot sample 50ms, EWMA 25ms.
        est.observe(100.0, 1)
        assert est.per_slot_ms == pytest.approx(25.0)
        assert est.samples == 1

    def test_converges_to_steady_state(self):
        est = EwmaWaitEstimator(beta=0.5, prior_ms=1000.0)
        for _ in range(30):
            est.observe(10.0, 0)
        assert est.per_slot_ms == pytest.approx(10.0, rel=1e-3)

    def test_bad_beta_rejected(self):
        for beta in (0.0, 1.0, -1.0):
            with pytest.raises(ValueError, match="beta"):
                EwmaWaitEstimator(beta=beta)


# ---------------------------------------------------------------------------
# weighted-class queue
# ---------------------------------------------------------------------------


def _wcq() -> WeightedClassQueue:
    return WeightedClassQueue({"interactive": 4, "batch": 1}, "interactive")


class TestWeightedClassQueue:
    def test_wrr_drains_four_to_one(self):
        q = _wcq()
        for i in range(8):
            q.append(_req(priority="interactive", seed=i))
        for i in range(8):
            q.append(_req(priority="batch", seed=100 + i))
        first_five = [q.popleft().priority for _ in range(5)]
        assert first_five.count("interactive") == 4
        assert first_five.count("batch") == 1

    def test_no_class_starves(self):
        # Batch-only backlog: every WRR cycle visits every class, so the
        # weight-1 class drains even with zero interactive traffic.
        q = _wcq()
        for i in range(3):
            q.append(_req(priority="batch", seed=i))
        assert [q.popleft().seed for _ in range(3)] == [0, 1, 2]
        with pytest.raises(IndexError):
            q.popleft()

    def test_appendleft_goes_to_own_class_head(self):
        q = _wcq()
        a, b = _req(priority="batch", seed=1), _req(priority="batch", seed=2)
        q.append(a)
        q.appendleft(b)  # the pool-full retry path
        assert q.popleft() is b

    def test_unknown_priority_falls_back_to_default(self):
        q = _wcq()
        q.append(_req(priority="platinum"))
        assert q.depths() == {"interactive": 1, "batch": 0}

    def test_sweep_removes_matches_keeps_order(self):
        q = _wcq()
        reqs = [_req(priority="interactive", seed=i) for i in range(4)]
        for r in reqs:
            q.append(r)
        out = q.sweep(lambda r: r.seed % 2 == 0)
        assert [r.seed for r in out] == [0, 2]
        assert [q.popleft().seed for _ in range(2)] == [1, 3]

    def test_len_bool_iter(self):
        q = _wcq()
        assert not q and len(q) == 0
        q.append(_req(priority="batch"))
        q.append(_req(priority="interactive"))
        assert q and len(q) == 2
        assert len(list(iter(q))) == 2

    def test_validation(self):
        with pytest.raises(ValueError, match="at least one class"):
            WeightedClassQueue({}, "interactive")
        with pytest.raises(ValueError, match="default class"):
            WeightedClassQueue({"a": 1}, "b")
        with pytest.raises(ValueError, match="weight"):
            WeightedClassQueue({"a": 0}, "a")


# ---------------------------------------------------------------------------
# brownout hysteresis
# ---------------------------------------------------------------------------


class TestBrownout:
    def test_enters_after_consecutive_high_ticks_only(self):
        b = Brownout(high_ms=100.0, low_ms=20.0, enter_ticks=3, exit_ticks=2)
        assert b.tick(150.0) is None
        assert b.tick(150.0) is None
        assert b.tick(50.0) is None  # dip resets the streak
        assert b.tick(150.0) is None
        assert b.tick(150.0) is None
        assert b.tick(150.0) == "entered"
        assert b.active and b.entries == 1

    def test_no_flap_between_watermarks(self):
        b = Brownout(high_ms=100.0, low_ms=20.0, enter_ticks=1, exit_ticks=1)
        assert b.tick(100.0) == "entered"
        # Pressure fell below HIGH but not below LOW: still browned out.
        for _ in range(10):
            assert b.tick(50.0) is None
        assert b.active

    def test_exits_after_consecutive_low_ticks(self):
        b = Brownout(high_ms=100.0, low_ms=20.0, enter_ticks=1, exit_ticks=2)
        assert b.tick(200.0) == "entered"
        assert b.tick(10.0) is None
        assert b.tick(30.0) is None  # bounce resets the exit streak
        assert b.tick(10.0) is None
        assert b.tick(10.0) == "exited"
        assert not b.active and b.exits == 1

    def test_watermark_validation(self):
        with pytest.raises(ValueError, match="watermark"):
            Brownout(high_ms=100.0, low_ms=100.0)


# ---------------------------------------------------------------------------
# retry budget + per-client gate
# ---------------------------------------------------------------------------


class TestRetryBudget:
    def test_spends_then_denies_then_window_resets(self):
        clock = FakeClock()
        rb = RetryBudget(2, 10.0, clock=clock)
        assert rb.try_spend() and rb.try_spend()
        assert not rb.try_spend()
        assert rb.remaining() == 0
        clock.advance(10.0)
        assert rb.remaining() == 2
        assert rb.try_spend()

    def test_validation(self):
        with pytest.raises(ValueError, match="budget"):
            RetryBudget(-1, 1.0)
        with pytest.raises(ValueError, match="window"):
            RetryBudget(1, 0.0)


class TestClientRateGate:
    def test_clients_are_isolated(self):
        clock = FakeClock()
        gate = ClientRateGate(1.0, 1, clock=clock)
        assert gate.check("alice") is None
        assert gate.check("alice") is not None  # burst spent
        assert gate.check("bob") is None  # own bucket

    def test_retry_after_hint_positive(self):
        clock = FakeClock()
        gate = ClientRateGate(2.0, 1, clock=clock)
        assert gate.check("c") is None
        assert gate.check("c") == pytest.approx(0.5)

    def test_lru_cap_bounds_cardinality(self):
        clock = FakeClock()
        gate = ClientRateGate(0.001, 1, max_clients=2, clock=clock)
        assert gate.check("a") is None
        assert gate.check("b") is None
        assert gate.check("c") is None  # evicts "a"
        # "a" comes back with a FRESH burst: its old spent bucket is gone.
        assert gate.check("a") is None


# ---------------------------------------------------------------------------
# controller: admission verdicts, shedding, brownout plumbing
# ---------------------------------------------------------------------------


class TestOverloadController:
    def test_admits_in_calm_seas(self):
        ov = OverloadController(queue_cap=4)
        assert ov.admission_check(_req(), depth=0) is None

    def test_queue_full_rejects_with_retry_after(self):
        ov = OverloadController(queue_cap=4, prior_wait_ms=100.0)
        verdict = ov.admission_check(_req(), depth=4)
        assert verdict is not None
        reason, retry_after = verdict
        assert reason == REASON_QUEUE_FULL
        assert retry_after > 0

    def test_class_bucket_rate_limits(self):
        clock = FakeClock()
        ov = OverloadController(
            queue_cap=64,
            class_rate_rps={"batch": 1.0},
            class_burst={"batch": 1},
            clock=clock,
        )
        assert ov.admission_check(_req(priority="batch"), depth=0) is None
        verdict = ov.admission_check(_req(priority="batch"), depth=0)
        assert verdict is not None and verdict[0] == REASON_RATE_LIMITED
        # The interactive class has no bucket: never rate-limited.
        assert ov.admission_check(_req(priority="interactive"), depth=0) is None

    def test_deadline_unmeetable_rejects_at_submit(self):
        ov = OverloadController(queue_cap=64, prior_wait_ms=1000.0)
        verdict = ov.admission_check(_req(deadline_ms=10.0), depth=0)
        assert verdict is not None
        assert verdict[0] == REASON_DEADLINE_UNMEETABLE
        # No deadline = no deadline check, whatever the predicted wait.
        assert ov.admission_check(_req(), depth=0) is None

    def test_unknown_rate_class_rejected_at_build(self):
        with pytest.raises(ValueError, match="unknown class"):
            OverloadController(class_rate_rps={"platinum": 1.0})

    def test_shedding_requires_sustained_pressure(self):
        ov = OverloadController(
            prior_wait_ms=5.0, brownout_high_ms=100.0, brownout_low_ms=10.0
        )
        ov.tick(0)
        assert not ov.shedding_active  # calm seas: late requests still serve
        ov.tick(50)  # predicted 5 * 51 = 255ms >= high watermark
        assert ov.shedding_active

    def test_past_deadline(self):
        clock = FakeClock()
        ov = OverloadController(clock=clock)
        req = _req(deadline_ms=100.0)
        req.submitted_t = clock()
        assert not ov.past_deadline(req)
        clock.advance(0.2)
        assert ov.past_deadline(req)
        assert not ov.past_deadline(_req())  # deadline-less never expires

    def test_brownout_clamp_only_while_active(self):
        ov = OverloadController(
            prior_wait_ms=500.0,
            brownout_high_ms=100.0,
            brownout_low_ms=10.0,
            brownout_enter_ticks=1,
            brownout_max_new_tokens=8,
        )
        assert ov.clamp_new_tokens(64) == 64
        assert ov.tick(0) == "entered"
        assert ov.clamp_new_tokens(64) == 8
        assert ov.clamp_new_tokens(4) == 4

    def test_from_config_and_overrides(self):
        from llmtrain_tpu.config.schemas import OverloadConfig

        cfg = OverloadConfig(
            queue_cap=7,
            default_deadline_ms=1234.0,
            classes={"interactive": 3, "batch": 2},
            class_rate_rps={"batch": 5.0},
            brownout_high_ms=300.0,
            brownout_low_ms=30.0,
        )
        clock = FakeClock()
        ov = OverloadController.from_config(cfg, clock=clock)
        assert ov.queue_cap == 7
        assert ov.default_deadline_ms == 1234.0
        assert ov.class_weights == {"interactive": 3, "batch": 2}
        assert set(ov.buckets) == {"batch"}
        assert ov.brownout.high_ms == 300.0
        assert ov._clock is clock

    def test_stats_shape(self):
        ov = OverloadController(queue_cap=9)
        ov.note_rejection(REASON_QUEUE_FULL)
        ov.note_rejection(REASON_DEADLINE_EXCEEDED, shed=True)
        s = ov.stats()
        assert s["queue_cap"] == 9
        assert s["rejected"] == {
            REASON_QUEUE_FULL: 1,
            REASON_DEADLINE_EXCEEDED: 1,
        }
        assert s["rejected_total"] == 2
        assert s["shed"] == 1
        assert s["in_brownout"] is False
        assert set(s["queue_depths"]) == {"interactive", "batch"}


# ---------------------------------------------------------------------------
# labeled rejection counters -> one Prometheus family
# ---------------------------------------------------------------------------


class TestRejectedCounterRendering:
    def test_reasons_share_one_counter_family(self):
        from llmtrain_tpu.telemetry.prometheus import render_prometheus
        from llmtrain_tpu.telemetry.registry import MetricsRegistry

        reg = MetricsRegistry(None)
        reg.inc(rejected_counter(REASON_QUEUE_FULL), 3)
        reg.inc(rejected_counter(REASON_RATE_LIMITED))
        text = render_prometheus(reg.latest(), reg.counters())
        assert 'llmtrain_serve_rejected_total{reason="queue_full"} 3.0' in text
        assert 'llmtrain_serve_rejected_total{reason="rate_limited"} 1.0' in text
        assert (
            text.count("# TYPE llmtrain_serve_rejected_total counter") == 1
        )


# ---------------------------------------------------------------------------
# scheduler integration: FakeEngine over a REAL PagedKVPool
# ---------------------------------------------------------------------------


class FakeEngine:
    """Duck-types PagedDecodeEngine's scheduler surface with real pool
    accounting and deterministic token emission — overload-control paths
    (admission, shedding, clamping, chunked-prefill teardown) exercise
    without compiling anything."""

    def __init__(
        self,
        *,
        num_blocks: int = 64,
        block_tokens: int = 4,
        max_batch_slots: int = 4,
        prefill_chunk: int = 0,
        prefix_cache: bool = False,
    ) -> None:
        self.pool = PagedKVPool(
            num_blocks, block_tokens, prefix_cache=prefix_cache
        )
        self.prefill_chunk = prefill_chunk
        self.max_batch_slots = max_batch_slots
        self.max_blocks_per_seq = num_blocks
        self.cache_epoch = 0
        self.params = {"epoch": 0}

    def set_params(self, params) -> None:
        self.params = params

    def validate_request(self, prompt_len: int, max_new: int) -> str | None:
        return None

    def prefill(self, slab, table, *, seed, temperature, top_k, top_p,
                offset, params):
        return int(slab[-1])

    def decode(self, rows, *, params):
        return [(int(r["token"]) + 1) % 97 for r in rows]

    def cow_copy(self, src: int, dst: int) -> None:
        pass

    def compile_stats(self) -> dict:
        return {"within_budget": True}


class FakeTimeline:
    def __init__(self) -> None:
        self.instants: list[tuple[str, dict]] = []

    def instant(self, name: str, **kw) -> None:
        self.instants.append((name, kw))

    def record(self, name: str, **kw) -> None:
        pass

    def span(self, name: str, **kw):
        from contextlib import nullcontext

        return nullcontext(kw)  # EventTimeline.span yields the span's args


def _drain(sched: ContinuousBatchingScheduler, steps: int = 50) -> None:
    for _ in range(steps):
        if not sched.step():
            break


class TestSchedulerOverloadIntegration:
    def test_submit_rejects_synchronously_when_queue_full(self):
        from llmtrain_tpu.telemetry.registry import MetricsRegistry

        reg = MetricsRegistry(None)
        tl = FakeTimeline()
        ov = OverloadController(queue_cap=2)
        sched = ContinuousBatchingScheduler(
            FakeEngine(), overload=ov, registry=reg, timeline=tl
        )
        a, b = sched.submit(_req()), sched.submit(_req())
        c = sched.submit(_req(rid="req-c"))
        assert not a.done.is_set() and not b.done.is_set()
        assert c.done.is_set()
        assert c.finish_reason == "rejected"
        assert c.reject_reason == REASON_QUEUE_FULL
        assert c.retry_after_sec and c.retry_after_sec > 0
        assert reg.counters()[rejected_counter(REASON_QUEUE_FULL)] == 1.0
        name, kw = tl.instants[-1]
        assert name == "serve/rejected"
        assert kw["reason"] == REASON_QUEUE_FULL and kw["rid"] == "req-c"
        # The queued pair still completes: rejection never wedges admission.
        _drain(sched)
        assert a.finish_reason == "length" and b.finish_reason == "length"

    def test_tracer_failure_is_best_effort_not_a_hang(self):
        # _finish_trace runs on the completion path BEFORE req.done.set();
        # a tracer/timeline failure (full disk, broken adapter) must be
        # swallowed, never leaving the waiter hanging or killing the loop.
        from llmtrain_tpu.telemetry.tracing import TailSampler, Tracer

        class BoomTimeline(FakeTimeline):
            def record(self, name: str, **kw) -> None:
                if kw.get("cat") == "trace":  # the tracer's flush records
                    raise OSError("disk full")

            def flush(self) -> None:
                raise OSError("disk full")

        tl = BoomTimeline()
        sched = ContinuousBatchingScheduler(
            FakeEngine(),
            timeline=tl,
            tracer=Tracer(tl, sampler=TailSampler(warmup=16)),
        )
        r = sched.submit(_req(prompt=5, max_new=3))
        _drain(sched)
        assert r.done.is_set()
        assert r.finish_reason == "length" and len(r.tokens) == 3

    def test_submit_rejects_unmeetable_deadline(self):
        ov = OverloadController(queue_cap=64, prior_wait_ms=1000.0)
        sched = ContinuousBatchingScheduler(FakeEngine(), overload=ov)
        r = sched.submit(_req(deadline_ms=5.0))
        assert r.finish_reason == "rejected"
        assert r.reject_reason == REASON_DEADLINE_UNMEETABLE

    def test_default_deadline_is_stamped_at_submit(self):
        ov = OverloadController(queue_cap=64, default_deadline_ms=9000.0)
        sched = ContinuousBatchingScheduler(FakeEngine(), overload=ov)
        r = sched.submit(_req())
        assert r.deadline_ms == 9000.0

    def test_end_to_end_completion_and_exact_pool_release(self):
        ov = OverloadController(queue_cap=8)
        eng = FakeEngine()
        sched = ContinuousBatchingScheduler(eng, overload=ov)
        reqs = [sched.submit(_req(prompt=5, max_new=3)) for _ in range(3)]
        _drain(sched)
        for r in reqs:
            assert r.finish_reason == "length" and len(r.tokens) == 3
        stats = eng.pool.stats()
        assert stats["allocated_blocks"] == 0
        assert stats["reserved_blocks"] == 0
        assert stats["active_sequences"] == 0
        assert sched.stats()["overload"]["rejected_total"] == 0

    def test_eager_shed_past_deadline_under_pressure(self):
        # prior 50ms/slot -> pressure >= high watermark from the first
        # tick at any depth: shedding is ACTIVE.
        ov = OverloadController(
            queue_cap=8, prior_wait_ms=50.0, brownout_high_ms=40.0,
            brownout_low_ms=4.0,
        )
        sched = ContinuousBatchingScheduler(FakeEngine(), overload=ov)
        r = sched.submit(_req(deadline_ms=60.0))
        assert not r.done.is_set()
        time.sleep(0.09)  # now past its deadline while still queued
        sched.step()
        assert r.finish_reason == "shed"
        assert r.reject_reason == REASON_DEADLINE_EXCEEDED
        assert sched.stats()["overload"]["shed"] == 1

    def test_calm_seas_late_request_still_served(self):
        # Same expired deadline, but pressure far below the watermark:
        # no shedding, the request serves.
        ov = OverloadController(
            queue_cap=8, prior_wait_ms=1.0, brownout_high_ms=5000.0,
            brownout_low_ms=500.0,
        )
        sched = ContinuousBatchingScheduler(FakeEngine(), overload=ov)
        r = sched.submit(_req(max_new=2, deadline_ms=20.0))
        time.sleep(0.05)
        _drain(sched)
        assert r.finish_reason == "length" and len(r.tokens) == 2

    def test_brownout_clamps_admissions_then_exits(self):
        tl = FakeTimeline()
        ov = OverloadController(
            queue_cap=8,
            prior_wait_ms=50.0,
            brownout_high_ms=40.0,
            brownout_low_ms=4.0,
            brownout_enter_ticks=1,
            brownout_exit_ticks=1,
            brownout_max_new_tokens=2,
        )
        sched = ContinuousBatchingScheduler(
            FakeEngine(), overload=ov, timeline=tl
        )
        sched.step()  # pressure 50ms >= 40ms for 1 tick -> entered
        assert ov.in_brownout
        assert any(n == "serve/brownout_entered" for n, _ in tl.instants)
        r = sched.submit(_req(max_new=16))
        _drain(sched)
        assert r.finish_reason == "length"
        assert len(r.tokens) == 2  # clamped BEFORE reservation/decode
        # Observed waits collapse -> EWMA decays below the low watermark
        # -> hysteresis exits.
        for _ in range(40):
            ov.observe_queue_wait(0.0, 0)
        sched.step()
        assert not ov.in_brownout
        assert any(n == "serve/brownout_exited" for n, _ in tl.instants)
        s = sched.stats()["overload"]
        assert s["brownout_entries"] == 1 and s["brownout_exits"] == 1

    def test_pool_full_requeues_instead_of_wedging(self):
        # Capacity 4 usable blocks; each request reserves 2 (4+4 tokens,
        # block 4): two admit, the third re-queues and admits as the
        # earlier ones retire. Nothing wedges, nothing leaks.
        ov = OverloadController(queue_cap=8)
        eng = FakeEngine(num_blocks=5, block_tokens=4, max_batch_slots=8)
        sched = ContinuousBatchingScheduler(eng, overload=ov)
        reqs = [sched.submit(_req(prompt=4, max_new=4)) for _ in range(3)]
        _drain(sched)
        assert [r.finish_reason for r in reqs] == ["length"] * 3
        assert eng.pool.stats()["allocated_blocks"] == 0

    def test_shed_mid_chunked_prefill_releases_blocks_and_no_prefix(self):
        # The satellite property: a request shed PART WAY through chunked
        # prefill returns the pool to its pre-admission state and never
        # publishes its partial prefix to the cache.
        ov = OverloadController(queue_cap=8)
        eng = FakeEngine(
            num_blocks=32, block_tokens=4, prefill_chunk=2, prefix_cache=True
        )
        sched = ContinuousBatchingScheduler(eng, overload=ov)
        before = eng.pool.stats()
        assert before["allocated_blocks"] == 0 and before["reserved_blocks"] == 0
        r = sched.submit(_req(prompt=8, max_new=2))
        sched.step()  # admit + stream FIRST chunk only (2 of 8 tokens)
        mid = eng.pool.stats()
        assert mid["active_sequences"] == 1
        assert mid["reserved_blocks"] > 0 and mid["allocated_blocks"] > 0
        assert sched._prefilling and sched._prefilling[0].prefilled < 8
        r.abandon()  # the waiter gave up mid-prefill
        sched.step()
        after = eng.pool.stats()
        assert after["allocated_blocks"] == before["allocated_blocks"]
        assert after["reserved_blocks"] == before["reserved_blocks"]
        assert after["active_sequences"] == 0
        # The partial prompt was NEVER registered: no cached blocks, and
        # a fresh lookup of the same prompt misses outright.
        assert after["prefix_cached_blocks"] == 0
        assert not eng.pool.match_prefix(r.prompt_ids).hit

    def test_predicted_wait_and_brownout_gauges_published(self):
        from llmtrain_tpu.telemetry.registry import MetricsRegistry

        reg = MetricsRegistry(None)
        ov = OverloadController(queue_cap=8)
        sched = ContinuousBatchingScheduler(
            FakeEngine(), overload=ov, registry=reg
        )
        sched.submit(_req(max_new=1))
        _drain(sched)
        latest = reg.latest()
        assert "serve/predicted_wait_ms" in latest
        assert latest["serve/brownout"][0] == 0.0


# ---------------------------------------------------------------------------
# router: retry budget + backpressure rejection
# ---------------------------------------------------------------------------


class _SinkReplica:
    """Always-succeeds fake replica (router-surface duck type)."""

    def __init__(self, name: str = "sink") -> None:
        self.name = name
        self.submitted: list[ServeRequest] = []

    def submit(self, req: ServeRequest) -> None:
        self.submitted.append(req)
        req.finish_reason = "length"
        req.finished_t = time.monotonic()
        req.done.set()

    def load(self) -> float:
        return 0.0

    def stats(self) -> dict:
        return {}

    def close(self) -> None:
        pass


class TestRouterRetryBudget:
    def test_budget_spends_then_rejects_fast(self):
        from llmtrain_tpu.serving.router import ReplicaRouter
        from llmtrain_tpu.telemetry.registry import MetricsRegistry

        reg = MetricsRegistry(None)
        sink = _SinkReplica()
        router = ReplicaRouter(
            [sink], registry=reg, retry_budget=1, retry_window_sec=60.0
        )
        ok = _req()
        router._failover(ok, exclude=set(), cause=RuntimeError("transport"))
        assert ok.finish_reason == "length" and sink.submitted == [ok]
        # Budget (1) spent: the next failover is rejected honestly
        # instead of re-hammering the fleet.
        r2 = _req()
        router._failover(r2, exclude=set(), cause=RuntimeError("transport"))
        assert r2.done.is_set()
        assert r2.finish_reason == "rejected"
        assert r2.reject_reason == REASON_RETRY_BUDGET
        assert r2.retry_after_sec == pytest.approx(60.0)
        assert router.retries_rejected == 1
        assert reg.counters()[rejected_counter(REASON_RETRY_BUDGET)] == 1.0
        s = router.stats()["router"]["overload"]
        assert s["retries_rejected"] == 1
        assert s["retry_budget_remaining"] == 0

    def test_zero_budget_means_unlimited(self):
        from llmtrain_tpu.serving.router import ReplicaRouter

        sink = _SinkReplica()
        router = ReplicaRouter([sink], retry_budget=0)
        for _ in range(5):
            router._failover(_req(), exclude=set(), cause=RuntimeError("x"))
        assert len(sink.submitted) == 5
        assert router.retries_rejected == 0

    def test_backpressure_parse_and_window(self):
        from llmtrain_tpu.serving.router import ReplicaBackpressure

        exc = ReplicaBackpressure("replica0", "queue_full", 2.5)
        assert exc.replica_name == "replica0"
        assert exc.reason == "queue_full"
        assert exc.retry_after == pytest.approx(2.5)


# ---------------------------------------------------------------------------
# HTTP boundary: deadline header, client gate, SLO headers, rid echo
# ---------------------------------------------------------------------------


class _StubModel:
    vocab_size = 64
    block_size = 128


class _RejectingScheduler:
    """Scheduler stub whose admission always says 429."""

    engine = None

    def __init__(self) -> None:
        self.seen: list[ServeRequest] = []

    def submit(self, req: ServeRequest) -> ServeRequest:
        self.seen.append(req)
        req.finish_reason = "rejected"
        req.reject_reason = REASON_QUEUE_FULL
        req.retry_after_sec = 0.25
        req.finished_t = time.monotonic()
        req.done.set()
        return req


def _state(**kw):
    from llmtrain_tpu.serving.http import ServerState

    defaults = dict(
        model=_StubModel(), params=None, tokenizer=None, step=0,
        checkpoint="ckpt",
    )
    defaults.update(kw)
    return ServerState(**defaults)


class TestHTTPOverloadSurface:
    def test_bad_deadline_header_is_400(self):
        from llmtrain_tpu.serving.http import _handle_generate_request

        for bad in ("nope", "-5", "0"):
            code, payload = _handle_generate_request(
                _state(), {"prompt_ids": [1, 2]}, {"X-Deadline-Ms": bad}
            )
            assert code == 400
            assert "X-Deadline-Ms" in payload["error"]

    def test_request_id_echoes_on_errors(self):
        from llmtrain_tpu.serving.http import _handle_generate_request

        code, payload = _handle_generate_request(
            _state(), {}, {"X-Request-Id": "trace-1"}
        )
        assert code == 400
        assert payload["request_id"] == "trace-1"

    def test_client_gate_429_with_retry_after(self):
        from llmtrain_tpu.serving.http import _handle_generate_request
        from llmtrain_tpu.telemetry.registry import MetricsRegistry

        clock = FakeClock()
        reg = MetricsRegistry(None)
        state = _state(
            client_gate=ClientRateGate(0.5, 1, clock=clock), registry=reg
        )
        headers = {"X-Client-Id": "tenant-a", "X-Request-Id": "r-9"}
        code, _ = _handle_generate_request(state, {}, headers)
        assert code == 400  # gate admitted; body validation said no
        code, payload = _handle_generate_request(state, {}, headers)
        assert code == 429
        assert payload["reason"] == REASON_RATE_LIMITED
        assert payload["retry_after"] > 0
        assert payload["request_id"] == "r-9"
        assert reg.counters()[rejected_counter(REASON_RATE_LIMITED)] == 1.0
        # A different tenant is untouched by tenant-a's bucket.
        code, _ = _handle_generate_request(
            state, {}, {"X-Client-Id": "tenant-b"}
        )
        assert code == 400

    def test_scheduler_rejection_maps_to_429_payload(self):
        from llmtrain_tpu.serving.http import _handle_generate_request

        sched = _RejectingScheduler()
        state = _state(scheduler=sched)
        headers = {
            "X-Request-Id": "abc",
            "X-Deadline-Ms": "150",
            "X-Priority": "batch",
        }
        code, payload = _handle_generate_request(
            state, {"prompt_ids": [1, 2, 3]}, headers
        )
        assert code == 429
        assert payload["reason"] == REASON_QUEUE_FULL
        assert payload["finish_reason"] == "rejected"
        assert payload["retry_after"] == pytest.approx(0.25)
        assert payload["request_id"] == "abc"
        # The SLO envelope rode the headers into the ServeRequest.
        req = sched.seen[0]
        assert req.deadline_ms == 150.0
        assert req.priority == "batch"
        assert req.rid == "abc"

    def test_slo_headers_lift(self):
        from llmtrain_tpu.serving.http import _Handler

        out = _Handler._slo_headers(
            429, {"retry_after": 0.2, "request_id": "r1"}
        )
        assert out == {"Retry-After": "1", "X-Request-Id": "r1"}
        assert _Handler._slo_headers(429, {"retry_after": 3.2}) == {
            "Retry-After": "4"
        }
        assert _Handler._slo_headers(503, {"retry_after": 2}) == {
            "Retry-After": "2"
        }
        # 200s never carry Retry-After, whatever the payload says.
        assert _Handler._slo_headers(200, {"retry_after": 9}) == {}


# ---------------------------------------------------------------------------
# the seeded overload acceptance drill (compiles a model)
# ---------------------------------------------------------------------------


def _tiny_stack(vocab=32, block=64):
    import jax
    import jax.numpy as jnp
    from flax.linen import meta as nn_meta

    from llmtrain_tpu.models.gpt import GPT

    model = GPT(
        vocab_size=vocab,
        block_size=block,
        d_model=32,
        n_layers=1,
        n_heads=2,
        d_ff=64,
        dropout=0.0,
        tie_embeddings=True,
    )
    params = nn_meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))[
            "params"
        ]
    )
    return model, params


def _reference(model, params, req: ServeRequest) -> list[int]:
    import jax

    from llmtrain_tpu.generation import generate

    out = generate(
        model,
        params,
        req.prompt_ids[None, :],
        max_new_tokens=req.max_new_tokens,
        temperature=req.temperature,
        eos_token_id=req.eos_token_id,
        rng=jax.random.key(req.seed),
    )
    toks = [int(t) for t in np.asarray(out)[0, req.prompt_ids.shape[0]:]]
    if req.eos_token_id is not None and req.eos_token_id in toks:
        toks = toks[: toks.index(req.eos_token_id) + 1]
    return toks


@pytest.mark.slow
class TestOverloadDrills:
    def test_burst_drill_parity_shedding_and_brownout_hysteresis(self):
        """The acceptance drill: a seeded 10x burst against a 2-replica
        router with bounded admission. Accepted greedy requests stay
        bitwise generate()-exact, rejections are fast and carry the
        documented taxonomy, the scheduler never wedges, brownout enters
        AND exits, and the KV pools account to exactly zero."""
        from llmtrain_tpu.serving import (
            ContinuousBatchingScheduler,
            InProcessReplica,
            PagedDecodeEngine,
            ReplicaRouter,
            build_requests,
            run_loadgen,
        )
        from llmtrain_tpu.telemetry.prometheus import render_prometheus
        from llmtrain_tpu.telemetry.registry import MetricsRegistry

        model, params = _tiny_stack()
        registry = MetricsRegistry(None)
        controllers: list[OverloadController] = []

        def mk(i):
            eng = PagedDecodeEngine(
                model,
                params,
                block_tokens=4,
                max_batch_slots=4,
                prompt_buckets=[8, 16],
                batch_buckets=[2, 4],
                prefix_cache=False,
            )
            ov = OverloadController(
                queue_cap=6,
                prior_wait_ms=5.0,
                brownout_high_ms=40.0,
                brownout_low_ms=8.0,
                brownout_enter_ticks=2,
                brownout_exit_ticks=2,
                brownout_max_new_tokens=4,
            )
            controllers.append(ov)
            sched = ContinuousBatchingScheduler(
                eng, registry=registry, overload=ov
            ).start()
            return InProcessReplica(sched, f"replica{i}")

        router = ReplicaRouter(
            [mk(0), mk(1)],
            registry=registry,
            retry_budget=8,
            retry_window_sec=5.0,
        )
        try:
            reqs = build_requests(
                num_requests=80,
                seed=13,
                vocab_size=32,
                prompt_tokens_min=4,
                prompt_tokens_max=8,
                max_new_tokens=6,
                deadline_ms=2000.0,
                batch_fraction=0.3,
            )
            block = run_loadgen(
                router,
                reqs,
                rate_rps=60.0,
                seed=7,
                timeout_sec=120.0,
                arrival="burst",
                burst_factor=10.0,
            )

            # -- no wedge: every request reached a terminal state.
            rq = block["requests"]
            assert rq["timed_out"] == 0 and rq["failed"] == 0
            assert (
                rq["completed"] + rq["rejected"] + rq["shed"] == len(reqs)
            )
            # -- the burst actually overloaded: fast rejections happened,
            #    every reason is from the documented taxonomy.
            assert rq["rejected"] + rq["shed"] > 0
            ob = block["overload"]
            assert set(ob["rejected_by_reason"]) <= set(REJECT_REASONS)
            assert ob["rejected"] == rq["rejected"]
            assert ob["shed"] == rq["shed"]
            assert ob["controller"] is not None
            # -- submit-time rejections are FAST (the whole point of
            #    admission control); queue-sheds are bounded by deadline
            #    plus one sweep interval.
            for r in reqs:
                if r.finish_reason == "rejected":
                    assert (r.finished_t - r.submitted_t) < 0.5
                elif r.finish_reason == "shed":
                    assert (r.finished_t - r.submitted_t) < 2.0 + 5.0
            # -- accepted requests hold the latency SLO (loose bound:
            #    the drill must bound the tail, not win a benchmark).
            done = [r for r in reqs if r.finish_reason in ("eos", "length")]
            assert done, "the drill must complete some requests"
            lat = sorted(r.latency_ms for r in done)
            assert lat[int(len(lat) * 0.99) - 1] < 30_000.0
            # -- bitwise parity on every ACCEPTED greedy request, on the
            #    post-clamp token budget it actually decoded under.
            for r in done:
                assert r.tokens == _reference(model, params, r), r.request_id
            # -- brownout hysteresis: entered under the burst...
            assert sum(ov.brownout.entries for ov in controllers) >= 1
            # ... and exits once calm traffic drains the EWMA back down.
            # Submit the calm trickle to each replica DIRECTLY: the
            # router's placement penalty steers traffic away from a
            # browned-out replica, which is exactly right in production
            # but would starve it of the small-wait observations its
            # EWMA needs to decay below the exit watermark here.
            calm_deadline = time.monotonic() + 60.0
            while (
                any(ov.brownout.active for ov in controllers)
                and time.monotonic() < calm_deadline
            ):
                for rep, ov in zip(router.replicas, controllers):
                    if not ov.brownout.active:
                        continue
                    trickle = _req(prompt=4, max_new=2)
                    rep.scheduler.submit(trickle)
                    trickle.done.wait(10.0)
            assert not any(ov.brownout.active for ov in controllers)
            assert sum(ov.brownout.exits for ov in controllers) >= 1
            # -- pool accounting is EXACT at drill end: every accepted,
            #    shed, and trickle request returned its blocks.
            for rep in router.replicas:
                pool = rep.scheduler.engine.pool.stats()
                assert pool["allocated_blocks"] == 0
                assert pool["reserved_blocks"] == 0
                assert pool["active_sequences"] == 0
            # -- the decisions are all visible as labeled counters and
            #    gauges on the shared registry.
            text = render_prometheus(registry.latest(), registry.counters())
            assert "llmtrain_serve_rejected_total{reason=" in text
            assert "llmtrain_serve_brownout" in text
            assert "llmtrain_serve_predicted_wait_ms" in text
            assert block["arrival"]["process"] == "burst-open-loop"
            assert block["arrival"]["burst_factor"] == 10.0
        finally:
            router.close()

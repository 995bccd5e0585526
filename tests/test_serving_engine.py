"""Continuous-batching serving subsystem (llmtrain_tpu/serving/).

The contracts docs/serving.md promises, pinned:

* the paged KV pool's free-list/reservation invariants (admission is the
  ONLY place allocation can fail);
* batched paged decode emits token-ids **bitwise identical** to
  sequential single-request ``generate()`` for identical seeds/sampling
  params — greedy AND sampled (per-request temperature/top-k/top-p);
* the decode loop compiles once per shape bucket and the total program
  count stays within the configured budget;
* continuous batching holds >= 2 sequences in flight and retires
  finishers without draining the batch;
* the speculative scheduler policy is token-identical to ``generate()``
  under greedy sampling;
* the seeded open-loop load harness emits the p50/p95/p99 SLO block the
  telemetry report consumes.

Everything runs the tiny GPT (1-2 layers, 32-wide) so the tier-1 gate
stays cheap; the longer soak is ``@pytest.mark.slow`` (make
verify-serving runs it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.linen import meta as nn_meta

from llmtrain_tpu.generation import generate
from llmtrain_tpu.models.gpt import GPT
from llmtrain_tpu.serving import (
    ContinuousBatchingScheduler,
    PagedDecodeEngine,
    PagedKVPool,
    ServeRequest,
    bucket_for,
    build_requests,
    percentiles,
    run_loadgen,
)
from llmtrain_tpu.telemetry.registry import MetricsRegistry

VOCAB = 32
BLOCK = 32


def _unboxed_params(model):
    return nn_meta.unbox(
        model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), deterministic=True
        )["params"]
    )


def _llama(**kw):
    from llmtrain_tpu.models.llama import Llama

    return Llama(vocab_size=VOCAB, block_size=BLOCK, n_layers=1, dropout=0.0, **kw)


# The shapes the paged pool's layout rule branches on (models/gpt.py
# ``paged_block_fold``; a pool row is ``fold`` positions of
# ``kv_heads * head_dim`` values), with the engine's ``block_tokens: 8``:
LAYOUT_MODELS = {
    # 2 heads of 16: a row of 32, four positions fold into 128 lanes.
    "gpt-row32-fold4": lambda: GPT(
        vocab_size=VOCAB, block_size=BLOCK, d_model=32, n_layers=1, n_heads=2,
        d_ff=64, dropout=0.0, tie_embeddings=True,
    ),
    # 5 heads of 40: a row of 200, wider than a lane tile and no multiple of it.
    "gpt-row200": lambda: GPT(
        vocab_size=VOCAB, block_size=BLOCK, d_model=200, n_layers=1, n_heads=5,
        d_ff=256, dropout=0.0, tie_embeddings=True,
    ),
    # Llama family (RoPE rotates before the write), GQA 8 -> 4 heads of 32:
    # a row of exactly 128.
    "llama-gqa-row128": lambda: _llama(d_model=256, n_heads=8, n_kv_heads=4, d_ff=128),
    # GQA 4 -> 2 heads of 32: a row of 64, two positions fold.
    "llama-gqa-row64-fold2": lambda: _llama(
        d_model=128, n_heads=4, n_kv_heads=2, d_ff=128
    ),
    # MQA, one head of 16: a whole block of 8 positions is one 128-lane row.
    "llama-mqa-row16-fold8": lambda: _llama(d_model=64, n_heads=4, n_kv_heads=1, d_ff=128),
}


@pytest.fixture(scope="module", params=list(LAYOUT_MODELS))
def layout_model(request):
    model = LAYOUT_MODELS[request.param]()
    return model, _unboxed_params(model)


@pytest.fixture(scope="module")
def tiny_model():
    # 1 layer: the pool/engine/scheduler logic is layer-count-uniform
    # (per-layer cache vars are created by the same code path), and the
    # tier-1 gate runs this file serially against a tight time budget.
    model = LAYOUT_MODELS["gpt-row32-fold4"]()
    return model, _unboxed_params(model)


def _engine(model, params, **kw):
    defaults = dict(
        block_tokens=8,
        max_batch_slots=4,
        prompt_buckets=[8, 16, BLOCK],
        batch_buckets=[2, 4],
    )
    return PagedDecodeEngine(model, params, **{**defaults, **kw})


def _drain(scheduler, requests, max_steps=500):
    """Run the scheduler loop inline (no thread) until every request is
    done — deterministic, and failures surface as assertions rather than
    a wedged background thread."""
    steps = 0
    while not all(r.done.is_set() for r in requests):
        scheduler.step()
        steps += 1
        assert steps < max_steps, "scheduler failed to finish the batch"
    return steps


def _reference(model, params, req: ServeRequest) -> list[int]:
    """What sequential single-request generate() emits for this request."""
    out = generate(
        model,
        params,
        req.prompt_ids[None, :],
        max_new_tokens=req.max_new_tokens,
        temperature=req.temperature,
        top_k=req.top_k,
        top_p=req.top_p,
        eos_token_id=req.eos_token_id,
        rng=jax.random.key(req.seed),
    )
    ref = [int(t) for t in np.asarray(out)[0, req.prompt_ids.shape[0]:]]
    if req.eos_token_id is not None and req.eos_token_id in ref:
        ref = ref[: ref.index(req.eos_token_id) + 1]
    return ref


class TestPagedKVPool:
    def test_sizing_and_reservation_accounting(self):
        pool = PagedKVPool(num_blocks=9, block_tokens=4)
        assert pool.blocks_needed(1) == 1
        assert pool.blocks_needed(4) == 1
        assert pool.blocks_needed(5) == 2
        assert pool.available_blocks == 8  # block 0 is the null block
        t1 = pool.try_reserve(10)  # 3 blocks
        assert t1 is not None and pool.available_blocks == 5
        t2 = pool.try_reserve(20)  # 5 blocks
        assert t2 is not None and pool.available_blocks == 0
        assert pool.try_reserve(1) is None  # admission is the only "no"
        pool.release(t1)
        assert pool.available_blocks == 3
        pool.release(t2)
        assert pool.available_blocks == 8
        assert pool.allocated_blocks == 0

    def test_grow_is_lazy_and_bounded_by_reservation(self):
        pool = PagedKVPool(num_blocks=9, block_tokens=4)
        table = pool.try_reserve(12)  # 3 blocks reserved
        assert table.allocated == 0  # nothing bound at admission
        pool.grow(table, 4)
        assert table.allocated == 1
        pool.grow(table, 4)  # idempotent
        assert table.allocated == 1
        pool.grow(table, 12)
        assert table.allocated == 3
        with pytest.raises(ValueError, match="admission sizing bug"):
            pool.grow(table, 13)  # beyond the reservation
        assert 0 not in table.blocks  # the null block is never handed out

    def test_release_guards_double_free(self):
        pool = PagedKVPool(num_blocks=5, block_tokens=2)
        table = pool.try_reserve(4)
        pool.grow(table, 4)
        pool.release(table)
        with pytest.raises(ValueError, match="released or foreign"):
            pool.release(table)
        with pytest.raises(ValueError, match="released or foreign"):
            pool.grow(table, 2)

    def test_padded_table_and_stats(self):
        pool = PagedKVPool(num_blocks=9, block_tokens=4)
        table = pool.try_reserve(8)
        pool.grow(table, 8)
        padded = table.padded(4)
        assert len(padded) == 4
        assert padded[2:] == [0, 0]  # null-block padding
        stats = pool.stats()
        assert stats["allocated_blocks"] == 2
        assert stats["reserved_blocks"] == 2
        assert stats["active_sequences"] == 1
        assert 0.0 < stats["utilization"] <= 1.0

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="num_blocks"):
            PagedKVPool(num_blocks=1, block_tokens=4)
        with pytest.raises(ValueError, match="block_tokens"):
            PagedKVPool(num_blocks=4, block_tokens=0)


class TestBuckets:
    def test_bucket_for(self):
        assert bucket_for(1, [2, 4, 8]) == 2
        assert bucket_for(3, [2, 4, 8]) == 4
        assert bucket_for(8, [2, 4, 8]) == 8
        with pytest.raises(ValueError, match="exceeds"):
            bucket_for(9, [2, 4, 8])

    def test_engine_bucket_validation(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="prompt bucket"):
            _engine(model, params, prompt_buckets=[8, 2 * BLOCK])
        with pytest.raises(ValueError, match="must equal"):
            _engine(model, params, batch_buckets=[2, 3])


class TestBatchedParity:
    def test_greedy_bitwise_parity_mixed_lengths(self, layout_model):
        """The acceptance contract: >= 2 sequences concurrently in flight,
        batched output token-ids bitwise identical to sequential
        generate(), compile count within the bucket budget — at every
        shape the pool's layout rule branches on."""
        model, params = layout_model
        engine = _engine(model, params)
        scheduler = ContinuousBatchingScheduler(engine, registry=MetricsRegistry(None))
        rng = np.random.default_rng(7)
        requests = [
            ServeRequest(
                prompt_ids=rng.integers(0, VOCAB, size=tp).astype(np.int32),
                max_new_tokens=mnt,
                seed=int(rng.integers(0, 2**31 - 1)),
            )
            for tp, mnt in ((3, 6), (9, 4), (5, 8))
        ]
        for req in requests:
            scheduler.submit(req)
        _drain(scheduler, requests)

        assert scheduler.peak_occupancy >= 2  # genuinely batched
        for req in requests:
            assert req.finish_reason == "length"
            assert req.tokens == _reference(model, params, req)
        # Finished sequences returned their blocks to the pool.
        stats = engine.pool.stats()
        assert stats["active_sequences"] == 0
        assert stats["allocated_blocks"] == 0
        assert engine.compile_stats()["within_budget"]

    @pytest.mark.slow  # tier-1 pins greedy parity; `make verify-serving`
    # (and the k8s e2e's serve-bench) still run this sampled variant.
    def test_sampled_parity_per_request_knobs(self, tiny_model):
        """Sampled rows replay generate()'s exact per-request recipe even
        when temperature/top-k/top-p DIFFER across the in-flight batch."""
        model, params = tiny_model
        engine = _engine(model, params)
        scheduler = ContinuousBatchingScheduler(engine)
        requests = [
            ServeRequest(
                prompt_ids=np.asarray([1, 2, 3], np.int32),
                max_new_tokens=5,
                temperature=0.8,
                top_k=5,
                seed=11,
            ),
            ServeRequest(
                prompt_ids=np.asarray([4, 5, 6, 7, 8], np.int32),
                max_new_tokens=5,
                temperature=1.3,
                top_p=0.9,
                seed=22,
            ),
            ServeRequest(
                prompt_ids=np.asarray([9, 10], np.int32),
                max_new_tokens=5,
                temperature=0.0,  # greedy row in the same batch
                seed=33,
            ),
        ]
        for req in requests:
            scheduler.submit(req)
        _drain(scheduler, requests)
        assert scheduler.peak_occupancy >= 2
        for req in requests:
            assert req.tokens == _reference(model, params, req), req.request_id

    def test_eos_retires_without_draining_the_batch(self, tiny_model):
        """A finisher leaves per-step while the other sequence keeps
        decoding — continuous batching, not drain-and-refill."""
        model, params = tiny_model
        engine = _engine(model, params)
        scheduler = ContinuousBatchingScheduler(engine)
        short = ServeRequest(
            prompt_ids=np.asarray([1, 2, 3], np.int32), max_new_tokens=2, seed=0
        )
        long = ServeRequest(
            prompt_ids=np.asarray([4, 5, 6], np.int32), max_new_tokens=7, seed=0
        )
        scheduler.submit(short)
        scheduler.submit(long)
        steps = 0
        while not short.done.is_set():
            scheduler.step()
            steps += 1
            assert steps < 50
        # The long request is still mid-flight after the short one retired.
        assert not long.done.is_set()
        assert len(scheduler._active) == 1
        _drain(scheduler, [long])
        assert short.tokens == _reference(model, params, short)
        assert long.tokens == _reference(model, params, long)

    def test_pool_exhaustion_queues_instead_of_evicting(self, tiny_model):
        """Admission control: a request the pool cannot guarantee stays
        queued (FIFO) and joins when a finisher frees its budget."""
        model, params = tiny_model
        # Pool sized for ONE worst-case sequence: 1 null + 2 blocks.
        engine = _engine(
            model, params, num_blocks=3, max_batch_slots=2, batch_buckets=[2]
        )
        scheduler = ContinuousBatchingScheduler(engine)
        a = ServeRequest(
            prompt_ids=np.asarray([1, 2, 3, 4], np.int32),
            max_new_tokens=12,  # reserves ceil(16/8)=2 blocks — whole pool
            seed=0,
        )
        b = ServeRequest(
            prompt_ids=np.asarray([5, 6], np.int32), max_new_tokens=4, seed=0
        )
        scheduler.submit(a)
        scheduler.submit(b)
        scheduler.step()
        assert len(scheduler._active) == 1  # b is queued, not admitted
        assert scheduler.stats()["queue_depth"] == 1
        _drain(scheduler, [a, b])
        assert a.finish_reason == "length" and b.finish_reason == "length"
        assert b.tokens == _reference(model, params, b)

    def test_never_fitting_request_fails_instead_of_wedging_the_queue(
        self, tiny_model
    ):
        """A request this engine can NEVER serve (oversized for the
        context, the prompt buckets, or the whole pool) must fail alone —
        try_reserve can only say 'not yet', so without the
        validate_request guard it would sit at the FIFO head forever and
        starve everything behind it."""
        model, params = tiny_model
        # Pool capacity: 2 blocks = 16 positions total.
        engine = _engine(
            model, params, num_blocks=3, max_batch_slots=2, batch_buckets=[2]
        )
        assert "block_size" in engine.validate_request(4, BLOCK)
        # (the prompt-bucket reason is pinned at the HTTP boundary in
        # tests/test_serving.py — a 400, not a late 500)
        assert "pool" in engine.validate_request(4, 20)  # needs 3 > 2
        assert engine.validate_request(4, 12) is None  # exactly fits
        never = ServeRequest(
            prompt_ids=np.asarray([1, 2, 3, 4], np.int32),
            max_new_tokens=20,  # 24 <= block_size, but needs 3 pool blocks
            seed=0,
        )
        behind = ServeRequest(
            prompt_ids=np.asarray([5, 6], np.int32), max_new_tokens=3, seed=0
        )
        scheduler = ContinuousBatchingScheduler(engine)
        scheduler.submit(never)
        scheduler.submit(behind)
        _drain(scheduler, [never, behind])
        assert never.finish_reason == "error"
        assert "pool" in never.error
        assert behind.finish_reason == "length"  # not starved


class TestPoolLayout:
    """The pool leaf's shape follows from (block_tokens, kv_heads, head_dim)
    alone and every paged path — batched speculative ``verify``, a prefix
    hit that forces a ``cow_copy`` — stays bitwise equal to ``generate()``
    on it."""

    @pytest.mark.parametrize(
        "block_tokens, width, fold",
        [
            (16, 768, 1),  # gpt2-small
            (16, 1600, 1),  # gpt2-xl: 12.5 lane tiles, padded by the tiling
            (16, 128, 1),
            (16, 64, 2),  # MQA, one head of 64
            (8, 32, 4),
            (8, 16, 8),
            (4, 16, 4),  # no fold reaches a lane tile: the whole block is one row
            (6, 48, 3),  # only divisors of block_tokens
        ],
    )
    def test_fold_makes_a_row_lane_dense(self, block_tokens, width, fold):
        from llmtrain_tpu.models.gpt import paged_block_fold

        assert paged_block_fold(block_tokens, width) == fold

    def test_cache_leaves_are_rows_of_folded_positions(self, layout_model):
        from llmtrain_tpu.models.gpt import paged_block_fold

        model, params = layout_model
        engine = _engine(model, params)
        kv_heads = getattr(model, "n_kv_heads", 0) or model.n_heads
        width = kv_heads * (model.d_model // model.n_heads)
        fold = paged_block_fold(8, width)
        leaves = jax.tree.leaves(engine._cache)
        assert len(leaves) == 2  # K and V of the one layer
        for leaf in leaves:
            assert leaf.shape == (engine.pool.num_blocks, 8 // fold, fold * width)
            assert leaf.shape[-1] >= 128

    def test_batched_speculative_verify_parity(self, layout_model):
        """Draft-and-verify through ``engine.verify`` (a multi-token write
        per row, rejected slots overwritten later) on a draft that
        disagrees with the target."""
        model, params = layout_model
        draft_params = jax.tree.map(lambda x: x * 0.5, params)
        kw = dict(
            block_tokens=8, max_batch_slots=2, prompt_buckets=[8], batch_buckets=[1, 2]
        )
        scheduler = ContinuousBatchingScheduler(
            PagedDecodeEngine(model, params, **kw),
            policy="speculative",
            model=model,
            params=params,
            draft_model=model,
            draft_params=draft_params,
            draft_engine=PagedDecodeEngine(model, draft_params, **kw),
            gamma=3,
        )
        requests = [
            ServeRequest(
                prompt_ids=np.arange(i, i + 5, dtype=np.int32), max_new_tokens=9, seed=0
            )
            for i in range(3)
        ]
        for req in requests:
            scheduler.submit(req)
        _drain(scheduler, requests)
        for req in requests:
            assert req.finish_reason == "length", req.error
            assert req.tokens == _reference(model, params, req)
        stats = scheduler.stats()["speculative"]
        assert stats["mode"] == "batched" and stats["rounds"] > 0
        assert scheduler.engine.compile_stats()["verify_programs"] >= 1

    def test_prefix_hit_with_cow_copy_parity(self, layout_model):
        """A prompt that shares one full block and PART of the next with a
        cached one binds both, copies the partial block on write
        (``cow_copy``), and still decodes what ``generate()`` does."""
        model, params = layout_model
        engine = _engine(model, params, prefix_cache=True)
        scheduler = ContinuousBatchingScheduler(engine)
        first = ServeRequest(
            prompt_ids=np.arange(1, 19, dtype=np.int32) % VOCAB, max_new_tokens=4, seed=0
        )
        scheduler.submit(first)
        _drain(scheduler, [first])
        # Same first block (8 tokens), same 5 tokens of the second, then its own.
        second_prompt = np.concatenate(
            [first.prompt_ids[:13], np.asarray([30, 29, 28], np.int32)]
        )
        second = ServeRequest(prompt_ids=second_prompt, max_new_tokens=6, seed=0)
        scheduler.submit(second)
        _drain(scheduler, [second])
        assert engine.compile_stats()["cow_programs"] == 1
        assert engine.pool.stats()["prefix_tokens_reused"] == 13
        for req in (first, second):
            assert req.finish_reason == "length", req.error
            assert req.tokens == _reference(model, params, req)


class TestFailureContainment:
    def test_abandonment_shedding_and_donated_cache_recovery(self, tiny_model):
        """One engine/scheduler, two containment contracts (a single test
        so tier-1 pays the prefill/decode compiles once):

        1. A waiter that gave up (HTTP 503 timeout, lapsed loadgen
           deadline) must not keep consuming device time: an abandoned
           queued request is skipped without prefill, an abandoned
           in-flight one is evicted with its blocks released, and traffic
           behind both is unaffected.
        2. The prefill/decode jits donate the cache, so a call failing at
           RUNTIME has already deleted it. The engine must rebuild a
           zeroed cache (not leave every later request dying on 'Array
           has been deleted'), the scheduler must fail the in-flight
           sequences whose KV went with it — and must itself survive the
           decode exception (it used to escape step() and kill the loop
           thread)."""
        model, params = tiny_model
        engine = _engine(model, params)
        scheduler = ContinuousBatchingScheduler(engine)

        # --- 1: abandoned requests are shed, queued and in flight.
        flying = ServeRequest(
            prompt_ids=np.asarray([1, 2, 3], np.int32), max_new_tokens=8, seed=0
        )
        scheduler.submit(flying)
        scheduler.step()  # admitted: prefill + one decode advance
        assert not flying.done.is_set()
        tokens_at_shed = len(flying.tokens)
        assert tokens_at_shed >= 1
        queued = ServeRequest(
            prompt_ids=np.asarray([4, 5], np.int32), max_new_tokens=4, seed=0
        )
        survivor = ServeRequest(
            prompt_ids=np.asarray([6, 7], np.int32), max_new_tokens=4, seed=0
        )
        flying.abandon()
        queued.abandon()
        scheduler.submit(queued)
        scheduler.submit(survivor)
        _drain(scheduler, [flying, queued, survivor])
        assert flying.finish_reason == "abandoned"
        assert queued.finish_reason == "abandoned"
        assert queued.tokens == []  # never prefilled
        assert len(flying.tokens) == tokens_at_shed  # never advanced again
        assert survivor.tokens == _reference(model, params, survivor)
        stats = engine.pool.stats()
        assert stats["allocated_blocks"] == 0 and stats["active_sequences"] == 0

        # --- 2: runtime failure consumes the donated cache; recover.
        victim = ServeRequest(
            prompt_ids=np.asarray([1, 2, 3], np.int32), max_new_tokens=6, seed=0
        )
        scheduler.submit(victim)
        scheduler.step()
        assert len(scheduler._active) == 1
        real_decode = engine._decode_jit

        def exploding_decode(params_, cache, *rest):
            for leaf in jax.tree.leaves(cache):
                leaf.delete()  # what donation does on a runtime failure
            raise RuntimeError("injected device failure")

        engine._decode_jit = exploding_decode
        scheduler.step()  # must not raise
        assert victim.done.is_set() and victim.finish_reason == "error"
        assert "injected device failure" in victim.error
        assert engine.cache_epoch == 1  # rebuilt, not left deleted
        engine._decode_jit = real_decode
        after = ServeRequest(
            prompt_ids=np.asarray([4, 5, 6], np.int32), max_new_tokens=4, seed=1
        )
        scheduler.submit(after)
        _drain(scheduler, [after])
        assert after.tokens == _reference(model, params, after)
        assert engine.pool.stats()["allocated_blocks"] == 0


class TestCompileBudget:
    def test_decode_compiles_once_per_bucket(self, tiny_model):
        """Repeating a bucket shape must NOT grow the program count —
        unbounded recompilation is how a JAX server falls over."""
        model, params = tiny_model
        engine = _engine(model, params)
        scheduler = ContinuousBatchingScheduler(engine)

        def burst(seed):
            reqs = [
                ServeRequest(
                    prompt_ids=np.asarray([seed, 2, 3], np.int32),
                    max_new_tokens=3,
                    seed=seed,
                ),
                ServeRequest(
                    prompt_ids=np.asarray([seed, 5], np.int32),
                    max_new_tokens=3,
                    seed=seed,
                ),
            ]
            for r in reqs:
                scheduler.submit(r)
            _drain(scheduler, reqs)

        burst(1)
        first = engine.compile_stats()
        burst(2)  # same shapes again
        second = engine.compile_stats()
        assert second["prefill_programs"] == first["prefill_programs"]
        assert second["decode_programs"] == first["decode_programs"]
        assert second["within_budget"]
        assert (
            second["prefill_programs"] + second["decode_programs"]
            <= second["budget"]
        )
        # The used shapes are real buckets, not raw request shapes.
        assert set(second["prefill_shapes_used"]) <= set(engine.prompt_buckets)
        assert set(second["decode_shapes_used"]) <= set(engine.batch_buckets)


class TestSpeculativePolicy:
    def test_speculative_greedy_token_identical_to_generate(self, tiny_model):
        """Speculative decoding as a scheduler policy: same queue, same
        SLO accounting, token-identical output under greedy sampling."""
        model, params = tiny_model
        scheduler = ContinuousBatchingScheduler(
            None,
            policy="speculative",
            model=model,
            params=params,
            draft_model=model,  # self-draft: always accepted, still exact
            draft_params=params,
            gamma=3,
            registry=MetricsRegistry(None),
        )
        requests = [
            ServeRequest(
                prompt_ids=np.asarray([1, 2, 3], np.int32),
                max_new_tokens=6,
                seed=0,
            ),
            ServeRequest(
                prompt_ids=np.asarray([7, 8], np.int32),
                max_new_tokens=4,
                seed=0,
            ),
        ]
        for req in requests:
            scheduler.submit(req)
        _drain(scheduler, requests)
        for req in requests:
            assert req.finish_reason == "length"
            assert req.tokens == _reference(model, params, req)
        assert scheduler.stats()["policy"] == "speculative"
        assert scheduler.peak_occupancy == 1  # batch-1 by contract

    def test_policy_validation(self, tiny_model):
        model, params = tiny_model
        with pytest.raises(ValueError, match="unknown"):
            ContinuousBatchingScheduler(None, policy="warp")
        with pytest.raises(ValueError, match="PagedDecodeEngine"):
            ContinuousBatchingScheduler(None, policy="paged")
        with pytest.raises(ValueError, match="draft_model"):
            ContinuousBatchingScheduler(
                None, policy="speculative", model=model, params=params
            )


class TestLoadgen:
    def test_percentiles(self):
        assert percentiles([])["p50"] is None
        pct = percentiles([float(i) for i in range(1, 101)])
        assert pct["p50"] == 50.0
        assert pct["p95"] == 95.0
        assert pct["p99"] == 99.0
        assert pct["max"] == 100.0

    def test_build_requests_is_seeded(self):
        kw = dict(
            num_requests=5,
            seed=42,
            vocab_size=VOCAB,
            prompt_tokens_min=2,
            prompt_tokens_max=10,
            max_new_tokens=4,
        )
        a, b = build_requests(**kw), build_requests(**kw)
        for ra, rb in zip(a, b):
            assert np.array_equal(ra.prompt_ids, rb.prompt_ids)
            assert ra.seed == rb.seed
        assert any(
            not np.array_equal(ra.prompt_ids, rb.prompt_ids)
            for ra, rb in zip(a, build_requests(**{**kw, "seed": 43}))
        )

    def test_loadgen_slo_block_and_registry(self, tiny_model):
        """Open-loop seeded run → the serving report block: percentiles,
        throughput, occupancy >= 2 in flight, and llmtrain_serve_* gauges
        in the registry (the Prometheus surface)."""
        model, params = tiny_model
        engine = _engine(model, params)
        registry = MetricsRegistry(None)
        scheduler = ContinuousBatchingScheduler(engine, registry=registry).start()
        try:
            requests = build_requests(
                num_requests=6,
                seed=9,
                vocab_size=VOCAB,
                prompt_tokens_min=2,
                prompt_tokens_max=12,
                max_new_tokens=5,
            )
            # High rate => arrivals overlap => a real in-flight batch.
            block = run_loadgen(
                scheduler, requests, rate_rps=200.0, seed=9, timeout_sec=120.0
            )
        finally:
            scheduler.close()
        assert block["requests"]["completed"] == 6
        assert block["requests"]["failed"] == 0
        assert block["slo"]["ttft_ms"]["p50"] is not None
        assert block["slo"]["ttft_ms"]["p99"] >= block["slo"]["ttft_ms"]["p50"]
        assert block["slo"]["per_token_ms"]["p50"] is not None
        assert block["throughput"]["new_tokens"] == 6 * 5
        assert block["throughput"]["tokens_per_sec"] > 0
        assert block["occupancy"]["peak"] >= 2
        assert block["compile"]["within_budget"]
        assert block["arrival"]["process"] == "poisson-open-loop"
        latest = registry.latest()
        assert "serve/ttft_ms_p50" in latest
        assert "serve/tokens_per_sec" in latest
        assert latest["serve/peak_batch_occupancy"][0] >= 2
        assert registry.counters()["serve/requests"] == 6

    @pytest.mark.slow
    def test_loadgen_soak_parity(self, tiny_model):
        """Longer seeded soak (make verify-serving): every completion
        bitwise-identical to sequential generate()."""
        model, params = tiny_model
        engine = _engine(model, params, max_batch_slots=4)
        scheduler = ContinuousBatchingScheduler(engine).start()
        try:
            requests = build_requests(
                num_requests=24,
                seed=123,
                vocab_size=VOCAB,
                prompt_tokens_min=2,
                prompt_tokens_max=16,
                max_new_tokens=8,
            )
            block = run_loadgen(
                scheduler, requests, rate_rps=100.0, seed=123, timeout_sec=300.0
            )
        finally:
            scheduler.close()
        assert block["requests"]["completed"] == 24
        assert block["occupancy"]["peak"] >= 2
        for req in requests:
            assert req.tokens == _reference(model, params, req)


# ---------------------------------------------------------------------------
# recurrent-state rows beside the block pool (models/falcon_h1.py)
# ---------------------------------------------------------------------------


def _falcon(dtype=jnp.float32):
    """2 layers, GQA (4 query heads on 2 KV heads of 8), 2 groups, chunk 8:
    the Falcon-H1 block at a size the tier-1 gate compiles in seconds."""
    from llmtrain_tpu.models.falcon_h1 import FalconH1

    return FalconH1(
        vocab_size=VOCAB, block_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=48, dropout=0.0,
        n_kv_heads=2, head_dim=8, mamba_d_ssm=32, mamba_d_state=8, mamba_n_heads=4, mamba_n_groups=2,
        mamba_chunk_size=8, embedding_multiplier=5.0, key_multiplier=0.4, attention_out_multiplier=0.6,
        ssm_in_multiplier=0.5, ssm_out_multiplier=0.8, ssm_multipliers=(0.4, 0.3, 0.2, 0.5, 0.35),
        mlp_multipliers=(0.6, 0.5), lm_head_multiplier=0.3, dtype=dtype,
    )


@pytest.fixture(scope="module")
def falcon_model():
    model = _falcon()
    params = _unboxed_params(model)
    # Norm scales and the conv away from their neutral start, so a row that
    # read another's state, or a stale one, would show.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape, x.dtype) for x, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, leaves)


def _full_forward_tokens(model, params, req: ServeRequest) -> tuple[list[int], float]:
    """Greedy tokens of the model's FULL forward (no cache) at the served
    positions, teacher forced on what was served, and the widest gap by
    which a served token's logit lies under that forward's best."""
    seq = np.concatenate([req.prompt_ids, np.asarray(req.tokens, np.int32)])
    ids = np.zeros((1, model.block_size), np.int32)
    ids[0, : len(seq)] = seq
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))[0]
    at = logits[len(req.prompt_ids) - 1 : len(seq) - 1]
    gap = at.max(-1) - at[np.arange(len(req.tokens)), req.tokens]
    return [int(t) for t in at.argmax(-1)], float(gap.max())


def _state_engine(model, params, **kw):
    defaults = dict(block_tokens=8, max_batch_slots=3, prompt_buckets=[8, 16, 32], batch_buckets=[3])
    return PagedDecodeEngine(model, params, **{**defaults, **kw})


def _falcon_requests(rng, shapes):
    return [
        ServeRequest(prompt_ids=rng.integers(0, VOCAB, n).astype(np.int32), max_new_tokens=m,
                     temperature=0.0, eos_token_id=None, seed=i)
        for i, (n, m) in enumerate(shapes)
    ]


class TestRecurrentStateRows:
    def test_pool_hands_out_a_row_with_the_reservation_and_takes_it_back(self):
        pool = PagedKVPool(64, 8, state_rows=2)
        a, b = pool.try_reserve(16), pool.try_reserve(16)
        assert {a.state_row, b.state_row} == {1, 2}  # row 0 is the null row
        before = pool.available_blocks
        assert pool.try_reserve(8) is None  # blocks there are, a row there is not
        assert pool.available_blocks == before  # and nothing was reserved for it
        assert pool.stats()["state_rows_in_use"] == 2 and pool.stats()["state_rows_free"] == 0
        row = a.state_row
        pool.release(a)
        assert a.state_row == 0 and pool.try_reserve(8).state_row == row
        assert "state_rows_free" not in PagedKVPool(8, 8).stats()  # a model without state: no rows, no keys
        assert PagedKVPool(8, 8).try_reserve(8).state_row == 0
        with pytest.raises(ValueError, match="prefix_cache cannot serve a model with recurrent state"):
            PagedKVPool(8, 8, prefix_cache=True, state_rows=2)

    def test_served_tokens_agree_with_the_full_forward_as_rows_join_and_retire(self, falcon_model):
        """Seven requests on three slots: rows join and retire at different
        ticks, decode batches are compacted (a row's index is not its
        identity), and every retired row's state row goes to a later prompt.
        float32 throughout, so the served tokens ARE the full forward's
        greedy tokens (gap 0), not merely close."""
        model, params = falcon_model
        engine = _state_engine(model, params)
        assert engine.state_bytes_per_row == 2 * (3 * 64 * 4 + 4 * 8 * 8 * 4)
        spans = []

        class Spans:
            def __call__(self, name, **args):
                spans.append((name, args))
                from contextlib import nullcontext

                return nullcontext()

        engine.span_factory = Spans()
        scheduler = ContinuousBatchingScheduler(engine)
        reqs = _falcon_requests(
            np.random.default_rng(0), [(5, 6), (17, 9), (9, 3), (30, 12), (3, 20), (12, 5), (8, 8)]
        )
        for r in reqs:
            scheduler.submit(r)
        _drain(scheduler, reqs)
        for r in reqs:
            assert r.finish_reason == "length", r.error
            want, gap = _full_forward_tokens(model, params, r)
            assert r.tokens == want and gap == 0.0
        stats = engine.pool.stats()
        assert stats["state_rows_free"] == 3 and stats["allocated_blocks"] == 0
        assert scheduler.stats()["kv_pool"]["state_rows_in_use"] == 0
        assert engine.compile_stats()["within_budget"] and engine.compile_stats()["state_leaves"] == 4
        # The counters the benchmark reads, on the stage span of each call.
        decode = [a for n, a in spans if n == "serve/engine.stage" and a["call"] == "decode"]
        prefill = [a for n, a in spans if n == "serve/engine.stage" and a["call"] == "prefill"]
        assert decode and all(a["state_bytes"] == 2 * a["state_rows"] * engine.state_bytes_per_row for a in decode)
        assert max(a["state_rows"] for a in decode) == 3 and "kv_live_tokens" in decode[0]
        assert all(a["scan_chunks"] == a["bucket"] // 8 and a["state_bytes"] == engine.state_bytes_per_row
                   for a in prefill)

    def test_a_reused_state_row_gives_what_a_fresh_engine_gives(self, falcon_model):
        model, params = falcon_model
        rng = np.random.default_rng(1)
        first, second = _falcon_requests(rng, [(20, 10), (11, 7)])
        engine = _state_engine(model, params, max_batch_slots=1, batch_buckets=[1])
        scheduler = ContinuousBatchingScheduler(engine)
        scheduler.submit(first)
        _drain(scheduler, [first])
        dirty = [np.asarray(leaf[1]) for leaf in jax.tree.leaves(engine._cache) if leaf.shape[0] == 2]
        assert any(np.abs(d).max() > 0 for d in dirty)  # the one row holds the first request's state
        scheduler.submit(second)
        _drain(scheduler, [second])
        fresh = ServeRequest(prompt_ids=second.prompt_ids, max_new_tokens=7, temperature=0.0, seed=1)
        other = ContinuousBatchingScheduler(_state_engine(model, params, max_batch_slots=1, batch_buckets=[1]))
        other.submit(fresh)
        _drain(other, [fresh])
        assert second.tokens == fresh.tokens == _full_forward_tokens(model, params, second)[0]

    def test_chunked_prefill_carries_the_state_from_chunk_to_chunk(self, falcon_model):
        model, params = falcon_model
        shapes = [(30, 6), (13, 4), (7, 5)]
        whole = _falcon_requests(np.random.default_rng(2), shapes)
        chunked = _falcon_requests(np.random.default_rng(2), shapes)
        for reqs, chunk in ((whole, 0), (chunked, 8)):
            scheduler = ContinuousBatchingScheduler(_state_engine(model, params, prefill_chunk=chunk))
            for r in reqs:
                scheduler.submit(r)
            _drain(scheduler, reqs)
        for a, b in zip(whole, chunked):
            assert a.tokens == b.tokens == _full_forward_tokens(model, params, a)[0]

    def test_cow_copy_and_recovery_follow_the_two_kinds_of_leaf(self, falcon_model):
        model, params = falcon_model
        engine = _state_engine(model, params)
        scheduler = ContinuousBatchingScheduler(engine)
        req = _falcon_requests(np.random.default_rng(3), [(12, 3)])[0]
        scheduler.submit(req)
        scheduler.step()

        def split(cache):
            flat = jax.tree_util.tree_leaves_with_path(cache)
            state = {jax.tree_util.keystr(p): np.asarray(x) for p, x in flat if "state_" in jax.tree_util.keystr(p)}
            pool = {jax.tree_util.keystr(p): np.asarray(x) for p, x in flat if "state_" not in jax.tree_util.keystr(p)}
            return state, pool

        state, pool = split(engine._cache)
        assert len(state) == 4 and len(pool) == 4
        engine.cow_copy(1, 2)  # block 1 -> 2 in every POOL leaf; a state leaf's rows 1, 2 are sequences
        state_after, pool_after = split(engine._cache)
        for name, leaf in state.items():
            np.testing.assert_array_equal(state_after[name], leaf)
        for name, leaf in pool.items():
            np.testing.assert_array_equal(pool_after[name][2], leaf[1])
        # A failed call that consumed only SOME leaves: those are rebuilt
        # zeroed, the others kept, and the epoch tells the scheduler.
        leaves = jax.tree_util.tree_leaves_with_path(engine._cache)
        for path, leaf in leaves:
            if "state_ssm" in jax.tree_util.keystr(path):
                leaf.delete()
        engine._recover_cache_after_error()
        assert engine.cache_epoch == 1
        rebuilt_state, rebuilt_pool = split(engine._cache)
        for name, leaf in rebuilt_state.items():
            if "state_ssm" in name:
                assert leaf.shape == state[name].shape and not leaf.any()
            else:
                np.testing.assert_array_equal(leaf, state_after[name])
        for name, leaf in rebuilt_pool.items():
            np.testing.assert_array_equal(leaf, pool_after[name])
        engine._recover_cache_after_error()  # nothing deleted: nothing to do
        assert engine.cache_epoch == 1

    def test_hot_swap_holds_a_row_on_the_params_it_was_admitted_under(self, falcon_model):
        model, params = falcon_model
        new_params = jax.tree.map(lambda x: x * 1.1, params)
        engine = _state_engine(model, params)
        scheduler = ContinuousBatchingScheduler(engine)
        old, new = _falcon_requests(np.random.default_rng(4), [(10, 12), (10, 6)])
        scheduler.submit(old)
        scheduler.step()
        scheduler.hot_swap(new_params)
        scheduler.submit(new)
        _drain(scheduler, [old, new])
        assert scheduler.hot_swaps == 1
        assert old.tokens == _full_forward_tokens(model, params, old)[0]
        assert new.tokens == _full_forward_tokens(model, new_params, new)[0]
        assert engine.pool.stats()["state_rows_free"] == 3

    def test_what_the_state_cannot_follow_is_refused_by_name(self, falcon_model):
        model, params = falcon_model
        with pytest.raises(ValueError, match="prefix_cache cannot serve a model with recurrent state"):
            _state_engine(model, params, prefix_cache=True)
        engine = _state_engine(model, params)
        with pytest.raises(ValueError, match="verify .* recurrent state"):
            engine.verify([{"tokens": [1, 2], "position": 0, "table": [0] * 8}], width=2)
        with pytest.raises(ValueError, match="speculative policy cannot serve a model with recurrent state"):
            ContinuousBatchingScheduler(
                engine, policy="speculative", model=model, params=params,
                draft_model=model, draft_params=params, draft_engine=_state_engine(model, params),
            )
        with pytest.raises(ValueError, match="no linear decode cache"):
            generate(model, params, jnp.zeros((1, 4), jnp.int32), max_new_tokens=2, temperature=0.0)
        with pytest.raises(ValueError, match="state_rows >= 2"):
            model.for_paged_decoding(num_blocks=8, block_tokens=8)


class TestModelsWithoutState:
    @pytest.mark.parametrize("name", ["gpt-row32-fold4", "llama-gqa-row128"])
    def test_staged_arguments_are_what_they_were(self, name):
        """A model without state leaves: the engine stages, compiles and
        counts exactly what it did before state rows existed."""
        model = LAYOUT_MODELS[name]()
        engine = _engine(model, _unboxed_params(model))
        assert engine.state_bytes_per_row == 0 and engine.pool.state_rows == 0
        calls, spans = {}, []
        real_prefill, real_decode = engine._prefill_jit, engine._decode_jit
        engine._prefill_jit = lambda p, c, *rest: calls.setdefault("prefill", rest) and real_prefill(p, c, *rest)
        engine._decode_jit = lambda p, c, *rest: calls.setdefault("decode", rest) and real_decode(p, c, *rest)
        engine.span_factory = lambda n, **a: (spans.append((n, a)), __import__("contextlib").nullcontext())[1]
        scheduler = ContinuousBatchingScheduler(engine)
        req = ServeRequest(prompt_ids=np.asarray([1, 2, 3], np.int32), max_new_tokens=3, seed=0)
        scheduler.submit(req)
        _drain(scheduler, [req])
        assert [(a.shape, str(a.dtype)) for a in calls["prefill"]] == [
            ((1, 8), "int32"), ((1,), "int32"), ((1,), "int32"), ((1, 4), "int32"),
            ((1,), "uint32"), ((1,), "float32"), ((1,), "int32"), ((1,), "float32"),
        ]
        assert [(a.shape, str(a.dtype)) for a in calls["decode"]] == [
            ((2,), "int32"), ((2,), "int32"), ((2, 4), "int32"), ((2,), "uint32"),
            ((2,), "int32"), ((2,), "float32"), ((2,), "int32"), ((2,), "float32"),
        ]
        stage = [a for n, a in spans if n == "serve/engine.stage"]
        assert {k for a in stage for k in a} == {
            "call", "prompt_tokens", "bucket", "kv_live_tokens", "kv_gathered_tokens", "kv_form"
        }
        # The form of the paged read a decode call ran: a row of 32 lanes
        # folds four positions; a row of 128 is read as it lies.
        assert {a["kv_form"] for a in stage if a["call"] == "decode"} == {
            {"gpt-row32-fold4": "heads", "llama-gqa-row128": "rows"}[name]
        }
        assert "state_leaves" not in engine.compile_stats() and "state_rows_free" not in engine.pool.stats()


# an expert layer and a latent pool behind the engine (models/latent_moe.py)
# ---------------------------------------------------------------------------


def _latent_moe(**kw):
    """1 dense + 2 expert layers, 4 heads of 8 + 4 / 8 over a 12-wide latent
    row, 8 experts in 4 groups (2 stay, 3 a token) of which this holder has
    experts 2-5, a shared expert, YaRN."""
    from llmtrain_tpu.models.latent_moe import LatentMoE

    base = dict(
        vocab_size=VOCAB, block_size=64, d_model=32, n_layers=3, n_heads=4, d_ff=48, q_lora_rank=12,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, moe_intermediate_size=16,
        n_routed_experts=8, num_experts_per_tok=3, n_group=4, topk_group=2, routed_scaling_factor=2.5,
        experts_held=(2, 4),
        rope_scaling=(("factor", 32.0), ("original_max_position_embeddings", 16.0), ("beta_fast", 32.0),
                      ("beta_slow", 1.0), ("mscale", 1.0), ("mscale_all_dim", 1.0)),
    )
    return LatentMoE(**{**base, **kw})


def _shaken_params(model, seed):
    """The initialiser's draw with noise on every leaf: away from its
    symmetry, routers that disagree, norms off 1."""
    leaves, tree = jax.tree.flatten(_unboxed_params(model))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(
        tree, [x + 0.3 * jax.random.normal(k, x.shape, x.dtype) for x, k in zip(leaves, keys)]
    )


@pytest.fixture(scope="module")
def latent_moe_model():
    model = _latent_moe()
    return model, _shaken_params(model, 11)


class TestExpertLayerBehindTheEngine:
    def test_served_tokens_agree_with_the_full_forward_and_the_fetch_span_counts_the_experts(self, latent_moe_model):
        """Seven requests on three slots through the latent pool: prefill
        materialises keys and values, decode attends the latent rows
        absorbed, the expert layers regroup each call's tokens; float32
        throughout, so the served tokens ARE the full forward's greedy tokens."""
        from contextlib import nullcontext

        model, params = latent_moe_model
        engine = _state_engine(model, params)
        assert engine.state_bytes_per_row == 0 and engine.pool.state_rows == 0  # the latent is paged, not a state row
        leaves = jax.tree.leaves(engine._cache)
        assert len(leaves) == 3 and {leaf.shape for leaf in leaves} == {(25, 1, 8 * 12)}  # one leaf a layer
        spans = []
        engine.span_factory = lambda n, **a: (spans.append((n, a)), nullcontext(a))[1]
        scheduler = ContinuousBatchingScheduler(engine)
        reqs = _falcon_requests(
            np.random.default_rng(0), [(5, 6), (17, 9), (9, 3), (30, 12), (3, 20), (12, 5), (8, 8)]
        )
        for r in reqs:
            scheduler.submit(r)
        _drain(scheduler, reqs)
        for r in reqs:
            assert r.finish_reason == "length", r.error
            want, gap = _full_forward_tokens(model, params, r)
            assert r.tokens == want and gap == 0.0
        assert engine.pool.stats()["allocated_blocks"] == 0 and engine.compile_stats()["within_budget"]
        # Two counters beside the tokens, on the fetch span of a DECODE call only.
        fetch = [a for n, a in spans if n == "serve/engine.fetch"]
        decode = [a for a in fetch if a["call"] == "decode"]
        assert decode and all({"expert_pairs", "experts_hit"} <= set(a) for a in decode)
        assert all("expert_pairs" not in a for a in fetch if a["call"] == "prefill")
        # 3 rows (the bucket) x 3 experts a token x 2 expert layers bound the pairs; 4 held x 2 the hits.
        assert all(0 <= a["experts_hit"] <= min(a["expert_pairs"], 8) and a["expert_pairs"] <= 18 for a in decode)
        assert sum(a["expert_pairs"] for a in decode) > 0
        stage = [a for n, a in spans if n == "serve/engine.stage" and a["call"] == "decode"]
        assert all(a["kv_gathered_tokens"] == 3 * 64 and 0 < a["kv_live_tokens"] <= 3 * 64 for a in stage)

    def test_the_counters_are_the_layers_own_and_a_holder_of_everything_sees_every_pair(self, latent_moe_model):
        from contextlib import nullcontext

        model, params = latent_moe_model
        whole = _latent_moe(experts_held=None)
        engine = _state_engine(whole, _shaken_params(whole, 12))
        spans = []
        engine.span_factory = lambda n, **a: (spans.append((n, a)), nullcontext(a))[1]
        scheduler = ContinuousBatchingScheduler(engine)
        reqs = _falcon_requests(np.random.default_rng(1), [(6, 4), (11, 4), (4, 4)])
        for r in reqs:
            scheduler.submit(r)
        _drain(scheduler, reqs)
        decode = [a for n, a in spans if n == "serve/engine.fetch" and a["call"] == "decode"]
        assert decode and all(a["expert_pairs"] == 3 * 3 * 2 for a in decode)  # rows x top-k x expert layers
        # without a span factory the call returns its tokens and nothing else
        bare = _state_engine(model, params)
        other = ContinuousBatchingScheduler(bare)
        req = _falcon_requests(np.random.default_rng(2), [(7, 5)])[0]
        other.submit(req)
        _drain(other, [req])
        assert len(req.tokens) == 5 and req.tokens == _full_forward_tokens(model, params, req)[0]

    def test_cow_copy_prefix_reuse_and_chunked_prefill_hold_for_a_latent_leaf(self, latent_moe_model):
        """`_cow_impl` indexes a leaf's block axis only, the prefix cache
        hands a request the latent blocks another wrote, and a chunk of a
        prompt attends the cached rest through the materialised path."""
        model, params = latent_moe_model
        rng = np.random.default_rng(3)
        prefix = rng.integers(0, VOCAB, 16).astype(np.int32)
        tails = [rng.integers(0, VOCAB, n).astype(np.int32) for n in (5, 9, 3)]
        reqs = [ServeRequest(prompt_ids=np.concatenate([prefix, t]), max_new_tokens=6, temperature=0.0,
                             eos_token_id=None, seed=i) for i, t in enumerate(tails)]
        engine = _state_engine(model, params, prefix_cache=True, prefill_chunk=8)
        scheduler = ContinuousBatchingScheduler(engine)
        for r in reqs:
            scheduler.submit(r)
            _drain(scheduler, [r])  # one after another, so the later ones find the prefix cached
        assert engine.pool.stats()["prefix_tokens_reused"] >= 2 * 16  # the later two found the prefix's latent blocks
        for r in reqs:
            assert r.tokens == _full_forward_tokens(model, params, r)[0]
        before = [np.asarray(leaf) for leaf in jax.tree.leaves(engine._cache)]
        engine.cow_copy(1, 2)
        for old, new in zip(before, jax.tree.leaves(engine._cache)):
            np.testing.assert_array_equal(np.asarray(new)[2], old[1])
            np.testing.assert_array_equal(np.asarray(new)[3:], old[3:])
        profiles = engine.cost_profile(full=False)  # the AOT cost profile takes the leaf as it is
        assert {p["name"] for p in profiles} == {"prefill_T32", "decode_B3"}


# attention over a chosen subset of the cache behind the engine (models/indexed_moe.py)
# ---------------------------------------------------------------------------


def _indexed_moe(**kw):
    """2 layers, 4 query / 2 K/V heads of 8 with per-head q/k norms, an
    indexer of 3 heads of 4 that picks topk 6 positions a query (queries in
    chunks of 8), 8 softmax-routed experts (3 a token) of which this holder
    has experts 2-5."""
    from llmtrain_tpu.models.indexed_moe import IndexedMoE

    base = dict(
        vocab_size=VOCAB, block_size=64, d_model=32, n_layers=2, n_heads=4, num_key_value_heads=2, head_dim=8,
        indexer_num_heads=3, indexer_head_dim=4, topk=6, q_chunk_size=8, kv_chunk_size=8, moe_intermediate_size=16,
        num_experts=8, num_experts_per_tok=3, experts_held=(2, 4), rope_theta=1e7,
    )
    return IndexedMoE(**{**base, **kw})


@pytest.fixture(scope="module")
def indexed_moe_model():
    model = _indexed_moe()
    return model, _shaken_params(model, 21)


class TestSelectionBehindTheEngine:
    def test_served_tokens_agree_with_the_full_forward_and_the_stage_span_counts_the_selection(
            self, indexed_moe_model, latent_moe_model):
        """Seven requests on three slots through three pool leaves a layer:
        prefill selects under a mask in chunks of queries, decode scores the
        row's table, takes the top 6 and gathers THEIR K/V rows; prompts
        under and past ``topk``, answers that cross it; float32 throughout,
        so the served tokens ARE the full forward's greedy tokens."""
        from contextlib import nullcontext

        model, params = indexed_moe_model
        engine = _state_engine(model, params)
        assert engine.state_bytes_per_row == 0 and engine.pool.state_rows == 0  # the index key is paged, not a state row
        leaves = {jax.tree_util.keystr(p): leaf.shape for p, leaf in jax.tree_util.tree_leaves_with_path(engine._cache)}
        assert len(leaves) == 6  # K, V and the index key, in each of two layers
        assert {s for n, s in leaves.items() if "paged_index" in n} == {(25, 8, 128)}  # 4 wide, padded to a lane tile
        assert {s for n, s in leaves.items() if "paged_index" not in n} == {(25, 1, 8 * 16)}
        spans = []
        engine.span_factory = lambda n, **a: (spans.append((n, a)), nullcontext(a))[1]
        scheduler = ContinuousBatchingScheduler(engine)
        shapes = [(5, 6), (17, 9), (9, 3), (30, 12), (3, 20), (12, 5), (8, 8)]
        reqs = _falcon_requests(np.random.default_rng(0), shapes)
        for r in reqs:
            scheduler.submit(r)
        _drain(scheduler, reqs)
        for r in reqs:
            assert r.finish_reason == "length", r.error
            want, gap = _full_forward_tokens(model, params, r)
            assert r.tokens == want and gap == 0.0
        assert engine.pool.stats()["allocated_blocks"] == 0 and engine.compile_stats()["within_budget"]
        # What a selection scored and attended, from positions alone, on the stage span.
        stage = [a for n, a in spans if n == "serve/engine.stage"]
        prefill = sorted((a["prompt_tokens"], a["index_pairs"], a["selected_pairs"]) for a in stage if a["call"] == "prefill")
        assert prefill == sorted(
            (n, n * (n + 1) // 2, sum(min(p + 1, 6) for p in range(n))) for n, _ in shapes)
        decode = [a for a in stage if a["call"] == "decode"]
        assert decode and all({"kv_selected_tokens", "rows_past_topk"} <= set(a) for a in decode)
        assert all(a["kv_selected_tokens"] <= min(a["kv_live_tokens"], 3 * 6) and 0 <= a["rows_past_topk"] <= 3
                   for a in decode)
        assert any(a["kv_selected_tokens"] < a["kv_live_tokens"] for a in decode)  # the selection was live ...
        assert min(a["rows_past_topk"] for a in decode) < 3 <= max(a["rows_past_topk"] for a in decode)  # ... not in every row
        fetch = [a for n, a in spans if n == "serve/engine.fetch" and a["call"] == "decode"]
        assert fetch and all({"expert_pairs", "experts_hit"} <= set(a) for a in fetch)  # the expert layers' counters ride along
        # a model that selects nothing counts none of it
        assert _state_engine(*latent_moe_model)._selects == 0

    def test_cow_copy_recovery_prefix_reuse_and_chunked_prefill_hold_for_the_index_leaf(self, indexed_moe_model):
        """`_cow_impl` copies a block in all three leaves, the prefix cache
        hands a request the K, V AND index keys another wrote, a chunk of a
        prompt selects among the cached rest, and a failed call that consumed
        the index leaf gets it rebuilt."""
        model, params = indexed_moe_model
        rng = np.random.default_rng(3)
        prefix = rng.integers(0, VOCAB, 16).astype(np.int32)
        tails = [rng.integers(0, VOCAB, n).astype(np.int32) for n in (5, 9, 3)]
        reqs = [ServeRequest(prompt_ids=np.concatenate([prefix, t]), max_new_tokens=6, temperature=0.0,
                             eos_token_id=None, seed=i) for i, t in enumerate(tails)]
        engine = _state_engine(model, params, prefix_cache=True, prefill_chunk=8)
        scheduler = ContinuousBatchingScheduler(engine)
        for r in reqs:
            scheduler.submit(r)
            _drain(scheduler, [r])  # one after another, so the later ones find the prefix cached
        assert engine.pool.stats()["prefix_tokens_reused"] >= 2 * 16
        for r in reqs:
            assert r.tokens == _full_forward_tokens(model, params, r)[0]
        before = [np.asarray(leaf) for leaf in jax.tree.leaves(engine._cache)]
        assert any(leaf[1].any() for leaf in before)
        engine.cow_copy(1, 2)
        for old, new in zip(before, jax.tree.leaves(engine._cache)):
            np.testing.assert_array_equal(np.asarray(new)[2], old[1])
            np.testing.assert_array_equal(np.asarray(new)[3:], old[3:])
        after = {jax.tree_util.keystr(p): np.asarray(leaf)
                 for p, leaf in jax.tree_util.tree_leaves_with_path(engine._cache)}
        for path, leaf in jax.tree_util.tree_leaves_with_path(engine._cache):
            if "paged_index" in jax.tree_util.keystr(path):
                leaf.delete()
        engine._recover_cache_after_error()
        assert engine.cache_epoch == 1
        for path, leaf in jax.tree_util.tree_leaves_with_path(engine._cache):
            name = jax.tree_util.keystr(path)
            if "paged_index" in name:
                assert leaf.shape == after[name].shape and not np.asarray(leaf).any()
            else:
                np.testing.assert_array_equal(np.asarray(leaf), after[name])
        profiles = engine.cost_profile(full=False)  # the AOT cost profile takes the three leaves as they are
        assert {p["name"] for p in profiles} == {"prefill_T32", "decode_B3"}


# ---------------------------------------------------------------------------
# window layers' ring beside the global pool (models/windowed_moe.py)
# ---------------------------------------------------------------------------


def _windowed_moe(**kw):
    """4 layers (three window layers of 12 positions, then a global one), 4
    query / 2 K/V heads of 8, 8 sigmoid-routed experts (3 a token) of which
    this holder has experts 2-5, beside 2 averaged shared experts."""
    from llmtrain_tpu.models.windowed_moe import WindowedMoE

    base = dict(
        vocab_size=VOCAB, block_size=64, d_model=32, n_layers=4, n_heads=4, num_key_value_heads=2, head_dim=8,
        intermediate_size=16, num_experts=8, num_experts_per_tok=3, num_shared_experts=2, experts_held=(2, 4),
        sliding_window=12, layer_types=("sliding_attention",) * 3 + ("full_attention",), rope_theta=50000.0,
    )
    return WindowedMoE(**{**base, **kw})


@pytest.fixture(scope="module")
def windowed_moe_model():
    model = _windowed_moe()
    return model, _shaken_params(model, 31)


class TestWindowBlocks:
    RING = 3  # ceil(12 / 8) + 1 blocks of 8 positions

    def test_a_sequence_never_binds_more_than_its_ring_and_both_budgets_come_and_go_together(self):
        pool = PagedKVPool(1 + 4 * 8, 8, window_tokens=12, window_num_blocks=1 + 4 * 3)
        assert pool.window_ring == self.RING
        start = pool.stats()
        assert (start["window_capacity_blocks"], start["window_allocated_blocks"], start["window_reserved_blocks"]) == (12, 0, 0)
        short, long = pool.try_reserve(10), pool.try_reserve(64)
        # a short request reserves what it can reach, not a window's worth; a long one is capped at the ring
        assert (short.reserved, short.window_reserved) == (2, 2) and (long.reserved, long.window_reserved) == (8, 3)
        assert pool.stats()["window_reserved_blocks"] == 5 and pool.stats()["reserved_blocks"] == 10
        for upto in range(1, 65):
            pool.grow(long, upto)
            assert len(long.window_blocks) == min(-(-upto // 8), self.RING)  # bound lazily, then no more, ever
            assert len(long.blocks) == -(-upto // 8)
        ring = list(long.window_blocks)
        pool.grow(short, 10)
        assert len(short.window_blocks) == 2 and not set(short.window_blocks) & set(ring)
        assert long.padded_window(self.RING) == ring and short.padded_window(self.RING) == short.window_blocks + [0]
        assert pool.stats()["window_allocated_blocks"] == 5 and pool.stats()["window_peak_allocated_blocks"] == 5
        # reserve both or neither: the window pool is the one that runs out here (12 blocks, 5 + 3 + 3 reserved)
        third, fourth = pool.try_reserve(64), pool.try_reserve(30)
        assert third is not None and fourth is not None and pool.stats()["window_reserved_blocks"] == 11
        before = pool.available_blocks
        assert pool.try_reserve(20) is None and pool.available_blocks == before  # 2 window blocks wanted, 1 left
        assert pool.try_reserve(8) is not None  # one of each is there
        # the global pool running out refuses the window budget too
        tight = PagedKVPool(1 + 4, 8, window_tokens=12, window_num_blocks=1 + 12)
        assert tight.try_reserve(40) is None and tight.stats()["window_reserved_blocks"] == 0
        # release returns both, and a drained pool is where it started
        for table in (short, long, third, fourth):
            pool.release(table)
            assert table.window_blocks == [] and table.window_reserved == 0
        pool.release(next(iter([t for t in [pool.try_reserve(8)] if t])))  # (and a fresh one goes round again)
        assert pool.stats()["window_reserved_blocks"] == 1  # the table of 8 positions reserved above still holds its one
        with pytest.raises(ValueError, match="released or foreign"):
            pool.release(long)

    def test_named_refusals_of_the_pool(self):
        with pytest.raises(ValueError, match="prefix_cache cannot serve a model with window layers"):
            PagedKVPool(64, 8, prefix_cache=True, window_tokens=12, window_num_blocks=13)
        with pytest.raises(ValueError, match="come together"):
            PagedKVPool(64, 8, window_tokens=12)
        with pytest.raises(ValueError, match="come together"):
            PagedKVPool(64, 8, window_num_blocks=13)
        stats = PagedKVPool(8, 8).stats()  # a model without window layers: no second pool, no keys
        assert not any(key.startswith("window_") for key in stats)
        assert PagedKVPool(8, 8).try_reserve(8).window_blocks == []

    def test_a_mixed_queue_is_served_as_the_full_forward_and_the_pools_return_to_their_start(self, windowed_moe_model):
        """Eight requests on three slots, short and long in one queue:
        prompts under, at and past the window of 12 (a prompt of 30 is
        prefilled exactly from its own keys and leaves only its last ring in
        the window leaves), answers that cross the window and wrap the ring
        of 3 blocks several times; float32 throughout, so the served tokens
        ARE the full forward's greedy tokens."""
        from contextlib import nullcontext

        model, params = windowed_moe_model
        engine = _state_engine(model, params)
        assert (engine.window_tokens, engine.window_ring, engine.pool.window_num_blocks) == (12, 3, 1 + 3 * 3)
        leaves = {jax.tree_util.keystr(p): leaf.shape for p, leaf in jax.tree_util.tree_leaves_with_path(engine._cache)}
        assert len(leaves) == 8  # K and V in each of four layers
        # the window layers' leaves are sized by the window pool (3 slots x ring 3 + null), NOT by the global pool
        assert {s for n, s in leaves.items() if "window_" in n} == {(10, 1, 8 * 16)}
        assert {s for n, s in leaves.items() if "paged_" in n} == {(25, 1, 8 * 16)}
        assert sum("window_" in n for n in leaves) == 6 and engine.compile_stats()["window_num_blocks"] == 10
        start = engine.pool.stats()
        spans = []
        engine.span_factory = lambda n, **a: (spans.append((n, a)), nullcontext(a))[1]
        scheduler = ContinuousBatchingScheduler(engine)
        shapes = [(5, 6), (17, 9), (9, 30), (30, 12), (3, 40), (12, 5), (13, 20), (32, 32)]
        reqs = _falcon_requests(np.random.default_rng(0), shapes)
        for r in reqs:
            scheduler.submit(r)
        steps = 0
        while not all(r.done.is_set() for r in reqs):
            scheduler.step()
            steps += 1
            assert steps < 500
            # never more than a ring a sequence, whatever it has grown to
            assert engine.pool.window_allocated_blocks <= 3 * self.RING
        for r in reqs:
            assert r.finish_reason == "length", r.error
            want, gap = _full_forward_tokens(model, params, r)
            assert r.tokens == want and gap == 0.0
        end = engine.pool.stats()
        for key in ("allocated_blocks", "reserved_blocks", "window_allocated_blocks", "window_reserved_blocks"):
            assert end[key] == start[key] == 0
        assert end["window_peak_allocated_blocks"] == 3 * self.RING and engine.compile_stats()["within_budget"]
        # What the window spares, from positions alone, on the stage span.
        stage = [a for n, a in spans if n == "serve/engine.stage"]
        prefill = sorted((a["prompt_tokens"], a["causal_pairs"], a["window_pairs"]) for a in stage if a["call"] == "prefill")
        assert prefill == sorted((n, n * (n + 1) // 2, sum(min(p + 1, 12) for p in range(n))) for n, _ in shapes)
        decode = [a for a in stage if a["call"] == "decode"]
        assert decode and all(
            {"kv_window_tokens", "window_blocks_bound", "global_blocks_bound", "kv_live_tokens"} <= set(a) for a in decode)
        assert all(a["kv_window_tokens"] <= min(a["kv_live_tokens"], 3 * 12) for a in decode)
        assert all(a["kv_window_gathered_tokens"] == 3 * self.RING * 8 < a["kv_gathered_tokens"] for a in decode)
        assert any(a["kv_window_tokens"] < a["kv_live_tokens"] for a in decode)  # some rows lay past the window ...
        assert all(a["window_blocks_bound"] <= min(a["global_blocks_bound"], 3 * self.RING) for a in decode)
        assert any(a["window_blocks_bound"] < a["global_blocks_bound"] for a in decode)  # blocks really were spared
        fetch = [a for n, a in spans if n == "serve/engine.fetch" and a["call"] == "decode"]
        assert fetch and all({"expert_pairs", "experts_hit"} <= set(a) for a in fetch)  # the expert layers' counters ride along
        profiles = engine.cost_profile(full=False)  # the AOT cost profile takes both tables
        assert {p["name"] for p in profiles} == {"prefill_T32", "decode_B3"}

    def test_what_would_read_a_window_layers_earlier_keys_is_refused_by_name(self, windowed_moe_model, latent_moe_model):
        model, params = windowed_moe_model
        with pytest.raises(ValueError, match="prefix_cache cannot serve a model with window layers"):
            _state_engine(model, params, prefix_cache=True)
        with pytest.raises(ValueError, match="chunked prefill cannot serve a model with window layers"):
            _state_engine(model, params, prefill_chunk=8)
        engine = _state_engine(model, params)
        with pytest.raises(ValueError, match="verify .* cannot serve a model with window layers"):
            engine.verify([{"tokens": [1, 2], "position": 0, "table": [0] * 8}], width=2)
        with pytest.raises(ValueError, match="cow_copy cannot serve a model with window layers"):
            engine.cow_copy(1, 2)
        table = engine.pool.try_reserve(24)
        engine.pool.grow(table, 9)
        with pytest.raises(ValueError, match="prefills a prompt whole, from position 0"):
            engine.prefill(np.arange(4, dtype=np.int32), table.padded(8), seed=0, temperature=0.0, top_k=None,
                           top_p=None, offset=5, window_table=table.padded_window(3))
        with pytest.raises(ValueError, match="speculative policy cannot serve a model with window layers"):
            ContinuousBatchingScheduler(
                engine, policy="speculative", model=model, params=params, draft_model=model, draft_params=params,
                draft_engine=_state_engine(model, params))
        # a model of this family with no window layer is one pool, as every family; so is every other model
        plain = _windowed_moe(layer_types=("full_attention",) * 4)
        one_pool = _state_engine(plain, _shaken_params(plain, 32))
        assert one_pool.window_tokens == 0 and one_pool.pool.window_num_blocks == 0
        assert "window_tokens" not in one_pool.compile_stats()
        assert _state_engine(*latent_moe_model).window_ring == 0

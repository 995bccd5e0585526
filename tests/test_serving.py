"""HTTP inference server (serving/ package + the ``serve`` CLI subcommand).

Beyond-reference serving surface. Unit tests drive the request logic
and a live in-process server over a tiny model — in BOTH backends (the
legacy one-decode-at-a-time lock and the continuous-batching
scheduler); one CLI test boots the real subprocess on an ephemeral port
and round-trips a request. The engine/scheduler internals live in
tests/test_serving_engine.py.
"""

from __future__ import annotations

import json
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest
from flax.linen import meta as nn_meta

from llmtrain_tpu.serving import (
    ContinuousBatchingScheduler,
    PagedDecodeEngine,
    ServerState,
    ServerStats,
    _handle_generate_request,
    make_server,
)


def _tiny_model():
    from llmtrain_tpu.models.gpt import GPT

    model = GPT(
        vocab_size=64,
        block_size=16,
        d_model=32,
        n_layers=1,
        n_heads=2,
        d_ff=64,
        dropout=0.0,
        tie_embeddings=True,
    )
    params = nn_meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    )
    return model, params


def _tiny_state(**kw):
    model, params = _tiny_model()
    defaults = dict(
        model=model,
        params=params,
        tokenizer=None,
        step=7,
        checkpoint="mem://tiny",
        max_new_tokens_cap=8,
        default_max_new_tokens=4,
    )
    return ServerState(**{**defaults, **kw})


def _continuous_state(**kw):
    """ServerState over a real continuous-batching scheduler (started).

    Callers must close ``state.scheduler``."""
    from llmtrain_tpu.telemetry.registry import MetricsRegistry

    model, params = _tiny_model()
    engine = PagedDecodeEngine(
        model,
        params,
        block_tokens=4,
        max_batch_slots=2,
        prompt_buckets=[4, 8],
        batch_buckets=[1, 2],
    )
    registry = MetricsRegistry(None)
    scheduler = ContinuousBatchingScheduler(engine, registry=registry).start()
    defaults = dict(
        model=model,
        params=params,
        tokenizer=None,
        step=7,
        checkpoint="mem://tiny",
        max_new_tokens_cap=8,
        default_max_new_tokens=4,
        scheduler=scheduler,
        registry=registry,
    )
    return ServerState(**{**defaults, **kw})


class TestServerStats:
    def test_concurrent_record_hammer(self):
        """The satellite regression: ``requests_served += 1`` from N
        ThreadingHTTPServer handler threads was a read-modify-write race;
        every mutation now lands under the lock, so the totals are exact."""
        stats = ServerStats()
        threads_n, per_thread = 8, 250
        barrier = threading.Barrier(threads_n)

        def hammer():
            barrier.wait()  # maximize interleaving
            for _ in range(per_thread):
                stats.record(latency_ms=1.0, tokens=3)
                stats.record_error()

        threads = [threading.Thread(target=hammer) for _ in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        expected = threads_n * per_thread
        assert stats.requests_served == expected
        snap = stats.snapshot()
        assert snap["requests_served"] == expected
        assert snap["errors"] == expected
        assert snap["tokens_out"] == 3 * expected
        assert snap["mean_latency_ms"] == 1.0

    def test_latency_reservoir_is_bounded(self):
        stats = ServerStats()
        for i in range(ServerStats._RESERVOIR + 100):
            stats.record(latency_ms=float(i), tokens=1)
        snap = stats.snapshot()
        assert snap["requests_served"] == ServerStats._RESERVOIR + 100
        assert len(stats._latencies_ms) == ServerStats._RESERVOIR
        assert snap["p50_latency_ms"] is not None


class TestRequestLogic:
    def test_greedy_is_deterministic(self):
        state = _tiny_state()
        req = {"prompt_ids": [1, 2, 3], "max_new_tokens": 4, "temperature": 0.0}
        code1, out1 = _handle_generate_request(state, req)
        code2, out2 = _handle_generate_request(state, req)
        assert code1 == code2 == 200
        assert out1["completion_ids"] == out2["completion_ids"]
        assert len(out1["completion_ids"]) == 4
        assert out1["prompt_tokens"] == 3
        assert out1["latency_ms"] > 0
        assert state.requests_served == 2

    def test_default_max_new_tokens(self):
        state = _tiny_state()
        code, out = _handle_generate_request(
            state, {"prompt_ids": [5], "temperature": 0.0}
        )
        assert code == 200
        assert len(out["completion_ids"]) == state.default_max_new_tokens

    @pytest.mark.parametrize(
        "body, msg",
        [
            ({}, "exactly one"),
            ({"prompt": "x", "prompt_ids": [1]}, "exactly one"),
            ({"prompt": "hi"}, "no tokenizer"),
            ({"prompt_ids": []}, "non-empty list"),
            ({"prompt_ids": [1, "a"]}, "non-empty list"),
            ({"prompt_ids": [1], "max_new_tokens": 0}, "positive int"),
            ({"prompt_ids": [1], "max_new_tokens": 9}, "server cap"),
            ({"prompt_ids": [1], "nope": 1}, "unknown fields"),
            ({"prompt_ids": [1], "seed": "x"}, "'seed' must be an int"),
            ({"prompt_ids": list(range(14)), "max_new_tokens": 8}, "block_size"),
        ],
    )
    def test_rejections(self, body, msg):
        code, out = _handle_generate_request(_tiny_state(), body)
        assert code == 400
        assert msg in out["error"]

    def test_eos_truncates_completion(self):
        state = _tiny_state()
        code, out = _handle_generate_request(
            state, {"prompt_ids": [1, 2], "max_new_tokens": 6, "temperature": 0.0}
        )
        assert code == 200
        # Greedy on random weights repeats a token quickly; use the first
        # emitted token as a forced EOS and check truncation.
        eos = out["completion_ids"][0]
        code, out2 = _handle_generate_request(
            state,
            {
                "prompt_ids": [1, 2],
                "max_new_tokens": 6,
                "temperature": 0.0,
                "eos_token_id": eos,
            },
        )
        assert code == 200
        assert out2["completion_ids"][-1] == eos
        assert len(out2["completion_ids"]) <= 6


class TestContinuousBackend:
    """The scheduler-backed request path (serving.mode: continuous)."""

    @pytest.fixture()
    def cstate(self):
        state = _continuous_state()
        yield state
        state.scheduler.close()

    def test_greedy_matches_legacy_lock_path(self, cstate):
        """Same weights, same request: the continuous backend emits the
        same tokens the legacy one-decode-at-a-time path does, plus the
        serving extras (ttft_ms, finish_reason)."""
        body = {"prompt_ids": [1, 2, 3], "max_new_tokens": 4, "temperature": 0.0}
        code, out = _handle_generate_request(cstate, body)
        assert code == 200
        assert out["finish_reason"] == "length"
        assert out["ttft_ms"] > 0
        code2, out2 = _handle_generate_request(_tiny_state(), body)
        assert code2 == 200
        assert out["completion_ids"] == out2["completion_ids"]
        assert cstate.stats.requests_served == 1

    def test_request_error_is_500_not_a_dead_scheduler(self, cstate):
        """A request the scheduler fails (oversized for the engine,
        submitted past HTTP validation) answers 500; the NEXT request
        still succeeds — errors are per-request."""
        cstate.max_new_tokens_cap = 64  # let the bad request through
        code, out = _handle_generate_request(
            cstate,
            {"prompt_ids": [1, 2], "max_new_tokens": 14, "temperature": 0.0},
        )
        assert code == 200  # 2 + 14 fits block_size 16: sanity
        code, out = _handle_generate_request(
            cstate,
            {"prompt_ids": list(range(1, 10)), "max_new_tokens": 10,
             "temperature": 0.0},
        )
        assert code == 400  # http bound still applies
        # Paged-backend bound: a prompt past the largest prompt bucket is
        # a 400 at the boundary, not a late 500 from inside prefill.
        code, out = _handle_generate_request(
            cstate,
            {"prompt_ids": list(range(1, 11)), "max_new_tokens": 2,
             "temperature": 0.0},
        )
        assert code == 400
        assert "prompt bucket" in out["error"]
        # Bypass HTTP validation: submit an oversized ServeRequest directly.
        import numpy as np

        from llmtrain_tpu.serving import ServeRequest

        bad = ServeRequest(
            prompt_ids=np.asarray([1, 2, 3], np.int32), max_new_tokens=20
        )
        cstate.scheduler.submit(bad)
        assert bad.done.wait(timeout=60)
        assert bad.finish_reason == "error"
        code, out = _handle_generate_request(
            cstate, {"prompt_ids": [5], "max_new_tokens": 2, "temperature": 0.0}
        )
        assert code == 200  # scheduler survived

    def test_healthz_and_metrics_surfaces(self, cstate):
        """/healthz carries scheduler/KV-pool/compile stats; /metrics
        exposes llmtrain_serve_* in Prometheus text format."""
        from llmtrain_tpu.serving.http import _handle_health, _handle_metrics

        _handle_generate_request(
            cstate, {"prompt_ids": [1, 2], "max_new_tokens": 3,
                     "temperature": 0.0}
        )
        code, payload = _handle_health(cstate)
        assert code == 200
        sched = payload["scheduler"]
        assert sched["policy"] == "paged"
        assert sched["requests_finished"] == 1
        assert sched["kv_pool"]["active_sequences"] == 0
        assert sched["compile"]["within_budget"]
        code, text = _handle_metrics(cstate)
        assert code == 200
        assert "llmtrain_serve_requests_total 1" in text
        assert "llmtrain_serve_queue_depth" in text
        assert "llmtrain_serve_kv_pool_utilization" in text

    def test_metrics_404_without_registry(self):
        from llmtrain_tpu.serving.http import _handle_metrics

        code, _ = _handle_metrics(_tiny_state())
        assert code == 404


class TestTickSpanTree:
    """The span tree and work counters of a scheduler tick
    (docs/observability.md "Serving timeline"): two requests scripted
    through ``step()`` on the tiny model, every number worked out by hand.

    Engine: 16 positions in blocks of 4 (4 blocks a sequence), 2 slots,
    prompt buckets 4/8, batch buckets 1/2. Request A: 3 prompt tokens, 3 new;
    request B: 6 prompt tokens, 2 new.
    Tick 1 admits both (prefill buckets 4 and 8), then decodes both: the fed
    tokens sit at positions 3 and 6, so the rows attend 4 + 7 = 11 positions
    while the program gathers 2 rows x 4 blocks x 4 = 32; B is done.
    Tick 2 decodes A alone at position 4: 5 live of 1 x 16 gathered; A is done.
    A third ``step()`` finds nothing: its tick says ``worked: False``."""

    @pytest.fixture()
    def events(self):
        import numpy as np

        from llmtrain_tpu.serving import ServeRequest
        from llmtrain_tpu.telemetry.timeline import EventTimeline

        model, params = _tiny_model()
        engine = PagedDecodeEngine(
            model, params, block_tokens=4, max_batch_slots=2,
            prompt_buckets=[4, 8], batch_buckets=[1, 2],
        )
        timeline = EventTimeline(None)
        sched = ContinuousBatchingScheduler(engine, timeline=timeline)
        a = sched.submit(ServeRequest(
            prompt_ids=np.array([1, 2, 3], np.int32), max_new_tokens=3, temperature=0.0))
        b = sched.submit(ServeRequest(
            prompt_ids=np.array([4, 5, 6, 7, 8, 9], np.int32), max_new_tokens=2, temperature=0.0))
        assert [sched.step(), sched.step(), sched.step()] == [True, True, False]
        assert a.finish_reason == b.finish_reason == "length"
        assert (len(a.tokens), len(b.tokens)) == (3, 2)
        return [e for e in timeline.events() if e["ph"] == "X" and e["cat"] == "serve"]

    @staticmethod
    def _named(events, name):
        return [e for e in events if e["name"] == name]

    def test_one_working_tick_per_working_step(self, events):
        ticks = self._named(events, "serve/tick")
        assert [t["args"] for t in ticks] == [
            {"tick": 1, "worked": True},
            {"tick": 2, "worked": True},
            {"tick": 3, "worked": False},  # an idle poll
        ]

    def test_children_carry_the_tick_lie_inside_it_and_add_up(self, events):
        for tick in self._named(events, "serve/tick"):
            t0, t1 = tick["ts_us"], tick["ts_us"] + tick["dur_us"]
            number = tick["args"]["tick"]
            inside = [e for e in events if e is not tick and e["name"] != "serve/queue_wait"
                      and e["args"].get("tick") == number]
            assert inside
            for e in inside:
                assert t0 <= e["ts_us"] and e["ts_us"] + e["dur_us"] <= t1, e["name"]
            children = sorted(
                (e for e in inside if e["args"]["parent"] == "serve/tick"),
                key=lambda e: e["ts_us"],
            )
            assert [e["name"] for e in children] == (
                ["serve/admit", "serve/decode", "serve/emit", "serve/publish"]
                if tick["args"]["worked"] else ["serve/admit", "serve/publish"])
            for first, second in zip(children, children[1:]):
                assert first["ts_us"] + first["dur_us"] <= second["ts_us"]
            self_us = tick["dur_us"] - sum(e["dur_us"] for e in children)
            assert 0 <= self_us <= tick["dur_us"]
        # No serving span of the scheduler thread is outside a tick.
        numbers = {e["args"]["tick"] for e in events if e["name"] != "serve/queue_wait"}
        assert numbers == {1, 2, 3}

    def test_decode_counters_equal_the_hand_worked_values(self, events):
        decodes = self._named(events, "serve/decode")
        assert [d["args"] for d in decodes] == [
            {"tick": 1, "parent": "serve/tick", "batch": 2, "param_epoch": 0},
            {"tick": 2, "parent": "serve/tick", "batch": 1, "param_epoch": 0},
        ]
        # The engine counts the gather where it pads the rows. A row of 32
        # lanes folds four positions, so the per-head form reads it.
        stages = [e["args"] for e in self._named(events, "serve/engine.stage")
                  if e["args"]["call"] == "decode"]
        assert stages == [
            {"tick": 1, "parent": "serve/decode", "call": "decode",
             "kv_live_tokens": 11, "kv_gathered_tokens": 32, "kv_form": "heads"},
            {"tick": 2, "parent": "serve/decode", "call": "decode",
             "kv_live_tokens": 5, "kv_gathered_tokens": 16, "kv_form": "heads"},
        ]

    def test_prefills_nest_in_admit_and_their_calls_count_the_bucket(self, events):
        prefills = self._named(events, "serve/prefill")
        assert [
            {k: p["args"][k] for k in ("tick", "parent", "prompt_tokens", "offset")}
            for p in prefills
        ] == [
            {"tick": 1, "parent": "serve/admit", "prompt_tokens": 3, "offset": 0},
            {"tick": 1, "parent": "serve/admit", "prompt_tokens": 6, "offset": 0},
        ]
        assert all("request_id" in p["args"] and "trace_id" in p["args"] for p in prefills)
        stages = [e["args"] for e in self._named(events, "serve/engine.stage")
                  if e["args"]["call"] == "prefill"]
        assert stages == [
            {"tick": 1, "parent": "serve/prefill", "call": "prefill",
             "prompt_tokens": 3, "bucket": 4},
            {"tick": 1, "parent": "serve/prefill", "call": "prefill",
             "prompt_tokens": 6, "bucket": 8},
        ]

    def test_every_engine_call_splits_into_stage_dispatch_fetch(self, events):
        calls = sorted(
            (e for e in events if e["name"].startswith("serve/engine.")),
            key=lambda e: e["ts_us"],
        )
        got = [(e["name"].rsplit(".", 1)[1], e["args"]["call"], e["args"]["parent"],
                e["args"]["tick"]) for e in calls]
        phases = ("stage", "dispatch", "fetch")
        assert got == (
            [(ph, "prefill", "serve/prefill", 1) for ph in phases] * 2
            + [(ph, "decode", "serve/decode", 1) for ph in phases]
            + [(ph, "decode", "serve/decode", 2) for ph in phases]
        )
        # The three phases of one call lie inside the span that made it.
        for decode in self._named(events, "serve/decode"):
            mine = [e for e in calls if e["args"]["parent"] == "serve/decode"
                    and e["args"]["tick"] == decode["args"]["tick"]]
            assert sum(e["dur_us"] for e in mine) <= decode["dur_us"]
            assert all(decode["ts_us"] <= e["ts_us"]
                       and e["ts_us"] + e["dur_us"] <= decode["ts_us"] + decode["dur_us"]
                       for e in mine)


def test_run_forever_marks_idle_polls_and_works_inside_ticks():
    """``run_forever`` opens one ``serve/tick`` per poll: the polls of an
    idle loop say ``worked: False`` (about ten a second), and a request's
    prefill and decodes all lie in working ticks at the top of the tree."""
    import time

    import numpy as np

    from llmtrain_tpu.serving import ServeRequest
    from llmtrain_tpu.telemetry.timeline import EventTimeline

    model, params = _tiny_model()
    engine = PagedDecodeEngine(
        model, params, block_tokens=4, max_batch_slots=2,
        prompt_buckets=[4, 8], batch_buckets=[1, 2],
    )
    timeline = EventTimeline(None)
    sched = ContinuousBatchingScheduler(engine, timeline=timeline).start()
    try:
        time.sleep(0.35)  # three polls of the idle loop
        req = sched.submit(ServeRequest(
            prompt_ids=np.array([1, 2, 3], np.int32), max_new_tokens=3, temperature=0.0))
        assert req.done.wait(timeout=120.0) and req.finish_reason == "length"
    finally:
        sched.close()
    spans = [e for e in timeline.events() if e["ph"] == "X" and e["cat"] == "serve"]
    ticks = [e for e in spans if e["name"] == "serve/tick"]
    assert all("parent" not in t["args"] for t in ticks)
    working = {t["args"]["tick"] for t in ticks if t["args"]["worked"]}
    idle = [t for t in ticks if not t["args"]["worked"]]
    assert len(working) == 2 and 1 <= len(idle) <= 20  # ~10 polls a second, not one per 5 ms
    for first, second in zip(ticks, ticks[1:]):
        assert first["ts_us"] + first["dur_us"] <= second["ts_us"]
    calls = [e for e in spans if e["name"] in ("serve/prefill", "serve/decode")]
    assert len(calls) == 3 and {e["args"]["tick"] for e in calls} == working


class TestLiveServer:
    @pytest.fixture()
    def server(self):
        state = _tiny_state()
        httpd = make_server(state, "127.0.0.1", 0)
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        yield f"http://127.0.0.1:{httpd.server_address[1]}"
        httpd.shutdown()
        httpd.server_close()

    def _post(self, url, body):
        req = urllib.request.Request(
            url + "/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())

    def test_healthz(self, server):
        with urllib.request.urlopen(server + "/healthz", timeout=30) as resp:
            payload = json.loads(resp.read())
        assert resp.status == 200
        assert payload["status"] == "ok"
        assert payload["step"] == 7

    def test_generate_roundtrip(self, server):
        status, out = self._post(
            server, {"prompt_ids": [1, 2, 3], "max_new_tokens": 3,
                     "temperature": 0.0}
        )
        assert status == 200
        assert len(out["completion_ids"]) == 3

    def test_concurrent_requests_serialize(self, server):
        """Two simultaneous posts both succeed: the device lock queues
        them instead of interleaving decodes."""
        results = []

        def post():
            results.append(
                self._post(
                    server,
                    {"prompt_ids": [1, 2], "max_new_tokens": 2,
                     "temperature": 0.0},
                )
            )

        threads = [threading.Thread(target=post) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert len(results) == 2
        assert all(status == 200 for status, _ in results)
        # Identical greedy requests: identical outputs.
        assert results[0][1]["completion_ids"] == results[1][1]["completion_ids"]

    def test_bad_json_is_400(self, server):
        req = urllib.request.Request(
            server + "/v1/generate", data=b"{not json", method="POST"
        )
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 400

    def test_unknown_route_is_404(self, server):
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(server + "/nope", timeout=30)
        assert err.value.code == 404


class TestServeBenchCLI:
    def test_nonpositive_max_new_tokens_is_a_config_error(self, tmp_path):
        """--max-new-tokens 0 used to sail past validation, emit one
        unavoidable prefill token per request, and then fail --verify-parity
        against generate()'s empty continuation — a misleading train-failure
        exit. It must be rejected up front as a config error."""
        import yaml

        from llmtrain_tpu.cli import main
        from llmtrain_tpu.resilience.exit_codes import EXIT_CONFIG_ERROR

        cfg_path = tmp_path / "cfg.yaml"
        cfg_path.write_text(
            yaml.safe_dump(
                {
                    "run": {"name": "mnt0", "seed": 0, "device": "cpu"},
                    "model": {"name": "dummy_gpt"},
                    "data": {"name": "dummy_text"},
                    "trainer": {"max_steps": 1},
                    "mlflow": {"enabled": False},
                    "output": {"root_dir": str(tmp_path / "runs")},
                }
            )
        )
        rc = main(
            ["serve-bench", "--config", str(cfg_path), "--from", "nope",
             "--max-new-tokens", "0"]
        )
        assert rc == EXIT_CONFIG_ERROR

    @pytest.mark.slow
    def test_serve_bench_and_continuous_serve_subprocess(self, tmp_path):
        """Real CLI, one tiny checkpoint, both serving entrypoints:

        1. ``serve-bench --verify-parity`` — seeded open-loop load run;
           report.json gains the serving block with p50/p95/p99, >= 2
           sequences were concurrently in flight, the compile count is
           within the bucket budget, and batched output matched
           sequential generate() bitwise (the flag exits nonzero else).
        2. ``serve --mode continuous`` — live HTTP server; concurrent
           posts succeed and /metrics exposes llmtrain_serve_*.
        """
        import yaml

        cfg = {
            "run": {"name": "sbench", "seed": 0, "device": "cpu"},
            "model": {
                "name": "gpt",
                "block_size": 32,
                "d_model": 32,
                "n_layers": 1,
                "n_heads": 2,
                "d_ff": 64,
                "dropout": 0.0,
                "vocab_size": 64,
            },
            "data": {"name": "dummy_text"},
            "trainer": {
                "max_steps": 4,
                "micro_batch_size": 2,
                "grad_accum_steps": 1,
                "warmup_steps": 0,
                "log_every_steps": 2,
                "eval_every_steps": 4,
                "save_every_steps": 4,
            },
            "serving": {
                "mode": "continuous",
                "max_batch_slots": 4,
                "block_tokens": 8,
                "prompt_buckets": [8, 16],
                "batch_buckets": [2, 4],
            },
            "mlflow": {"enabled": False},
            "output": {"root_dir": str(tmp_path / "runs")},
        }
        cfg_path = tmp_path / "cfg.yaml"
        cfg_path.write_text(yaml.safe_dump(cfg, sort_keys=False))
        train = subprocess.run(
            [sys.executable, "-m", "llmtrain_tpu", "train", "--config",
             str(cfg_path), "--run-id", "sbench"],
            capture_output=True, text=True, timeout=600,
        )
        assert train.returncode == 0, train.stderr

        out_dir = tmp_path / "bench_report"
        bench = subprocess.run(
            [sys.executable, "-m", "llmtrain_tpu", "serve-bench",
             "--config", str(cfg_path), "--from", "sbench",
             "--requests", "6", "--rate-rps", "64", "--max-new-tokens", "6",
             "--prompt-tokens-max", "12", "--verify-parity",
             "--out", str(out_dir)],
            capture_output=True, text=True, timeout=600,
        )
        assert bench.returncode == 0, bench.stderr
        report = json.loads((out_dir / "report.json").read_text())
        serving = report["serving"]
        assert serving["requests"]["completed"] == 6
        assert serving["occupancy"]["peak"] >= 2
        for q in ("p50", "p95", "p99"):
            assert serving["slo"]["ttft_ms"][q] is not None
            assert serving["slo"]["per_token_ms"][q] is not None
        assert serving["compile"]["within_budget"] is True
        assert serving["parity"]["bitwise_identical"] is True
        assert "## Serving" in (out_dir / "report.md").read_text()

        proc = subprocess.Popen(
            [sys.executable, "-m", "llmtrain_tpu", "serve", "--config",
             str(cfg_path), "--from", "sbench", "--port", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            lines: list[str] = []
            reader = threading.Thread(
                target=lambda: lines.append(proc.stdout.readline()), daemon=True
            )
            reader.start()
            reader.join(timeout=300)
            assert lines and lines[0], "serve never printed its ready line"
            ready = json.loads(lines[0])
            assert ready["mode"] == "continuous"  # from the config
            assert ready["policy"] == "paged"
            url = f"http://127.0.0.1:{ready['port']}"
            results = []

            def post():
                req = urllib.request.Request(
                    url + "/v1/generate",
                    data=json.dumps(
                        {"prompt_ids": [1, 2, 3], "max_new_tokens": 4,
                         "temperature": 0.0}
                    ).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=300) as resp:
                    results.append(json.loads(resp.read()))

            threads = [threading.Thread(target=post) for _ in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert len(results) == 2
            assert results[0]["completion_ids"] == results[1]["completion_ids"]
            assert all("ttft_ms" in r for r in results)
            with urllib.request.urlopen(url + "/metrics", timeout=60) as resp:
                metrics = resp.read().decode()
            assert "llmtrain_serve_requests_total 2" in metrics
            assert "llmtrain_serve_kv_pool_utilization" in metrics
        finally:
            proc.terminate()
            proc.wait(timeout=30)


class TestServeCLI:
    def test_serve_subprocess_roundtrip(self, tmp_path):
        """Real CLI: train a checkpoint, boot `serve --port 0`, read the
        ready line for the bound port, round-trip a request."""
        import yaml

        cfg = {
            "run": {"name": "srv", "seed": 0, "device": "cpu"},
            "model": {
                "name": "gpt",
                "block_size": 16,
                "d_model": 32,
                "n_layers": 1,
                "n_heads": 2,
                "d_ff": 64,
                "dropout": 0.0,
                # Derived from the byte tokenizer (>= 256): "ab" encodes
                # to ids 97/98, which a small explicit vocab would reject.
                "vocab_size": None,
                "extra": {"tokenizer": "byte"},
            },
            "data": {"name": "dummy_text"},
            "trainer": {
                "max_steps": 4,
                "micro_batch_size": 2,
                "grad_accum_steps": 1,
                "warmup_steps": 0,
                "log_every_steps": 2,
                "eval_every_steps": 4,
                "save_every_steps": 4,
            },
            "mlflow": {"enabled": False},
            "output": {"root_dir": str(tmp_path / "runs")},
        }
        cfg_path = tmp_path / "cfg.yaml"
        cfg_path.write_text(yaml.safe_dump(cfg, sort_keys=False))
        train = subprocess.run(
            [sys.executable, "-m", "llmtrain_tpu", "train", "--config",
             str(cfg_path), "--run-id", "srv"],
            capture_output=True,
            text=True,
            timeout=600,
        )
        assert train.returncode == 0, train.stderr

        proc = subprocess.Popen(
            [sys.executable, "-m", "llmtrain_tpu", "serve", "--config",
             str(cfg_path), "--from", "srv", "--port", "0",
             "--max-new-tokens-cap", "8"],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        try:
            # readline() has no timeout: read the ready line through a
            # watchdog thread so a wedged server fails the test instead
            # of hanging the suite.
            lines: list[str] = []
            reader = threading.Thread(
                target=lambda: lines.append(proc.stdout.readline()), daemon=True
            )
            reader.start()
            reader.join(timeout=300)
            assert lines and lines[0], "serve never printed its ready line"
            ready = json.loads(lines[0])
            url = f"http://127.0.0.1:{ready['port']}"
            req = urllib.request.Request(
                url + "/v1/generate",
                data=json.dumps(
                    {"prompt": "ab", "max_new_tokens": 3, "temperature": 0.0}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=300) as resp:
                out = json.loads(resp.read())
            assert resp.status == 200
            assert len(out["completion_ids"]) == 3
            assert out["text"] is not None  # byte tokenizer decodes
        finally:
            proc.terminate()
            proc.wait(timeout=30)

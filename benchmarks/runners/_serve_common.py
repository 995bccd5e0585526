"""What the two serving runners share: the program's continuous paged
scheduler with weights from ``--seed``, the warm-up of the cell's own
shapes, the bookkeeping of a finished window and the comparison with the
plain reference.

The system under test is built as ``llmtrain serve`` builds it
(``cli._build_serving_backend``): ``PagedDecodeEngine`` +
``ContinuousBatchingScheduler`` with a memory-only ``EventTimeline`` and
the default request tracer; floating weights in the compute dtype (the
CLI's ``--decode-param-dtype compute`` default). Only public names of
``llmtrain_tpu.serving`` and ``llmtrain_tpu.telemetry`` are used.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any

import numpy as np

from benchmarks.lib import loadgen

TRACE_AFTER_S = 5.0  # the traced part starts this long after the window opens ...
TRACE_SECONDS = 3.0  # ... and lasts this long
PROBES = 96  # one-token requests after the window, for ``correct`` (loadgen.plan_probes)


def build_run_config(ctx) -> dict:
    traffic = ctx.traffic
    context = ctx.reference.context_length(ctx.config)
    program_model = ctx.reference.program_model(ctx.config)
    if ctx.rehearse:
        program_model["attention"] = "dense"
    slots = int(traffic["slots"]) if not ctx.rehearse else 4
    buckets = [int(b) for b in traffic["prompt_buckets"]]
    if ctx.rehearse:
        buckets = [context // 2]
    return {
        "schema_version": 1,
        "run": {"name": ctx.workload.replace(".", "_"), "seed": int(ctx.seed % (2**31 - 1)),
                "device": "cpu" if ctx.rehearse else "tpu"},
        "model": program_model,
        "data": {"name": "dummy_text"},
        "trainer": {"max_steps": 1, "micro_batch_size": 1, "warmup_steps": 0},
        "serving": {
            "mode": "continuous",
            "max_batch_slots": slots,
            "block_tokens": int(traffic.get("block_tokens", 16)),
            "prompt_buckets": buckets,
            "batch_buckets": [slots],
            "max_new_tokens_cap": context,
        },
        "mlflow": {"enabled": False},
        "output": {"root_dir": str(ctx.work_dir / "runs")},
    }


def scaled_traffic(ctx) -> dict:
    """The cell's traffic; a rehearsal shrinks lengths to the tiny model."""
    traffic = dict(ctx.traffic)
    if ctx.rehearse:
        limit = ctx.reference.context_length(ctx.config)
        traffic["prompt_tokens"] = {"median": limit // 4, "sigma": 0.4, "min": 4, "max": limit // 2}
        traffic["output_tokens"] = {"median": limit // 8, "sigma": 0.4, "min": 2, "max": limit // 4}
        traffic.update(clients=8, requests_per_client=400, ramp_seconds=1.0, rate_rps=6.0)
    return traffic


class TraceWindow(threading.Thread):
    """Profiles ``duration`` seconds starting ``after`` seconds from now, on
    a thread of its own: starting the profiler takes a second or two, and
    the load generator must not stand still for it."""

    def __init__(self, ctx, after: float, duration: float) -> None:
        super().__init__(name="bench-trace", daemon=True)
        self.ctx, self.after, self.duration = ctx, after, duration
        self.info: dict | None = None

    def run(self) -> None:
        time.sleep(self.after)
        self.info = self.ctx.start_trace()
        time.sleep(self.duration)
        self.ctx.stop_trace(self.info)

    @classmethod
    def maybe(cls, ctx, ramp: float, seconds: float):
        """A window that opens ``ramp`` seconds from now and lasts ``seconds``."""
        if not ctx.trace:
            return None
        window = cls(ctx, ramp + min(TRACE_AFTER_S, 0.3 * seconds), min(TRACE_SECONDS, 0.4 * seconds))
        window.start()
        return window

    def result(self) -> dict | None:
        self.join(timeout=120.0)
        return self.info if self.info and "t1" in self.info else None


class _Notify(threading.Event):
    """``ServeRequest.done`` that also tells the load generator."""

    def __init__(self, on_set) -> None:
        super().__init__()
        self._on_set = on_set

    def set(self) -> None:
        super().set()
        self._on_set()


class Server:
    """The system under test, from set-up to teardown."""

    def __init__(self, ctx) -> None:
        import jax
        import jax.numpy as jnp

        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.distributed import configure_compilation_cache, resolve_devices
        from llmtrain_tpu.models.lora import build_adapter
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.serving import ContinuousBatchingScheduler, PagedDecodeEngine
        from llmtrain_tpu.telemetry.registry import MetricsRegistry
        from llmtrain_tpu.telemetry.timeline import EventTimeline

        configure_compilation_cache()
        initialize_registries()
        self.ctx = ctx
        cfg = RunConfig.model_validate(build_run_config(ctx))
        resolve_devices(cfg.run.device)
        model = build_adapter(cfg).build_model(cfg)
        ref, model_cfg = ctx.reference, ctx.config
        dtype = jnp.dtype(cfg.model.dtype)
        self._make_params = jax.jit(
            lambda key: ref.program_tree(ref.make_weights(model_cfg, key, dtype), model_cfg)
        )
        params = self._make_params(ref.seed_key(ctx.seed, 1))
        scfg = cfg.serving
        self.engine = PagedDecodeEngine(
            model, params,
            block_tokens=scfg.block_tokens, num_blocks=scfg.num_blocks or None,
            max_batch_slots=scfg.max_batch_slots,
            prompt_buckets=scfg.prompt_buckets or None, batch_buckets=scfg.batch_buckets or None,
        )
        self.timeline_t0 = time.perf_counter()
        self.timeline = EventTimeline(
            None, max_events=2_000_000, xprof_annotations=cfg.telemetry.xprof_annotations
        )
        self.scheduler = ContinuousBatchingScheduler(
            self.engine, registry=MetricsRegistry(None), timeline=self.timeline
        )
        self.scheduler.start()
        self.slots = scfg.max_batch_slots
        self.buckets = list(self.engine.prompt_buckets)
        self.vocab = ref.vocab_size(model_cfg)
        self.max_positions = ref.context_length(model_cfg)

    def swap_weights(self, seed: int) -> None:
        """New weights from ``seed`` through the scheduler's own hot swap
        (calibration reads many seeds on one warm server)."""
        before = self.scheduler.hot_swaps
        self.scheduler.hot_swap(self._make_params(self.ctx.reference.seed_key(seed, 1)))
        deadline = time.monotonic() + 120.0
        while self.scheduler.hot_swaps == before:
            if time.monotonic() > deadline:
                raise RuntimeError("hot swap was not applied")
            time.sleep(0.01)

    def request(self, plan: loadgen.Planned, on_done=None):
        from llmtrain_tpu.serving import ServeRequest

        kwargs = {}
        if on_done is not None:
            kwargs["done"] = _Notify(on_done)
        return ServeRequest(
            prompt_ids=plan.prompt_ids, max_new_tokens=plan.max_new_tokens,
            temperature=0.0, eos_token_id=None, seed=plan.index, **kwargs,
        )

    def warm_up(self) -> None:
        """One request per prompt bucket, together: compiles (or loads)
        every prefill program and the one decode program."""
        rng = np.random.default_rng(0)
        reqs = []
        for bucket in self.buckets:
            n = min(bucket, self.max_positions - 8)
            plan = loadgen.Planned(
                index=0, prompt_ids=rng.integers(0, self.vocab, n).astype(np.int32),
                max_new_tokens=6,
            )
            reqs.append(self.scheduler.submit(self.request(plan)))
        for req in reqs:
            if not req.done.wait(timeout=1500.0) or req.finish_reason != "length":
                raise RuntimeError(f"warm-up request did not finish: {req.finish_reason} {req.error}")

    def probe(self, traffic: dict) -> list[loadgen.Planned]:
        """After the window, through the window's own entry and prefill
        programs: ``PROBES`` requests for one token each. The window's served
        tokens repeat one token with a wide margin (random weights, tied
        head), so only a request's FIRST token shows what precision the
        program computes in; the probes add such positions."""
        plans = loadgen.plan_probes(traffic, self.ctx.seed, self.vocab, PROBES if not self.ctx.rehearse else 8)
        reqs = [self.scheduler.submit(self.request(plan)) for plan in plans]
        t0 = time.monotonic()
        for plan, req in zip(plans, reqs):
            req.done.wait(timeout=max(0.0, t0 + 300.0 - time.monotonic()))
            self.collect(plan, req, t0)
            if plan.failed or not plan.tokens:
                raise RuntimeError(f"probe {plan.index} was not answered: {req.finish_reason} {req.error}")
        return plans

    def programs(self) -> int:
        stats = self.engine.compile_stats()
        return int(stats["prefill_programs"] + stats["decode_programs"])

    def collect(self, plan: loadgen.Planned, req, t0: float) -> None:
        """Copy the program's per-request stamps (time.monotonic) onto the
        plan, relative to the window's start."""
        plan.submitted_s = req.submitted_t - t0
        plan.first_token_s = None if req.first_token_t is None else req.first_token_t - t0
        plan.token_s = [t - t0 for t in req.token_times]
        plan.tokens = [int(t) for t in req.tokens]
        plan.finished_s = None if req.finished_t is None else req.finished_t - t0
        plan.truncated = req.finish_reason != "length" and req.first_token_t is not None and req.error is None
        plan.failed = req.finish_reason != "length" and not plan.truncated

    def spans(self, t_start_pc: float, t_end_pc: float) -> list[tuple[str, float, float, dict]]:
        """The scheduler's own timeline spans on the perf_counter clock."""
        out = []
        for ev in self.timeline.events():
            if ev.get("ph") != "X" or ev.get("cat") != "serve":
                continue  # cat "trace" repeats a request's spans when its trace is flushed
            t0 = self.timeline_t0 + ev["ts_us"] / 1e6
            if t_start_pc <= t0 < t_end_pc:
                out.append((ev["name"], t0, t0 + ev["dur_us"] / 1e6, ev.get("args") or {}))
        return out

    def close(self) -> None:
        self.scheduler.close(timeout=60.0)
        self.engine = None
        self.scheduler = None
        gc.collect()


def check_against_reference(ctx, server_buckets: list[int], plans: list[loadgen.Planned]) -> tuple[list[dict], dict]:
    """After the program is freed: the reference once over the prompt and
    the served tokens of EVERY request the window finished and every probe.
    Two numbers: the widest gap by which any served token's logit lies below
    the reference's best (an altered token, a slot that reads another's
    cache), and the mean gap of each request's FIRST token (lower precision
    loses more of those close calls, by wider gaps: PERF.md). ``ctx.control``
    reads the control's two numbers at the same positions (no decoding)."""
    ref = ctx.reference
    names = ("served_token_logit_gap", "first_token_mean_gap")
    done = [p for p in plans if not p.failed and not p.truncated and p.finished_s is not None and p.tokens]
    if not done:
        return [{"name": n, "value": float("inf"), "limit": ctx.limits[n], "detail": "no finished request"}
                for n in names], {}
    weights = ref.init_weights(ctx.config, ctx.seed)
    precision = ctx.traffic["control"]["precision"] if ctx.control else "f32"
    out = ref.served_token_gaps(
        weights, ctx.config, [(p.prompt_ids, np.asarray(p.tokens, np.int32)) for p in done],
        precision=precision, pad_to=tuple(server_buckets),
    )
    del weights
    detail = f"{len(done)} requests and probes, {out['tokens']} served tokens"
    values = (out["widest_gap"], out["first_mean_gap"])
    return [{"name": n, "value": v, "limit": ctx.limits[n], "detail": detail} for n, v in zip(names, values)], out


def check_seeds(ctx, seeds: list[int], drive) -> list[dict]:
    """The readings a limit is set from, many seeds in ONE process: the warm
    server takes each seed's weights by hot swap, serves a short window at
    the cell's own load and the probes; once it is closed and freed, the
    reference runs once per seed, in float32 for the program's numbers and
    in the control's precision for the control's, at the same positions."""
    traffic = scaled_traffic(ctx)
    ctx.seed = int(seeds[0])
    server = Server(ctx)
    server.warm_up()
    served = []
    for i, seed in enumerate(seeds):
        ctx.seed = int(seed)
        if i:
            server.swap_weights(ctx.seed)
        served.append((ctx.seed, drive(ctx, server, traffic, ctx.seconds)["finished"] + server.probe(traffic)))
    buckets = server.buckets
    server.close()
    import jax

    jax.clear_caches()
    ctx.control = True
    rows = []
    for seed, plans in served:
        ctx.seed = seed
        checks, out = check_against_reference(ctx, buckets, plans)
        row = {c["name"]: c["value"] for c in checks}
        row.update({"control:" + checks[0]["name"]: out.get("control_widest_gap"),
                    "control:" + checks[1]["name"]: out.get("control_first_mean_gap")})
        rows.append({"seed": seed, "checks": row, "detail": checks[0]["detail"]})
    return rows


def finish(ctx, server: Server, plans: list[loadgen.Planned], finished: list[loadgen.Planned], *,
           t_start_pc: float, window_s: float, end_to_end: dict, trace_info: Any, compiles: int,
           programs_before: int, extra_records: dict) -> dict:
    """Everything after the window: probes, counters, teardown, the reference."""
    spans = server.spans(t_start_pc, t_start_pc + window_s)
    probes = server.probe(scaled_traffic(ctx))
    programs_after = server.programs()
    compiled = compiles + max(0, programs_after - programs_before)
    peak = ctx.memory_peak_bytes()
    slots, buckets = server.slots, server.buckets
    server.close()
    import jax

    jax.clear_caches()
    checks, ref_out = check_against_reference(ctx, buckets, finished + probes)
    attempted = len(plans)
    failed = sum(1 for p in plans if p.failed)
    ctx.log(
        f"serve: {attempted} requests, {failed} failed or unanswered, "
        f"{sum(len(p.tokens) for p in plans)} tokens; programs {programs_before} -> {programs_after}; "
        f"compiles in window={compiled}"
    )
    return {
        "attempted": attempted,
        "failed": failed if compiled == 0 else attempted,
        "compiles_in_window": compiled,
        "checks": checks,
        "memory_peak_bytes": peak,
        "end_to_end": end_to_end,
        "reference": ref_out,
        "records": {
            "spans": [(n, a, b) for n, a, b, _ in spans],
            "span_args": [(n, a, b, args) for n, a, b, args in spans],
            "window": (t_start_pc, t_start_pc + window_s),
            "window_s": window_s,
            "slots": slots,
            "trace": trace_info,
            **extra_records,
        },
    }

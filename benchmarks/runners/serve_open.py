"""Runner ``serve_open``: open-loop arrivals at a fixed rate into the
program's continuous paged scheduler; the tails are the result.

Requests are due on a seeded schedule that does not wait for answers; each
is timed from when it was DUE. Arrivals start ``ramp_seconds`` before the
window (set-up: they load the system), run for ``--seconds`` and are
followed by a short drain in which the last arrivals get their first token.
Inter-token gaps count where they END inside the window: the drain, in which
nothing arrives and no prefill stalls a decode, would dilute the tail. A
request that fails or gets no first token counts as missing the limit.
"""

from __future__ import annotations

import time

from benchmarks.lib import loadgen
from benchmarks.runners import _serve_common as common

DRAIN_SECONDS = 5.0  # for the last arrivals' first tokens; TTFT sits near 0.4 s


def drive(ctx, server, traffic: dict, seconds: float) -> dict:
    """One window on a warm server. Arrivals start ``ramp_seconds`` before
    the window opens (they load the system and are not measured), run for
    ``seconds`` and are followed by a bounded drain. A request still decoding
    when the drain ends is cut there: it has its first token and its gaps so
    far, and is no failure. Times are relative to the window's opening."""
    plans = loadgen.plan_open(traffic, ctx.seed, seconds, server.vocab)
    ramp = float(traffic.get("ramp_seconds", 0.0))
    compiles = ctx.compile_counter()
    t0 = time.monotonic() + ramp
    t_start_pc = time.perf_counter() + ramp
    tracer = common.TraceWindow.maybe(ctx, ramp, seconds)
    reqs = []
    for plan in plans:
        delay = plan.due_s - (time.monotonic() - t0)
        if delay > 0:
            time.sleep(delay)  # open loop: the arrival clock, never the answers
        reqs.append(server.scheduler.submit(server.request(plan)))
    remaining = seconds - (time.monotonic() - t0)
    if remaining > 0:
        time.sleep(remaining)
    waiting_at_end = sum(1 for r in reqs if r.first_token_t is None)
    trace_info = tracer.result() if tracer else None
    deadline = time.monotonic() + DRAIN_SECONDS
    for req in reqs:
        if not req.done.wait(timeout=max(0.0, deadline - time.monotonic())):
            req.abandon()
    for req in reqs:
        req.done.wait(timeout=30.0)
    compiled = compiles.stop()
    for plan, req in zip(plans, reqs):
        server.collect(plan, req, t0)
    measured = [p for p in plans if p.due_s >= 0.0]
    ttft = loadgen.ttft_ms(measured)
    itl = loadgen.inter_token_ms(plans, start_s=0.0, end_s=seconds)
    late = loadgen.lateness_ms(measured)
    half = loadgen.ttft_ms([p for p in measured if p.due_s >= seconds / 2])
    stats = {
        "rate_rps": float(traffic["rate_rps"]), "due_in_window": len(measured), "due_in_ramp": len(plans) - len(measured),
        "failed": sum(1 for p in measured if p.failed), "cut_at_drain_end": sum(1 for p in measured if p.truncated),
        "waiting_for_first_token_at_end": waiting_at_end,
        "ttft_p50_ms": loadgen.percentile(ttft, 50), "ttft_p95_ms": loadgen.p95_with_missing(ttft, len(measured)),
        "ttft_p50_second_half_ms": loadgen.percentile(half, 50), "ttft_max_ms": max(ttft) if ttft else None,
        "itl_p50_ms": loadgen.percentile(itl, 50), "itl_p95_ms": loadgen.percentile(itl, 95),
        "itl_p99_ms": loadgen.percentile(itl, 99), "itl_gaps": len(itl),
        "lateness_p95_ms": loadgen.percentile(late, 95), "lateness_max_ms": max(late, default=None),
    }
    ctx.log(f"open loop: {stats}")
    return {"plans": measured, "finished": plans, "t_start_pc": t_start_pc, "trace_info": trace_info,
            "compiled": compiled, "stats": stats, "ttft": ttft}


def run(ctx) -> dict:
    traffic = common.scaled_traffic(ctx)
    server = common.Server(ctx)
    server.warm_up()
    programs_before = server.programs()
    ctx.mark_window_start(time.perf_counter() + float(traffic.get("ramp_seconds", 0.0)))
    out = drive(ctx, server, traffic, ctx.seconds)
    # The 99th percentile, not the 95th: a gap that holds a prefill call is longer by the prompt
    # bucket's call (85 -> 101 / 106 / 123 ms at gpt2-xl), so the gaps are four plateaus with steps
    # between them, and a percentile that lies ON a step (the 95th did: 4.5-5.0% of the gaps hold a
    # prefill call at 0.6 requests/s) reads 86 or 100 ms by chance (PERF.md, PR 40). The 99th lies
    # inside the longest bucket's plateau and has some 28 of 2,800 gaps beyond it.
    end_to_end = {"serve_itl_p99_ms": out["stats"]["itl_p99_ms"]}
    return common.finish(
        ctx, server, out["plans"], out["finished"], t_start_pc=out["t_start_pc"], window_s=ctx.seconds,
        end_to_end=end_to_end, trace_info=out["trace_info"], compiles=out["compiled"],
        programs_before=programs_before,
        extra_records={"ttft_ms": out["ttft"], "stats": out["stats"]},
    )


def sweep(ctx, rates: list[float], seconds: float) -> list[dict]:
    """The knee, found ONCE: the same warm server under each rate in turn."""
    server = common.Server(ctx)
    server.warm_up()
    rows = []
    for rate in rates:
        traffic = dict(common.scaled_traffic(ctx), rate_rps=rate)
        rows.append(drive(ctx, server, traffic, seconds)["stats"])
    server.close()
    return rows


def check_seeds(ctx, seeds: list[int]) -> list[dict]:
    return common.check_seeds(ctx, seeds, drive)

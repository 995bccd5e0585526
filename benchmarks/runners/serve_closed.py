"""Runner ``serve_closed``: a closed loop of clients, each sending its next
request when its last is answered (batch inference: callers that wait);
output tokens per second is the result.

The window opens and closes on the system's own step boundaries, as the
training window does on log boundaries: it runs from the first token stamp
at or after the ramp's end to the first token stamp at or after ``--seconds``
later, and counts every output token stamped inside it, whichever request
it belongs to. (Counting whole requests on a wall-clock window made the
rate jump by one request, 1%, from run to run: PERF.md, PR 23.)

One load thread (this one) is woken by each answer through the request's
own ``done`` event and submits that client's next request at once.
"""

from __future__ import annotations

import queue
import time

from benchmarks.lib import loadgen
from benchmarks.runners import _serve_common as common

OVERRUN_S = 1.0  # the loop runs this long past the window, so a step boundary past its end exists


def drive(ctx, server, traffic: dict, seconds: float) -> dict:
    """One window on a warm server: every client sends at once, then each
    sends its next request when its last is answered, until the clock ends."""
    clients = loadgen.plan_closed(traffic, ctx.seed, server.vocab)
    compiles = ctx.compile_counter()
    answered: queue.SimpleQueue[int] = queue.SimpleQueue()
    cursor = [0] * len(clients)
    sent: list[tuple[loadgen.Planned, object]] = []

    def send(client: int) -> None:
        if cursor[client] >= len(clients[client]):
            return
        plan = clients[client][cursor[client]]
        cursor[client] += 1
        req = server.request(plan, on_done=lambda c=client: answered.put(c))
        sent.append((plan, req))
        server.scheduler.submit(req)

    ramp = float(traffic.get("ramp_seconds", 0.0))  # the loop fills the slots before the window opens
    t_start_pc = time.perf_counter() + ramp
    t0 = time.monotonic() + ramp
    for client in range(len(clients)):
        send(client)
    tracer = common.TraceWindow.maybe(ctx, ramp, seconds)
    while True:
        now = time.monotonic() - t0
        if now >= seconds + OVERRUN_S:
            break
        try:
            client = answered.get(timeout=max(0.001, min(0.05, seconds + OVERRUN_S - now)))
        except queue.Empty:
            continue
        send(client)
    trace_info = tracer.result() if tracer else None
    compiled = compiles.stop()
    # The window is closed: what is still in flight is shed, not awaited.
    for _, req in sent:
        if not req.done.is_set():
            req.abandon()
    deadline = time.monotonic() + 30.0
    for _, req in sent:
        req.done.wait(timeout=max(0.0, deadline - time.monotonic()))
    for plan, req in sent:
        server.collect(plan, req, t0)
        plan.failed = req.finish_reason == "error"  # shed at the window's end is no failure
    stamps = sorted(t for p, _ in sent for t in p.token_s)
    opened = next((t for t in stamps if t >= 0.0), None)
    closed = next((t for t in stamps if opened is not None and t >= opened + seconds), None)
    if opened is None or closed is None:
        raise RuntimeError("no token was stamped at the window's edges: the server stalled")
    tokens = sum(1 for t in stamps if opened < t <= closed)
    window_s = closed - opened
    completed = [
        p for p, r in sent
        if r.finish_reason == "length" and p.finished_s is not None and opened < p.finished_s <= closed
    ]
    ctx.log(
        f"closed loop, {len(clients)} clients on {server.slots} slots: {len(sent)} sent, "
        f"{len(completed)} completed, {tokens} tokens stamped in the window of {window_s:.4f}s "
        f"(opened {opened:.4f}s after the ramp) = {tokens / window_s:.2f} tokens/s"
    )
    return {"plans": completed, "finished": completed, "t_start_pc": t_start_pc + opened, "trace_info": trace_info,
            "compiled": compiled, "window_s": window_s, "tokens": tokens, "sent": len(sent),
            "errors": sum(1 for p, _ in sent if p.failed)}


def run(ctx) -> dict:
    traffic = common.scaled_traffic(ctx)
    server = common.Server(ctx)
    server.warm_up()
    programs_before = server.programs()
    ctx.mark_window_start(time.perf_counter() + float(traffic.get("ramp_seconds", 0.0)))
    out = drive(ctx, server, traffic, ctx.seconds)
    result = common.finish(
        ctx, server, out["plans"], out["finished"], t_start_pc=out["t_start_pc"], window_s=out["window_s"],
        end_to_end={"serve_tokens_per_s": out["tokens"] / out["window_s"]},
        trace_info=out["trace_info"], compiles=out["compiled"], programs_before=programs_before,
        extra_records={"sent": out["sent"]},
    )
    if not result["compiles_in_window"]:
        result["failed"] = out["errors"]
    return result


def check_seeds(ctx, seeds: list[int]) -> list[dict]:
    return common.check_seeds(ctx, seeds, drive)

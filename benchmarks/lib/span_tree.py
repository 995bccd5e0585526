"""Arithmetic over the scheduler's span tree that several serving readers
share. ``run["records"]["span_args"]`` holds every ``cat: "serve"`` span
that STARTS in the measured window as ``(name, t0, t1, args)`` on the
``perf_counter`` clock (runners/_serve_common.py:Server.spans).

The tree of one scheduler tick (docs/observability.md, "Serving timeline"):
``serve/tick`` > ``serve/admit`` > ``serve/prefill``; ``serve/tick`` >
``serve/decode``, ``serve/emit``, ``serve/publish``; every engine call >
``serve/engine.stage``, ``.dispatch``, ``.fetch`` (``args["call"]`` names the
program). Spans of one tick share ``args["tick"]``; ``args["parent"]`` names
the enclosing span. ``step()`` opens a tick on every poll and marks it
``worked``; the thread's idle time is the window less its working ticks. A program
that records none of this (the parent of the PR that added it) gives every
function here nothing to read, and they return ``None``.
"""

from __future__ import annotations

import statistics

Span = tuple[str, float, float, dict]


def spans(run: dict, name: str) -> list[Span]:
    return [s for s in run["records"].get("span_args", ()) if s[0] == name]


def _ms(span: Span) -> float:
    return (span[2] - span[1]) * 1e3


def _ratio(num: float, den: float) -> float | None:
    return num / den if den else None


def _stages(run: dict, call: str, counter: str) -> list[dict]:
    """Args of the ``serve/engine.stage`` spans of one program that counted."""
    return [s[3] for s in spans(run, "serve/engine.stage") if s[3].get("call") == call and counter in s[3]]


def scheduler_self_ms(run: dict) -> float | None:
    """Median over the window's working ticks of the tick's duration minus what its
    ``serve/prefill`` and ``serve/decode`` spans cover (joined by ``tick``):
    what the scheduler thread spends on admission, row building, emitting,
    retiring and publishing."""
    covered: dict[int, float] = {}
    for name in ("serve/prefill", "serve/decode"):
        for span in spans(run, name):
            tick = span[3].get("tick")
            if tick is not None:
                covered[tick] = covered.get(tick, 0.0) + _ms(span)
    own = [_ms(t) - covered.get(t[3].get("tick"), 0.0) for t in spans(run, "serve/tick") if t[3].get("worked")]
    return statistics.median(own) if own else None


def engine_host_ms(run: dict) -> float | None:
    """Median over the window's decode calls of the host's part of the
    call: ``serve/engine.stage`` + ``serve/engine.dispatch`` + whatever of
    the ``serve/decode`` span no engine child covers (``fetch``, the wait
    for the device, is the rest)."""
    fetches: dict[int, list[Span]] = {}
    for span in spans(run, "serve/engine.fetch"):
        if span[3].get("parent") == "serve/decode":
            fetches.setdefault(span[3].get("tick"), []).append(span)
    host = []
    for decode in spans(run, "serve/decode"):
        mine = [f for f in fetches.get(decode[3].get("tick"), ()) if decode[1] <= f[1] and f[2] <= decode[2]]
        if mine:  # a program that records no engine spans gives nothing to read
            host.append(_ms(decode) - sum(_ms(f) for f in mine))
    return statistics.median(host) if host else None


def device_wait_share(run: dict) -> float | None:
    """Summed ``serve/engine.fetch`` (any call) over the window, in %: the
    share of wall time the scheduler thread is blocked on the device."""
    fetches = spans(run, "serve/engine.fetch")
    window_s = run["records"].get("window_s")
    if not fetches or not window_s:
        return None
    return 100.0 * sum(t1 - t0 for _, t0, t1, _ in fetches) / window_s


def kv_read_useful_share(run: dict) -> float | None:
    """Positions the decode rows attend over positions the decode program
    gathers (``kv_live_tokens`` / ``kv_gathered_tokens``, counted by the
    engine on the ``stage`` span of each of the window's decode calls), in %."""
    decodes = _stages(run, "decode", "kv_gathered_tokens")
    share = _ratio(sum(a["kv_live_tokens"] for a in decodes), sum(a["kv_gathered_tokens"] for a in decodes))
    return None if share is None else 100.0 * share


def prefill_pad_share(run: dict) -> float | None:
    """Padding in the window's prefill calls: 1 - prompt tokens over the
    buckets they were padded to (``prompt_tokens`` / ``bucket`` on the
    ``stage`` span of each prefill call), in %."""
    prefills = _stages(run, "prefill", "bucket")
    share = _ratio(sum(a["prompt_tokens"] for a in prefills), sum(a["bucket"] for a in prefills))
    return None if share is None else 100.0 * (1.0 - share)

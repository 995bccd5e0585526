"""Reduction from a profiler trace to busy/idle, per-op and gap numbers.

The neutral form is ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns], ...]}]}]}``; :func:`load_xplane` makes it from
the ``.xplane.pb`` the JAX profiler writes (``jax.profiler.ProfileData``,
nothing but JAX), and ``recorded_trace.json`` beside this file is a small
cut of a real v5e trace in the same form, which the tests reduce by hand.

What a TPU trace looks like (read by hand on the v5e, PR 23): one plane
per chip named ``/device:TPU:<n>``; its line ``XLA Ops`` holds every HLO
op's execution, control flow (``while``, ``conditional``) ENCLOSING the ops
of its body on the same line; ``XLA Modules`` holds one event per program
run; ``Steps`` one per step. Host threads are lines of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Iterable

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVE_PREFIXES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "send", "recv",
)
CONTROL_FLOW_PREFIXES = ("while", "conditional", "call")
# A Pallas kernel's event carries its HLO text, which names the target.
PALLAS_MARKER = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def op_label(name: str) -> str:
    """A short stable label for an op event whose name is its HLO text:
    ``%pallas_flash_attention_bwd.7 = (...) custom-call(...)`` becomes
    ``pallas_flash_attention_bwd [pallas]``; the numeric suffix goes, so the
    instances of one op in every layer add up."""
    head, _, rest = name.partition(" = ")
    stem = re.sub(r"(\.\d+)+$", "", head.strip().lstrip("%"))
    if not rest:
        return stem
    if PALLAS_MARKER in rest:
        return f"{stem} [pallas]"
    match = _OPCODE.search(" " + rest)
    return f"{stem} [{match.group(1)}]" if match else stem


def load_xplane(trace_dir: str) -> dict[str, Any]:
    """Newest ``*.xplane.pb`` under ``trace_dir`` in the neutral form."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def merged(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals: Iterable[tuple[int, int]]) -> int:
    """Total length covered by half-open ``(start, end)`` intervals."""
    return sum(e - s for s, e in merged(intervals))


def subtract_length(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def is_collective(name: str) -> bool:
    base = name.lstrip("%")
    return base.startswith(COLLECTIVE_PREFIXES)


def is_control_flow(name: str) -> bool:
    base = name.lstrip("%").split(".")[0].split(":")[0]
    return base in CONTROL_FLOW_PREFIXES


def self_times(events: list[list]) -> list[tuple[str, int, int, int]]:
    """``(name, start, dur, self_ns)`` per event of ONE line: an event's
    self time is its duration minus what the events nested in it cover."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    out: dict[int, int] = {}
    stack: list[int] = []
    for i in order:
        _, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(d, events[stack[-1]][1] + events[stack[-1]][2] - s)
        out[i] = d
        stack.append(i)
    return [(events[i][0], events[i][1], events[i][2], max(out[i], 0)) for i in order]


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PLANE_PREFIX)]


def _op_events(plane: dict) -> list[list]:
    return [ev for line in plane["lines"] if line["name"] == OPS_LINE for ev in line["events"]]


def reduce_trace(trace: dict, window_ns: tuple[int, int] | None = None,
                 kernel_markers: tuple[str, ...] = (PALLAS_MARKER,)) -> dict[str, Any]:
    """Busy/idle, per-op self time, kernel and collective shares.

    ``window_ns`` clips to the traced steady window (trace clock); without
    it the window is first op start to last op end over all device planes.
    Numbers are averaged over the device planes that ran an op.
    ``kernel_markers``: substrings that mark a hand-written kernel's events.
    ``ops`` lists self time per :func:`op_label`, longest first.
    """
    planes = [p for p in device_planes(trace) if _op_events(p)]
    if not planes:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "ops": [], "gaps": []}
    if window_ns is None:
        starts = [ev[1] for p in planes for ev in _op_events(p)]
        ends = [ev[1] + ev[2] for p in planes for ev in _op_events(p)]
        window_ns = (min(starts), max(ends))
    w0, w1 = window_ns
    busy = kernel = coll = exposed = 0
    per_op: dict[str, int] = {}
    gaps: list[tuple[int, int]] = []
    for idx, plane in enumerate(planes):
        evs = []
        for name, s, d in _op_events(plane):
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 > s2:
                evs.append([name, s2, e2 - s2])
        spans = merged((s, s + d) for _, s, d in evs)
        busy += sum(e - s for s, e in spans)
        leaf = [(n, s, d, self_ns) for n, s, d, self_ns in self_times(evs)]
        coll_iv = merged((s, s + d) for n, s, d, _ in leaf if is_collective(n))
        comp_iv = merged(
            (s, s + d) for n, s, d, _ in leaf if not is_collective(n) and not is_control_flow(n)
        )
        coll += sum(e - s for s, e in coll_iv)
        exposed += subtract_length(coll_iv, comp_iv)
        for n, _, _, self_ns in leaf:
            if is_control_flow(n):
                continue
            label = op_label(n)
            per_op[label] = per_op.get(label, 0) + self_ns
            if kernel_markers and any(m in n for m in kernel_markers):
                kernel += self_ns
        if idx == 0:
            edges = [(w0, w0)] + spans + [(w1, w1)]
            gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    n = len(planes)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "devices": n,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / n / 1e9,
        "kernel_s": kernel / n / 1e9,
        "collective_s": coll / n / 1e9,
        "collective_exposed_s": exposed / n / 1e9,
        "ops": [[name, ns / n / 1e9] for name, ns in ops],
        "gaps": sorted(gaps, key=lambda g: g[0] - g[1]),  # longest first, trace clock ns
    }


def find_marker(trace: dict, name: str) -> int | None:
    """Start (trace clock, ns) of the first host event called ``name``."""
    best = None
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            for ev_name, start, _ in line["events"]:
                if ev_name == name and (best is None or start < best):
                    best = start
    return best


def name_gaps(gaps: list[tuple[int, int]], host_spans: list[tuple[str, int, int]],
              top: int = 10) -> list[list]:
    """Attribute each idle gap (trace clock) to the host span covering most
    of it; sum by span name; the ``top`` largest as ``[name, seconds]``."""
    by_name: dict[str, int] = {}
    for g0, g1 in gaps:
        best_name, best_cover = "(no host span)", 0
        for name, s, e in host_spans:
            cover = min(g1, e) - max(g0, s)
            if cover > best_cover:
                best_name, best_cover = name, cover
        by_name[best_name] = by_name.get(best_name, 0) + (g1 - g0)
    rows = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in rows]


def cut(trace: dict, n_ops: int = 60) -> dict:
    """A small cut of a trace for the recorded fixture: ``n_ops`` consecutive
    events from the middle of each device's op line, and from every other
    line the events that overlap that stretch."""
    planes = []
    for plane in trace["planes"]:
        ops = sorted(_op_events(plane), key=lambda ev: ev[1])
        if not ops:
            continue
        mid = ops[len(ops) // 2 : len(ops) // 2 + n_ops]
        lo, hi = mid[0][1], max(ev[1] + ev[2] for ev in mid)
        lines = []
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                keep = [ev for ev in line["events"] if lo <= ev[1] and ev[1] + ev[2] <= hi]
            else:
                keep = [ev for ev in line["events"] if ev[1] < hi and ev[1] + ev[2] > lo][:20]
            lines.append({"name": line["name"], "events": keep})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}

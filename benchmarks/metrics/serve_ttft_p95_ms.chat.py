"""serving scheduler: 95th percentile of DUE time -> first token over the
requests due in the window (a failed or unanswered one counts as missing).
A per-layer reading, not an end-to-end metric: at today's service rate a
window holds some twenty requests and the p95 of twenty swings by 3-6%."""

from benchmarks.lib.loadgen import p95_with_missing


def read(run):
    rec = run["records"]
    if "ttft_ms" not in rec or not rec.get("stats"):
        return None
    return p95_with_missing(rec["ttft_ms"], rec["stats"]["due_in_window"])

"""serving scheduler: 95th percentile of the gaps between consecutive tokens of one request that
END inside the window: the end-to-end metric until PR 40. A per-layer reading since: at 0.6
requests/s 4.5-5.0% of the gaps hold a prefill call, so it reads the longest plain tick (86 ms) or
the shortest prefill tick (100 ms) by chance; ``serve_itl_p99_ms`` is the tail that is judged."""


def read(run):
    stats = run["records"].get("stats")
    return None if not stats else stats.get("itl_p95_ms")

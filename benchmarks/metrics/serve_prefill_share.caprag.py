"""engine: summed ``serve/prefill`` spans (one prompt of 256-6,144 tokens per
call, window and causal attention by blocks) over the window: the share of
the scheduler's time that goes to prefill calls."""


def read(run):
    rec = run["records"]
    total = sum(t1 - t0 for name, t0, t1 in rec.get("spans", ()) if name == "serve/prefill")
    return 100.0 * total / rec["window_s"] if rec.get("window_s") else None

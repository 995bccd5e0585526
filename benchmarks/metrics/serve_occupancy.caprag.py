"""serving scheduler: mean number of sequences in a decode step (the
``batch`` argument of each ``serve/decode`` span) over the slots, in %: under
100 where a slot waits for its prompt's prefill or the queue runs dry."""

from benchmarks.lib.span_tree import spans


def read(run):
    sizes = [s[3]["batch"] for s in spans(run, "serve/decode") if s[3].get("batch") is not None]
    slots = run["records"].get("slots")
    if not sizes or not slots:
        return None
    return 100.0 * sum(sizes) / len(sizes) / slots

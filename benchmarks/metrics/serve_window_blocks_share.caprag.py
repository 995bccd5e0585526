"""engine (the pool's window blocks): over the window's decode calls, the
window blocks the pool had bound at the call (``window_blocks_bound`` of the
call's ``serve/engine.stage`` span: the pool's own accounting, all sequences
it holds) over what a window layer would bind with NO window (one block for
every block the global layer holds: ``global_blocks_bound`` of the same
span), in %. It falls under 100% only if a sequence past the window really
holds no more than its ring. A program that counts neither (a model without
window layers, or the parent of the PR that added the counters) gives nothing
to read."""

from benchmarks.lib.span_tree import spans


def read(run):
    stages = [s[3] for s in spans(run, "serve/engine.stage")
              if s[3].get("call") == "decode" and "window_blocks_bound" in s[3]]
    unbounded = sum(a["global_blocks_bound"] for a in stages)
    if not unbounded:
        return None
    return 100.0 * sum(a["window_blocks_bound"] for a in stages) / unbounded

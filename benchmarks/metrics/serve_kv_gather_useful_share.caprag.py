"""engine (window + global layers): positions the decode rows attend over
positions the decode program gathers, over the layers of both kinds, from the
decode calls' ``serve/engine.stage`` counters, in %. A global layer attends
``kv_live_tokens`` of the ``kv_gathered_tokens`` its block tables name; a
window layer attends ``kv_window_tokens`` of the ``kv_window_gathered_tokens``
its ring tables name (every row's whole ring, whatever the row's depth). The
two layer counts are the family's (``reference/<family>.py:layer_counts``). A
program that counts no ``kv_window_gathered_tokens`` (a model without window
layers, or the parent of the PR that added the counter) gives nothing to
read."""

from benchmarks.lib.span_tree import spans


def read(run):
    ref = run["reference"]
    stages = [s[3] for s in spans(run, "serve/engine.stage")
              if s[3].get("call") == "decode" and "kv_window_gathered_tokens" in s[3]]
    if not stages or not hasattr(ref, "layer_counts"):
        return None
    window_layers, global_layers = ref.layer_counts(run["config"])
    live = sum(global_layers * a["kv_live_tokens"] + window_layers * a["kv_window_tokens"] for a in stages)
    gathered = sum(
        global_layers * a["kv_gathered_tokens"] + window_layers * a["kv_window_gathered_tokens"] for a in stages
    )
    return 100.0 * live / gathered if gathered else None

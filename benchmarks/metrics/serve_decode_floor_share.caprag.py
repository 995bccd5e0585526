"""engine (window + global layers, held + shared experts): over the window's
decode calls, the least time the chip could take for the bytes a call MUST
move, over the time the calls took (summed ``serve/decode`` spans), in %.

Bytes of one call: the weights every call reads (``weight_bytes``: every layer
outside its routed experts, the shared experts among them, and the head), the
routed experts some token of the call picked (the ``experts_hit`` counter of
the call's ``serve/engine.fetch`` span x ``expert_bytes``), and K and V of the
positions it attends: every live position in each GLOBAL layer
(``kv_live_tokens`` of its ``serve/engine.stage`` span) and the live positions
inside the window in each WINDOW layer (``kv_window_tokens`` of the same span,
one window layer's worth), each x ``kv_bytes_per_position`` (one layer's);
at the device's HBM bandwidth (lib/peaks.py). The byte functions and the two
layer counts are the family's (``reference/<family>.py``). A program that
counts no ``kv_window_tokens`` (a model without window layers, or the parent
of the PR that added the counter) gives nothing to read."""

from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.span_tree import spans


def read(run):
    ref, cfg = run["reference"], run["config"]
    if run["device"]["platform"] != "tpu" or not hasattr(ref, "layer_counts"):
        return None  # a share of a chip's bandwidth exists only on the chip
    decodes = lambda name: [s[3] for s in spans(run, name) if s[3].get("call") == "decode"]  # noqa: E731
    hits = [a["experts_hit"] for a in decodes("serve/engine.fetch") if "experts_hit" in a]
    stages = [a for a in decodes("serve/engine.stage") if "kv_window_tokens" in a]
    taken_s = sum(t1 - t0 for _, t0, t1, _ in spans(run, "serve/decode"))
    if not hits or not stages or not taken_s:
        return None
    window_layers, global_layers = ref.layer_counts(cfg)
    positions = sum(global_layers * a["kv_live_tokens"] + window_layers * a["kv_window_tokens"] for a in stages)
    moved = (
        len(stages) * ref.weight_bytes(cfg) + sum(hits) * ref.expert_bytes(cfg)
        + positions * ref.kv_bytes_per_position(cfg)
    )
    return 100.0 * moved / float(peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]) / taken_s

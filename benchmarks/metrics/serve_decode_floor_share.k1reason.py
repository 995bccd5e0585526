"""engine (latent pool + held experts): over the window's decode calls, the
least time the chip could take for the bytes a call MUST move, over the time
the calls took (summed ``serve/decode`` spans), in %.

Bytes of one call: the weights every call reads (``weight_bytes``: every layer
outside its routed experts, and the head), the routed experts some token of
the call picked (the ``experts_hit`` counter x ``expert_bytes``; the engine
puts the counter on the call's ``serve/engine.fetch`` span, where it comes off
the device beside the sampled tokens), and the latent rows its sequences
attend (``kv_live_tokens`` of the call's ``serve/engine.stage`` span x
``kv_bytes_per_position``); at the device's HBM bandwidth (lib/peaks.py). The
byte functions are the family's (``reference/<family>.py``). A program that
counts no ``experts_hit`` (a model without an expert layer, or the parent of
the PR that added the counter) gives nothing to read."""

from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.span_tree import spans


def read(run):
    ref, cfg = run["reference"], run["config"]
    if run["device"]["platform"] != "tpu" or not hasattr(ref, "expert_bytes"):
        return None  # a share of a chip's bandwidth exists only on the chip
    decodes = lambda name: [s[3] for s in spans(run, name) if s[3].get("call") == "decode"]  # noqa: E731
    hits = [a["experts_hit"] for a in decodes("serve/engine.fetch") if "experts_hit" in a]
    live = [a["kv_live_tokens"] for a in decodes("serve/engine.stage") if "kv_live_tokens" in a]
    taken_s = sum(t1 - t0 for _, t0, t1, _ in spans(run, "serve/decode"))
    if not hits or not taken_s:
        return None
    moved = (
        len(hits) * ref.weight_bytes(cfg) + sum(hits) * ref.expert_bytes(cfg)
        + sum(live) * ref.kv_bytes_per_position(cfg)
    )
    return 100.0 * moved / float(peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]) / taken_s

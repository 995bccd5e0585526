"""engine (paged KV + state rows): over the window's decode calls, the least
time the chip could take for the bytes a call MUST move, over the time the
calls took (summed ``serve/decode`` spans), in %.

Bytes of one call: the weights every call reads (``weight_bytes``: layers and
head), the recurrent state of its rows read and written (the ``state_bytes``
counter the engine puts on the call's ``serve/engine.stage`` span), and the
K/V its rows attend (``kv_live_tokens`` x ``kv_bytes_per_position``); at the
device's HBM bandwidth (lib/peaks.py). The byte functions are the family's
(``reference/<family>.py``). A program that counts no ``state_bytes`` (a model
without state rows, or the parent of the PR that added the counter) gives
nothing to read."""

from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.span_tree import spans


def read(run):
    ref, cfg = run["reference"], run["config"]
    if run["device"]["platform"] != "tpu" or not hasattr(ref, "weight_bytes"):
        return None  # a share of a chip's bandwidth exists only on the chip
    calls = [s[3] for s in spans(run, "serve/engine.stage") if s[3].get("call") == "decode" and "state_bytes" in s[3]]
    taken_s = sum(t1 - t0 for _, t0, t1, _ in spans(run, "serve/decode"))
    if not calls or not taken_s:
        return None
    moved = sum(
        ref.weight_bytes(cfg) + a["state_bytes"] + a["kv_live_tokens"] * ref.kv_bytes_per_position(cfg) for a in calls
    )
    return 100.0 * moved / float(peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]) / taken_s

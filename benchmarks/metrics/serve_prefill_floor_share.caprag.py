"""engine (window + global layers, held + shared experts): over the window's
prefill calls, the least time the chip could take for what a call MUST do,
over the time the calls took (summed ``serve/prefill`` spans), in %.

A call's least time is the larger of its operations at the device's bf16 peak
and its weights' bytes at the HBM bandwidth (lib/peaks.py). Operations:
``prefill_flops`` of the call's TRUE prompt length (``prompt_tokens`` of its
``serve/engine.stage`` span, not the bucket it was padded to) with the
``window_pairs`` a window layer must attend and the ``causal_pairs`` a global
one must (the same span). Bytes: ``weight_bytes`` and every held expert
(hundreds of tokens hit them all). The functions are the family's
(``reference/<family>.py``). A program that counts no ``window_pairs`` (a
model without window layers, or the parent of the PR that added the counter)
gives nothing to read."""

from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.span_tree import spans


def read(run):
    ref, cfg = run["reference"], run["config"]
    if run["device"]["platform"] != "tpu" or not hasattr(ref, "prefill_flops"):
        return None  # a share of a chip's peak exists only on the chip
    calls = [s[3] for s in spans(run, "serve/engine.stage") if s[3].get("call") == "prefill" and "window_pairs" in s[3]]
    taken_s = sum(t1 - t0 for _, t0, t1, _ in spans(run, "serve/prefill"))
    if not calls or not taken_s:
        return None
    peaks = peaks_for(run["device"]["kind"])
    held = int(cfg["experts_held"][1]) * int(cfg["num_hidden_layers"])
    weights_s = (ref.weight_bytes(cfg) + held * ref.expert_bytes(cfg)) / float(peaks["hbm_bytes_per_s"])
    least_s = sum(
        max(ref.prefill_flops(cfg, a["prompt_tokens"], a["window_pairs"], a["causal_pairs"])
            / float(peaks["bf16_flops_per_s"]), weights_s)
        for a in calls
    )
    return 100.0 * least_s / taken_s

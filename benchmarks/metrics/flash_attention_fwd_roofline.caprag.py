"""kernels: share of its roofline of the flash attention forward kernel
(``flash_attention_fwd``, every layer of every prefill call) on the SERVING
path, in the traced part of the window, in %.

Work: over the prefill calls whose ``serve/prefill`` span overlaps the traced
part, ``flash_forward_cost`` (``reference/<family>.py``; the conventions of
lib/kernel_costs.py) of each call's TRUE prompt (``prompt_tokens``,
``window_pairs``, ``causal_pairs`` of its ``serve/engine.stage`` span):
band-limited pairs in the window layers, the lower triangle in the global
ones, not the bucket's padding. A call wholly inside counts whole; one that
straddles an edge counts by the share of its span inside (its kernels, one a
layer, lie evenly through the call: off by at most one layer's kernel, a
quarter of a call here, where counting only whole calls against all of the
kernel's time swung the share by half with how many calls straddled). Time:
the kernel's device self time in the trace (label ``flash_attention_fwd
[pallas]``); the reduced trace keeps it summed, not by event, so the time
cannot be cut to the counted calls instead (PERF.md section 7). ``None`` off
the chip, without a trace, where no prefill call overlaps the traced part, or
where the program counts no ``window_pairs``."""

from benchmarks.lib.kernel_costs import least_seconds
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.span_tree import spans


def read(run):
    trace, info = run.get("trace"), run["records"].get("trace")
    ref = run["reference"]
    if not trace or not info or run["device"]["platform"] != "tpu" or not hasattr(ref, "flash_forward_cost"):
        return None
    measured = sum(seconds for label, seconds in trace.get("ops", ()) if label == "flash_attention_fwd [pallas]")
    inside = [  # (start, end, share of the span inside the traced part)
        (t0, t1, (min(t1, info["t1"]) - max(t0, info["t0"])) / (t1 - t0))
        for _, t0, t1, _ in spans(run, "serve/prefill") if t1 > t0 and min(t1, info["t1"]) > max(t0, info["t0"])
    ]
    calls = [
        (s[3], share) for s in spans(run, "serve/engine.stage")
        if s[3].get("call") == "prefill" and "window_pairs" in s[3]
        for t0, t1, share in inside if t0 <= s[1] and s[2] <= t1
    ]
    if measured <= 0.0 or not calls:
        return None
    peaks = peaks_for(run["device"]["kind"])
    least_s = sum(
        share * least_seconds(
            ref.flash_forward_cost(run["config"], a["prompt_tokens"], a["window_pairs"], a["causal_pairs"]), peaks)
        for a, share in calls
    )
    return 100.0 * least_s / measured

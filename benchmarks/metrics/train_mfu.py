"""model: tokens/s (from the median untraced step) x the benchmark's own
FLOPs per token (the family's ``train_flops_per_token`` under reference/:
6N + 12*L*T*d, N = matmul parameters, no recomputation) over chips x the
published bf16 peak (lib/peaks.py)."""

import statistics

from benchmarks.lib.peaks import peaks_for


def read(run):
    rec = run["records"]
    if not rec.get("step_seconds") or run["device"]["platform"] != "tpu":
        return None  # a share of a chip's peak exists only on the chip
    tokens_per_s = rec["tokens_per_step"] / statistics.median(rec["step_seconds"])
    flops = run["reference"].train_flops_per_token(run["config"], rec["seq_len"])
    peak = float(peaks_for(run["device"]["kind"])["bf16_flops_per_s"])
    return 100.0 * tokens_per_s * flops / (run["chips"] * peak)

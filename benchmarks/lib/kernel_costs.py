"""Operations and bytes of the train step's Pallas kernels, from shapes, and
the roofline share the per-kernel readers under ``metrics/`` report.

A kernel's roofline share is the least time the chip could take for the
calls in the traced window, ``max(ops / bf16_flops_per_s, bytes /
hbm_bytes_per_s)`` with the published peaks of ``lib/peaks.py``, over the
device self time of its events in the trace (``run["trace"]["ops"]``, label
``<name> [pallas]``: the kernel's ``name=`` in ``llmtrain_tpu/ops/``).

Conventions, the same for every kernel:

* Operations are what the algorithm needs, counted once. Recomputation is
  not counted: the fused-CE backward recomputes the logits tile in both of
  its kernels and flash attention's backward recomputes the scores in both
  of its, and neither recomputation is in the count. Work on padding (the
  vocabulary padded to the block size) is not counted either.
* Causal attention counts half of the ``T x T`` score matrix.
* Every operand is read once and every output written once, at the width
  the kernel is handed (the activations' dtype in, float32 where the kernel
  writes float32).

At GPT-2 small, micro-batch 32 x 1,024 (the one cell that reports these):
both fused-CE kernels are bound by compute (2.5 TFLOP against 0.13 GB a
call); flash attention forward by compute, narrowly (0.262 ms of matmuls
against 0.248 ms of traffic a layer and micro-batch); flash attention
backward by memory, narrowly (0.654 ms against 0.684 ms).

Nothing here runs on a device: a CPU rehearsal has no roofline, and the
readers return ``None`` off the chip.
"""

from __future__ import annotations

from typing import Any, Callable

from benchmarks.lib.peaks import peaks_for

F32 = 4
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
Cost = tuple[float, float]  # (floating-point operations, bytes) of ONE call


def fused_ce_fwd(tokens: int, d: int, vocab: int, width: int) -> Cost:
    """Logits of every token against the vocabulary, reduced to the log-sum
    and the label's logit on the way: one ``tokens x d x vocab`` matmul."""
    flops = 2.0 * tokens * d * vocab
    moved = tokens * d * width + vocab * d * width + tokens * 4 + 3 * tokens * F32
    return flops, float(moved)


def fused_ce_bwd(tokens: int, d: int, vocab: int, width: int) -> Cost:
    """``fused_ce_bwd_dh`` (dlogits x W) and ``fused_ce_bwd_dw`` (dlogits^T
    x h) together: two matmuls; each kernel reads the forward's operands
    and three per-token rows, and writes its gradient in float32."""
    flops = 4.0 * tokens * d * vocab
    operands = tokens * d * width + vocab * d * width + tokens * 4 + 3 * tokens * F32
    moved = 2 * operands + tokens * d * F32 + vocab * d * F32
    return flops, float(moved)


def flash_attention_fwd(batch: int, heads: int, seq: int, head_dim: int, width: int) -> Cost:
    """Scores and weighted values of the lower triangle: two matmuls of
    ``seq x seq x head_dim`` a head, halved. Reads q, k, v; writes the
    output and the float32 log-sum."""
    rows = batch * heads
    flops = 2 * 2.0 * seq * seq * head_dim * rows / 2
    moved = 4 * rows * seq * head_dim * width + rows * seq * F32
    return flops, float(moved)


def flash_attention_bwd(batch: int, heads: int, seq: int, head_dim: int, width: int) -> Cost:
    """``flash_attention_bwd_dq`` and ``flash_attention_bwd_dkdv`` together:
    the five matmuls of the gradient (scores, dP, dV, dQ, dK), halved. Each
    kernel reads q, k, v, dO and the float32 log-sum and delta rows; one
    writes dq, the other dk and dv."""
    rows = batch * heads
    flops = 5 * 2.0 * seq * seq * head_dim * rows / 2
    tensor = rows * seq * head_dim * width
    moved = 2 * (4 * tensor + 2 * rows * seq * F32) + 3 * tensor
    return flops, float(moved)


def _ce_cost(fn: Callable[..., Cost]) -> Callable[[dict, dict], tuple[Cost, int]]:
    def cost(model: dict, traffic: dict) -> tuple[Cost, int]:
        tokens = int(traffic["micro_batch_size"]) * int(model["block_size"])
        width = DTYPE_BYTES[model["dtype"]]
        return fn(tokens, int(model["d_model"]), int(model["vocab_size"]), width), 1

    return cost


def _attention_cost(fn: Callable[..., Cost]) -> Callable[[dict, dict], tuple[Cost, int]]:
    def cost(model: dict, traffic: dict) -> tuple[Cost, int]:
        heads = int(model["n_heads"])
        head_dim = int(model["d_model"]) // heads
        width = DTYPE_BYTES[model["dtype"]]
        one = fn(int(traffic["micro_batch_size"]), heads, int(model["block_size"]), head_dim, width)
        return one, int(model["n_layers"])

    return cost


# metric suffix -> (the kernels' names in llmtrain_tpu/ops/, (cost of one call, calls a micro-batch))
KERNELS: dict[str, tuple[tuple[str, ...], Callable[[dict, dict], tuple[Cost, int]]]] = {
    "fused_ce_fwd": (("fused_ce_fwd",), _ce_cost(fused_ce_fwd)),
    "fused_ce_bwd": (("fused_ce_bwd_dh", "fused_ce_bwd_dw"), _ce_cost(fused_ce_bwd)),
    "flash_attention_fwd": (("flash_attention_fwd",), _attention_cost(flash_attention_fwd)),
    "flash_attention_bwd": (
        ("flash_attention_bwd_dq", "flash_attention_bwd_dkdv"), _attention_cost(flash_attention_bwd),
    ),
}


def least_seconds(cost: Cost, peaks: dict[str, Any]) -> float:
    """The roofline: the larger of compute time and memory time."""
    flops, moved = cost
    return max(flops / float(peaks["bf16_flops_per_s"]), moved / float(peaks["hbm_bytes_per_s"]))


def roofline_share(run: dict, kernel: str) -> float | None:
    """``kernel``'s share of its roofline in the traced window, in %; ``None``
    off the chip, without a trace, or where the trace has no such kernel.
    The window holds ``trace_steps`` optimizer steps of ``grad_accum_steps``
    micro-batches each (runners/train.py), on one chip (the cell's mesh)."""
    trace = run.get("trace")
    if not trace or run["device"]["platform"] != "tpu":
        return None
    names, cost_of = KERNELS[kernel]
    labels = {f"{name} [pallas]" for name in names}
    measured = sum(seconds for label, seconds in trace.get("ops", ()) if label in labels)
    if measured <= 0.0:
        return None
    traffic = run["traffic"]
    model = run["reference"].program_model(run["config"], int(traffic["seq_len"]))
    one_call, calls_per_micro_batch = cost_of(model, traffic)
    calls = int(traffic["trace_steps"]) * int(traffic["grad_accum_steps"]) * calls_per_micro_batch
    return 100.0 * calls * least_seconds(one_call, peaks_for(run["device"]["kind"])) / measured

"""Tier-1 tests of the per-layer readers PR 24 added: each reader is fed
hand-made ``records`` (the scheduler's span tree as the serving runners copy
it) or a hand-made ``ops`` list, and its value is checked against arithmetic
done by hand. A program that records no such span or counter (the parent
commit) and a run off the chip give ``None``, never an error.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import run as harness  # noqa: E402
from benchmarks.lib import kernel_costs, span_tree  # noqa: E402
from benchmarks.reference import gpt2 as family  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = [m for m in BENCH["per_layer"]
       if m["name"].startswith(("kernel_roofline_share.", "serve_scheduler_self_ms.", "serve_engine_host_ms.",
                                "serve_device_wait_share.", "serve_kv_read_useful_share.",
                                "serve_prefill_pad_share."))]
ROOFLINE = [m["name"] for m in NEW if m["name"].startswith("kernel_roofline_share.")]
SERVING = [m["name"] for m in NEW if not m["name"].startswith("kernel_roofline_share.")]


def _read(metric: str, run: dict):
    return harness.load_module("metrics", metric).read(run)


# ------------------------------------------------------------- the entries


def test_the_fourteen_entries_and_their_files():
    assert len(NEW) == 14 and len(ROOFLINE) == 4
    cells = {c["name"]: c for c in BENCH["workloads"]}
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    layers = {m["layer"] for m in BENCH["per_layer"] if m not in NEW}
    for metric in NEW:
        assert (ROOT / "benchmarks" / "metrics" / f"{metric['name']}.py").is_file()
        assert metric["layer"] in layers  # no new layer name
        assert metric["workloads"] and all(w in cells for w in metric["workloads"])
        assert all(w in end_to_end[metric["moves"]]["workloads"] for w in metric["workloads"])
    assert BENCH["per_layer"][-14:] == NEW  # appended, nothing put in the middle


# ------------------------------------------------- serving: the span tree


def _tick(number: int, t0: float, decode_ms: float, fetch_ms: float, *, prefill_ms: float = 0.0,
          rows: int = 2, live: int = 100, prompt: int = 0, bucket: int = 0) -> list[tuple]:
    """One tick on the perf_counter clock, in seconds: 1 ms of admission
    (holding the prefill call, if any), then a decode call (0.5 ms stage, 0.25
    ms dispatch, ``fetch_ms`` fetch, the rest uncovered), 0.5 ms emit, 0.25 ms
    publish, and 0.5 ms of the tick nobody covers."""
    ms = 1e-3
    spans, t = [], t0
    admit0 = t
    if prefill_ms:
        args = {"tick": number, "parent": "serve/admit", "prompt_tokens": prompt, "offset": 0}
        spans.append(("serve/prefill", t + 0.5 * ms, t + (0.5 + prefill_ms) * ms, args))
        call = {"tick": number, "parent": "serve/prefill", "call": "prefill"}
        spans.append(("serve/engine.stage", t + 0.5 * ms, t + 0.75 * ms,
                      dict(call, prompt_tokens=prompt, bucket=bucket)))
        spans.append(("serve/engine.fetch", t + 0.75 * ms, t + (0.25 + prefill_ms) * ms, call))
    t += (1.0 + prefill_ms) * ms
    spans.append(("serve/admit", admit0, t, {"tick": number, "parent": "serve/tick"}))
    t += 0.25 * ms  # row building: the tick's own time
    d0 = t
    child = {"tick": number, "parent": "serve/decode", "call": "decode"}
    spans.append(("serve/engine.stage", t, t + 0.5 * ms,
                  dict(child, kv_live_tokens=live, kv_gathered_tokens=4 * 1024)))
    spans.append(("serve/engine.dispatch", t + 0.5 * ms, t + 0.75 * ms, dict(child)))
    spans.append(("serve/engine.fetch", t + 0.75 * ms, t + (0.75 + fetch_ms) * ms, dict(child)))
    t += decode_ms * ms
    spans.append(("serve/decode", d0, t, {"tick": number, "parent": "serve/tick", "batch": rows}))
    spans.append(("serve/emit", t, t + 0.5 * ms, {"tick": number, "parent": "serve/tick"}))
    spans.append(("serve/publish", t + 0.5 * ms, t + 0.75 * ms, {"tick": number, "parent": "serve/tick"}))
    t += 1.0 * ms  # emit, publish and 0.25 ms more of the tick's own
    spans.append(("serve/tick", t0, t, {"tick": number, "worked": True}))
    return spans


def _serving_run() -> dict:
    # Three ticks in a window of 0.1 s. Decode calls of 10, 12 and 20 ms with
    # fetches of 8, 9 and 17 ms; tick 2 admits a 100-token prompt in a
    # 128-bucket (6 ms, 5.5 ms of it fetch); tick 3 a 300-token prompt in 640.
    spans = (
        _tick(1, 10.000, 10.0, 8.0, live=100)
        + _tick(2, 10.020, 12.0, 9.0, prefill_ms=6.0, live=300, prompt=100, bucket=128)
        + _tick(3, 10.050, 20.0, 17.0, prefill_ms=4.0, live=624, prompt=300, bucket=640)
        + [("serve/queue_wait", 9.0, 10.02, {"request_id": 7}),  # a wait, on no thread: nobody's child
           ("serve/tick", 10.09, 10.0901, {"tick": 4, "worked": False})]  # an idle poll: no tick of work
    )
    return {"records": {"span_args": spans, "spans": [s[:3] for s in spans], "window": (10.0, 10.1),
                        "window_s": 0.1, "slots": 4},
            "trace": None, "device": {"platform": "cpu", "kind": "cpu"}}


SERVING_VALUES = {
    # tick = 1 (admit) + prefill + 0.25 + decode + 1.0; self = tick - prefill - decode = 2.25 ms, every tick
    "serve_scheduler_self_ms": 2.25,
    # decode - fetch: 2.0, 3.0, 3.0 -> median 3.0
    "serve_engine_host_ms": 3.0,
    # fetches: 8 + 9 + 17 (decode) + 5.5 + 3.5 (prefill) = 43 ms of 100 ms
    "serve_device_wait_share": 43.0,
    # (100 + 300 + 624) / (3 x 4,096) = 1,024 / 12,288
    "serve_kv_read_useful_share": 100.0 / 12.0,
    # 1 - (100 + 300) / (128 + 640)
    "serve_prefill_pad_share": 100.0 * (1.0 - 400.0 / 768.0),
}


@pytest.mark.parametrize("metric", SERVING)
def test_serving_reader_on_a_hand_made_span_tree(metric):
    assert _read(metric, _serving_run()) == pytest.approx(SERVING_VALUES[metric.rsplit(".", 1)[0]])


@pytest.mark.parametrize("metric", SERVING)
def test_serving_reader_finds_nothing_in_the_parents_spans(metric):
    """The parent of PR 24 records ``serve/queue_wait``, ``serve/prefill`` and
    ``serve/decode`` with neither ``tick`` nor counters nor engine spans:
    every new reader returns ``None`` there, and on no spans at all."""
    old = [("serve/queue_wait", 9.0, 10.0, {"request_id": 1}),
           ("serve/prefill", 10.0, 10.01, {"request_id": 1, "prompt_tokens": 100, "offset": 0}),
           ("serve/decode", 10.01, 10.02, {"request_ids": [1], "batch": 1, "param_epoch": 0})]
    run = _serving_run()
    run["records"].update(span_args=old, spans=[s[:3] for s in old])
    assert _read(metric, run) is None
    run["records"].update(span_args=[], spans=[])
    assert _read(metric, run) is None


def test_a_tick_cut_by_the_windows_edge_is_left_out():
    """Spans are kept by where they START: a tick that began before the
    window leaves children without a ``serve/tick``; they join nothing."""
    run = _serving_run()
    orphans = [s for s in _tick(0, 9.99, 15.0, 14.0) if s[0] != "serve/tick" and s[1] >= 10.0]
    run["records"]["span_args"] = orphans + run["records"]["span_args"]
    assert span_tree.scheduler_self_ms(run) == pytest.approx(2.25)


# ------------------------------------------------------ kernels: rooflines


GPT2_SMALL = json.loads((ROOT / "benchmarks" / "configs" / "gpt2-small.json").read_text())
TRAIN_64K = json.loads((ROOT / "benchmarks" / "traffic" / "train-64k.json").read_text())
# One call at micro-batch 32 x 1,024, d 768, V 50,257, 12 heads of 64, bf16.
N, D, V, HEADS, T, HD = 32 * 1024, 768, 50257, 12, 1024, 64
ROWS = 32 * HEADS


def test_kernel_costs_by_hand():
    flops, moved = kernel_costs.fused_ce_fwd(N, D, V, 2)
    assert flops == 2.0 * N * D * V
    assert moved == N * D * 2 + V * D * 2 + N * 4 + 3 * N * 4
    flops, moved = kernel_costs.fused_ce_bwd(N, D, V, 2)
    assert flops == 4.0 * N * D * V
    assert moved == 2 * (N * D * 2 + V * D * 2 + N * 4 + 3 * N * 4) + N * D * 4 + V * D * 4
    flops, moved = kernel_costs.flash_attention_fwd(32, HEADS, T, HD, 2)
    assert flops == 2 * T * T * HD * ROWS  # two matmuls of 2*T*T*hd, halved
    assert moved == 4 * ROWS * T * HD * 2 + ROWS * T * 4
    flops, moved = kernel_costs.flash_attention_bwd(32, HEADS, T, HD, 2)
    assert flops == 5 * T * T * HD * ROWS
    assert moved == 2 * (4 * ROWS * T * HD * 2 + 2 * ROWS * T * 4) + 3 * ROWS * T * HD * 2
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert kernel_costs.least_seconds((1000.0, 50.0), peaks) == 10.0  # compute binds
    assert kernel_costs.least_seconds((1000.0, 200.0), peaks) == 20.0  # memory binds


def _train_run(ops: list, platform: str = "tpu") -> dict:
    kind = "TPU v5 lite" if platform == "tpu" else "cpu"
    return {"records": {"seq_len": 1024}, "trace": {"ops": ops, "busy_s": 2.0, "window_s": 2.1},
            "config": GPT2_SMALL, "traffic": TRAIN_64K, "reference": family, "chips": 1,
            "device": {"platform": platform, "kind": kind}}


# The ledger's times of PR 23's traced run (3 steps x 2 micro-batches), under the kernels' new names.
OPS = [["fused_ce_bwd_dh [pallas]", 0.142], ["fused_ce_bwd_dw [pallas]", 0.200], ["fused_ce_fwd [pallas]", 0.206],
       ["flash_attention_bwd_dkdv [pallas]", 0.180], ["flash_attention_bwd_dq [pallas]", 0.114],
       ["flash_attention_fwd [pallas]", 0.172], ["fusion [fusion]", 0.225], ["copy [copy]", 0.105]]
CALLS = 3 * 2
ROOFLINE_VALUES = {
    "kernel_roofline_share.fused_ce_fwd": 100 * CALLS * (2.0 * N * D * V / 197e12) / 0.206,
    "kernel_roofline_share.fused_ce_bwd": 100 * CALLS * (4.0 * N * D * V / 197e12) / 0.342,
    "kernel_roofline_share.flash_attention_fwd": 100 * CALLS * 12 * (2 * T * T * HD * ROWS / 197e12) / 0.172,
    # memory binds the backward pair: its bytes over 819 GB/s
    "kernel_roofline_share.flash_attention_bwd":
        100 * CALLS * 12 * ((11 * ROWS * T * HD * 2 + 4 * ROWS * T * 4) / 819e9) / 0.294,
}


@pytest.mark.parametrize("metric", ROOFLINE)
def test_roofline_reader_on_a_hand_made_ops_list(metric):
    value = _read(metric, _train_run(OPS))
    assert value == pytest.approx(ROOFLINE_VALUES[metric], rel=1e-9)
    assert 0.0 < value < 100.0


@pytest.mark.parametrize("metric", ROOFLINE)
def test_roofline_reader_returns_none_where_there_is_nothing_to_read(metric):
    assert _read(metric, _train_run(OPS, platform="cpu")) is None  # a CPU rehearsal has no roofline
    run = _train_run(OPS)
    run["trace"] = None
    assert _read(metric, run) is None  # an untraced run
    # The parent's trace names the kernels after the enclosing transform.
    old = [["transpose_jvp___ [pallas]", 0.342], ["jvp__ [pallas]", 0.206],
           ["pallas_flash_attention_bwd [pallas]", 0.294], ["pallas_flash_attention_fwd [pallas]", 0.172]]
    assert _read(metric, _train_run(old)) is None

"""Masked-vs-packed A/B + GQA narrow-K/V train-step deltas — on one chip.

VERDICT r3 weak #6: the headline bench deliberately runs the packed fast
path (``assume_packed: True`` drops the mask operand from the Pallas
flash kernels), so the in-kernel padding masks added in round 3
(ops/pallas_attention.py) never get a measured cost, and the native GQA
grouping never gets a measured train-step benefit. This tool measures
both at the bench shape:

* packed vs masked: identical config except ``assume_packed`` — the
  delta is the mask-operand overhead (mask loads + select in-kernel).
* ``--kv-heads`` sweep: full MHA vs GQA vs MQA train step — the delta is
  the narrow-K/V saving (smaller K/V projections + kernel reads).

Usage (repo root, TPU):

    python tools/bench_mask_ab.py                 # bench shape, all cells
    python tools/bench_mask_ab.py --batch 16 --steps 5
    JAX_PLATFORMS=cpu python tools/bench_mask_ab.py --cpu-smoke

Emits one JSON line per cell. Every timed step syncs via device_get.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def _cell(
    *,
    batch: int,
    seq: int,
    steps: int,
    assume_packed: bool,
    n_kv_heads: int,
    cpu_smoke: bool,
) -> dict:
    from _bench_common import build_train_cell, make_batch, measure_cell
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.utils.hw import mfu as compute_mfu

    if cpu_smoke:
        dims = dict(d_model=64, n_layers=2, n_heads=4, d_ff=128, vocab_size=256)
    else:  # the headline bench shape (bench.py)
        dims = dict(d_model=768, n_layers=12, n_heads=12, d_ff=3072,
                    vocab_size=50257)
    extra: dict = {"tokenizer": "byte", "assume_packed": assume_packed}
    if n_kv_heads:
        extra["n_kv_heads"] = n_kv_heads
    cfg = RunConfig.model_validate(
        {
            "run": {"name": "mask-ab", "device": "cpu" if cpu_smoke else "tpu"},
            "model": {
                "name": "gpt",
                "block_size": seq,
                "dropout": 0.0,
                "dtype": "float32" if cpu_smoke else "bfloat16",
                "attention": "flash",
                "extra": extra,
                **dims,
            },
            "data": {"name": "dummy_text"},
            "trainer": {"micro_batch_size": batch, "grad_accum_steps": 1,
                        "warmup_steps": 0},
        }
    )
    step_fn, state, n_params = build_train_cell(cfg)
    mask = np.ones((1, batch, seq), dtype=np.int32)
    if not assume_packed:
        # Realistic padded batch: tails of varying length are masked out,
        # so the masked cell actually exercises the mask operand's effect
        # (an all-ones mask would measure the load but not the selects'
        # worst case; padding also matches the fine-tuning workload this
        # path exists for).
        pad = np.linspace(0, seq // 4, num=batch, dtype=np.int64)
        for i, p in enumerate(pad):
            if p:
                mask[0, i, seq - int(p):] = 0
    batch_dict = make_batch(batch, seq, dims["vocab_size"], mask=mask)

    m = measure_cell(step_fn, state, batch_dict, steps)
    step_time = m["step_time_s"]
    tokens_per_sec = batch * seq / step_time
    return {
        "cell": ("packed" if assume_packed else "masked")
        + (f"+gqa{n_kv_heads}" if n_kv_heads else ""),
        "backend": jax.default_backend(),
        "batch": batch,
        "seq": seq,
        "n_kv_heads": n_kv_heads or dims["n_heads"],
        "assume_packed": assume_packed,
        "params": n_params,
        "step_time_ms": round(step_time * 1e3, 2),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(
            compute_mfu(tokens_per_sec, n_params=n_params,
                        n_layers=dims["n_layers"], seq_len=seq,
                        d_model=dims["d_model"]), 4,
        ),
        "compile_s": round(m["compile_s"], 1),
        "loss": m["loss"],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--kv-heads", default="0,4",
                    help="comma list; 0 = full MHA (A/B runs per value)")
    ap.add_argument("--cpu-smoke", action="store_true")
    args = ap.parse_args()
    if args.cpu_smoke:
        args.batch, args.seq = 4, 128

    rows = []
    for kv in (int(s) for s in args.kv_heads.split(",")):
        for packed in (True, False):
            try:
                row = _cell(batch=args.batch, seq=args.seq, steps=args.steps,
                            assume_packed=packed, n_kv_heads=kv,
                            cpu_smoke=args.cpu_smoke)
            except Exception as exc:  # noqa: BLE001 — report OOM etc. per cell
                row = {"cell": f"{'packed' if packed else 'masked'}+kv{kv}",
                       "error": str(exc)[:200]}
            rows.append(row)
            print(json.dumps(row), flush=True)

    ok = [r for r in rows if "error" not in r]
    by = {r["cell"]: r["step_time_ms"] for r in ok}
    summary: dict = {}
    # Mask-operand overhead per kv width (masked vs packed, same kv).
    suffixes = {c[len("packed"):] for c in by if c.startswith("packed")}
    for sfx in sorted(suffixes):
        p, m_ = by.get(f"packed{sfx}"), by.get(f"masked{sfx}")
        if p and m_:
            summary[f"mask_overhead_pct{sfx or '+mha'}"] = round(
                100 * (m_ / p - 1), 2
            )
    # Narrow-K/V train-step delta per kv width (gqa vs MHA, packed path).
    if "packed" in by:
        for cell, t in by.items():
            if cell.startswith("packed+gqa"):
                summary[f"gqa_speedup_pct{cell[len('packed'):]}"] = round(
                    100 * (by["packed"] / t - 1), 2
                )
    if summary:
        print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()

"""Long-context training sweep: tokens/s + peak HBM across T.

VERDICT r2 #7: ring/Ulysses exist but the longest measured context was
4k on one chip. This sweeps single-chip T (16k-32k with remat + flash is
the target) and, with --mesh sequence=N, the SP paths on a virtual mesh.
Each cell runs a few real optimizer steps of a GPT sized to fit and
reports tokens/s, step time, and the device's peak_bytes_in_use.

Usage (repo root):

    python tools/bench_longctx.py                    # single-chip sweep
    python tools/bench_longctx.py --seqs 16384,32768 --batch 1
    JAX_PLATFORMS=cpu python tools/bench_longctx.py --seqs 1024 --cpu-smoke

Emits one JSON line per T.
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


def _peak_bytes() -> float:
    from llmtrain_tpu.utils.hw import peak_memory_bytes

    return peak_memory_bytes()


def _mem_keys() -> list[str]:
    from llmtrain_tpu.utils.hw import memory_stats_keys

    return memory_stats_keys()


def _cell(seq: int, batch: int, *, attention: str, cpu_smoke: bool,
          steps: int, window: int = 0) -> dict:
    from _bench_common import build_train_cell, make_batch, measure_cell
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.utils.hw import mfu as compute_mfu

    if cpu_smoke:
        dims = dict(d_model=64, n_layers=2, n_heads=4, d_ff=128, vocab_size=256)
    else:  # GPT-2-small body, long context
        dims = dict(d_model=768, n_layers=12, n_heads=12, d_ff=3072,
                    vocab_size=50257)
    cfg = RunConfig.model_validate(
        {
            "run": {"name": f"lc{seq}", "device": "cpu" if cpu_smoke else "tpu"},
            "model": {
                "name": "gpt",
                "block_size": seq,
                "dropout": 0.0,
                "dtype": "float32" if cpu_smoke else "bfloat16",
                "attention": attention,
                "remat": True,
                "extra": {
                    "tokenizer": "byte",
                    "loss_impl": "chunked_ce",
                    "assume_packed": True,
                    **({"sliding_window": window} if window else {}),
                },
                **dims,
            },
            "data": {"name": "dummy_text"},
            "trainer": {
                "micro_batch_size": batch,
                "grad_accum_steps": 1,
                "warmup_steps": 0,
            },
        }
    )
    # Measurement discipline (device_get-synced median of per-step times)
    # lives in _bench_common.measure_cell: blocking only on the final
    # loss once under-measured T=4k by >2x (mfu 3.78 — beyond the
    # device's peak, i.e. impossible).
    step_fn, state, n_params = build_train_cell(cfg)
    batch_dict = make_batch(batch, seq, dims["vocab_size"])
    m = measure_cell(step_fn, state, batch_dict, steps)
    step_time = m["step_time_s"]
    tokens_per_sec = batch * seq / step_time
    # One memory_stats RPC; the note keys off the ROUNDED value actually
    # recorded, so a row can never read 0.0 without its diagnostic.
    peak_hbm_gb = round(_peak_bytes() / 2**30, 3)
    return {
        "seq": seq,
        "batch": batch,
        "attention": attention,
        "window": window,
        "backend": jax.default_backend(),
        "step_time_s": round(step_time, 4),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(
            compute_mfu(tokens_per_sec, n_params=n_params,
                        n_layers=dims["n_layers"], seq_len=seq,
                        d_model=dims["d_model"]), 4,
        ),
        "peak_hbm_gb": peak_hbm_gb,
        "compile_s": round(m["compile_s"], 1),
        "loss": m["loss"],
        # r4 chip windows recorded peak_hbm_gb 0.0 in every row; when that
        # happens again, record what the device DOES report so the failure
        # is diagnosable from the artifact alone.
        **(
            {}
            if peak_hbm_gb > 0
            else {"hbm_note": f"memory_stats keys: {_mem_keys()}"}
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="4096,8192,16384,32768")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--attention", default="flash")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument(
        "--window", type=int, default=0,
        help="sliding-window size (0 = full causal); the O(T*W) cell",
    )
    ap.add_argument("--cpu-smoke", action="store_true")
    args = ap.parse_args()

    for seq in (int(s) for s in args.seqs.split(",")):
        try:
            row = _cell(seq, args.batch, attention=args.attention,
                        cpu_smoke=args.cpu_smoke, steps=args.steps,
                        window=args.window)
        except Exception as exc:  # noqa: BLE001 — report OOM etc. per cell
            row = {"seq": seq, "batch": args.batch, "error": str(exc)[:200]}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

"""Decode-scaling microbench: ms/step and tokens/s across batch sizes.

Diagnoses the KV-cache decode curve (older hand-taken figures reported a non-monotone
ms/token at batch 1/8/32 in round 1) and measures the GQA narrow-cache
effect — n_kv_heads shrinks per-step K/V cache traffic by
n_heads/n_kv_heads, which is where small-batch decode spends its HBM
bandwidth.

Usage (repo root):

    python tools/bench_decode.py                       # default sweep
    python tools/bench_decode.py --batches 1,8,32 --kv-heads 0,4,1
    LLMTRAIN_PROFILE_DIR=/tmp/tr python tools/bench_decode.py  # + traces

Emits one JSON line per (batch, n_kv_heads) cell:
    {"batch": 8, "n_kv_heads": 0, "ms_per_step": ..., "tokens_per_sec": ...}
and a final summary line. Works on CPU (tiny model smoke) and TPU (the
real measurement — GPT-2-small shape).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np


def _build_model(on_tpu: bool, n_kv_heads: int):
    from llmtrain_tpu.models.gpt import GPT

    if on_tpu:  # GPT-2-small shape, the decode config
        kw = dict(vocab_size=50257, block_size=1024, d_model=768,
                  n_layers=12, n_heads=12, d_ff=3072)
    else:  # CPU smoke
        kw = dict(vocab_size=256, block_size=128, d_model=64,
                  n_layers=2, n_heads=4, d_ff=128)
    return GPT(
        dropout=0.0,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        n_kv_heads=n_kv_heads,
        **kw,
    )


def _bench_cell(model, params, batch: int, prompt_len: int, new_tokens: int,
                repeats: int) -> dict:
    from _bench_common import time_generate

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, model.vocab_size, (batch, prompt_len)).astype(np.int32)
    ms_per_tok = time_generate(
        model, params, prompt, new_tokens=new_tokens, repeats=repeats
    )
    best = ms_per_tok * new_tokens / 1e3
    return {
        "ms_per_step": round(ms_per_tok, 3),
        "tokens_per_sec": round(batch * new_tokens / best, 1),
        "wall_s": round(best, 3),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="1,8,32")
    ap.add_argument("--kv-heads", default="0",
                    help="comma list; 0 = MHA, 1 = MQA, else GQA width")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    on_tpu = jax.default_backend() == "tpu"
    batches = [int(x) for x in args.batches.split(",")]
    kv_widths = [int(x) for x in args.kv_heads.split(",")]
    if not on_tpu:
        args.new_tokens = min(args.new_tokens, 32)

    profile_dir = os.environ.get("LLMTRAIN_PROFILE_DIR")
    rows = []
    for kvh in kv_widths:
        model = _build_model(on_tpu, kvh)
        params = model.init(
            jax.random.key(0),
            jnp.zeros((1, 8), jnp.int32),
            deterministic=True,
        )["params"]
        from flax.linen import meta as nn_meta

        params = nn_meta.unbox(params)
        for b in batches:
            if profile_dir:
                cell_dir = os.path.join(profile_dir, f"kv{kvh}_b{b}")
                with jax.profiler.trace(cell_dir):
                    cell = _bench_cell(
                        model, params, b, args.prompt_len,
                        args.new_tokens, args.repeats,
                    )
                cell["trace"] = cell_dir
            else:
                cell = _bench_cell(
                    model, params, b, args.prompt_len,
                    args.new_tokens, args.repeats,
                )
            row = {"backend": jax.default_backend(), "batch": b,
                   "n_kv_heads": kvh, **cell}
            rows.append(row)
            print(json.dumps(row), flush=True)

    print(json.dumps({"summary": rows}))


if __name__ == "__main__":
    main()

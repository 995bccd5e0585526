"""Decode per-token cost attribution by ablation (VERDICT r3 #4).

The chip decode curve is nearly batch-flat (4.4-5.0 ms/token for MHA at
batch 1/8/32, older hand-taken chip figures), i.e. dominated by a
batch-independent term. Rather than eyeballing a profiler trace, this
tool attributes the per-token cost by differencing ablations of the REAL
decode path (generation.generate, one-scan KV decode):

* ``layers``: L=12 vs L=2 at fixed vocab — the slope is the
  per-transformer-layer cost (weights traffic + per-op latency);
  extrapolated to 12 layers it is the trunk's share.
* ``vocab``: V=50257 vs V=512 at fixed depth — the delta is the
  lm_head GEMV + (B, V) sampling share.
* ``sampler``: greedy vs top-k=40/top-p=0.9 — the sort/filter share
  (the benched sweep is greedy, so this is the serving-config delta).
* ``bf16 params``: cast float params to the model compute dtype —
  the candidate fix: decode of a bf16-compute model reads f32 weights
  today, paying 2x the weight bandwidth the math needs.

Whatever the four ablations do not explain is scan/dispatch overhead +
cache update traffic (reported as ``unattributed``).

Usage (repo root):

    python tools/diag_decode.py                  # TPU: GPT-2-small shape
    JAX_PLATFORMS=cpu python tools/diag_decode.py --cpu-smoke
    python tools/diag_decode.py --batches 1,32 --kv-heads 0,4

Emits one JSON line per cell plus an attribution summary per batch.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

from llmtrain_tpu.distributed import configure_platform  # noqa: E402

if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
    configure_platform("cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _model(*, n_layers: int, vocab: int, n_kv_heads: int, cpu_smoke: bool):
    from llmtrain_tpu.models.gpt import GPT

    if cpu_smoke:
        kw = dict(block_size=128, d_model=64, n_heads=4, d_ff=128)
    else:
        kw = dict(block_size=1024, d_model=768, n_heads=12, d_ff=3072)
    return GPT(
        vocab_size=vocab,
        n_layers=n_layers,
        dropout=0.0,
        dtype=jnp.float32 if cpu_smoke else jnp.bfloat16,
        n_kv_heads=n_kv_heads,
        **kw,
    )


def _time_generate(
    model,
    params,
    batch: int,
    *,
    prompt_len: int,
    new_tokens: int,
    repeats: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
) -> float:
    from _bench_common import time_generate

    prompt = (
        np.random.default_rng(0)
        .integers(0, model.vocab_size, (batch, prompt_len))
        .astype(np.int32)
    )
    return time_generate(
        model, params, prompt, new_tokens=new_tokens, repeats=repeats,
        temperature=temperature, top_k=top_k, top_p=top_p,
    )


def _cast_params(params, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        params,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="1,8,32")
    ap.add_argument("--kv-heads", default="0")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--cpu-smoke", action="store_true")
    args = ap.parse_args()
    if args.cpu_smoke:
        args.new_tokens = min(args.new_tokens, 32)

    full_layers = 2 if args.cpu_smoke else 12
    few_layers = 1 if args.cpu_smoke else 2
    full_vocab = 256 if args.cpu_smoke else 50257
    small_vocab = 64 if args.cpu_smoke else 512

    from flax.linen import meta as nn_meta

    for kvh in (int(x) for x in args.kv_heads.split(",")):
        variants = {
            "base": _model(n_layers=full_layers, vocab=full_vocab,
                           n_kv_heads=kvh, cpu_smoke=args.cpu_smoke),
            "shallow": _model(n_layers=few_layers, vocab=full_vocab,
                              n_kv_heads=kvh, cpu_smoke=args.cpu_smoke),
            "small_vocab": _model(n_layers=full_layers, vocab=small_vocab,
                                  n_kv_heads=kvh, cpu_smoke=args.cpu_smoke),
        }
        param_sets = {}
        for name, m in variants.items():
            p = m.init(
                jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                deterministic=True,
            )["params"]
            param_sets[name] = nn_meta.unbox(p)

        for b in (int(x) for x in args.batches.split(",")):
            kw = dict(prompt_len=args.prompt_len, new_tokens=args.new_tokens,
                      repeats=args.repeats)
            base = _time_generate(variants["base"], param_sets["base"], b, **kw)
            shallow = _time_generate(
                variants["shallow"], param_sets["shallow"], b, **kw
            )
            small_v = _time_generate(
                variants["small_vocab"], param_sets["small_vocab"], b, **kw
            )
            sampled = _time_generate(
                variants["base"], param_sets["base"], b,
                temperature=0.8, top_k=40, top_p=0.9, **kw
            )
            compute_dtype = variants["base"].dtype
            cast = _time_generate(
                variants["base"],
                _cast_params(param_sets["base"], compute_dtype), b, **kw
            )

            per_layer = (base - shallow) / (full_layers - few_layers)
            trunk = per_layer * full_layers
            head_and_sample = base - small_v
            row = {
                "backend": jax.default_backend(),
                "batch": b,
                "n_kv_heads": kvh,
                "n_layers": full_layers,
                "ms_per_token": {
                    "base_greedy": round(base, 3),
                    "topk_topp": round(sampled, 3),
                    "params_cast_to_compute_dtype": round(cast, 3),
                },
                "attribution_ms": {
                    f"trunk_{full_layers}L": round(trunk, 3),
                    "lm_head_plus_sampling": round(head_and_sample, 3),
                    "sampler_delta_topk_topp": round(sampled - base, 3),
                    "unattributed_scan_cache_overhead": round(
                        base - trunk - head_and_sample, 3
                    ),
                },
                "cast_win_pct": round(100 * (1 - cast / base), 1),
            }
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

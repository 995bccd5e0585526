"""Shared single-cell measurement harness for the bench tools.

One place for the timing discipline:

* every timed step syncs by pulling the loss scalar host-side with
  ``device_get``, so the clock covers execution and not just dispatch.
* Per-step timing, median-of-steps — robust to a straggler dispatch.
* The jit train step donates the state buffers like the real Trainer.

``bench.py`` keeps its own copy of the pattern: it is the driver
contract file and must stay runnable standalone (the driver copies it
out of the repo); tools/ can share.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def build_train_cell(cfg: Any) -> tuple[Any, Any, int]:
    """(jitted step_fn, initial state, param count) for a RunConfig.

    The adapter comes from the registry (cfg.model.name), so the same
    cell harness measures any registered family (gpt, llama, ...)."""
    from flax.linen import meta as nn_meta

    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import initialize_registries
    from llmtrain_tpu.training.optimizer import build_optimizer
    from llmtrain_tpu.training.train_step import create_train_state, make_train_step

    initialize_registries()
    # build_adapter: same factory the Trainer uses, so lora configs (and
    # any future adapter wrap) measure through the identical step.
    adapter = build_adapter(cfg)
    model = adapter.build_model(cfg)
    tx = build_optimizer(cfg.trainer)
    wrap_tx = getattr(adapter, "wrap_optimizer", None)
    if wrap_tx is not None:
        tx = wrap_tx(tx)
    params = nn_meta.unbox(adapter.init_params(model, cfg, jax.random.key(0)))
    n_params = sum(int(np.prod(np.shape(x))) for x in jax.tree.leaves(params))
    state = create_train_state(params, tx)
    step_fn = jax.jit(
        make_train_step(adapter, model, tx, grad_accum_steps=1, use_dropout=False),
        donate_argnums=(0,),
    )
    return step_fn, state, n_params


def make_batch(
    batch: int, seq: int, vocab: int, mask: np.ndarray | None = None
) -> dict[str, jnp.ndarray]:
    """A deterministic (1, batch, seq) accum-shaped batch dict."""
    tokens = np.random.default_rng(0).integers(
        0, vocab, size=(1, batch, seq), dtype=np.int32
    )
    arr = jnp.asarray(tokens)
    return {
        "input_ids": arr,
        "labels": arr,
        "attention_mask": jnp.asarray(mask) if mask is not None
        else jnp.ones_like(arr),
    }


def time_generate(
    model,
    params,
    prompt: np.ndarray,
    *,
    new_tokens: int,
    repeats: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
) -> float:
    """ms/token for one-scan KV-cache decode (best of ``repeats``).

    Shared by bench_decode and diag_decode so the decode measurement
    discipline lives in one place (np.asarray pulls the tokens host-side
    — the device_get-grade sync; see module docstring).
    """
    from llmtrain_tpu.generation import generate

    def run():
        return np.asarray(
            generate(
                model, params, prompt, max_new_tokens=new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                use_cache=True,
            )
        )

    run()  # compile
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    return min(times) / new_tokens * 1e3


def measure_cell(step_fn, state, batch_dict, steps: int) -> dict:
    """Compile, then time ``steps`` device_get-synced steps (median)."""
    rng = jax.random.key(0)
    t0 = time.perf_counter()
    state, metrics = step_fn(state, batch_dict, rng)
    jax.device_get(metrics["loss"])
    compile_s = time.perf_counter() - t0

    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch_dict, rng)
        jax.device_get(metrics["loss"])
        times.append(time.perf_counter() - t0)
    return {
        "step_time_s": float(np.median(times)),
        "compile_s": compile_s,
        "loss": float(jax.device_get(metrics["loss"])),
    }

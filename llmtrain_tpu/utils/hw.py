"""Hardware peak-FLOPs lookup and MFU arithmetic.

New capability over the reference (SURVEY §5: profiling/MFU absent there —
``peak_memory`` is a hardcoded 0.0 at reference trainer.py:542). Peak numbers
are bf16 per-chip figures by TPU generation; a TPU whose ``device_kind`` is
not in the table is an error, never a default. The CPU figure is a nominal
placeholder so local smoke runs still produce a (meaningless in absolute
terms, but trend-comparable) MFU.
"""

from __future__ import annotations

# bf16 peak FLOP/s per chip by TPU generation.
TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}

CPU_NOMINAL_FLOPS = 2e11  # placeholder for local smoke runs


def peak_flops_per_chip() -> float:
    """bf16 peak FLOP/s of one local device: the table row on platform
    tpu (an unknown ``device_kind`` raises — an assumed peak makes every
    MFU derived from it fiction), the nominal placeholder off it."""
    import jax

    if jax.default_backend() != "tpu":
        return CPU_NOMINAL_FLOPS
    kind = jax.devices()[0].device_kind
    for key, peak in TPU_PEAK_FLOPS.items():
        if key in kind.lower():
            return peak
    raise ValueError(
        f"no peak FLOP/s known for TPU device_kind {kind!r}; add its row "
        f"to utils/hw.py TPU_PEAK_FLOPS (known: {sorted(TPU_PEAK_FLOPS)})"
    )


def transformer_flops_per_token(
    *,
    n_params: int,
    n_layers: int,
    seq_len: int,
    d_model: int,
    n_trainable_params: int | None = None,
) -> float:
    """Training FLOPs/token ~ 6N + 12*L*T*d (PaLM appendix B approximation).

    With frozen parameters (LoRA, models/lora.py) the dW backward pass
    only runs for the trainable subset: forward 2N + activation-gradient
    chain 2N + weight gradients 2n → ``4N + 2n``, which degrades to the
    classic 6N when everything trains. Keeping the FLOP model honest here
    keeps the reported MFU honest (a frozen-base step does less math, so
    equal throughput must not claim equal utilization).
    """
    n_t = n_params if n_trainable_params is None else n_trainable_params
    return 4.0 * n_params + 2.0 * n_t + 12.0 * n_layers * seq_len * d_model


def mfu(
    tokens_per_sec_per_chip: float,
    *,
    n_params: int,
    n_layers: int,
    seq_len: int,
    d_model: int,
    peak_flops: float | None = None,
    n_trainable_params: int | None = None,
) -> float:
    """Model FLOPs utilization of one chip at the given throughput."""
    peak = peak_flops if peak_flops is not None else peak_flops_per_chip()
    flops_per_token = transformer_flops_per_token(
        n_params=n_params,
        n_layers=n_layers,
        seq_len=seq_len,
        d_model=d_model,
        n_trainable_params=n_trainable_params,
    )
    return tokens_per_sec_per_chip * flops_per_token / peak


def peak_bytes_from_stats(stats: dict) -> float:
    """Peak device bytes out of one device's ``memory_stats()``.

    On the TPU the allocator's ``peak_bytes_in_use`` counts live arrays
    only; the temporaries of compiled programs sit in a region the runtime
    reserves beside them, ``peak_bytes_reserved`` (PR 23, GPT-2 small
    train step: 2.17 GB + 10.42 GB, against 10.96 GB of temporaries in the
    step's ``memory_analysis``). The peak is their sum, as
    ``benchmarks/run.py:Context.memory_peak_bytes`` counts it.
    ``bytes_in_use`` is a floor where a backend has no peak counter."""
    in_use = float(stats.get("peak_bytes_in_use") or stats.get("bytes_in_use") or 0.0)
    return in_use + float(stats.get("peak_bytes_reserved") or 0.0)


def peak_memory_bytes() -> float:
    """Best-effort peak device-memory bytes of the first local device.

    Single owner of the lookup (trainer metrics, bench.py, and
    tools/bench_longctx.py all report it); :func:`peak_bytes_from_stats`
    says which counters it sums. Returns 0.0 when the backend reports
    nothing (CPU PJRT)."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return 0.0
    if not stats:
        return 0.0
    return peak_bytes_from_stats(stats)


def memory_stats_keys() -> list[str]:
    """Diagnostic: the keys the first local device's memory_stats reports
    (empty list = no stats). Logged by the long-context sweep when the
    peak reads 0.0 so the record says WHY."""
    import jax

    try:
        return sorted((jax.local_devices()[0].memory_stats() or {}).keys())
    except Exception:
        return []


__all__ = [
    "TPU_PEAK_FLOPS",
    "CPU_NOMINAL_FLOPS",
    "peak_flops_per_chip",
    "transformer_flops_per_token",
    "mfu",
    "peak_bytes_from_stats",
    "peak_memory_bytes",
    "memory_stats_keys",
]

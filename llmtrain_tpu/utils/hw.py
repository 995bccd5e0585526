"""What a device can do (the one table of device kinds), and MFU arithmetic.

New capability over the reference (SURVEY §5: profiling/MFU absent there —
``peak_memory`` is a hardcoded 0.0 at reference trainer.py:542).
:data:`DEVICE_TABLE` is the program's only table keyed by device kind and
:func:`device_row` the only code that matches a ``device_kind`` string
against it: the trainer's MFU (:func:`peak_flops_per_chip`), the roofline
peaks (``telemetry/profiling.py:resolve_peaks``) and the auto-tuner's HBM
budget (``autotune/search.py:resolve_hbm_limit``) all read it. A TPU whose
``device_kind`` is not in the table is an error, never a default.
"""

from __future__ import annotations

_CARRIED = (
    "approximate public per-chip figures, no chip run behind them; "
    "telemetry.device_peaks / tune.hbm_limit_bytes override"
)
_V5E = (
    "Google Cloud docs, 'TPU v5e' (v5litepod), per chip: 197 TFLOP/s bf16, "
    "16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of ICI; the row of "
    "benchmarks/lib/peaks.py (tests/test_utils.py holds the two equal)"
)


def _row(peak_flops: float, hbm_bw: float, ici_bw: float, hbm: float, source: str) -> dict:
    return {
        "peak_flops": peak_flops,
        "hbm_bytes_per_sec": hbm_bw,
        "ici_bytes_per_sec": ici_bw,
        "hbm_bytes": hbm,
        "source": source,
    }


# One chip under the two names its device_kind has carried.
_V5E_ROW = _row(197e12, 819e9, 200e9, 16e9, _V5E)
_V6E_ROW = _row(918e12, 1640e9, 360e9, 32e9, _CARRIED)

# Per chip, by device-kind substring: bf16 FLOP/s, HBM bandwidth (bytes/s),
# aggregate ICI bandwidth (bytes/s, all links) and HBM capacity (bytes).
# The bandwidths set roofline *ratios*. The cpu row is a nominal
# placeholder so local smoke runs still give trend-comparable MFU and
# roofline classes, and an emulated-device budget generous enough for every
# smoke shape yet small enough that deliberately oversized tune candidates
# still prune.
DEVICE_TABLE: dict[str, dict[str, float | str]] = {
    "v4": _row(275e12, 1228e9, 270e9, 32e9, _CARRIED),
    "v5e": _V5E_ROW,
    "v5 lite": _V5E_ROW,
    "v5p": _row(459e12, 2765e9, 540e9, 95e9, _CARRIED),
    "v6e": _V6E_ROW,
    "v6 lite": _V6E_ROW,
    "cpu": _row(2e11, 50e9, 10e9, 8e9, "nominal placeholder, not a measurement"),
}


def device_row(device_kind: str | None = None) -> dict[str, float | str]:
    """The :data:`DEVICE_TABLE` row of ``device_kind`` (None: the first
    local jax device): the longest key that is a substring of the
    lower-cased kind. With no match, a kind on platform ``tpu`` or one that
    names a TPU raises — an assumed peak makes every MFU, roofline share and
    memory budget derived from it fiction; anything else takes the nominal
    ``cpu`` row."""
    on_tpu = False
    if device_kind is None:
        import jax

        on_tpu = jax.default_backend() == "tpu"
        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    key = max((k for k in DEVICE_TABLE if k in kind), key=len, default=None)
    if key is None:
        if on_tpu or "tpu" in kind:
            raise ValueError(
                f"no row for TPU device_kind {device_kind!r}; add a sourced "
                f"row to utils/hw.py DEVICE_TABLE (known: {sorted(DEVICE_TABLE)})"
            )
        key = "cpu"
    return DEVICE_TABLE[key]


def peak_flops_per_chip() -> float:
    """bf16 peak FLOP/s of one local device, from :func:`device_row`."""
    return float(device_row()["peak_flops"])


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

    Single owner of the lookup (the trainer's metrics report it);
    :func:`peak_bytes_from_stats` says which counters it sums. Returns 0.0
    when the backend reports nothing (CPU PJRT)."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return 0.0
    if not stats:
        return 0.0
    return peak_bytes_from_stats(stats)


__all__ = [
    "DEVICE_TABLE",
    "device_row",
    "peak_flops_per_chip",
    "transformer_flops_per_token",
    "mfu",
    "peak_bytes_from_stats",
    "peak_memory_bytes",
]

"""Per-layer activation policies: remat tiers + backward-pass host offload.

Maps the parsed ``model.extra.activation_tiers`` spec (see
config/activation_tiers.py for the grammar) onto flax block wrappers:

- ``none``      — the bare block class (save everything).
- ``selective`` — ``nn.remat`` with ``dots_saveable``: matmul outputs stay
  resident, elementwise ops replay in the backward pass.
- ``full``      — ``nn.remat`` with the default save-nothing policy.
- ``offload``   — ``nn.remat`` with
  ``save_and_offload_only_these_names``: the tagged block-input residual
  (:data:`OFFLOAD_RESIDUAL_NAME`, see ``checkpoint_name`` in the block
  bodies) is staged to the backend's ``pinned_host`` memory space between
  the forward and backward pass; everything else recomputes like ``full``.

Offload needs a ``pinned_host`` memory space on the backend.  The CPU
emulation backend exposes only ``unpinned_host`` (which *is* device memory
there), so OFF the chip :func:`resolve_activation_tiers` downgrades
``offload`` -> ``full`` with a once-per-process warning — the same
capability-probe discipline as ``trainer.zero.host_offload``
(parallel/sharding.py ``host_memory_kind``) and
``resolve_matmul_precision`` (ops/quant.py). On platform ``tpu`` a
requested tier that cannot run is an error, never a downgrade.
"""

from __future__ import annotations

import functools
import logging
from typing import Any

import jax
import jax.ad_checkpoint
from flax import linen as nn

logger = logging.getLogger("llmtrain")

# Residual name tagged via jax.ad_checkpoint.checkpoint_name at block
# entry; inert under every policy except offload's.
OFFLOAD_RESIDUAL_NAME = "block_input"

_FALLBACK_WARNED: set[str] = set()


@functools.lru_cache(maxsize=1)
def offload_supported() -> bool:
    """True when the default backend exposes a ``pinned_host`` memory
    space (real TPU/GPU runtimes; the CPU container does not)."""
    try:
        dev = jax.local_devices()[0]
        kinds = {m.kind for m in dev.addressable_memories()}
    except Exception:  # pragma: no cover - defensive: odd backends
        return False
    return "pinned_host" in kinds


def resolve_activation_tiers(tiers: tuple[str, ...]) -> tuple[str, ...]:
    """Downgrade ``offload`` to ``full`` when an off-chip backend has no
    ``pinned_host`` memory space, warning once per process; on platform
    tpu the same gap raises."""
    if "offload" not in tiers or offload_supported():
        return tiers
    if jax.default_backend() == "tpu":
        raise ValueError(
            "activation_tiers: 'offload' requested on platform tpu but the "
            "device exposes no pinned_host memory space; use 'full' — a "
            "requested tier is never swapped for another on the chip"
        )
    if "offload" not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add("offload")
        n = sum(1 for t in tiers if t == "offload")
        logger.warning(
            "activation_tiers: backend %s has no pinned_host memory space; "
            "falling back offload -> full remat for %d layer(s) "
            "(residuals recompute instead of staging to host)",
            jax.default_backend(),
            n,
        )
    return tuple("full" if t == "offload" else t for t in tiers)


def _offload_policy() -> Any:
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=[OFFLOAD_RESIDUAL_NAME],
        offload_src="device",
        offload_dst="pinned_host",
    )


def tier_block_classes(
    block_cls: Any, tiers: tuple[str, ...]
) -> dict[str, Any]:
    """One wrapped block class per tier actually used in ``tiers``.

    Built once per model ``__call__`` so flax sees a stable class per
    tier (static_argnums=(3,) keeps ``deterministic`` trace-static, same
    as the legacy ``model.remat`` wrap).
    """
    classes: dict[str, Any] = {}
    for tier in set(tiers):
        if tier == "none":
            classes[tier] = block_cls
        elif tier == "selective":
            classes[tier] = nn.remat(
                block_cls,
                static_argnums=(3,),
                policy=jax.checkpoint_policies.dots_saveable,
            )
        elif tier == "full":
            classes[tier] = nn.remat(block_cls, static_argnums=(3,))
        elif tier == "offload":
            classes[tier] = nn.remat(
                block_cls, static_argnums=(3,), policy=_offload_policy()
            )
        else:  # pragma: no cover - parser rejects unknown tiers upstream
            raise ValueError(f"unknown activation tier {tier!r}")
    return classes


def tag_block_input(x: jax.Array) -> jax.Array:
    """Tag the block-input residual for the offload checkpoint policy.

    A no-op identity under every other policy (and outside remat), so the
    blocks call it unconditionally.
    """
    return jax.ad_checkpoint.checkpoint_name(x, OFFLOAD_RESIDUAL_NAME)


__all__ = [
    "OFFLOAD_RESIDUAL_NAME",
    "offload_supported",
    "resolve_activation_tiers",
    "tag_block_input",
    "tier_block_classes",
]

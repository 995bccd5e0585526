"""Flash attention dispatch: Pallas kernels on TPU, blockwise elsewhere.

New TPU capability beyond the reference (full-matrix attention only,
reference models/gpt.py:56-69). Training differentiates through a
``jax.custom_vjp``:

* on TPU both directions run the Pallas kernels (pallas_attention.py) —
  the forward saves its logsumexp residual and the backward computes
  dq/dk/dv in two fused kernels (FlashAttention-2 scheme);
* elsewhere the backward differentiates the checkpointed XLA blockwise
  implementation.

Both paths are O(T) memory — no (T, T) materialization.

The platform decides, never the shape: on ``tpu`` a sequence length the
kernels cannot tile is an error (no silent blockwise), and
``resolved_attention_impl`` names what a run will execute for its report.
On a mesh of more than one device the call wraps itself in ``shard_map``
(batch over data×fsdp×expert, heads over tensor) — GSPMD cannot partition
a Mosaic kernel — so each chip runs the kernel on its own shard.

Key-padding masks are applied INSIDE attention on every path — flash
here, ring/ulysses in their own modules — matching the reference
(models/gpt.py:60-64): masked keys get -inf logits before the softmax.
Packed pipelines (hf_text/dummy_text windows) emit all-ones masks, for
which the masked and unmasked kernels agree exactly;
``model.extra.assume_packed`` drops the mask operand from the hot path
when the data is provably packed.

Grouped-query attention is native end to end: ``k``/``v`` may carry
n_kv_heads < n_heads — the Pallas kernels index K/V by head group and
the blockwise fallback groups queries in its einsums; K/V are never
materialized at full width on any path here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import BATCH_AXES, kernel_mesh, shard_axes
from .blockwise_attention import blockwise_attention


def _auto_block(t: int) -> tuple[int, int] | None:
    """``(resident, streamed)`` tile of the kernels for sequence length
    ``t``, or None when no legal tile divides it: the forward and dq hold
    ``resident`` query rows a program and stream keys, dk/dv holds
    ``resident`` keys and streams queries.

    Measured on the v5e at the train cell's shape, (32, 1024, 12, 64) bf16,
    at T 2,048 / 4,096 and with a 128-wide head over 4 K/V heads
    (``PERF.md`` section 6, PR 30): every kernel wants its resident block as
    large as divides T (a grid step costs what it costs, and the diagonal
    block's strips already skip what a smaller block would have skipped),
    and streams the rest 512 at a time; smaller tiles keep lengths like 384
    or 768 on the Pallas path. 1,024 rows is the cap: 2,048 was faster
    still at T 2,048 and 4,096 with a 64-wide head, and runs out of VMEM
    with a 128-wide one at T 8,192. Below the cap neither the kernel nor
    the head width moved the choice, so it is one pair from T alone.
    """
    for resident in (1024, 512, 256, 128):
        if t >= resident and t % resident == 0:
            return resident, min(resident, 512)
    return None


def _use_pallas(t: int) -> bool:
    """Pallas on platform ``tpu``, blockwise off it. A length the kernels
    cannot tile is an error on the chip, not a quiet change of path."""
    if jax.default_backend() != "tpu":
        return False
    if _auto_block(t) is None:
        raise ValueError(
            f"attention 'flash' on platform tpu needs a sequence length "
            f"that is a multiple of 128 (got T={t}); pad the sequence or "
            "use attention: dense — the Pallas kernel is never swapped "
            "for the blockwise reference on the chip"
        )
    return True


def resolved_attention_impl(attention: str) -> str:
    """What ``model.attention`` executes on this platform, for the run
    report: ``flash`` is the Pallas kernels on tpu and the XLA blockwise
    twin elsewhere; every other value runs as named."""
    if attention != "flash":
        return attention
    return "pallas_flash" if jax.default_backend() == "tpu" else "blockwise"


def _blockwise(q, k, v, key_mask=None, window=0):
    # blockwise consumes grouped-query narrow K/V natively. query_mask =
    # key_mask upgrades to segment semantics (q and k cover the same
    # sequence here), matching the Pallas kernels and dense_attention.
    return blockwise_attention(q, k, v, causal=True, key_mask=key_mask,
                               query_mask=key_mask, window=window)


def _pallas_fwd(window, q, k, v, maskf=None):
    from .pallas_attention import pallas_flash_attention_fwd

    resident, streamed = _auto_block(q.shape[1])
    return pallas_flash_attention_fwd(
        q, k, v, maskf, causal=True, block_q=resident, block_k=streamed, window=window
    )


def _pallas_bwd(window, q, k, v, out, lse, g, maskf=None):
    from .pallas_attention import pallas_flash_attention_bwd

    resident, streamed = _auto_block(q.shape[1])
    return pallas_flash_attention_bwd(
        q, k, v, out, lse, g, maskf, causal=True,
        block_q=resident, block_k=streamed,
        dkdv_block_q=streamed, dkdv_block_k=resident, window=window,
    )


# ``window`` is a static Python int (0 = off) and travels as the leading
# nondiff arg of both custom_vjps — Mistral-style sliding-window masking
# with dead K/V blocks skipped in the Pallas kernels.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(window, q, k, v):
    if _use_pallas(q.shape[1]):
        return _pallas_fwd(window, q, k, v)[0]
    return _blockwise(q, k, v, window=window)


def _flash_fwd(window, q, k, v):
    if _use_pallas(q.shape[1]):
        out, lse = _pallas_fwd(window, q, k, v)
        return out, (q, k, v, out, lse)
    return _flash(window, q, k, v), (q, k, v, None, None)


def _flash_bwd(window, residuals, g):
    q, k, v, out, lse = residuals
    if out is not None:
        return _pallas_bwd(window, q, k, v, out, lse, g)
    _, vjp = jax.vjp(lambda q_, k_, v_: _blockwise(q_, k_, v_, window=window),
                     q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


# Masked variant: the (B, T) key-padding mask travels as float32 so the
# custom_vjp can return a well-typed zero cotangent for it.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_masked(window, q, k, v, maskf):
    if _use_pallas(q.shape[1]):
        return _pallas_fwd(window, q, k, v, maskf)[0]
    return _blockwise(q, k, v, key_mask=maskf, window=window)


def _flash_masked_fwd(window, q, k, v, maskf):
    if _use_pallas(q.shape[1]):
        out, lse = _pallas_fwd(window, q, k, v, maskf)
        return out, (q, k, v, maskf, out, lse)
    return _flash_masked(window, q, k, v, maskf), (q, k, v, maskf, None, None)


def _flash_masked_bwd(window, residuals, g):
    q, k, v, maskf, out, lse = residuals
    if out is not None:
        dq, dk, dv = _pallas_bwd(window, q, k, v, out, lse, g, maskf)
        return dq, dk, dv, jnp.zeros_like(maskf)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _blockwise(q_, k_, v_, key_mask=maskf, window=window),
        q, k, v,
    )
    dq, dk, dv = vjp(g)
    return dq, dk, dv, jnp.zeros_like(maskf)


_flash_masked.defvjp(_flash_masked_fwd, _flash_masked_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    attention_mask: jax.Array | None = None,
    causal: bool = True,
    window: int = 0,
) -> jax.Array:
    """Causal attention over (B, T, H, Dh); O(T) memory, differentiable.

    ``k``/``v`` may be grouped-query narrow (B, T, Hkv, Dh).
    ``attention_mask`` is the reference's (B, T) padding mask semantics
    (nonzero = real token): masked keys are excluded inside attention.
    ``window`` > 0 restricts each query to its trailing ``window`` keys
    (Mistral sliding-window semantics; requires ``causal``); the Pallas
    kernels skip dead K/V blocks, so compute is O(T·window).
    """
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if not causal:
        if window:
            raise ValueError("sliding window requires causal attention")
        return blockwise_attention(q, k, v, causal=False, key_mask=attention_mask)
    fn = functools.partial(
        _flash if attention_mask is None else _flash_masked, int(window)
    )
    args = (q, k, v)
    if attention_mask is not None:
        args += (attention_mask.astype(jnp.float32),)
    mesh = kernel_mesh()
    if mesh is None:
        return fn(*args)
    # Attention is independent per batch row and per head, so the shards
    # need no collective: batch over the batch axes, heads over tensor
    # (q and the possibly-narrower GQA k/v must both divide), full T.
    batch = shard_axes(mesh, BATCH_AXES, q.shape[0])
    spec = P(batch, None, shard_axes(mesh, ("tensor",), q.shape[2], k.shape[2]), None)
    in_specs = (spec, spec, spec) + ((P(batch, None),) if len(args) == 4 else ())
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False
    )(*args)

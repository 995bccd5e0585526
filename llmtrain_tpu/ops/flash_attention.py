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


def _auto_block(t: int) -> int | None:
    """Largest legal tile for sequence length ``t``.

    512 was fastest in older hand-taken v5e figures at GPT-2-small
    shapes; smaller tiles keep lengths like 384 or 768 on the Pallas path.
    """
    for block in (512, 256, 128):
        if t >= block and t % block == 0:
            return block
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


# ``window`` is a static Python int (0 = off) and travels as the leading
# nondiff arg of both custom_vjps — Mistral-style sliding-window masking
# with dead K/V blocks skipped in the Pallas kernels.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(window, q, k, v):
    if _use_pallas(q.shape[1]):
        from .pallas_attention import pallas_flash_attention

        block = _auto_block(q.shape[1])
        return pallas_flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block, window=window
        )
    return _blockwise(q, k, v, window=window)


def _flash_fwd(window, q, k, v):
    if _use_pallas(q.shape[1]):
        from .pallas_attention import pallas_flash_attention_fwd

        block = _auto_block(q.shape[1])
        out, lse = pallas_flash_attention_fwd(
            q, k, v, causal=True, block_q=block, block_k=block, window=window
        )
        return out, (q, k, v, out, lse)
    return _flash(window, q, k, v), (q, k, v, None, None)


def _flash_bwd(window, residuals, g):
    q, k, v, out, lse = residuals
    if out is not None:
        from .pallas_attention import pallas_flash_attention_bwd

        block = _auto_block(q.shape[1])
        return pallas_flash_attention_bwd(
            q, k, v, out, lse, g, causal=True, block_q=block, block_k=block,
            window=window,
        )
    _, vjp = jax.vjp(lambda q_, k_, v_: _blockwise(q_, k_, v_, window=window),
                     q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


# Masked variant: the (B, T) key-padding mask travels as float32 so the
# custom_vjp can return a well-typed zero cotangent for it.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_masked(window, q, k, v, maskf):
    if _use_pallas(q.shape[1]):
        from .pallas_attention import pallas_flash_attention

        block = _auto_block(q.shape[1])
        return pallas_flash_attention(
            q, k, v, maskf, causal=True, block_q=block, block_k=block,
            window=window,
        )
    return _blockwise(q, k, v, key_mask=maskf, window=window)


def _flash_masked_fwd(window, q, k, v, maskf):
    if _use_pallas(q.shape[1]):
        from .pallas_attention import pallas_flash_attention_fwd

        block = _auto_block(q.shape[1])
        out, lse = pallas_flash_attention_fwd(
            q, k, v, maskf, causal=True, block_q=block, block_k=block,
            window=window,
        )
        return out, (q, k, v, maskf, out, lse)
    return _flash_masked(window, q, k, v, maskf), (q, k, v, maskf, None, None)


def _flash_masked_bwd(window, residuals, g):
    q, k, v, maskf, out, lse = residuals
    if out is not None:
        from .pallas_attention import pallas_flash_attention_bwd

        block = _auto_block(q.shape[1])
        dq, dk, dv = pallas_flash_attention_bwd(
            q, k, v, out, lse, g, maskf, causal=True, block_q=block,
            block_k=block, window=window,
        )
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

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

The kernels take and give the projections' own rows, ``(B, T, H*D)``
(pallas_attention.py: a lane block is one head of a multiple of 128 or two
64-wide heads), so the ``(B, T, H, D)`` operands of ``flash_attention``
reach them by a reshape. A block whose q, k and v are the untouched output
of ONE projection calls ``flash_attention_qkv`` with that ``(B, T, 3, H,
D)`` array: the kernels read the three out of it where it lies and return
its gradient as one array. Shapes without whole lane blocks (a 64-wide head
under GQA, an odd head count at 64, another head width) take the folded
``(B*H, T, D)`` arrays, XLA transposing around the call; the shapes decide,
no option does. The custom_vjp's residuals are the operands as they were
handed in and the forward's output and logsumexp.

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


def _pallas_fwd(window, operands, maskf):
    from .pallas_attention import (
        pallas_flash_attention_fwd,
        pallas_flash_attention_qkv_fwd,
    )

    resident, streamed = _auto_block(operands[0].shape[1])
    fwd = pallas_flash_attention_fwd if len(operands) == 3 else pallas_flash_attention_qkv_fwd
    return fwd(*operands, maskf, causal=True, block_q=resident, block_k=streamed, window=window)


def _pallas_bwd(window, operands, maskf, out, lse, g):
    """The operands' gradients, one each: ``(dq, dk, dv)`` or ``(dqkv,)``."""
    from .pallas_attention import (
        pallas_flash_attention_bwd,
        pallas_flash_attention_qkv_bwd,
    )

    resident, streamed = _auto_block(operands[0].shape[1])
    bwd = pallas_flash_attention_bwd if len(operands) == 3 else pallas_flash_attention_qkv_bwd
    grads = bwd(
        *operands, out, lse, g, maskf, causal=True, block_q=resident, block_k=streamed,
        dkdv_block_q=streamed, dkdv_block_k=resident, window=window,
    )
    return grads if len(operands) == 3 else (grads,)


def _blockwise(operands, maskf, window):
    # blockwise consumes grouped-query narrow K/V natively. query_mask =
    # key_mask upgrades to segment semantics (q and k cover the same
    # sequence here), matching the Pallas kernels and dense_attention.
    if len(operands) == 1:  # the fused (B, T, 3, H, D) projection output
        operands = tuple(operands[0][:, :, i] for i in range(3))
    q, k, v = operands
    return blockwise_attention(q, k, v, causal=True, key_mask=maskf,
                               query_mask=maskf, window=window)


# One custom_vjp for every call: ``operands`` is ``(q, k, v)`` or the one
# fused ``(qkv,)``; ``maskf`` is None or the (B, T) key-padding mask as
# float32, so that its cotangent is a well-typed zero. ``window`` is a static
# Python int (0 = off) and travels as the leading nondiff arg —
# Mistral-style sliding-window masking with dead K/V blocks skipped in the
# Pallas kernels. The residuals are the operands as they were handed in:
# nothing is laid out anew for the backward.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(window, operands, maskf):
    if _use_pallas(operands[0].shape[1]):
        return _pallas_fwd(window, operands, maskf)[0]
    return _blockwise(operands, maskf, window)


def _flash_fwd(window, operands, maskf):
    if _use_pallas(operands[0].shape[1]):
        out, lse = _pallas_fwd(window, operands, maskf)
        return out, (operands, maskf, out, lse)
    return _flash(window, operands, maskf), (operands, maskf, None, None)


def _flash_bwd(window, residuals, g):
    operands, maskf, out, lse = residuals
    if out is not None:
        grads = _pallas_bwd(window, operands, maskf, out, lse, g)
    else:
        _, vjp = jax.vjp(lambda *ops: _blockwise(ops, maskf, window), *operands)
        grads = vjp(g)
    return tuple(grads), None if maskf is None else jnp.zeros_like(maskf)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _dispatch(operands, attention_mask, window: int) -> jax.Array:
    """Run ``_flash`` on this device, or on each device's shard of a mesh."""
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    maskf = None if attention_mask is None else attention_mask.astype(jnp.float32)
    mesh = kernel_mesh()
    if mesh is None:
        return _flash(int(window), operands, maskf)
    # Attention is independent per batch row and per head, so the shards
    # need no collective: batch over the batch axes, heads over tensor
    # (q and the possibly-narrower GQA k/v must both divide), full T. A
    # shard of the fused array is the local projection's own output, three
    # column ranges of whole heads, so the kernels decide on local shapes.
    batch = shard_axes(mesh, BATCH_AXES, operands[0].shape[0])
    heads = shard_axes(mesh, ("tensor",), *(x.shape[-2] for x in operands))
    spec = P(batch, None, heads, None)
    fused = P(batch, None, None, heads, None)
    return jax.shard_map(
        lambda ops, m: _flash(int(window), ops, m),
        mesh=mesh,
        in_specs=(tuple(spec if x.ndim == 4 else fused for x in operands),
                  None if maskf is None else P(batch, None)),
        out_specs=spec,
        check_vma=False,
    )(operands, maskf)


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
    if not causal:
        if window:
            raise ValueError("sliding window requires causal attention")
        return blockwise_attention(q, k, v, causal=False, key_mask=attention_mask)
    return _dispatch((q, k, v), attention_mask, window)


def flash_attention_qkv(
    qkv: jax.Array,
    *,
    attention_mask: jax.Array | None = None,
    window: int = 0,
) -> jax.Array:
    """``flash_attention`` (causal) for a block whose q, k and v are the
    untouched output of ONE projection, (B, T, 3, H, Dh): returns (B, T, H,
    Dh). On the chip the kernels read the three out of that array where it
    lies and write its gradient as one array, so neither a slice nor a
    concatenation of an activation stands beside them; a block that rotates
    or scales q or k after the projection calls ``flash_attention``."""
    return _dispatch((qkv,), attention_mask, window)

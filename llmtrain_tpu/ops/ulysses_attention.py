"""Ulysses (all-to-all) sequence parallelism: the alternative to ring.

New TPU capability beyond the reference (single-device attention only,
reference models/gpt.py:56-69). Where ring attention keeps queries local
and rotates K/V shards around the ``sequence`` axis (ops/ring_attention.py,
one ppermute per step), Ulysses (DeepSpeed-Ulysses; see PAPERS.md)
re-shards ONCE per attention: an all-to-all swaps the sharded dimension
from sequence to heads (q/k/v stacked into one collective), every device
runs exact attention over the FULL sequence for its ``H/s`` head slice,
and a second all-to-all swaps back.

Trade-off vs ring: 2 all-to-alls per attention (one for stacked q/k/v,
one for the output) instead of ``s`` ppermutes of K/V — fewer, larger
collectives (better for small ``s`` on fast ICI) — but it needs
``local_heads % s == 0`` (heads AFTER tensor sharding), so it caps at
H-way sequence sharding while ring scales to any ``s``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .blockwise_attention import blockwise_attention
from .ring_attention import (
    _dim_shards,
    attention_shard_map,
    min_widen_factor,
    route_or_blockwise,
    widen_kv_for_shards,
)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    key_mask: jax.Array | None = None,
    *,
    axis_name: str = "sequence",
    causal: bool = True,
) -> jax.Array:
    """Local-shard Ulysses attention; must run inside shard_map.

    q/k/v: (B, T_local, H, D) shards, contiguous along the global sequence
    in axis order; ``key_mask`` is the FULL-sequence (B, T) padding mask
    (replicated over the sequence axis by the shard_map spec — the
    post-exchange attention sees the whole sequence, and replicating
    beats an all-gather per layer). Returns the (B, T_local, H, D)
    output shard.
    """
    s = jax.lax.psum(1, axis_name)
    heads = q.shape[2]
    if heads % s != 0:
        raise ValueError(
            f"ulysses needs local heads ({heads}) divisible by the "
            f"sequence axis size ({s})"
        )
    if k.shape[2] != heads:
        # Grouped-query narrow K/V: keep it narrow through the exchange
        # when its head count splits across the axis (less wire traffic —
        # the post-exchange blockwise groups queries natively); otherwise
        # widen by the smallest exact factor that divides (w=group always
        # satisfies both conditions after the heads % s check above).
        w = min_widen_factor(heads // k.shape[2], k.shape[2], s)
        if w is not None and w > 1:
            k = jnp.repeat(k, w, axis=2)
            v = jnp.repeat(v, w, axis=2)

    if k.shape[2] == heads:
        # Collective 1: device i holds sequence shard i, all local heads;
        # after the exchange it holds head-slice i for the FULL sequence,
        # shards concatenated in axis order so positions line up globally.
        # q/k/v ride one stacked all-to-all (axes shift by 1 for the
        # stack dim).
        qkv = jnp.stack((q, k, v))  # (3, B, T_local, H, D)
        qkv = jax.lax.all_to_all(
            qkv, axis_name, split_axis=3, concat_axis=2, tiled=True
        )
        qh, kh, vh = qkv[0], qkv[1], qkv[2]  # each (B, T, H/s, D)
    else:
        # Narrow K/V: q and the stacked k/v exchange separately — two
        # collectives moving H + 2*Hkv head-widths instead of one moving
        # 3*H. Fewer bytes for any group factor > 1, at the cost of one
        # extra collective's latency; taken unconditionally (on chip: not
        # measured).
        qh = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
        kv = jnp.stack((k, v))  # (2, B, T_local, Hkv, D)
        kv = jax.lax.all_to_all(
            kv, axis_name, split_axis=3, concat_axis=2, tiled=True
        )
        kh, vh = kv[0], kv[1]  # each (B, T, Hkv/s, D)

    # query_mask = key_mask: q and k cover the same full sequence after
    # the all-to-all, so segment semantics (packed cross-document
    # masking) apply directly.
    out = blockwise_attention(
        qh, kh, vh, causal=causal, key_mask=key_mask, query_mask=key_mask
    )
    # Collective 2: back to sequence-sharded, all heads local.
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    causal: bool = True,
    key_mask: jax.Array | None = None,
) -> jax.Array:
    """shard_map wrapper: global (B, T, H, D) arrays over the named mesh
    (same activation layout as ring — ring_attention.attention_shard_map).
    """
    k, v = widen_kv_for_shards(q, k, v, mesh)
    fn = attention_shard_map(
        mesh,
        functools.partial(ulysses_attention, axis_name="sequence", causal=causal),
        with_mask=key_mask is not None,
        mask_replicated=True,
    )
    if key_mask is not None:
        return fn(q, k, v, key_mask)
    return fn(q, k, v)


def _local_heads_divide(mesh: jax.sharding.Mesh, q: jax.Array) -> bool:
    """Ulysses' extra constraint: heads remaining after tensor sharding
    must split across the sequence axis."""
    local_heads = q.shape[2] // _dim_shards(mesh, 2)
    return local_heads % mesh.shape["sequence"] == 0


def ulysses_or_blockwise(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    key_mask: jax.Array | None = None,
) -> jax.Array:
    """Ulysses when an ambient mesh shards the sequence and local heads
    divide by the sequence degree; blockwise otherwise (shared policy:
    ring_attention.route_or_blockwise). ``key_mask`` is the reference's
    (B, T) padding mask, applied inside attention on both paths."""
    return route_or_blockwise(
        q,
        k,
        v,
        causal=causal,
        scheme="ulysses",
        sharded_fn=ulysses_attention_sharded,
        extra_predicate=_local_heads_divide,
        key_mask=key_mask,
    )


__all__ = [
    "ulysses_attention",
    "ulysses_attention_sharded",
    "ulysses_or_blockwise",
]

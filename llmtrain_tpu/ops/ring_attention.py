"""Ring attention: exact causal attention sharded over the ``sequence`` axis.

New TPU capability beyond the reference (whose attention is single-device
full-matrix, reference models/gpt.py:56-69; max context = block_size). Each
device holds a (B, T/n, H, D) shard of Q/K/V. K/V shards rotate around the
``sequence`` mesh axis via ``lax.ppermute`` (one ICI hop per step) while each
device accumulates online-softmax partials of its local queries against the
visiting K/V block — so the full (T, T) score matrix never exists anywhere
and context length scales linearly with the number of devices. Pattern
follows the Ring Attention paper (see PAPERS.md); the per-block math reuses
``ops/blockwise_attention._chunk_scan``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .blockwise_attention import _chunk_scan, blockwise_attention


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    key_mask: jax.Array | None = None,
    *,
    axis_name: str = "sequence",
    causal: bool = True,
    kv_chunk: int = 512,
) -> jax.Array:
    """Local-shard ring attention; must run inside shard_map over ``axis_name``.

    q/k/v: (B, T_local, H, D) shards, contiguous along the global sequence in
    axis order; ``key_mask`` is the matching (B, T_local) padding-mask shard
    (nonzero = attend) and rotates around the ring WITH its K/V shard.
    Returns the (B, T_local, H, D) output shard.
    """
    axis_size = jax.lax.psum(1, axis_name)
    axis_index = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    q_offset = axis_index * t_local
    chunk = min(kv_chunk, t_local)
    if t_local % chunk != 0:
        chunk = t_local

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    masked = key_mask is not None

    def body(i, carry):
        acc, row_max, row_sum, k_cur, v_cur, m_cur = carry
        # After i rotations this device holds the K/V shard that started on
        # device (axis_index - i); its global offset drives the causal mask.
        kv_offset = ((axis_index - i) % axis_size) * t_local
        acc2, max2, sum2 = _chunk_scan(
            q,
            k_cur,
            v_cur,
            q_offset=q_offset,
            kv_offset=kv_offset,
            causal=causal,
            kv_chunk=chunk,
            key_mask=m_cur if masked else None,
            # The UNROTATED local mask is this shard's queries' segment
            # ids: equal-nonzero-value semantics (packed cross-document
            # masking) ride the ring exactly like the key shards do.
            query_mask=key_mask if masked else None,
        )
        new_max = jnp.maximum(row_max, max2)
        c1 = jnp.exp(row_max - new_max)
        c2 = jnp.exp(max2 - new_max)
        acc = acc * c1[..., None] + acc2 * c2[..., None]
        row_sum = row_sum * c1 + sum2 * c2
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        if masked:
            m_cur = jax.lax.ppermute(m_cur, axis_name, perm)
        return acc, new_max, row_sum, k_cur, v_cur, m_cur

    b, _, h, d = q.shape
    init = (
        jnp.zeros((b, t_local, h, d), jnp.float32),
        jnp.full((b, t_local, h), -1e30, jnp.float32),
        jnp.zeros((b, t_local, h), jnp.float32),
        k,
        v,
        jnp.asarray(key_mask, jnp.int32) if masked else jnp.zeros((), jnp.int32),
    )
    acc, _, row_sum, _, _, _ = jax.lax.fori_loop(0, axis_size, body, init)
    return (acc / row_sum[..., None]).astype(q.dtype)


# Mesh axes each (B, T, H, D) dim shards over — single source of truth for
# both the shard_map spec and the divisibility guard in ring_or_blockwise.
# Matches the activation logical-axis rules in parallel/sharding.py.
RING_DIM_AXES: tuple = (("data", "fsdp"), ("sequence",), ("tensor",), ())


def _dim_shards(mesh: jax.sharding.Mesh, dim: int) -> int:
    # Externally built meshes may carry a sequence axis without data/fsdp/
    # tensor names; absent axes count as unsharded (size 1).
    import math

    return math.prod(mesh.shape.get(a, 1) for a in RING_DIM_AXES[dim])


def _mesh_dim_axes(mesh: jax.sharding.Mesh) -> tuple:
    """RING_DIM_AXES restricted to axes the mesh actually has."""
    return tuple(
        tuple(a for a in axes if a in mesh.shape) for axes in RING_DIM_AXES
    )


def attention_shard_map(
    mesh: jax.sharding.Mesh,
    local_fn,
    *,
    with_mask: bool = False,
    mask_replicated: bool = False,
):
    """Wrap a local-shard attention fn into a (q, k, v[, key_mask])
    shard_map over the standard activation layout (``RING_DIM_AXES``):
    batch over (data, fsdp), sequence over ``sequence``, heads over
    ``tensor``. The (B, T) mask shards like (batch, sequence) — or, with
    ``mask_replicated``, only over batch, handing every device the full
    sequence mask (ulysses wants that post-exchange; gathering it at
    runtime would be a wasted per-layer collective).
    Shared by ring and ulysses (ops/ulysses_attention.py)."""
    P = jax.sharding.PartitionSpec
    dim_axes = _mesh_dim_axes(mesh)

    def _ax(axes):
        return axes if len(axes) > 1 else (axes[0] if axes else None)

    spec = P(*(_ax(axes) for axes in dim_axes))
    specs = [spec, spec, spec]
    if with_mask:
        specs.append(
            P(_ax(dim_axes[0]), None if mask_replicated else _ax(dim_axes[1]))
        )
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=spec,
        check_vma=False,
    )


def min_widen_factor(group: int, kv_heads: int, divisor: int) -> int | None:
    """Smallest exact K/V replication factor (a divisor of ``group``)
    making ``kv_heads * w`` divide ``divisor``; None when nothing does.
    The single widening rule shared by every narrow-K/V path."""
    return next(
        (
            w for w in range(1, group + 1)
            if group % w == 0 and (kv_heads * w) % divisor == 0
        ),
        None,
    )


def widen_kv_for_shards(q: jax.Array, k: jax.Array, v: jax.Array, mesh):
    """Widen grouped-query K/V by the SMALLEST exact factor that makes its
    head count divide the mesh's head shards — keeping K/V as narrow as
    the sharding allows (exact math; replicated kv heads) instead of
    abandoning a sharded path. Shared by ring and ulysses wrappers."""
    hs = _dim_shards(mesh, 2)
    if k.shape[2] % hs != 0:
        g = q.shape[2] // k.shape[2]
        w = min_widen_factor(g, k.shape[2], hs)
        if w is None:
            # g-fold widening reaches full H, which the caller's q check
            # already validated — only reachable when q itself doesn't
            # divide; keep the message clear instead of a StopIteration.
            raise ValueError(
                f"K/V heads ({k.shape[2]}, query heads {q.shape[2]}) cannot "
                f"be widened to divide the mesh head shards ({hs})"
            )
        k = jnp.repeat(k, w, axis=2)
        v = jnp.repeat(v, w, axis=2)
    return k, v


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    causal: bool = True,
    key_mask: jax.Array | None = None,
) -> jax.Array:
    """shard_map wrapper: global (B, T, H, D) arrays over the named mesh."""
    k, v = widen_kv_for_shards(q, k, v, mesh)
    fn = attention_shard_map(
        mesh,
        functools.partial(ring_attention, axis_name="sequence", causal=causal),
        with_mask=key_mask is not None,
    )
    if key_mask is not None:
        return fn(q, k, v, key_mask)
    return fn(q, k, v)


def route_or_blockwise(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scheme: str,
    sharded_fn,
    extra_predicate=None,
    key_mask: jax.Array | None = None,
):
    """Shared route-or-fallback policy for sequence-parallel schemes.

    Routes to ``sharded_fn(q, k, v, mesh, causal=..., key_mask=...)``
    when an ambient mesh has a sequence axis > 1, every sharded dim
    divides evenly, and the optional ``extra_predicate(mesh, q)`` holds;
    otherwise falls back to single-device blockwise. Batch-1 traces (the
    param-init probe, ModelAdapter.init_params' (1, block_size) batch)
    fall back silently by design; real batches losing sequence
    parallelism get a trace-time warning.
    """
    mesh = _ambient_mesh()
    if (
        mesh is not None
        and "sequence" in mesh.axis_names
        and mesh.shape["sequence"] > 1
    ):
        # Narrow grouped-query K/V is widened minimally inside the sharded
        # wrappers (widen_kv_for_shards) when its head count doesn't
        # divide the head shards — never a reason to fall back.
        dims_ok = all(q.shape[d] % _dim_shards(mesh, d) == 0 for d in range(3))
        if dims_ok and (extra_predicate is None or extra_predicate(mesh, q)):
            return sharded_fn(q, k, v, mesh, causal=causal, key_mask=key_mask)
        if q.shape[0] > 1:
            from ..utils.logging import get_logger

            get_logger().warning(
                "%s attention falling back to single-device blockwise: "
                "shape (B=%d, T=%d, H=%d, Hkv=%d) vs mesh shards (batch %d, "
                "sequence %d, heads %d) — sequence parallelism is DISABLED "
                "for this computation",
                scheme,
                q.shape[0],
                q.shape[1],
                q.shape[2],
                k.shape[2],
                _dim_shards(mesh, 0),
                _dim_shards(mesh, 1),
                _dim_shards(mesh, 2),
            )
    # query_mask = key_mask keeps SEGMENT semantics on the fallback: a
    # split_documents mask degrading to key-padding-only here would
    # silently re-open cross-document attention.
    return blockwise_attention(
        q, k, v, causal=causal, key_mask=key_mask, query_mask=key_mask
    )


def ring_or_blockwise(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    key_mask: jax.Array | None = None,
):
    """Ring attention when an ambient mesh shards the sequence; blockwise
    otherwise (same math, no ring). ``key_mask`` is the reference's (B, T)
    padding mask, applied inside attention on both paths."""
    return route_or_blockwise(
        q, k, v, causal=causal, scheme="ring",
        sharded_fn=ring_attention_sharded, key_mask=key_mask,
    )


def _ambient_mesh() -> jax.sharding.Mesh | None:
    """The mesh from an enclosing ``with mesh:`` block, if any."""
    from ..parallel.sharding import ambient_mesh

    return ambient_mesh()

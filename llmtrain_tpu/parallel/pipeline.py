"""GPipe pipeline parallelism over the mesh ``pipeline`` axis.

New TPU capability beyond the reference (data parallelism is its only
strategy — reference trainer.py:87-91; SURVEY §2.3 records PP as absent).
Design is TPU-first, not a port: stages are SPMD programs under
``shard_map``, activations hop stages over ICI with ``lax.ppermute``, and
the whole schedule — microbatch rotation, bubble, drain — is ONE
``lax.scan`` inside the jit-compiled train step. The backward schedule
falls out of differentiating the forward (ppermute transposes to the
reverse permutation), so GPipe's backward pass needs no extra code.

Layout contract: every parameter leaf carries its layer dim LEADING and
sharded over ``pipeline`` (logical axis ``"layers"``); activations are
batch-sharded over the data axes and replicated over ``pipeline``. With S
stages and M microbatches the bubble fraction is (S-1)/(M+S-1).

``virtual_chunks=v > 1`` selects the interleaved (Megatron-style) schedule:
each stage holds v non-contiguous layer chunks (stage s owns global chunks
s, s+S, s+2S, …), and every microbatch makes v passes around the stage
ring — the ``ppermute`` from the last stage back to stage 0 carries it
into its next chunk round. Bubble shrinks to (S-1)/(v·M+S-1) at the cost
of v× activation hops. Requires M >= S so a returning microbatch never
overtakes its own re-entry slot.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .sharding import BATCH_AXES


def pipeline_degree(mesh: jax.sharding.Mesh | None) -> int:
    return int(mesh.shape.get("pipeline", 1)) if mesh is not None else 1


def _interleave_permutation(n_layers: int, n_stages: int, v: int) -> np.ndarray:
    """Row order that makes a CONTIGUOUS shard hold strided chunks.

    shard_map splits the leading dim contiguously: device s gets rows
    [s·v·Lc, (s+1)·v·Lc). For the interleaved schedule device s must hold
    global chunks s, s+S, …, s+(v-1)S, i.e. layers r·S·Lc + s·Lc + j. The
    permutation lays those out so device s's local rows are ordered
    (round r, layer-in-chunk j).
    """
    lc = n_layers // (n_stages * v)
    return np.asarray(
        [
            r * n_stages * lc + s * lc + j
            for s in range(n_stages)
            for r in range(v)
            for j in range(lc)
        ],
        dtype=np.int32,
    )


def gpipe_apply(
    stage_fn: Callable[..., jax.Array],
    params: Any,
    x: jax.Array,
    mesh: jax.sharding.Mesh,
    *,
    n_microbatches: int,
    axis: str = "pipeline",
    remat_stage: bool = True,
    virtual_chunks: int = 1,
    param_specs: Any | None = None,
    mask: jax.Array | None = None,
) -> jax.Array:
    """Run ``x`` through all layers with pipeline scheduling over ``axis``.

    ``params``: pytree whose every leaf has a leading layer dim divisible by
    ``stage_count * virtual_chunks`` (sharded over ``axis``);
    ``stage_fn(stacked_layers, h)`` applies the given layers in order.
    ``x``: (B, T, D) activations with B sharded over the data axes. Returns
    (B, T, D) after all layers, replicated over ``axis`` (non-final stages
    receive the result via psum).

    ``mask``: optional (B, T) per-token padding mask. It does NOT ride the
    stage ring — each tick's stage knows which microbatch it is processing
    (work item t - stage), so the matching mask slice is indexed from the
    replicated-over-``axis`` array and passed as ``stage_fn``'s third
    argument.

    ``param_specs``: optional pytree of PartitionSpecs (matching ``params``)
    for the NON-layer dims — e.g. tensor-parallel sharding of head/mlp dims;
    every spec's dim 0 must be the ``axis`` entry. Default: non-layer dims
    replicated. When a leaf is tensor-sharded, ``stage_fn`` is responsible
    for the matching collectives (it runs inside shard_map — nothing is
    automatic).
    """
    n_stages = pipeline_degree(mesh)
    if n_stages == 1:
        return stage_fn(params, x) if mask is None else stage_fn(params, x, mask)
    n_micro = n_microbatches
    v = virtual_chunks
    if n_micro < 1:
        raise ValueError(f"n_microbatches must be >= 1, got {n_micro}")
    if v < 1:
        raise ValueError(f"virtual_chunks must be >= 1, got {v}")
    if v > 1 and n_micro < n_stages:
        raise ValueError(
            f"interleaved schedule needs n_microbatches ({n_micro}) >= "
            f"stage count ({n_stages}): a microbatch returns to stage 0 "
            "S ticks after entering and must not overtake its re-entry slot"
        )

    n_layers = jax.tree.leaves(params)[0].shape[0]
    if n_layers % (n_stages * v) != 0:
        raise ValueError(
            f"layer count {n_layers} must divide stages x virtual_chunks "
            f"({n_stages} x {v})"
        )
    layers_per_chunk = n_layers // (n_stages * v)

    if v > 1:
        # Reorder rows so contiguous shard s = its strided chunk set; the
        # gather's transpose routes chunk grads back automatically.
        # Deliberate tradeoff: this runs per step and moves ~(v-1)/v of the
        # stage params across the pipeline axis each forward (+ the
        # scatter-add in backward). Storing params pre-permuted would
        # avoid it but ties the CHECKPOINT layout to (stages, chunks) —
        # resuming on a different mesh would silently reorder layers.
        # Params are layout-independent; the traffic is bounded and
        # amortized against the bubble savings (docs/perf.md).
        perm_rows = jnp.asarray(_interleave_permutation(n_layers, n_stages, v))
        params = jax.tree.map(lambda a: jnp.take(a, perm_rows, axis=0), params)

    fn = jax.checkpoint(stage_fn) if remat_stage else stage_fn
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    x_spec = P(batch_axes, *([None] * (x.ndim - 1)))
    if param_specs is not None:
        p_specs = param_specs
        for spec in jax.tree.leaves(p_specs, is_leaf=lambda s: isinstance(s, P)):
            if not spec or spec[0] != axis:
                raise ValueError(
                    f"param_specs must shard dim 0 over {axis!r}, got {spec}"
                )
    else:
        p_specs = jax.tree.map(lambda a: P(axis, *([None] * (a.ndim - 1))), params)

    masked = mask is not None

    def inner(p: Any, x_local: jax.Array, *rest: jax.Array) -> jax.Array:
        stage = jax.lax.axis_index(axis)
        batch = x_local.shape[0]
        if batch % n_micro != 0:
            raise ValueError(
                f"per-shard batch {batch} not divisible by n_microbatches {n_micro}"
            )
        mb = batch // n_micro
        xm = x_local.reshape(n_micro, mb, *x_local.shape[1:])
        mask_m = None
        if masked:
            (mask_local,) = rest
            mask_m = mask_local.reshape(n_micro, mb, *mask_local.shape[1:])
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        last = n_stages - 1

        def round_of(k):
            return jnp.clip(jnp.maximum(k, 0) // n_micro, 0, v - 1)

        def micro_of(k):
            return jnp.clip(jnp.maximum(k, 0) - round_of(k) * n_micro, 0, n_micro - 1)

        def chunk_params(r):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(
                    a, r * layers_per_chunk, layers_per_chunk, axis=0
                ),
                p,
            )

        def write_at(buf, idx, value, enable):
            cur = jax.lax.dynamic_index_in_dim(buf, idx, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(
                buf, jnp.where(enable, value, cur), idx, 0
            )

        def tick(carry, t):
            state_in, ret_buf, out_buf = carry

            # Stage 0: bank the activation returning from the last stage
            # (work item t-S finished its round at tick t-1) for its next
            # chunk round. With M >= S the write at tick k+S always lands
            # at or before the read at tick k+M.
            k_ret = t - n_stages
            bank = (stage == 0) & (k_ret >= 0) & (k_ret < (v - 1) * n_micro)
            ret_buf = write_at(ret_buf, micro_of(k_ret), state_in, bank)

            # Stage 0 input for work item t: a fresh microbatch in round 0,
            # the banked activation afterwards. Clamped garbage during
            # drain ticks never reaches the output buffer.
            r0, m0 = round_of(t), micro_of(t)
            fresh = jax.lax.dynamic_index_in_dim(xm, m0, keepdims=False)
            banked = jax.lax.dynamic_index_in_dim(ret_buf, m0, keepdims=False)
            x0 = jnp.where(r0 == 0, fresh, banked)
            inp = jnp.where(stage == 0, x0, state_in)

            # This stage processes work item t - stage, whose round picks
            # which of the stage's local chunks to run.
            if masked:
                m_mb = jax.lax.dynamic_index_in_dim(
                    mask_m, micro_of(t - stage), keepdims=False
                )
                out = fn(chunk_params(round_of(t - stage)), inp, m_mb)
            else:
                out = fn(chunk_params(round_of(t - stage)), inp)

            # The final stage finishes work item t-(S-1); final-round items
            # are results.
            k_out = t - last
            done = (stage == last) & (k_out >= (v - 1) * n_micro) & (k_out < v * n_micro)
            out_buf = write_at(out_buf, micro_of(k_out), out, done)

            state_out = jax.lax.ppermute(out, axis, perm)
            return (state_out, ret_buf, out_buf), None

        # The carry varies over `axis` (each stage computes different
        # values), but the zero init doesn't — declare it varying so the
        # scan carry types line up under shard_map's vma tracking.
        mark_varying = lambda a: jax.lax.pcast(a, (axis,), to="varying")  # noqa: E731
        # v == 1 never banks (round 0 reads fresh microbatches only), so the
        # return buffer shrinks to one slot; out-of-range dynamic indices
        # clamp per XLA semantics and the clamped reads are never selected.
        ret_init = jnp.zeros_like(xm) if v > 1 else jnp.zeros_like(xm[:1])
        init = jax.tree.map(
            mark_varying, (jnp.zeros_like(xm[0]), ret_init, jnp.zeros_like(xm))
        )
        (_, _, out_buf), _ = jax.lax.scan(
            tick, init, jnp.arange(v * n_micro + n_stages - 1)
        )
        # Only the final stage ever wrote its buffer; every other stage
        # holds zeros, so a psum broadcasts the result to all stages.
        y = jax.lax.psum(out_buf, axis)
        return y.reshape(x_local.shape)

    in_specs: tuple = (p_specs, x_spec)
    operands: tuple = (params, x)
    if masked:
        in_specs = (*in_specs, P(batch_axes, None))
        operands = (*operands, mask)
    return shard_map(
        inner,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=x_spec,
    )(*operands)


__all__ = ["gpipe_apply", "pipeline_degree", "BATCH_AXES"]

"""Rotary position embeddings (RoPE): the half-split ("rotate_half") layout,
and the interleaved one beside it.

Llama-family models encode position by rotating query/key pairs instead
of adding learned position embeddings (GPT, models/gpt.py:497-515). The
layout here is the HF-transformers/Llama convention — feature dim split
into two halves, NOT interleaved even/odd pairs — so parameters ported
from (or parity-tested against) ``transformers`` Llama checkpoints match
bit-for-bit (tests/test_llama.py).

TPU notes: angles are computed in f32 (bf16 loses position resolution
past ~256 positions) and the rotation is two fused elementwise multiplies
— XLA folds it into the surrounding projection, so RoPE adds no HBM
round-trip. Everything is shape-static under jit; the ``positions``
operand may be a traced value (decode offsets the cache cursor).

**Interleaved pairs** (``interleaved=True``; GPT-J's layout, Cohere's
``position_embedding_type: rope_gptj``): pair ``i`` is dimensions ``(2i,
2i + 1)`` where the half-split layout pairs ``(i, i + d/2)``. The angles
are the same, only which two values turn together differs; the tables are
each frequency twice in a row and the partner of a value is its neighbour.
A checkpoint of one layout is a fixed permutation of the other's q/k
columns, and nothing but this switch tells them apart.

**A head that is rotated in part.** Latent attention (models/latent_moe.py)
splits a head into a part that carries no position and a part that does:
the caller slices the rotary part off (the last ``qk_rope_head_dim`` of a
query head; the ONE key row all heads share, passed as ``Hkv = 1``) and
rotates that alone, so nothing here knows of the split.

**YaRN** (Peng et al. 2023; the DeepSeek-V3 family's ``rope_scaling`` of
``type: yarn``): :func:`yarn_inv_freq` gives the per-frequency
interpolation that ``rope_angles`` / ``apply_rope`` take as ``inv_freq``,
:func:`yarn_mscale` the magnitude factor whose square a model multiplies
into its softmax scale.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 for ``factor <= 1``)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(
    head_dim: int,
    *,
    theta: float,
    factor: float,
    original_max_position_embeddings: int,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
) -> jax.Array:
    """(head_dim / 2,) inverse frequencies under YaRN, f32.

    Pair ``i`` turns ``theta^(-2i/d)`` radians a position. Pairs that make
    more than ``beta_fast`` turns over the original context keep that
    frequency, pairs that make fewer than ``beta_slow`` are slowed by
    ``factor`` (interpolated), and between the two dimensions where those
    turn counts fall a linear ramp blends the two.
    """

    def dim_of(turns: float) -> float:
        return (
            head_dim
            * math.log(original_max_position_embeddings / (turns * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # the family's guard against a zero-width ramp
    pairs = jnp.arange(head_dim // 2, dtype=jnp.float32)
    extrapolated = 1.0 / (theta ** (2.0 * pairs / head_dim))
    ramp = jnp.clip((pairs - low) / (high - low), 0.0, 1.0)
    return extrapolated / factor * ramp + extrapolated * (1.0 - ramp)


def rope_angles(
    positions: jax.Array,
    head_dim: int,
    *,
    theta: float = 10000.0,
    inv_freq: jax.Array | None = None,
    interleaved: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables, each ``positions.shape + (head_dim,)`` in f32.

    ``positions``: integer array of absolute token positions (any shape;
    typically (T,) at train time, (t,) offset by the cache cursor at
    decode time). ``inv_freq`` (head_dim / 2,) replaces ``theta``'s own
    frequencies (:func:`yarn_inv_freq`). ``interleaved``: frequency ``i`` at
    dimensions ``2i`` and ``2i + 1`` (else at ``i`` and ``i + head_dim / 2``).
    """
    if head_dim % 2 != 0:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    if inv_freq is None:
        exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
        inv_freq = 1.0 / (theta**exponent)  # (head_dim/2,)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., d/2)
    emb = jnp.repeat(freqs, 2, axis=-1) if interleaved else jnp.concatenate([freqs, freqs], axis=-1)  # (..., d)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rotate_pairs(x: jax.Array) -> jax.Array:
    """``(-x1, x0, -x3, x2, ...)``: each value's partner in its interleaved pair."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    return jnp.stack([-pairs[..., 1], pairs[..., 0]], axis=-1).reshape(x.shape)


def apply_rope(
    q: jax.Array,
    k: jax.Array,
    positions: jax.Array,
    *,
    theta: float = 10000.0,
    inv_freq: jax.Array | None = None,
    interleaved: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Rotate q and k by their absolute positions.

    q: (B, T, H, Dh); k: (B, T, Hkv, Dh) — K may be narrower (GQA); the
    rotation is per-head-feature so both use the same tables.
    ``positions``: (T,) absolute positions shared across the batch
    (generation batches rectangular prompts, generation.py:111-120), or
    (B, T) PER-ROW positions — paged decode batches sequences at
    different depths, so each row rotates by its own offsets.
    Rotation runs in f32 and casts back to the input dtype.
    ``interleaved`` rotates pairs ``(2i, 2i + 1)`` (module docstring).
    """
    cos, sin = rope_angles(positions, q.shape[-1], theta=theta, inv_freq=inv_freq, interleaved=interleaved)
    partner = _rotate_pairs if interleaved else _rotate_half
    if positions.ndim == 1:
        cos = cos[None, :, None, :]  # (1, T, 1, Dh)
        sin = sin[None, :, None, :]
    elif positions.ndim == 2:
        cos = cos[:, :, None, :]  # (B, T, 1, Dh)
        sin = sin[:, :, None, :]
    else:
        raise ValueError(
            f"positions must be (T,) or (B, T), got shape {positions.shape}"
        )

    def rot(x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        return (xf * cos + partner(xf) * sin).astype(x.dtype)

    return rot(q), rot(k)


__all__ = ["apply_rope", "rope_angles", "yarn_inv_freq", "yarn_mscale"]

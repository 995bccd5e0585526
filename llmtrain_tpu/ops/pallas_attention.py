"""Pallas TPU flash-attention kernels: forward and backward.

The MXU-resident hot path for causal attention: one grid program per
(batch*head, q-block), streaming K/V through VMEM with online softmax, so
nothing of shape (T, T) ever exists. Written per the Pallas TPU guide
(grid/BlockSpec tiling, f32 accumulation via preferred_element_type, 2-D
iota for masks).

Two capabilities beyond the plain causal kernel:

* **Key-padding masks** (reference src/llmtrain/models/gpt.py:60-64 applies
  the padding mask inside attention): an optional (B, T) mask streams
  through VMEM as (1, 1, block_k) tiles and masked keys get -inf logits
  before the online softmax. Fully-masked query rows self-correct: the
  running-max correction factor zeroes any transient garbage the moment a
  live block arrives, and rows that never see a live key are zeroed by the
  caller's output mask (models/gpt.py) with zero cotangents flowing back.
* **Native grouped-query attention**: K/V may have fewer heads than Q
  (n_kv_heads). The forward and dq kernels map each query head to its
  K/V group via the BlockSpec index map — no jnp.repeat materialization
  in HBM — and the dk/dv kernel grids over (batch*kv_head, k-block),
  streaming the whole query-head group and reducing in-kernel, so
  gradients are born at the narrow width.

Backward (FlashAttention-2 recompute scheme): the forward also emits the
per-row logsumexp L; the backward recomputes P = exp(S - L) block-by-block
— never materializing (T, T) — in two kernels:

* dq kernel, gridded like the forward (per q-block, streaming K/V):
  dS = P * (dO Vᵀ - D),  dQ = scale * dS K,  with D = rowsum(dO * O).
* dk/dv kernel, gridded per (kv-head, k-block), streaming Q/dO/L/D of the
  query group from the causal diagonal down:  dV = Pᵀ dO,  dK = scale * dSᵀ Q.

``ops/flash_attention.py`` wires these into a ``jax.custom_vjp``; on
non-TPU backends it falls back to differentiating the XLA blockwise
implementation.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, *rest, block_k: int, scale: float, causal: bool,
    masked: bool, window: int = 0,
):
    """One q-block vs the streamed K/V sequence.

    Ref shapes: q (1, BQ, D), k/v (1, T, D), o (1, BQ, D), l (1, 1, BQ),
    optional mask (1, 1, T) int32 + its q-block view (1, 1, BQ) ahead of
    the outputs when ``masked``. Mask values are SEGMENT ids: nonzero =
    real token, equal values = same document (plain 0/1 padding masks are
    the one-segment special case). ``l`` is the per-row logsumexp of the
    scaled/masked logits — the residual the backward kernels use to
    recompute P without a re-softmax. It is carried with a singleton
    middle dim so its block shape satisfies Mosaic's tiling rule
    (second-to-last block dim == array dim).
    """
    if masked:
        mask_ref, mask_q_ref, o_ref, l_ref = rest
    else:
        (o_ref, l_ref) = rest
        mask_ref = mask_q_ref = None
    block_q = q_ref.shape[1]
    head_dim = q_ref.shape[2]
    seq_len = k_ref.shape[1]
    qi = pl.program_id(1)

    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    num_kv = seq_len // block_k
    start_kv = 0
    if causal:
        # Only blocks that intersect the causal triangle for this q block.
        num_kv_live = jax.lax.div(qi * block_q + block_q + block_k - 1, block_k)
        num_kv = jnp.minimum(num_kv, num_kv_live)
    if window:
        # Sliding window: the earliest key this q block can see is
        # qi*BQ - window + 1; blocks wholly before it are dead.
        start_kv = jax.lax.div(
            jnp.maximum(qi * block_q - window + 1, 0), block_k
        )

    def body(kb, carry):
        acc, row_max, row_sum = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]  # (BK, D)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q,
            k_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            live = q_pos >= k_pos
            if window:
                live &= q_pos - k_pos < window
            s = jnp.where(live, s, _NEG_INF)
        if masked:
            m_blk = mask_ref[0, 0, pl.ds(kb * block_k, block_k)]  # (BK,) int32
            mq = mask_q_ref[0, 0]  # (BQ,) int32 — this q-block's segments
            s = jnp.where(
                (m_blk[None, :] != 0) & (mq[:, None] == m_blk[None, :]),
                s,
                _NEG_INF,
            )
        new_max = jnp.maximum(row_max, s.max(axis=1))
        p = jnp.exp(s - new_max[:, None])
        correction = jnp.exp(row_max - new_max)
        acc = acc * correction[:, None] + jax.lax.dot_general(
            p,
            v_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        row_sum = row_sum * correction + p.sum(axis=1)
        return acc, new_max, row_sum

    init = (
        jnp.zeros((block_q, head_dim), jnp.float32),
        jnp.full((block_q,), _NEG_INF, jnp.float32),
        jnp.zeros((block_q,), jnp.float32),
    )
    acc, row_max, row_sum = jax.lax.fori_loop(start_kv, num_kv, body, init)
    o_ref[0] = (acc / row_sum[:, None]).astype(o_ref.dtype)
    l_ref[0] = (row_max + jnp.log(row_sum))[None, :]


def _fold(x: jax.Array) -> jax.Array:
    """(B, T, H, D) -> (B*H, T, D): heads join the grid batch dimension."""
    b, t, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)


def _unfold(x: jax.Array, b: int, h: int) -> jax.Array:
    bh, t, d = x.shape
    return jnp.moveaxis(x.reshape(b, h, t, d), 1, 2)


def _check_blocks(t: int, block_q: int, block_k: int) -> tuple[int, int]:
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q != 0 or t % block_k != 0:
        raise ValueError(f"sequence length {t} must be divisible by block sizes")
    return block_q, block_k


def _head_groups(h: int, hkv: int) -> int:
    """Query heads per K/V head; validates the GQA head relationship."""
    if h % hkv != 0:
        raise ValueError(f"n_heads ({h}) must be a multiple of n_kv_heads ({hkv})")
    return h // hkv


def _kv_index(h: int, hkv: int):
    """Folded-q row (b*h + head) -> folded-kv row (b*hkv + head//group)."""
    group = h // hkv

    def kv_row(bh):
        return (bh // h) * hkv + (bh % h) // group

    return kv_row


def _mask3(mask: jax.Array | None) -> jax.Array | None:
    """(B, T) padding mask -> (B, 1, T) int32 for legal (1, 1, BK) tiling."""
    if mask is None:
        return None
    return mask.astype(jnp.int32)[:, None, :]


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret", "window")
)
def pallas_flash_attention_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    window: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Flash attention over (B, T, H, D) q returning ``(out, lse)``.

    ``k``/``v`` may carry fewer heads (B, T, Hkv, D) for grouped-query
    attention; ``mask`` is an optional (B, T) key-padding mask (nonzero =
    attend). ``window`` > 0 restricts each query to its trailing
    ``window`` keys (Mistral sliding-window semantics; requires
    ``causal``) — dead K/V blocks are skipped, so compute is O(T·W).
    ``lse`` has shape (B*H, T), float32 — the backward residual.
    Falls back to smaller blocks automatically when T < block size.
    """
    b, t, h, d = q.shape
    hkv = k.shape[2]
    _head_groups(h, hkv)
    block_q, block_k = _check_blocks(t, block_q, block_k)
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("sliding window requires causal attention")

    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    scale = 1.0 / math.sqrt(d)
    kv_row = _kv_index(h, hkv)
    masked = mask is not None

    kernel = functools.partial(
        _flash_kernel, block_k=block_k, scale=scale, causal=causal, masked=masked,
        window=window,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, t, d), lambda bh, qi: (kv_row(bh), 0, 0)),
        pl.BlockSpec((1, t, d), lambda bh, qi: (kv_row(bh), 0, 0)),
    ]
    operands = [qf, kf, vf]
    if masked:
        mask3 = _mask3(mask)
        in_specs.append(pl.BlockSpec((1, 1, t), lambda bh, qi: (bh // h, 0, 0)))
        operands.append(mask3)
        # The SAME mask array again, tiled per q-block (segment ids for
        # this block's queries).
        in_specs.append(pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh // h, 0, qi)))
        operands.append(mask3)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(*operands)

    return _unfold(out, b, h), lse.reshape(b * h, t)


def pallas_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    window: int = 0,
) -> jax.Array:
    """Causal flash attention over (B, T, H, D); forward only."""
    out, _ = pallas_flash_attention_fwd(
        q, k, v, mask, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window,
    )
    return out


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, *rest,
    block_k: int, scale: float, causal: bool, masked: bool, window: int = 0,
):
    """dQ for one q-block, streaming K/V (same schedule as the forward).

    Ref shapes: q/do/dq (1, BQ, D), k/v (1, T, D), l/d (1, 1, BQ),
    optional mask (1, 1, T) + its q-block view (1, 1, BQ) ahead of the
    output when ``masked`` (segment semantics — see ``_flash_kernel``).
    """
    if masked:
        mask_ref, mask_q_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
        mask_ref = mask_q_ref = None
    block_q = q_ref.shape[1]
    head_dim = q_ref.shape[2]
    seq_len = k_ref.shape[1]
    qi = pl.program_id(1)

    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)
    do = do_ref[0].astype(jnp.float32)  # (BQ, D)
    lse = l_ref[0, 0]  # (BQ,)
    delta = d_ref[0, 0]  # (BQ,) rowsum(dO * O)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    num_kv = seq_len // block_k
    start_kv = 0
    if causal:
        num_kv_live = jax.lax.div(qi * block_q + block_q + block_k - 1, block_k)
        num_kv = jnp.minimum(num_kv, num_kv_live)
    if window:
        start_kv = jax.lax.div(
            jnp.maximum(qi * block_q - window + 1, 0), block_k
        )

    def body(kb, dq_acc):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK), already scaled via q
        if causal:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            live = q_pos >= k_pos
            if window:
                live &= q_pos - k_pos < window
            s = jnp.where(live, s, _NEG_INF)
        if masked:
            m_blk = mask_ref[0, 0, pl.ds(kb * block_k, block_k)]
            mq = mask_q_ref[0, 0]  # (BQ,)
            s = jnp.where(
                (m_blk[None, :] != 0) & (mq[:, None] == m_blk[None, :]),
                s,
                _NEG_INF,
            )
        p = jnp.exp(s - lse[:, None])  # (BQ, BK)
        dp = jax.lax.dot_general(
            do, v_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        ds = p * (dp - delta[:, None])
        return dq_acc + jax.lax.dot_general(
            ds, k_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(
        start_kv, num_kv, body, jnp.zeros((block_q, head_dim), jnp.float32)
    )
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkdv_kernel(
    q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, *rest,
    block_q: int, scale: float, causal: bool, masked: bool, window: int = 0,
):
    """dK/dV for one (kv-head, k-block, group-member) grid point, streaming
    that query head's Q/dO/L/D from the causal diagonal down.

    Ref shapes: k/v/dk/dv (1, BK, D), q/do (1, T, D), l/d (1, 1, T),
    optional mask (1, 1, BK) + the full-length mask (1, 1, T) for the
    streamed queries' segments, ahead of the outputs when ``masked``
    (segment semantics — see ``_flash_kernel``).
    The query group (G = n_heads // n_kv_heads, 1 for classic MHA) is the
    INNERMOST grid dimension: the dk/dv output block stays resident across
    the G consecutive revisits and accumulates in float32 — VMEM stays
    O(T·D) however large the group (MQA makes G = n_heads).
    """
    if masked:
        mask_ref, mask_q_ref, dk_ref, dv_ref = rest
    else:
        dk_ref, dv_ref = rest
        mask_ref = mask_q_ref = None
    block_k = k_ref.shape[1]
    head_dim = k_ref.shape[2]
    seq_len = q_ref.shape[1]
    ki = pl.program_id(1)
    g = pl.program_id(2)

    k_blk = k_ref[0].astype(jnp.float32)  # (BK, D)
    v_blk = v_ref[0].astype(jnp.float32)

    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    if masked:
        k_seg = mask_ref[0, 0]  # (BK,) segment ids
        key_live = k_seg != 0

    num_q = seq_len // block_q
    start_q = 0
    if causal:
        # Q blocks strictly above the diagonal see none of this k-block.
        start_q = jax.lax.div(ki * block_k, block_q)
    if window:
        # The last query that can see this k-block sits at
        # k_pos_max + window - 1; later q blocks are dead.
        last_q = ki * block_k + block_k - 1 + window - 1
        num_q = jnp.minimum(num_q, jax.lax.div(last_q, block_q) + 1)

    def body(qb, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32) * scale
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = l_ref[0, 0, pl.ds(qb * block_q, block_q)]
        delta = d_ref[0, 0, pl.ds(qb * block_q, block_q)]
        s = jax.lax.dot_general(
            q_blk, k_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            live = q_pos >= k_pos
            if window:
                live &= q_pos - k_pos < window
            s = jnp.where(live, s, _NEG_INF)
        if masked:
            q_seg = mask_q_ref[0, 0, pl.ds(qb * block_q, block_q)]  # (BQ,)
            s = jnp.where(
                key_live[None, :] & (q_seg[:, None] == k_seg[None, :]),
                s,
                _NEG_INF,
            )
        p = jnp.exp(s - lse[:, None])  # (BQ, BK)
        dv_acc = dv_acc + jax.lax.dot_general(
            p, do_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, D)
        dp = jax.lax.dot_general(
            do_blk, v_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        ds = p * (dp - delta[:, None])
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BK, D)
        return dk_acc, dv_acc

    zeros = jnp.zeros((block_k, head_dim), jnp.float32)
    dk, dv = jax.lax.fori_loop(start_q, num_q, body, (zeros, zeros))

    @pl.when(g == 0)
    def _zero_init():
        dk_ref[0] = jnp.zeros((block_k, head_dim), dk_ref.dtype)
        dv_ref[0] = jnp.zeros((block_k, head_dim), dv_ref.dtype)

    # q was pre-scaled, so dk already carries one factor of scale. The
    # astype matters for group==1, where the output refs keep the narrow
    # K/V dtype (accumulation across revisits only happens at f32,
    # group>1 — see grad_dtypes at the pallas_call).
    dk_ref[0] += dk.astype(dk_ref.dtype)
    dv_ref[0] += dv.astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret", "window")
)
def pallas_flash_attention_bwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,
    g: jax.Array,
    mask: jax.Array | None = None,
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    window: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused flash-attention backward: ``(dq, dk, dv)`` for (B, T, H, D) q.

    ``k``/``v`` may be grouped-query narrow (B, T, Hkv, D) — dk/dv come
    back at that width, reduced over the query group in-kernel. ``out``/
    ``lse`` are the forward results (``pallas_flash_attention_fwd``); ``g``
    is the output cotangent; ``mask`` the same (B, T) key-padding mask as
    the forward. O(T) memory — P is recomputed per block from ``lse``,
    mirroring FlashAttention-2's backward.
    """
    b, t, h, d = q.shape
    hkv = k.shape[2]
    group = _head_groups(h, hkv)
    block_q, block_k = _check_blocks(t, block_q, block_k)
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("sliding window requires causal attention")

    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    of, gf = _fold(out), _fold(g)
    scale = 1.0 / math.sqrt(d)
    kv_row = _kv_index(h, hkv)
    masked = mask is not None
    mask_arr = _mask3(mask)

    # D = rowsum(dO * O): one cheap fused elementwise+reduce in XLA. lse and
    # delta travel as (BH, 1, T) so their (1, 1, block) specs tile legally.
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    lse3 = lse.reshape(b * h, 1, t)
    delta3 = delta.reshape(b * h, 1, t)

    seq_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),  # q
        pl.BlockSpec((1, t, d), lambda bh, qi: (kv_row(bh), 0, 0)),  # k
        pl.BlockSpec((1, t, d), lambda bh, qi: (kv_row(bh), 0, 0)),  # v
        pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),  # do
        pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),  # lse
        pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),  # delta
    ]
    dq_operands = [qf, kf, vf, gf, lse3, delta3]
    if masked:
        seq_specs.append(pl.BlockSpec((1, 1, t), lambda bh, qi: (bh // h, 0, 0)))
        dq_operands.append(mask_arr)
        # Same mask, q-block tiled (the queries' segment ids).
        seq_specs.append(
            pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh // h, 0, qi))
        )
        dq_operands.append(mask_arr)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_k=block_k, scale=scale, causal=causal,
            masked=masked, window=window,
        ),
        grid=(b * h, t // block_q),
        in_specs=seq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_operands)

    # dk/dv grid over (batch*kv_head, k-block, group-member). The group is
    # innermost so the (1, BK, D) output block stays resident across the G
    # revisits and accumulates in f32; head g of kv-head j in batch b_i is
    # folded-q row b_i*h + j*G + g.
    def _q_row(r, g):
        return (r // hkv) * h + (r % hkv) * group + g

    kv_specs = [
        pl.BlockSpec((1, t, d), lambda r, ki, g: (_q_row(r, g), 0, 0)),  # q
        pl.BlockSpec((1, block_k, d), lambda r, ki, g: (r, ki, 0)),  # k
        pl.BlockSpec((1, block_k, d), lambda r, ki, g: (r, ki, 0)),  # v
        pl.BlockSpec((1, t, d), lambda r, ki, g: (_q_row(r, g), 0, 0)),  # do
        pl.BlockSpec((1, 1, t), lambda r, ki, g: (_q_row(r, g), 0, 0)),  # lse
        pl.BlockSpec((1, 1, t), lambda r, ki, g: (_q_row(r, g), 0, 0)),  # delta
    ]
    dkdv_operands = [qf, kf, vf, gf, lse3, delta3]
    if masked:
        kv_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda r, ki, g: (r // hkv, 0, ki))
        )
        dkdv_operands.append(mask_arr)
        # Full-length mask for the streamed queries' segment ids.
        kv_specs.append(
            pl.BlockSpec((1, 1, t), lambda r, ki, g: (r // hkv, 0, 0))
        )
        dkdv_operands.append(mask_arr)
    # f32 block residency is only needed when the group accumulates across
    # revisits; classic MHA (group == 1) writes each block once, so it
    # keeps the narrow dtype and its HBM footprint.
    grad_dtypes = (jnp.float32, jnp.float32) if group > 1 else (k.dtype, v.dtype)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, block_q=block_q, scale=scale, causal=causal,
            masked=masked, window=window,
        ),
        grid=(b * hkv, t // block_k, group),
        in_specs=kv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda r, ki, g: (r, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda r, ki, g: (r, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, t, d), grad_dtypes[0]),
            jax.ShapeDtypeStruct((b * hkv, t, d), grad_dtypes[1]),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkdv",
    )(*dkdv_operands)

    return (
        _unfold(dq, b, h),
        _unfold(dk.astype(k.dtype), b, hkv),
        _unfold(dv.astype(v.dtype), b, hkv),
    )

"""Pallas TPU flash-attention kernels: forward and backward.

The MXU-resident hot path for causal attention: one grid program per
(batch*head, q-block), K/V of the head whole in VMEM, online softmax, so
nothing of shape (T, T) ever exists. Every per-block operation happens in
the layout and the dtype the chip already holds its operands in:

* **Softmax statistics stay on sublanes.** The running max ``m``, the
  running sum ``l`` and the output accumulator live in ``pltpu.VMEM``
  scratch with one row a sublane: ``m`` / ``l`` are ``(block_q, lanes)``
  lane-replicated (what a row reduction leaves behind and what the next
  block's broadcast wants), never a 1-D ``(block_q,)`` value. The
  lane-major ``lse`` row the backward reads is produced once a program,
  after the loops; in HBM ``lse`` and ``delta`` stay ``(B*H, T)`` float32.
* **No transposed left operand.** The dk/dv kernel computes the scores
  transposed (``k @ q^T``, keys on rows), so ``p^T`` and ``ds^T`` are born
  in the orientation ``dv = p^T dO`` and ``dk = ds^T q`` consume, and
  ``lse`` / ``delta`` broadcast along rows from the lane-major rows they
  are stored as. Every product is ``A @ B`` or ``A @ B^T``.
* **The MXU gets the dtype it was handed.** ``q``/``k``/``v``/``dO`` go
  to ``dot_general`` as they arrive with float32 accumulation; ``p`` and
  ``ds`` are cast to the operand dtype for the second product (as
  ``dense_attention`` does). The softmax scale is folded into the resident
  operand only where that adds no rounding the caller's dtype did not
  already have (float32 operands, or a power-of-two scale such as 1/8 for
  a 64-wide head); otherwise it multiplies the float32 scores.
* **The mask only where the diagonal crosses.** Blocks wholly under the
  diagonal (and wholly inside a sliding window) take a path with no iota,
  compare or select; the diagonal block is walked in column strips, each
  over only the rows that can see it, so the dead triangle's sub-tiles
  are never computed. A segment mask, when given, applies to every block.

Tiles are two numbers a kernel: the resident ("outer") block of the grid
and the streamed ("inner") block of the loop. Any pair the sequence
length divides is legal; ``ops/flash_attention.py:_auto_block`` picks the
pairs the chip was measured to like.

Two capabilities beyond the plain causal kernel:

* **Key-padding / segment masks** (reference src/llmtrain/models/gpt.py:60-64
  applies the padding mask inside attention): an optional (B, T) mask
  streams through VMEM and masked keys get -inf logits before the online
  softmax. Fully-masked query rows self-correct: the running-max
  correction factor zeroes any transient garbage the moment a live block
  arrives, and rows that never see a live key are zeroed by the caller's
  output mask (models/gpt.py) with zero cotangents flowing back.
* **Native grouped-query attention**: K/V may have fewer heads than Q
  (n_kv_heads). The forward and dq kernels map each query head to its
  K/V group via the BlockSpec index map — no jnp.repeat materialization
  in HBM — and the dk/dv kernel grids over (batch*kv_head, k-block,
  group member), accumulating the group in float32 VMEM scratch, so
  gradients are born at the narrow width and in the narrow dtype.

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
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
# Width of the column strips the diagonal block is walked in (v5e, T 1,024,
# bf16, PERF.md section 6, PR 30). Every strip is one online-softmax update
# of all the rows below it, and the statistics cost a strip as much as 128
# columns of scores: the forward wants few wide strips, the backward (no
# statistics to update) the narrowest that fills a lane tile.
_FWD_STRIP = 512
_BWD_STRIP = 128

_NT = (((1,), (1,)), ((), ()))  # A @ B^T
_NN = (((1,), (0,)), ((), ()))  # A @ B


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _bcast(x, width: int):
    """(rows, lanes) lane-replicated -> (rows, width)."""
    lanes = x.shape[1]
    if width == lanes:
        return x
    if width < lanes:
        return x[:, :width]
    if width % lanes == 0:
        return jnp.concatenate([x] * (width // lanes), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _to_col(row, lanes: int):
    """(1, n) lane-major -> (n, lanes): rows on sublanes, lane-replicated."""
    return jnp.broadcast_to(row, (lanes, row.shape[1])).T


def _to_row(col):
    """(n, lanes) lane-replicated -> (1, n) lane-major."""
    return col.T[:1, :]


def _scores(a, b, scale, offset, *, diag: bool, window: int, segments=None,
            transposed: bool = False):
    """Masked float32 scores of one tile: ``a @ b^T`` (times ``scale``
    unless it is already folded into ``a``: then None), -inf where a query
    may not see a key.

    The tile is (queries, keys), or (keys, queries) when ``transposed``.
    ``offset`` is ``q_pos - k_pos`` at its [0, 0] (a Python int on the
    diagonal, a traced scalar on a window's edge); ``diag`` asks for the
    causal triangle, ``window`` for the window's far edge. ``segments`` is
    the (column, row) pair of segment ids, (rows, lanes) lane-replicated
    and (1, width): equal and nonzero = same document. An interior tile
    without segments pays for no iota, compare or select.
    """
    s = _dot(a, b, _NT)
    if scale is not None:
        s = s * scale
    live = None
    if diag or window:
        r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        rel = (c - r if transposed else r - c) + offset
        if diag:
            live = rel >= 0
        if window:
            live = rel < window if live is None else live & (rel < window)
    if segments is not None:
        col, row = segments
        same = (row != 0) & (_bcast(col, s.shape[1]) == row)
        live = same if live is None else live & same
    return s if live is None else jnp.where(live, s, _NEG_INF)


def _loop(lo, hi, body):
    """``for i in range(lo, hi): body(i)`` with traced bounds, no carry."""
    jax.lax.fori_loop(lo, hi, lambda i, c: (body(i), c)[1], 0)


def _span(i, width: int):
    return pl.ds(pl.multiple_of(i * width, width), width)


def _query_block_schedule(q0, block_q, block_k, seq_len, *, causal, window, strip, step):
    """Walk the K/V of one q block [q0, q0 + block_q).

    ``step(key_slice, width, r0, rows, offset, causal, window)`` is called
    for every tile: interior blocks (no positional mask), the blocks a
    window's left edge crosses, and the diagonal strips, each over the rows
    [r0, r0 + rows) that can see it; ``offset`` is ``q_pos - k_pos`` at the
    tile's [0, 0].
    """
    if not causal:
        _loop(0, seq_len // block_k,
              lambda kb: step(_span(kb, block_k), block_k, 0, block_q, 0, False, 0))
        return
    n_full = q0 // block_k  # blocks whose every key precedes every query
    full_lo = 0
    if window:
        lo = jnp.maximum(q0 - window + 1, 0) // block_k
        full_lo = jnp.clip(
            (jnp.maximum(q0 + block_q - window, 0) + block_k - 1) // block_k, lo, n_full
        )
        _loop(lo, full_lo, lambda kb: step(
            _span(kb, block_k), block_k, 0, block_q, q0 - kb * block_k, False, window))
    _loop(full_lo, n_full,
          lambda kb: step(_span(kb, block_k), block_k, 0, block_q, 0, False, 0))
    if block_q % block_k:
        # Keys between the last whole block and the q block's own keys.
        w = math.gcd(block_q, block_k)
        _loop(n_full * (block_k // w), q0 // w, lambda i: step(
            _span(i, w), w, 0, block_q, q0 - i * w, False, window))
    strip = math.gcd(strip, block_q)
    for c0 in range(0, block_q, strip):
        start = pl.multiple_of(q0 + c0, strip)
        step(pl.ds(start, strip), strip, c0, block_q - c0, 0, True, window)


def _key_block_schedule(k0, block_k, block_q, seq_len, *, causal, window, strip, step):
    """Walk the queries of one k block [k0, k0 + block_k): the transposed
    twin of ``_query_block_schedule``. ``step(q_slice, width, rows, offset,
    causal, window)`` sees key rows [0, rows) of the block."""
    num_q = seq_len // block_q
    if not causal:
        _loop(0, num_q, lambda qb: step(_span(qb, block_q), block_q, block_k, 0, False, 0))
        return
    strip = math.gcd(strip, block_k)
    for c0 in range(0, block_k, strip):
        start = pl.multiple_of(k0 + c0, strip)
        step(pl.ds(start, strip), strip, c0 + strip, c0, True, window)
    first = (k0 + block_k + block_q - 1) // block_q  # first q block wholly below
    if block_k % block_q:
        w = math.gcd(block_q, block_k)
        _loop((k0 + block_k) // w, first * (block_q // w), lambda i: step(
            _span(i, w), w, block_k, i * w - k0, False, window))
    full_hi = num_q
    if window:
        # The last query that can see this k block sits at
        # k0 + block_k - 1 + window - 1; later q blocks are dead. A q block
        # is wholly inside the window when its last query still sees k0.
        hi = jnp.minimum(num_q, (k0 + block_k + window - 2) // block_q + 1)
        full_hi = jnp.clip((k0 + window) // block_q, first, hi)
        _loop(full_hi, hi, lambda qb: step(
            _span(qb, block_q), block_q, block_k, qb * block_q - k0, False, window))
    _loop(first, full_hi,
          lambda qb: step(_span(qb, block_q), block_q, block_k, 0, False, 0))


def _flash_kernel(
    q_ref, k_ref, v_ref, *rest, block_k: int, scale: float, fold_scale: bool,
    causal: bool, masked: bool, window: int = 0,
):
    """One q-block vs the K/V sequence of its head.

    Ref shapes: q (1, BQ, D), k/v (1, T, D), o (1, BQ, D), l (1, 1, BQ),
    optional mask (1, 1, T) int32 + its q-block view (1, 1, BQ) ahead of
    the outputs when ``masked``; scratch m/l (BQ, lanes) and acc (BQ, D),
    float32. Mask values are SEGMENT ids: nonzero = real token, equal
    values = same document (plain 0/1 padding masks are the one-segment
    special case). ``l`` is the per-row logsumexp of the scaled/masked
    logits — the residual the backward kernels use to recompute P without
    a re-softmax. It is carried with a singleton middle dim so its block
    shape satisfies Mosaic's tiling rule (second-to-last block dim ==
    array dim).
    """
    if masked:
        mask_ref, mask_q_ref, o_ref, l_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, l_ref, m_s, l_s, acc_s = rest
    block_q, head_dim = q_ref.shape[1:]
    lanes = m_s.shape[1]
    q0 = pl.program_id(1) * block_q

    q = q_ref[0] * scale if fold_scale else q_ref[0]  # (BQ, D), operand dtype
    score_scale = None if fold_scale else scale
    if masked:
        q_seg = _to_col(mask_q_ref[0], lanes)  # (BQ, lanes) int32
    m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
    l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
    acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def step(keys, width, r0, rows, offset, diag, win):
        sl = pl.ds(r0, rows)
        k_blk = k_ref[0, keys, :]  # (width, D)
        v_blk = v_ref[0, keys, :]
        segments = (q_seg[r0:r0 + rows], mask_ref[0, :, keys]) if masked else None
        s = _scores(q[r0:r0 + rows], k_blk, score_scale, offset, diag=diag,
                    window=win, segments=segments)  # (rows, width)
        m_prev = m_s[sl, :]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _bcast(m_new, width))
        alpha = jnp.exp(m_prev - m_new)
        m_s[sl, :] = m_new
        l_s[sl, :] = alpha * l_s[sl, :] + p.sum(axis=1, keepdims=True)
        acc_s[sl, :] = acc_s[sl, :] * _bcast(alpha, head_dim) + _dot(
            p.astype(v_blk.dtype), v_blk, _NN
        )

    _query_block_schedule(
        q0, block_q, block_k, k_ref.shape[1], causal=causal, window=window,
        strip=_FWD_STRIP, step=step,
    )

    row_sum = l_s[...]
    o_ref[0] = (acc_s[...] * _bcast(1.0 / row_sum, head_dim)).astype(o_ref.dtype)
    l_ref[0] = _to_row(m_s[...] + jnp.log(row_sum))


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


def _check_window(window: int, causal: bool) -> None:
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if window and not causal:
        raise ValueError("sliding window requires causal attention")


def _stat_lanes(outer: int, inner: int) -> int:
    """Lane width of the sublane-major statistics: a whole lane tile on the
    chip, and whatever divides every tile width the schedule uses off it."""
    return math.gcd(_LANES, outer, inner)


def _fold_scale(scale: float, dtype) -> bool:
    """Whether scaling the resident operand rounds nothing the caller's
    dtype had not: float32 operands, or a power-of-two scale."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


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
    ``block_q`` rows are resident a program, ``block_k`` keys stream a
    loop step; both fall back to T when T is smaller.
    """
    b, t, h, d = q.shape
    hkv = k.shape[2]
    _head_groups(h, hkv)
    block_q, block_k = _check_blocks(t, block_q, block_k)
    _check_window(window, causal)

    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    scale = 1.0 / math.sqrt(d)
    kv_row = _kv_index(h, hkv)
    masked = mask is not None
    lanes = _stat_lanes(block_q, block_k)

    kernel = functools.partial(
        _flash_kernel, block_k=block_k, scale=scale,
        fold_scale=_fold_scale(scale, q.dtype), causal=causal, masked=masked,
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
        scratch_shapes=[
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
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
    block_k: int, scale: float, fold_scale: bool, causal: bool, masked: bool,
    window: int = 0,
):
    """dQ for one q-block against the K/V of its head (the forward's
    schedule).

    Ref shapes: q/do/dq (1, BQ, D), k/v (1, T, D), l/d (1, 1, BQ),
    optional mask (1, 1, T) + its q-block view (1, 1, BQ) ahead of the
    output when ``masked`` (segment semantics — see ``_flash_kernel``);
    scratch acc (BQ, D) float32. ``l`` / ``d`` turn from lane-major rows
    into sublane-major columns once, before the loops.
    """
    if masked:
        mask_ref, mask_q_ref, dq_ref, acc_s = rest
    else:
        dq_ref, acc_s = rest
    block_q = q_ref.shape[1]
    lanes = _stat_lanes(block_q, block_k)
    q0 = pl.program_id(1) * block_q

    q = q_ref[0] * scale if fold_scale else q_ref[0]  # (BQ, D)
    score_scale = None if fold_scale else scale
    do = do_ref[0]
    lse = _to_col(l_ref[0], lanes)  # (BQ, lanes)
    delta = _to_col(d_ref[0], lanes)  # rowsum(dO * O)
    if masked:
        q_seg = _to_col(mask_q_ref[0], lanes)
    acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def step(keys, width, r0, rows, offset, diag, win):
        k_blk = k_ref[0, keys, :]
        v_blk = v_ref[0, keys, :]
        segments = (q_seg[r0:r0 + rows], mask_ref[0, :, keys]) if masked else None
        s = _scores(q[r0:r0 + rows], k_blk, score_scale, offset, diag=diag,
                    window=win, segments=segments)  # (rows, width)
        p = jnp.exp(s - _bcast(lse[r0:r0 + rows], width))
        dp = _dot(do[r0:r0 + rows], v_blk, _NT)
        ds = p * (dp - _bcast(delta[r0:r0 + rows], width))
        acc_s[pl.ds(r0, rows), :] += _dot(ds.astype(k_blk.dtype), k_blk, _NN)

    _query_block_schedule(
        q0, block_q, block_k, k_ref.shape[1], causal=causal, window=window,
        strip=_BWD_STRIP, step=step,
    )
    dq_ref[0] = (acc_s[...] * scale).astype(dq_ref.dtype)


def _bwd_dkdv_kernel(
    q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, *rest,
    block_q: int, scale: float, fold_scale: bool, causal: bool, masked: bool,
    window: int = 0,
):
    """dK/dV for one (kv-head, k-block, group-member) grid point, walking
    that query head's Q/dO/L/D from the causal diagonal down with the
    scores TRANSPOSED: keys on rows, queries on lanes.

    Ref shapes: k/v/dk/dv (1, BK, D), q/do (1, T, D), l/d (1, 1, T),
    optional mask (1, 1, BK) + the full-length mask (1, 1, T) for the
    streamed queries' segments, ahead of the outputs when ``masked``
    (segment semantics — see ``_flash_kernel``); scratch dk/dv (BK, D)
    float32. The query group (G = n_heads // n_kv_heads, 1 for classic
    MHA) is the INNERMOST grid dimension: the scratch accumulates across
    the G consecutive visits and the output block is written on the last,
    in the K/V dtype — VMEM stays O(T·D) however large the group (MQA
    makes G = n_heads).
    """
    if masked:
        mask_ref, mask_q_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    else:
        dk_ref, dv_ref, dk_s, dv_s = rest
    block_k = k_ref.shape[1]
    k0 = pl.program_id(1) * block_k
    g = pl.program_id(2)

    k_blk = k_ref[0]  # (BK, D)
    v_blk = v_ref[0]
    k_scaled = k_blk * scale if fold_scale else k_blk
    score_scale = None if fold_scale else scale
    if masked:
        k_seg = _to_col(mask_ref[0], _stat_lanes(block_k, block_q))  # (BK, lanes)

    @pl.when(g == 0)
    def _zero_init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def step(queries, width, rows, offset, diag, win):
        q_blk = q_ref[0, queries, :]  # (width, D)
        do_blk = do_ref[0, queries, :]
        segments = (k_seg[:rows], mask_q_ref[0, :, queries]) if masked else None
        st = _scores(k_scaled[:rows], q_blk, score_scale, offset, diag=diag,
                     window=win, segments=segments, transposed=True)  # s^T
        pt = jnp.exp(st - l_ref[0, :, queries])  # (rows, width)
        dv_s[pl.ds(0, rows), :] += _dot(pt.astype(do_blk.dtype), do_blk, _NN)
        dpt = _dot(v_blk[:rows], do_blk, _NT)
        dst = pt * (dpt - d_ref[0, :, queries])
        dk_s[pl.ds(0, rows), :] += _dot(dst.astype(q_blk.dtype), q_blk, _NN)

    _key_block_schedule(
        k0, block_k, block_q, q_ref.shape[1], causal=causal, window=window,
        strip=_BWD_STRIP, step=step,
    )

    @pl.when(g == pl.num_programs(2) - 1)
    def _write():
        dk_ref[0] = (dk_s[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_k", "dkdv_block_q", "dkdv_block_k",
        "interpret", "window",
    ),
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
    dkdv_block_q: int | None = None,
    dkdv_block_k: int | None = None,
    interpret: bool = False,
    window: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused flash-attention backward: ``(dq, dk, dv)`` for (B, T, H, D) q.

    ``k``/``v`` may be grouped-query narrow (B, T, Hkv, D) — dk/dv come
    back at that width, reduced over the query group in-kernel. ``out``/
    ``lse`` are the forward results (``pallas_flash_attention_fwd``); ``g``
    is the output cotangent; ``mask`` the same (B, T) key-padding mask as
    the forward. O(T) memory — P is recomputed per block from ``lse``,
    mirroring FlashAttention-2's backward. ``block_q`` / ``block_k`` tile
    the dq kernel (q rows resident, keys streamed) and, unless
    ``dkdv_block_q`` / ``dkdv_block_k`` say otherwise, the dk/dv kernel
    (keys resident, queries streamed).
    """
    b, t, h, d = q.shape
    hkv = k.shape[2]
    group = _head_groups(h, hkv)
    block_q, block_k = _check_blocks(t, block_q, block_k)
    kv_block_q, kv_block_k = _check_blocks(
        t, dkdv_block_q or block_q, dkdv_block_k or block_k
    )
    _check_window(window, causal)

    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    of, gf = _fold(out), _fold(g)
    scale = 1.0 / math.sqrt(d)
    fold_scale = _fold_scale(scale, q.dtype)
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
            _bwd_dq_kernel, block_k=block_k, scale=scale, fold_scale=fold_scale,
            causal=causal, masked=masked, window=window,
        ),
        grid=(b * h, t // block_q),
        in_specs=seq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_operands)

    # dk/dv grid over (batch*kv_head, k-block, group-member). The group is
    # innermost so the (1, BK, D) output block stays resident across the G
    # visits; head g of kv-head j in batch b_i is folded-q row
    # b_i*h + j*G + g.
    def _q_row(r, g):
        return (r // hkv) * h + (r % hkv) * group + g

    kv_specs = [
        pl.BlockSpec((1, t, d), lambda r, ki, g: (_q_row(r, g), 0, 0)),  # q
        pl.BlockSpec((1, kv_block_k, d), lambda r, ki, g: (r, ki, 0)),  # k
        pl.BlockSpec((1, kv_block_k, d), lambda r, ki, g: (r, ki, 0)),  # v
        pl.BlockSpec((1, t, d), lambda r, ki, g: (_q_row(r, g), 0, 0)),  # do
        pl.BlockSpec((1, 1, t), lambda r, ki, g: (_q_row(r, g), 0, 0)),  # lse
        pl.BlockSpec((1, 1, t), lambda r, ki, g: (_q_row(r, g), 0, 0)),  # delta
    ]
    dkdv_operands = [qf, kf, vf, gf, lse3, delta3]
    if masked:
        kv_specs.append(
            pl.BlockSpec((1, 1, kv_block_k), lambda r, ki, g: (r // hkv, 0, ki))
        )
        dkdv_operands.append(mask_arr)
        # Full-length mask for the streamed queries' segment ids.
        kv_specs.append(
            pl.BlockSpec((1, 1, t), lambda r, ki, g: (r // hkv, 0, 0))
        )
        dkdv_operands.append(mask_arr)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, block_q=kv_block_q, scale=scale,
            fold_scale=fold_scale, causal=causal, masked=masked, window=window,
        ),
        grid=(b * hkv, t // kv_block_k, group),
        in_specs=kv_specs,
        out_specs=[
            pl.BlockSpec((1, kv_block_k, d), lambda r, ki, g: (r, ki, 0)),
            pl.BlockSpec((1, kv_block_k, d), lambda r, ki, g: (r, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((kv_block_k, d), jnp.float32),
            pltpu.VMEM((kv_block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkdv",
    )(*dkdv_operands)

    return _unfold(dq, b, h), _unfold(dk, b, hkv), _unfold(dv, b, hkv)

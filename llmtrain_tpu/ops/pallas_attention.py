"""Pallas TPU flash-attention kernels: forward and backward.

The MXU-resident hot path for causal attention: one grid program per
(batch, head block, q-block), K/V of the head block whole in VMEM, online
softmax, so nothing of shape (T, T) ever exists.

**The layout the kernels take and give is the projections' own: rows,
``(B, T, H*D)``**, as the qkv projection's matmul leaves them and the out
projection's takes them, so no transpose of an activation stands in XLA on
either side of a call, forward or backward. A BlockSpec takes a column
range of whole 128-lane tiles (``lane_block_heads``): one head where the
head width is a multiple of 128, TWO adjacent heads where it is 64. Inside
a program the two heads of a block are told apart by lane masks, never by
lane slices: the resident operand of a contraction is split into two copies
with the other head's lanes zeroed (``_split``: a contraction over 128 lanes
of which 64 are zeros is exact, and costs the MXU the passes a 64-deep
product costs: the systolic array is 128 deep either way), and a product
whose output columns are the block's lanes comes out once a head and is
joined by a lane select (``_join``). Statistics, accumulation and the
skip-schedule are per head, two sets a program. Three hand-offs share the
kernels (``_Handoff``), picked from the operands' shapes alone:

* *fused*: q, k and v are the three column ranges of the ONE
  ``(B, T, 3*H*D)`` array the qkv projection wrote
  (``pallas_flash_attention_qkv_fwd`` / ``_bwd``); the gradient is one such
  array, its first third written by the dq call and the rest, in place, by
  the dk/dv call (``input_output_aliases``), so nothing is sliced before or
  concatenated after;
* *merged*: q, k and v apart, each ``(B, T, heads*D)`` (a reshape of the
  ``(B, T, H, D)`` the wrappers are handed, no data moves): grouped-query
  K/V at a head width of 128, or operands the block rotated or scaled after
  the projection;
* *folded*: ``(B*H, T, D)`` arrays made and unmade by XLA transposes around
  the calls, for the shapes with no whole lane blocks: a 64-wide head under
  GQA (a pair of query heads would want half a lane block of K/V), an odd
  head count at 64, any other head width.

Every per-block operation happens in the layout and the dtype the chip
already holds its operands in:

* **Softmax statistics stay on sublanes.** The running max ``m``, the
  running sum ``l`` and the output accumulator live in ``pltpu.VMEM``
  scratch with one row a sublane: ``m`` / ``l`` are ``(block_q, lanes)``
  lane-replicated (what a row reduction leaves behind and what the next
  block's broadcast wants), never a 1-D ``(block_q,)`` value. The
  lane-major ``lse`` row the backward reads is produced once a program,
  after the loops; in HBM ``lse`` and ``delta`` are one float32 a row and
  head, ``(B, H, 1, T)``. ``delta`` = rowsum(dO * O) is formed by the dq
  kernel, which holds dO's rows anyway, and handed to the dk/dv kernel.
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
  in HBM — and the dk/dv kernel grids over (batch, kv-head block, k-block,
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

import dataclasses
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


def _split(x, hpb: int):
    """The heads of one lane block apart: per head, ``x`` (rows, W) with
    the other head's lanes zeroed, so that a contraction over all W lanes
    sees that head alone (exact: the other head's terms are zeros)."""
    if hpb == 1:
        return [x]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    half = x.shape[1] // 2
    zero = jnp.zeros_like(x)
    return [jnp.where(lane < half, x, zero), jnp.where(lane >= half, x, zero)]


def _join(parts):
    """One (rows, W) value that takes each head's lanes from its own part:
    the inverse of ``_split`` for products whose output columns are the
    block's lanes."""
    if len(parts) == 1:
        return parts[0]
    left, right = parts
    lane = jax.lax.broadcasted_iota(jnp.int32, left.shape, 1)
    return jnp.where(lane < left.shape[1] // 2, left, right)


def _flash_kernel(
    q_ref, k_ref, v_ref, *rest, hpb: int, block_k: int, scale: float,
    fold_scale: bool, causal: bool, masked: bool, window: int = 0,
):
    """One q-block vs the K/V sequence of its head block (``hpb`` heads:
    one, or two 64-wide heads side by side in a lane tile).

    Ref shapes: q (1, BQ, W), k/v (1, T, W), o (1, BQ, W), l (1, hpb, 1,
    BQ), optional mask (1, 1, T) int32 + its q-block view (1, 1, BQ) ahead
    of the outputs when ``masked``; scratch m/l (hpb, BQ, lanes) and acc
    (BQ, W), float32. Mask values are SEGMENT ids: nonzero = real token,
    equal values = same document (plain 0/1 padding masks are the
    one-segment special case). ``l`` is the per-row logsumexp of the
    scaled/masked logits — the residual the backward kernels use to
    recompute P without a re-softmax. It is carried with a singleton
    second-to-last dim so its block shape satisfies Mosaic's tiling rule
    (second-to-last block dim == array dim).
    """
    if masked:
        mask_ref, mask_q_ref, o_ref, l_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, l_ref, m_s, l_s, acc_s = rest
    block_q, width = q_ref.shape[1:]
    lanes = m_s.shape[2]
    q0 = pl.program_id(2) * block_q

    q = q_ref[0] * scale if fold_scale else q_ref[0]  # (BQ, W), operand dtype
    qs = _split(q, hpb)
    score_scale = None if fold_scale else scale
    if masked:
        q_seg = _to_col(mask_q_ref[0], lanes)  # (BQ, lanes) int32
    m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
    l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
    acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def step(keys, tile, r0, rows, offset, diag, win):
        sl = pl.ds(r0, rows)
        k_blk = k_ref[0, keys, :]  # (tile, W)
        v_blk = v_ref[0, keys, :]
        segments = (q_seg[r0:r0 + rows], mask_ref[0, :, keys]) if masked else None
        alphas, pvs = [], []
        for j, qj in enumerate(qs):
            s = _scores(qj[r0:r0 + rows], k_blk, score_scale, offset, diag=diag,
                        window=win, segments=segments)  # (rows, tile)
            m_prev = m_s[j, sl, :]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - _bcast(m_new, tile))
            alpha = jnp.exp(m_prev - m_new)
            m_s[j, sl, :] = m_new
            l_s[j, sl, :] = alpha * l_s[j, sl, :] + p.sum(axis=1, keepdims=True)
            alphas.append(_bcast(alpha, width))
            pvs.append(_dot(p.astype(v_blk.dtype), v_blk, _NN))
        acc_s[sl, :] = acc_s[sl, :] * _join(alphas) + _join(pvs)

    _query_block_schedule(
        q0, block_q, block_k, k_ref.shape[1], causal=causal, window=window,
        strip=_FWD_STRIP, step=step,
    )

    o_ref[0] = (
        acc_s[...] * _join([_bcast(1.0 / l_s[j], width) for j in range(hpb)])
    ).astype(o_ref.dtype)
    for j in range(hpb):
        l_ref[0, j] = _to_row(m_s[j] + jnp.log(l_s[j]))


def _fold(x: jax.Array) -> jax.Array:
    """(B, T, H, D) -> (B*H, T, D): heads join the grid batch dimension."""
    b, t, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)


def _unfold(x: jax.Array, b: int, h: int) -> jax.Array:
    bh, t, d = x.shape
    return jnp.moveaxis(x.reshape(b, h, t, d), 1, 2)


def lane_block_heads(h: int, hkv: int, d: int) -> int | None:
    """Heads in one lane block of a ``(B, T, H*D)`` array the kernels can
    index in place, or None where they cannot and the call folds.

    A block's last dimension is whole 128-lane tiles: one head where the
    head width is a multiple of 128 (any K/V grouping: a query head's block
    maps to its group's block), two adjacent heads where it is 64 and every
    query head has a K/V head of its own (under GQA a pair of query heads
    would want half a lane block of K/V). Anything else (an odd head count
    at 64, another width) keeps the folded ``(B*H, T, D)`` hand-off.
    """
    if d % _LANES == 0:
        return 1
    if d == _LANES // 2 and h == hkv and h % 2 == 0:
        return 2
    return None


@dataclasses.dataclass(frozen=True)
class _Handoff:
    """Where the kernels find a head block in the arrays they are handed.

    Three hand-offs, one set of kernels: *folded* ``(B*H, T, D)`` arrays
    (the transposes live in XLA, around the call), *merged* ``(B, T, H*D)``
    arrays as the projections' matmuls leave and take them (a block is a
    column range of whole lane tiles), and *fused*: merged, with q, k and v
    the three column ranges of ONE ``(B, T, 3*H*D)`` array (the qkv
    projection's output; the gradient is one such array too).
    """

    batch: int
    seq: int
    heads: int
    kv_heads: int
    head_dim: int
    hpb: int  # heads a block holds
    merged: bool
    fused: bool = False

    @property
    def width(self) -> int:
        return self.hpb * self.head_dim

    @property
    def q_blocks(self) -> int:
        return self.heads // self.hpb

    @property
    def kv_blocks(self) -> int:
        return self.kv_heads // self.hpb

    def at(self, which: int, b, blk, rows):
        """Block index of head block ``blk``, row block ``rows`` of batch
        row ``b``, in the array of q (``which`` 0), k (1) or v (2)."""
        if not self.merged:
            return (b * (self.kv_heads if which else self.heads) + blk, rows, 0)
        return (b, rows, blk + (which * self.q_blocks if self.fused else 0))

    def hand(self, x: jax.Array) -> jax.Array:
        """(B, T, H, D), or the fused (B, T, 3, H, D), as the kernels index it."""
        if not self.merged:
            return _fold(x)
        return x.reshape(self.batch, self.seq, -1)

    def shape(self, heads: int) -> tuple[int, int, int]:
        """Of a kernel's output that ``take`` turns into ``heads`` heads."""
        if not self.merged:
            return (self.batch * heads, self.seq, self.head_dim)
        return (self.batch, self.seq, heads * self.head_dim)

    def take(self, x: jax.Array, heads: int) -> jax.Array:
        """A kernel's output back as (B, T, heads, D)."""
        if not self.merged:
            return _unfold(x, self.batch, heads)
        return x.reshape(self.batch, self.seq, heads, self.head_dim)


def _handoff(q_shape, k_shape, fused: bool = False) -> _Handoff:
    """The hand-off a call's shapes allow: merged where ``lane_block_heads``
    finds whole lane blocks, folded otherwise."""
    b, t, h, d = q_shape
    hkv = k_shape[2]
    _head_groups(h, hkv)
    hpb = lane_block_heads(h, hkv, d)
    return _Handoff(b, t, h, hkv, d, hpb or 1, merged=hpb is not None, fused=fused)


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


def _mask3(mask: jax.Array | None) -> jax.Array | None:
    """(B, T) padding mask -> (B, 1, T) int32 for legal (1, 1, BK) tiling."""
    if mask is None:
        return None
    return mask.astype(jnp.int32)[:, None, :]


def _forward(lay: _Handoff, q, k, v, mask, *, causal, block_q, block_k, interpret, window):
    """The forward call on arrays already in ``lay``'s hand-off (the same
    array three times when fused). Returns ``(out, lse)``: ``out`` shaped
    ``lay.shape(heads)``, ``lse`` (B, H, 1, T) float32."""
    b, t, d, hpb, w = lay.batch, lay.seq, lay.head_dim, lay.hpb, lay.width
    group = lay.heads // lay.kv_heads
    block_q, block_k = _check_blocks(t, block_q, block_k)
    _check_window(window, causal)
    scale = 1.0 / math.sqrt(d)
    masked = mask is not None
    lanes = _stat_lanes(block_q, block_k)

    kernel = functools.partial(
        _flash_kernel, hpb=hpb, block_k=block_k, scale=scale,
        fold_scale=_fold_scale(scale, q.dtype), causal=causal, masked=masked,
        window=window,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, w), lambda bi, hb, qi: lay.at(0, bi, hb, qi)),
        pl.BlockSpec((1, t, w), lambda bi, hb, qi: lay.at(1, bi, hb // group, 0)),
        pl.BlockSpec((1, t, w), lambda bi, hb, qi: lay.at(2, bi, hb // group, 0)),
    ]
    operands = [q, k, v]
    if masked:
        mask3 = _mask3(mask)
        in_specs.append(pl.BlockSpec((1, 1, t), lambda bi, hb, qi: (bi, 0, 0)))
        operands.append(mask3)
        # The SAME mask array again, tiled per q-block (segment ids for
        # this block's queries).
        in_specs.append(pl.BlockSpec((1, 1, block_q), lambda bi, hb, qi: (bi, 0, qi)))
        operands.append(mask3)
    return pl.pallas_call(
        kernel,
        grid=(b, lay.q_blocks, t // block_q),
        in_specs=in_specs,
        out_specs=[
            # The output is an array of its own: q's block index without
            # the fused array's column offset (q's is 0).
            pl.BlockSpec((1, block_q, w), lambda bi, hb, qi: lay.at(0, bi, hb, qi)),
            pl.BlockSpec((1, hpb, 1, block_q), lambda bi, hb, qi: (bi, hb, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(lay.shape(lay.heads), q.dtype),
            jax.ShapeDtypeStruct((b, lay.heads, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hpb, block_q, lanes), jnp.float32),
            pltpu.VMEM((hpb, block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, w), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*operands)


_STATIC = ("causal", "block_q", "block_k", "interpret", "window")


@functools.partial(jax.jit, static_argnames=_STATIC)
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
    loop step; both fall back to T when T is smaller. The arrays reach the
    kernel merged, ``(B, T, H*D)`` (a reshape, no data moves), wherever
    ``lane_block_heads`` finds whole lane blocks, and folded otherwise.
    """
    lay = _handoff(q.shape, k.shape)
    out, lse = _forward(
        lay, lay.hand(q), lay.hand(k), lay.hand(v), mask, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret, window=window,
    )
    return lay.take(out, lay.heads), lse.reshape(lay.batch * lay.heads, lay.seq)


@functools.partial(jax.jit, static_argnames=_STATIC)
def pallas_flash_attention_qkv_fwd(
    qkv: jax.Array,
    mask: jax.Array | None = None,
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    window: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """``pallas_flash_attention_fwd`` over the qkv projection's own output,
    (B, T, 3, H, D): the kernel reads q, k and v as the three column ranges
    of the one ``(B, T, 3*H*D)`` array, so no slice of it is ever made.
    A shape without whole lane blocks (``lane_block_heads``) is sliced and
    handed over apart."""
    b, t, _, h, d = qkv.shape
    tiles = dict(causal=causal, block_q=block_q, block_k=block_k,
                 interpret=interpret, window=window)
    if lane_block_heads(h, h, d) is None:
        return pallas_flash_attention_fwd(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], mask, **tiles)
    lay = _handoff((b, t, h, d), (b, t, h, d), fused=True)
    merged = lay.hand(qkv)
    out, lse = _forward(lay, merged, merged, merged, mask, **tiles)
    return lay.take(out, h), lse.reshape(b * h, t)


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
    q_ref, k_ref, v_ref, do_ref, o_ref, l_ref, *rest,
    hpb: int, block_k: int, scale: float, fold_scale: bool, causal: bool,
    masked: bool, window: int = 0,
):
    """dQ for one q-block against the K/V of its head block (the forward's
    schedule), and D = rowsum(dO * O) of its rows, per head, for itself and
    for the dk/dv kernel.

    Ref shapes: q/do/o/dq (1, BQ, W), k/v (1, T, W), l/d (1, hpb, 1, BQ),
    optional mask (1, 1, T) + its q-block view (1, 1, BQ) ahead of the
    outputs (dq, then d) when ``masked`` (segment semantics — see
    ``_flash_kernel``); scratch acc (BQ, W) float32. ``l`` turns from a
    lane-major row into a sublane-major column once, before the loops; ``d``
    is born a column (a row reduction leaves it so) and leaves as a row.
    """
    if masked:
        mask_ref, mask_q_ref, dq_ref, d_ref, acc_s = rest
    else:
        dq_ref, d_ref, acc_s = rest
    block_q = q_ref.shape[1]
    lanes = _stat_lanes(block_q, block_k)
    q0 = pl.program_id(2) * block_q

    q = q_ref[0] * scale if fold_scale else q_ref[0]  # (BQ, W)
    qs = _split(q, hpb)
    dos = _split(do_ref[0], hpb)
    score_scale = None if fold_scale else scale
    lse = [_to_col(l_ref[0, j], lanes) for j in range(hpb)]  # (BQ, lanes)
    delta = [
        jnp.broadcast_to(part.sum(axis=1, keepdims=True), (block_q, lanes))
        for part in _split(do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32), hpb)
    ]
    for j in range(hpb):
        d_ref[0, j] = _to_row(delta[j])
    if masked:
        q_seg = _to_col(mask_q_ref[0], lanes)
    acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def step(keys, tile, r0, rows, offset, diag, win):
        k_blk = k_ref[0, keys, :]
        v_blk = v_ref[0, keys, :]
        segments = (q_seg[r0:r0 + rows], mask_ref[0, :, keys]) if masked else None
        parts = []
        for j in range(hpb):
            s = _scores(qs[j][r0:r0 + rows], k_blk, score_scale, offset, diag=diag,
                        window=win, segments=segments)  # (rows, tile)
            p = jnp.exp(s - _bcast(lse[j][r0:r0 + rows], tile))
            dp = _dot(dos[j][r0:r0 + rows], v_blk, _NT)
            ds = p * (dp - _bcast(delta[j][r0:r0 + rows], tile))
            parts.append(_dot(ds.astype(k_blk.dtype), k_blk, _NN))
        acc_s[pl.ds(r0, rows), :] += _join(parts)

    _query_block_schedule(
        q0, block_q, block_k, k_ref.shape[1], causal=causal, window=window,
        strip=_BWD_STRIP, step=step,
    )
    dq_ref[0] = (acc_s[...] * scale).astype(dq_ref.dtype)


def _bwd_dkdv_kernel(
    q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, *rest,
    hpb: int, fused: bool, block_q: int, scale: float, fold_scale: bool,
    causal: bool, masked: bool, window: int = 0,
):
    """dK/dV for one (kv-head block, k-block, visit) grid point, walking
    the query head block's Q/dO/L/D from the causal diagonal down with the
    scores TRANSPOSED: keys on rows, queries on lanes.

    Ref shapes: k/v/dk/dv (1, BK, W), q/do (1, T, W), l/d (1, hpb, 1, T),
    optional mask (1, 1, BK) + the full-length mask (1, 1, T) for the
    streamed queries' segments, ahead of the outputs when ``masked``
    (segment semantics — see ``_flash_kernel``); scratch dk/dv (BK, W)
    float32. The INNERMOST grid dimension is the visits of one output
    block. Apart (``fused`` false) they are the query group (G = n_heads //
    n_kv_heads, 1 for classic MHA): the scratch accumulates across the G
    consecutive visits and dk and dv, two arrays, are written on the last,
    in the K/V dtype — VMEM stays O(T·W) however large the group (MQA
    makes G = n_heads). Fused, dk and dv are two column ranges of the ONE
    gradient array the dq call began (``rest`` then starts with that array,
    aliased to the output and never read): visit 0 computes both and
    writes dk's block, visit 1 (the output block index has moved to v's
    columns, no input's has) writes dv's from the scratch.
    """
    if fused:
        rest = rest[1:]
    if masked:
        mask_ref, mask_q_ref, *rest = rest
    if fused:
        out_ref, dk_s, dv_s = rest
    else:
        dk_ref, dv_ref, dk_s, dv_s = rest
    block_k = k_ref.shape[1]
    k0 = pl.program_id(2) * block_k
    visit = pl.program_id(3)

    def accumulate():
        k_blk = k_ref[0]  # (BK, W)
        ks = _split(k_blk * scale if fold_scale else k_blk, hpb)
        vs = _split(v_ref[0], hpb)
        score_scale = None if fold_scale else scale
        if masked:
            k_seg = _to_col(mask_ref[0], _stat_lanes(block_k, block_q))  # (BK, lanes)

        def step(queries, tile, rows, offset, diag, win):
            q_blk = q_ref[0, queries, :]  # (tile, W)
            do_blk = do_ref[0, queries, :]
            segments = (k_seg[:rows], mask_q_ref[0, :, queries]) if masked else None
            dvs, dks = [], []
            for j in range(hpb):
                st = _scores(ks[j][:rows], q_blk, score_scale, offset, diag=diag,
                             window=win, segments=segments, transposed=True)  # s^T
                pt = jnp.exp(st - l_ref[0, j, :, queries])  # (rows, tile)
                dvs.append(_dot(pt.astype(do_blk.dtype), do_blk, _NN))
                dpt = _dot(vs[j][:rows], do_blk, _NT)
                dst = pt * (dpt - d_ref[0, j, :, queries])
                dks.append(_dot(dst.astype(q_blk.dtype), q_blk, _NN))
            dv_s[pl.ds(0, rows), :] += _join(dvs)
            dk_s[pl.ds(0, rows), :] += _join(dks)

        _key_block_schedule(
            k0, block_k, block_q, q_ref.shape[1], causal=causal, window=window,
            strip=_BWD_STRIP, step=step,
        )

    @pl.when(visit == 0)
    def _zero_init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    if fused:
        @pl.when(visit == 0)
        def _dk():
            accumulate()
            out_ref[0] = (dk_s[...] * scale).astype(out_ref.dtype)

        @pl.when(visit == 1)
        def _dv():
            out_ref[0] = dv_s[...].astype(out_ref.dtype)
    else:
        accumulate()

        @pl.when(visit == pl.num_programs(3) - 1)
        def _write():
            dk_ref[0] = (dk_s[...] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _backward(
    lay: _Handoff, q, k, v, out, lse, do, mask, *, causal, block_q, block_k,
    dkdv_block_q, dkdv_block_k, interpret, window,
):
    """The two backward calls on arrays already in ``lay``'s hand-off:
    ``out`` / ``do`` are the forward's output and its cotangent, ``lse`` is
    (B*H, T). Returns ``(dq, dk, dv)`` shaped ``lay.shape(...)``, or the one
    ``(B, T, 3*H*D)`` gradient when fused."""
    b, t, h, d, hpb, w = lay.batch, lay.seq, lay.heads, lay.head_dim, lay.hpb, lay.width
    group = h // lay.kv_heads
    block_q, block_k = _check_blocks(t, block_q, block_k)
    kv_block_q, kv_block_k = _check_blocks(
        t, dkdv_block_q or block_q, dkdv_block_k or block_k
    )
    _check_window(window, causal)
    scale = 1.0 / math.sqrt(d)
    fold_scale = _fold_scale(scale, q.dtype)
    masked = mask is not None
    mask_arr = _mask3(mask)
    rows = pl.BlockSpec((1, block_q, w), lambda bi, hb, qi: lay.at(0, bi, hb, qi))
    stats = pl.BlockSpec((1, hpb, 1, block_q), lambda bi, hb, qi: (bi, hb, 0, qi))
    # lse and delta travel as (B, H, 1, T) so their (1, hpb, 1, block)
    # specs tile legally.
    lse4 = lse.reshape(b, h, 1, t)
    seq_specs = [
        rows,  # q
        pl.BlockSpec((1, t, w), lambda bi, hb, qi: lay.at(1, bi, hb // group, 0)),  # k
        pl.BlockSpec((1, t, w), lambda bi, hb, qi: lay.at(2, bi, hb // group, 0)),  # v
        rows,  # do
        rows,  # out
        stats,  # lse
    ]
    dq_operands = [q, k, v, do, out, lse4]
    if masked:
        seq_specs.append(pl.BlockSpec((1, 1, t), lambda bi, hb, qi: (bi, 0, 0)))
        dq_operands.append(mask_arr)
        # Same mask, q-block tiled (the queries' segment ids).
        seq_specs.append(pl.BlockSpec((1, 1, block_q), lambda bi, hb, qi: (bi, 0, qi)))
        dq_operands.append(mask_arr)
    # Fused, dq is the first third of the columns of the one gradient
    # array; the dk/dv call below fills the rest in place.
    dq, delta4 = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, hpb=hpb, block_k=block_k, scale=scale,
            fold_scale=fold_scale, causal=causal, masked=masked, window=window,
        ),
        grid=(b, lay.q_blocks, t // block_q),
        in_specs=seq_specs,
        out_specs=[rows, stats],
        out_shape=[
            jax.ShapeDtypeStruct(lay.shape(3 * h if lay.fused else h), q.dtype),
            jax.ShapeDtypeStruct(lse4.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(*dq_operands)

    # dk/dv grid over (batch, kv-head block, k-block, visit). The visits of
    # one output block are innermost so it stays resident across them:
    # apart, the G members of the query group (head block r * G + m);
    # fused (G == 1), dk's columns then dv's. ``held`` says whose inputs a
    # grid step holds, as (batch, kv-head block, k-block, q head block): its
    # own, except that the fused call's second visit, which only copies the
    # scratch out and reads no input, already holds the NEXT program's, so
    # that the pipeline fetches them under this program's products and not
    # under that copy (1.75 -> 1.38 ms a call at the train cell's shape:
    # PERF.md section 6, PR 44).
    n_r, n_k = lay.kv_blocks, t // kv_block_k

    def held(bi, r, ki, m):
        if not lay.fused:
            return bi, r, ki, r * group + m
        step = jnp.minimum((bi * n_r + r) * n_k + ki + m, b * n_r * n_k - 1)
        return step // (n_r * n_k), step // n_k % n_r, step % n_k, step // n_k % n_r

    def spec(block, index):
        return pl.BlockSpec(block, lambda *ids: index(*held(*ids)))

    kv_specs = [
        spec((1, t, w), lambda bi, r, ki, qb: lay.at(0, bi, qb, 0)),  # q
        spec((1, kv_block_k, w), lambda bi, r, ki, qb: lay.at(1, bi, r, ki)),  # k
        spec((1, kv_block_k, w), lambda bi, r, ki, qb: lay.at(2, bi, r, ki)),  # v
        spec((1, t, w), lambda bi, r, ki, qb: lay.at(0, bi, qb, 0)),  # do
        spec((1, hpb, 1, t), lambda bi, r, ki, qb: (bi, qb, 0, 0)),  # lse
        spec((1, hpb, 1, t), lambda bi, r, ki, qb: (bi, qb, 0, 0)),  # delta
    ]
    dkdv_operands = [q, k, v, do, lse4, delta4]
    if lay.fused:
        kv_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        dkdv_operands.append(dq)
    if masked:
        kv_specs.append(spec((1, 1, kv_block_k), lambda bi, r, ki, qb: (bi, 0, ki)))
        dkdv_operands.append(mask_arr)
        # Full-length mask for the streamed queries' segment ids.
        kv_specs.append(spec((1, 1, t), lambda bi, r, ki, qb: (bi, 0, 0)))
        dkdv_operands.append(mask_arr)
    if lay.fused:
        out_specs = pl.BlockSpec(
            (1, kv_block_k, w), lambda bi, r, ki, m: lay.at(1 + m, bi, r, ki)
        )
        out_shape = jax.ShapeDtypeStruct(dq.shape, dq.dtype)
    else:
        out_specs = [
            pl.BlockSpec((1, kv_block_k, w), lambda bi, r, ki, m: lay.at(1, bi, r, ki)),
            pl.BlockSpec((1, kv_block_k, w), lambda bi, r, ki, m: lay.at(2, bi, r, ki)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct(lay.shape(lay.kv_heads), k.dtype),
            jax.ShapeDtypeStruct(lay.shape(lay.kv_heads), v.dtype),
        ]
    dkdv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, hpb=hpb, fused=lay.fused, block_q=kv_block_q,
            scale=scale, fold_scale=fold_scale, causal=causal, masked=masked,
            window=window,
        ),
        grid=(b, lay.kv_blocks, t // kv_block_k, 2 if lay.fused else group),
        in_specs=kv_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={6: 0} if lay.fused else {},
        scratch_shapes=[
            pltpu.VMEM((kv_block_k, w), jnp.float32),
            pltpu.VMEM((kv_block_k, w), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkdv",
    )(*dkdv_operands)
    if lay.fused:
        return dkdv
    return (dq, *dkdv)


_BWD_STATIC = _STATIC + ("dkdv_block_q", "dkdv_block_k")


@functools.partial(jax.jit, static_argnames=_BWD_STATIC)
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
    (keys resident, queries streamed). The hand-off is the forward's.
    """
    lay = _handoff(q.shape, k.shape)
    dq, dk, dv = _backward(
        lay, lay.hand(q), lay.hand(k), lay.hand(v), lay.hand(out), lse, lay.hand(g), mask,
        causal=causal, block_q=block_q, block_k=block_k,
        dkdv_block_q=dkdv_block_q, dkdv_block_k=dkdv_block_k,
        interpret=interpret, window=window,
    )
    return lay.take(dq, lay.heads), lay.take(dk, lay.kv_heads), lay.take(dv, lay.kv_heads)


@functools.partial(jax.jit, static_argnames=_BWD_STATIC)
def pallas_flash_attention_qkv_bwd(
    qkv: jax.Array,
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
) -> jax.Array:
    """``pallas_flash_attention_bwd`` for ``pallas_flash_attention_qkv_fwd``:
    the gradient of the (B, T, 3, H, D) qkv array as ONE such array. The dq
    call writes its first third, the dk/dv call the other two in place
    (``input_output_aliases``), so nothing is concatenated afterwards
    (where the forward sliced, the three gradients are stacked)."""
    b, t, _, h, d = qkv.shape
    tiles = dict(causal=causal, block_q=block_q, block_k=block_k,
                 dkdv_block_q=dkdv_block_q, dkdv_block_k=dkdv_block_k,
                 interpret=interpret, window=window)
    if lane_block_heads(h, h, d) is None:
        grads = pallas_flash_attention_bwd(
            qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], out, lse, g, mask, **tiles
        )
        return jnp.stack(grads, axis=2)
    lay = _handoff((b, t, h, d), (b, t, h, d), fused=True)
    merged = lay.hand(qkv)
    dqkv = _backward(lay, merged, merged, merged, lay.hand(out), lse, lay.hand(g), mask, **tiles)
    return dqkv.reshape(qkv.shape)

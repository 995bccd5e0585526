"""Grouped-query attention over a CHOSEN subset of the cache, and an expert
layer in every block.

The block of the ``KeyeVL2`` language model (a Qwen3-MoE-shaped backbone
with a DeepSeek-Sparse-Attention indexer, ``sa_config``). Pre-norm, RMSNorm,
two residuals, no biases, untied head::

    h = x + SparseGQA(RMSNorm(x))
    y = h + Experts(RMSNorm(h))          models/moe.py:DroplessMoE, softmax scores, no shared expert

**Attention.** ``q = RMSNorm_hd(x W_q)`` and ``k = RMSNorm_hd(x W_k)`` per
head, ``v = x W_v``; rotate-half RoPE over the whole head (ops/rope.py).
Beside them a small **indexer**: ``qI = x W_qI`` (``indexer_num_heads``
heads of ``indexer_head_dim``), ONE index key a position ``kI =
LayerNorm(x W_kI)``, head weights ``w = x W_w``; ``qI`` and ``kI`` rotated
like q and k. The index score of query ``t`` for position ``s <= t`` is::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])          (float32)

and query ``t`` attends ONLY the ``topk`` positions with the highest score
(ties: the lower position; all of them while ``t < topk``), with the usual
``softmax(q . k / sqrt(head_dim))`` over those.

**The cache is three pool leaves a layer**: ``paged_key`` and
``paged_value`` as every GQA family's, and ``paged_index``, the rotated index
key, ``indexer_head_dim`` values a position zero-padded to whole 128-lane
tiles (64 -> 128: a row a position, written by one scatter). All three are written
through ``models/gpt.py:paged_pool_writer`` and indexed by block on their
leading axis, so the engine's copy-on-write and recovery treat them alike.

Two paths read them:

* *decode* (a one-token paged call): the index keys of the row's whole table
  are scored, ``jax.lax.top_k`` (exact) picks ``topk`` positions, positions
  become pool rows through the block table, and K and V of THOSE rows only
  are gathered and attended. Dead picks (a row with fewer live positions
  than ``topk``) are masked out.
* *a slab* (the full forward, prefill, any call of more than one token):
  queries go through in chunks of ``q_chunk_size``; a chunk's index scores
  ``(chunk, positions)`` give each query its ``topk``-th highest score
  (:func:`top_k_mask`: a search over the score's bits, no sort), the chosen
  set is a mask (the same set, ties included), and the chunk attends the
  call's keys under it a block of ``kv_chunk_size`` keys at a time with a
  running softmax, stopping at the last block a query of the chunk may see.
  Nothing of shape ``(heads, t, s)`` exists: scores are ``(heads, chunk,
  block)``.

A call whose keys number at most ``topk`` selects nothing and computes no
index score (the index key is still cached).

``experts_held = (first, count)`` gives every expert layer that range of
the experts (models/moe.py). Serving is paged serving; the linear cursor
cache is refused by name. ``selects_positions`` (the ``topk``) tells the
engine to count what the selection scored and attended.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.schemas import RunConfig
from ..ops.rope import apply_rope
from ..registry.models import register_model
from .gpt import (
    _DENSE_INIT,
    _EMBED_INIT,
    GPTAdapter,
    _scaled_init,
    paged_block_fold,
    paged_pool_writer,
)
from .llama import RMSNorm
from .moe import DroplessMoE

_MASKED = jnp.finfo(jnp.float32).min


def top_k_mask(scores: jax.Array, k: int) -> jax.Array:
    """Which entries of each row of ``scores`` (N, S) float32 are among its
    ``k`` highest: exactly the set ``jax.lax.top_k`` returns (of equal
    scores the lower column), as a mask. Entries at ``-inf`` are never
    chosen. ``scores`` hold no ``-0.0`` (``top_k`` orders it below ``+0.0``).

    No sort: the ``k``-th highest score of a row is found bit by bit (a
    float's bits, the sign folded, order as the floats do: 32 passes of
    compare-and-count over the row), then how far into the columns the ties
    at that score reach (one pass a bit of the column index). On the TPU a
    sort of a (512, 6,656) chunk takes five times as long (PERF.md, PR 35)."""
    n, width = scores.shape
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    order = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))  # unsigned order = float order

    def raise_bit(i: jax.Array, least: jax.Array) -> jax.Array:
        higher = least | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(order >= higher[:, None], axis=-1) >= k
        return jnp.where(enough, higher, least)

    # The highest value that at least k entries reach: the k-th highest score.
    least = jax.lax.fori_loop(0, 32, raise_bit, jnp.zeros((n,), jnp.uint32))[:, None]
    above, ties = order > least, order == least
    room = k - jnp.sum(above, axis=-1)  # how many of the ties are in: the first `room` columns of them
    col = jnp.arange(width, dtype=jnp.int32)
    col_bits = max(1, (width - 1).bit_length())

    def widen(i: jax.Array, bound: jax.Array) -> jax.Array:
        wider = bound + (jnp.int32(1 << (col_bits - 1)) >> i)
        short = jnp.sum(ties & (col < wider[:, None]), axis=-1) < room
        return jnp.where(short, wider, bound)

    # The last column whose tie is in: the largest bound with fewer than `room` ties before it.
    last = jax.lax.fori_loop(0, col_bits, widen, jnp.zeros((n,), jnp.int32))[:, None]
    return (above | (ties & (col <= last))) & (scores > -jnp.inf)


class IndexedAttention(nn.Module):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_layers: int
    index_heads: int
    index_dim: int
    topk: int
    q_chunk: int
    kv_chunk: int
    rope_theta: float
    rms_norm_eps: float
    dtype: Any
    param_dtype: Any
    decode: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0

    def _index_scores(self, qi: jax.Array, wi: jax.Array, ki: jax.Array, live: jax.Array) -> jax.Array:
        """(B, c, S) float32: ``sum_j w_j relu(qI_j . kI_s)``, ``-inf`` where not live."""
        with jax.named_scope("index_scores"):
            dots = jnp.einsum("bthd,bsd->bths", qi, ki, preferred_element_type=jnp.float32)
            scores = jnp.einsum("bths,bth->bts", jax.nn.relu(dots), wi)
            # A weighted sum of ReLU zeros may be -0.0, which top_k orders
            # below +0.0: equal scores must be equal to it too (ties go to
            # the lower position).
            scores = jnp.where(scores == 0, 0.0, scores)
            return jnp.where(live, scores, -jnp.inf)

    def _attend_picked(self, q: jax.Array, keys: jax.Array, values: jax.Array, picked: jax.Array) -> jax.Array:
        """One query a row, q (B, 1, kv, g, hd), over the rows gathered for it:
        keys / values (B, topk, kv * hd) as the pool holds them, ``picked``
        (B, topk) false where a pick is no live position. A head's keys are a
        slice of whole lane tiles, so nothing gathered is re-tiled per head."""
        batch, _, kv, g, hd = q.shape
        out = []
        with jax.named_scope("sparse_attention"):
            for head in range(kv):
                lanes = slice(head * hd, (head + 1) * hd)
                scores = jnp.einsum(
                    "bgd,bsd->bgs", q[:, 0, head], keys[..., lanes], preferred_element_type=jnp.float32
                ) / math.sqrt(hd)
                scores = jnp.where(picked[:, None], scores, _MASKED)
                probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
                out.append(jnp.einsum("bgs,bsd->bgd", probs, values[..., lanes]))
        return jnp.stack(out, axis=1)[:, None]  # (B, 1, kv, g, hd)

    def _attend_blocks(self, q: jax.Array, keys: jax.Array, values: jax.Array, mask: jax.Array,
                       blocks: jax.Array | int) -> jax.Array:
        """A chunk of queries q (B, c, kv, g, hd) over keys / values
        (B, kv, S, hd) under mask (B, c, S), a block of ``kv_chunk`` keys at
        a time with a running softmax (float32 maximum, sum and accumulator),
        over the first ``blocks`` blocks only: the rest hold nothing the
        chunk may see. Scores exist for one block of keys at a time."""
        batch, c, kv, g, hd = q.shape
        kb = self.kv_chunk
        rows = q.transpose(0, 2, 3, 1, 4).reshape(batch, kv, g * c, hd)  # a K/V head's group: one matrix

        def block(j: jax.Array, carry: tuple) -> tuple:
            top, total, acc = carry
            k_j = jax.lax.dynamic_slice_in_dim(keys, j * kb, kb, axis=2)
            v_j = jax.lax.dynamic_slice_in_dim(values, j * kb, kb, axis=2)
            seen = jnp.broadcast_to(
                jax.lax.dynamic_slice_in_dim(mask, j * kb, kb, axis=2)[:, None, None], (batch, kv, g, c, kb)
            ).reshape(batch, kv, g * c, kb)
            scores = jnp.einsum(
                "bkqd,bksd->bkqs", rows, k_j, preferred_element_type=jnp.float32
            ) / math.sqrt(hd)
            scores = jnp.where(seen, scores, _MASKED)
            new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
            probs = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
            shrink = jnp.exp(top - new_top)
            acc = acc * shrink[..., None] + jnp.einsum(
                "bkqs,bksd->bkqd", probs.astype(q.dtype), v_j, preferred_element_type=jnp.float32
            )
            return new_top, total * shrink + jnp.sum(probs, axis=-1), acc

        with jax.named_scope("sparse_attention"):
            start = (
                jnp.full((batch, kv, g * c), _MASKED, jnp.float32),
                jnp.zeros((batch, kv, g * c), jnp.float32),
                jnp.zeros((batch, kv, g * c, hd), jnp.float32),
            )
            _, total, acc = jax.lax.fori_loop(0, blocks, block, start)
            out = (acc / total[..., None]).astype(q.dtype)
        return out.reshape(batch, kv, g, c, hd).transpose(0, 3, 1, 2, 4)

    def _slab(self, q, qi, wi, q_pos, keys, values, ki, allowed) -> jax.Array:
        """Every query of a slab, in chunks of ``q_chunk``: select, then
        attend under the selection's mask. keys / values (B, S, kv * hd),
        key ``s`` at position ``s``; ``allowed`` (B, t, S) or None adds
        segment visibility to causality. A choice of positions passes no
        gradient: the indexer is not trained through it."""
        batch, t = q.shape[:2]
        s = keys.shape[1]
        kv, hd = q.shape[2], q.shape[4]
        kb = self.kv_chunk
        short = -s % kb  # whole blocks of keys; the padding is seen by no query
        per_head = lambda a: jnp.pad(  # noqa: E731
            a.reshape(batch, s, kv, hd), ((0, 0), (0, short), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
        keys, values = per_head(keys), per_head(values)
        col = jnp.arange(s)

        def chunk(args) -> jax.Array:
            q_c, qi_c, wi_c, pos_c, allowed_c = args
            live = col[None, None, :] <= pos_c[:, :, None]
            if allowed_c is not None:
                live = live & allowed_c
            if s > self.topk:
                scores = jax.lax.stop_gradient(self._index_scores(qi_c, wi_c, ki, live))
                with jax.named_scope("index_top_k"):
                    live = top_k_mask(scores.reshape(-1, s), self.topk).reshape(scores.shape)
            # A served call knows its positions only when it runs, and stops at
            # the last block one of its queries may see; the full forward
            # walks every block (a loop of known length has a gradient).
            blocks = jnp.max(pos_c) // kb + 1 if self.decode else (s + short) // kb
            return self._attend_blocks(q_c, keys, values, jnp.pad(live, ((0, 0), (0, 0), (0, short))), blocks)

        c = self.q_chunk
        if t <= c:
            return chunk((q, qi, wi, q_pos, allowed))
        pad = -t % c

        def chunks(a: jax.Array | None) -> jax.Array | None:
            if a is None:
                return None
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            return jnp.moveaxis(a.reshape(batch, (t + pad) // c, c, *a.shape[2:]), 1, 0)

        out = jax.lax.map(chunk, (chunks(q), chunks(qi), chunks(wi), chunks(q_pos), chunks(allowed)))
        return jnp.moveaxis(out, 0, 1).reshape(batch, t + pad, *out.shape[3:])[:, :t]

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        attention_mask: jax.Array | None = None,
        *,
        positions: jax.Array | None = None,
        block_tables: jax.Array | None = None,
    ) -> jax.Array:
        batch, t, _ = x.shape
        heads, kv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        ih, idim = self.index_heads, self.index_dim
        kw = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)
        norm_kw = dict(eps=self.rms_norm_eps, dtype=self.dtype, param_dtype=self.param_dtype)

        def proj(name: str, features: tuple[int, ...] | int, axes: tuple) -> jax.Array:
            return nn.DenseGeneral(
                features=features,
                kernel_init=nn.with_logical_partitioning(_DENSE_INIT, axes),
                name=name, **kw,
            )(x)

        q = RMSNorm(name="q_norm", **norm_kw)(proj("q_proj", (heads, hd), ("embed", "heads", "kv")))
        k = RMSNorm(name="k_norm", **norm_kw)(proj("k_proj", (kv, hd), ("embed", "heads", "kv")))
        v = proj("v_proj", (kv, hd), ("embed", "heads", "kv"))
        qi = proj("index_q_proj", (ih, idim), ("embed", None, None))
        ki = nn.LayerNorm(
            epsilon=self.rms_norm_eps, dtype=self.dtype, param_dtype=self.param_dtype, name="index_k_norm"
        )(proj("index_k_proj", idim, ("embed", None)))
        wi = proj("index_weight_proj", ih, ("embed", None)).astype(jnp.float32)

        if self.decode:
            if positions is None or block_tables is None:
                raise ValueError(
                    "paged decode requires the `positions` (B,) and "
                    "`block_tables` (B, max_blocks) call arguments"
                )
            pos = positions[:, None] + jnp.arange(t)[None, :]  # (B, t)
        else:
            pos = jnp.arange(t)
        q, k = apply_rope(q, k, pos, theta=self.rope_theta)
        qi, ki = apply_rope(qi, ki[:, :, None, :], pos, theta=self.rope_theta)
        ki = ki[:, :, 0]
        q = q.reshape(batch, t, kv, heads // kv, hd)

        if not self.decode:
            allowed = None
            if attention_mask is not None:
                # Segment semantics, as models/gpt.py:dense_attention.
                seg = attention_mask
                allowed = (seg != 0)[:, None, :] & (seg[:, :, None] == seg[:, None, :])
            out = self._slab(
                q, qi, wi, jnp.broadcast_to(pos, (batch, t)),
                k.reshape(batch, t, kv * hd), v.reshape(batch, t, kv * hd), ki, allowed,
            )
        else:
            nb, bt = self.paged_num_blocks, self.paged_block_tokens
            width = kv * hd
            # The index key is zero-padded to whole 128-lane tiles (64 -> 128):
            # folded two positions to a row, as `paged_block_fold` would have
            # it, a slab's keys are written by a loop of one update a token
            # (`paged_pool_writer`), a quarter of a prefill call (PERF.md
            # section 6, PR 35); a whole row a position is one scatter.
            index_lanes = -(-idim // 128) * 128
            ki = jnp.pad(ki, ((0, 0), (0, 0), (0, index_lanes - idim)))
            qi = jnp.pad(qi, ((0, 0), (0, 0), (0, 0), (0, index_lanes - idim)))  # dots over the padding add 0
            leaves = {}
            for name, rows, lanes in (("paged_key", k, width), ("paged_value", v, width), ("paged_index", ki, index_lanes)):
                fold = paged_block_fold(bt, lanes)
                leaf = self.variable("cache", name, jnp.zeros, (nb, bt // fold, fold * lanes), self.dtype)
                leaf.value = paged_pool_writer(pos, block_tables, bt, lanes)(
                    leaf.value, rows.astype(self.dtype).reshape(batch, t, lanes)
                )
                leaves[name] = leaf.value
            # Logical slot index IS the absolute position: liveness is col <= row.
            s = block_tables.shape[1] * bt
            selects = s > self.topk
            pool_ki = leaves["paged_index"][block_tables].reshape(batch, s, index_lanes) if selects else None
            if t == 1 and selects:
                live = jnp.arange(s)[None, None, :] <= pos[:, :, None]
                scores = self._index_scores(qi, wi, pool_ki, live)[:, 0]  # (B, S)
                with jax.named_scope("index_top_k"):
                    picked_score, picked = jax.lax.top_k(scores, self.topk)  # (B, topk)
                with jax.named_scope("sparse_gather"):
                    # Position -> pool row, through the row's block table.
                    # A leaf seen as (positions, width) is the leaf itself (its
                    # rows in order); splitting the heads first would re-tile
                    # the whole pool.
                    rows = jnp.take_along_axis(block_tables, picked // bt, axis=1) * bt + picked % bt
                    keys = leaves["paged_key"].reshape(nb * bt, width)[rows]  # (B, topk, width)
                    values = leaves["paged_value"].reshape(nb * bt, width)[rows]
                out = self._attend_picked(q, keys, values, picked_score > -jnp.inf)
            else:
                keys = leaves["paged_key"][block_tables].reshape(batch, s, width)
                values = leaves["paged_value"][block_tables].reshape(batch, s, width)
                out = self._slab(q, qi, wi, pos, keys, values, pool_ki, None)
        return nn.DenseGeneral(
            features=self.d_model,
            axis=(-2, -1),
            kernel_init=nn.with_logical_partitioning(
                _scaled_init(self.n_layers), ("heads", "kv", "embed")
            ),
            name="o_proj", **kw,
        )(out.reshape(batch, t, heads, hd))


class IndexedMoEBlock(nn.Module):
    d_model: int
    n_layers: int
    attn: dict[str, Any]
    moe: dict[str, Any]
    rms_norm_eps: float
    dtype: Any
    param_dtype: Any
    decode: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        attention_mask: jax.Array | None = None,
        *,
        positions: jax.Array | None = None,
        block_tables: jax.Array | None = None,
    ) -> jax.Array:
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm_kw = dict(eps=self.rms_norm_eps, **kw)
        act = ("batch", "length", "act_embed")
        h = nn.with_logical_constraint(RMSNorm(name="attn_norm", **norm_kw)(x), act)
        x = x + IndexedAttention(
            d_model=self.d_model, n_layers=self.n_layers, rms_norm_eps=self.rms_norm_eps,
            decode=self.decode, paged_num_blocks=self.paged_num_blocks,
            paged_block_tokens=self.paged_block_tokens, name="attn", **self.attn, **kw,
        )(h, attention_mask, positions=positions, block_tables=block_tables)
        h = nn.with_logical_constraint(RMSNorm(name="mlp_norm", **norm_kw)(x), act)
        h = DroplessMoE(d_model=self.d_model, n_layers=self.n_layers, name="moe", **self.moe, **kw)(h)
        return nn.with_logical_constraint(x + h, act)


class IndexedMoE(nn.Module):
    """Decoder-only language model of :class:`IndexedMoEBlock`s."""

    vocab_size: int
    block_size: int
    d_model: int
    n_layers: int
    n_heads: int
    num_key_value_heads: int
    head_dim: int
    indexer_num_heads: int
    indexer_head_dim: int
    topk: int
    q_chunk_size: int
    kv_chunk_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool = True
    experts_held: tuple[int, int] | None = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    # The loss machinery GPTAdapter shares reads these.
    loss_impl: str = "dense"
    ce_chunk: int = 8192
    z_loss: float = 0.0
    # Decoding is paged decoding; set via for_paged_decoding().
    decode: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0

    @property
    def expert_layers(self) -> int:
        """Every block holds an expert layer (the engine reads the
        ``moe_stats`` counters of a decode call where this is not 0)."""
        return self.n_layers

    @property
    def selects_positions(self) -> int:
        """How many cached positions a query attends at most (the engine
        counts what the selection scored and attended where this is not 0)."""
        return self.topk

    def for_paged_decoding(
        self, *, num_blocks: int, block_tokens: int, state_rows: int = 0
    ) -> "IndexedMoE":
        """Clone configured for paged continuous-batching decode (the
        GPT.for_paged_decoding contract; ``state_rows`` is offered and not
        taken: the index key is paged like K and V)."""
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (got {num_blocks})")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1 (got {block_tokens})")
        return self.clone(
            decode=True, paged_num_blocks=num_blocks, paged_block_tokens=block_tokens
        )

    def for_decoding(self, cache_len: int | None = None, *, ring_slack: int = 0):
        """Refused by name: the linear cursor cache keeps no index key."""
        raise ValueError(
            "indexed_moe has no linear decode cache (generate(), serving.mode: "
            "simple, speculative decoding): the index keys a query selects by "
            "are kept only in the paged pool — use serving.mode: continuous"
        )

    @nn.compact
    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: jax.Array | None = None,
        *,
        deterministic: bool = True,
        return_hidden: bool = False,
        positions: jax.Array | None = None,
        block_tables: jax.Array | None = None,
    ) -> jax.Array:
        _, seqlen = input_ids.shape
        if seqlen > self.block_size:
            raise ValueError(
                f"Input sequence length {seqlen} exceeds block size {self.block_size}."
            )
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        token_embedding = nn.Embed(
            self.vocab_size,
            self.d_model,
            embedding_init=nn.with_logical_partitioning(_EMBED_INIT, ("vocab", "embed")),
            name="token_embedding", **kw,
        )
        x = nn.with_logical_constraint(token_embedding(input_ids), ("batch", "length", "act_embed"))
        attn = dict(
            n_heads=self.n_heads, n_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
            index_heads=self.indexer_num_heads, index_dim=self.indexer_head_dim, topk=self.topk,
            q_chunk=self.q_chunk_size, kv_chunk=self.kv_chunk_size, rope_theta=self.rope_theta,
        )
        moe = dict(
            d_ff=self.moe_intermediate_size, n_experts=self.num_experts, top_k=self.num_experts_per_tok,
            normalize=self.norm_topk_prob, scoring="softmax", experts_held=self.experts_held,
        )
        paged = dict(
            decode=True, paged_num_blocks=self.paged_num_blocks,
            paged_block_tokens=self.paged_block_tokens,
        ) if self.decode else {}
        for layer in range(self.n_layers):
            x = IndexedMoEBlock(
                d_model=self.d_model, n_layers=self.n_layers, attn=attn, moe=moe,
                rms_norm_eps=self.rms_norm_eps, name=f"block_{layer}", **paged, **kw,
            )(x, attention_mask, positions=positions, block_tables=block_tables)
        x = RMSNorm(name="norm_f", eps=self.rms_norm_eps, **kw)(x)
        if return_hidden:
            return nn.with_logical_constraint(x, ("batch", "length", "act_embed"))
        if self.tie_embeddings:
            logits = token_embedding.attend(x)
        else:
            logits = nn.Dense(
                self.vocab_size,
                use_bias=False,
                kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "vocab")),
                name="lm_head", **kw,
            )(x)
        return nn.with_logical_constraint(logits, ("batch", "length", "act_vocab"))


_SIZES = ("num_key_value_heads", "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok")
_INDEXER_KEYS = (
    "indexer_head_dim", "indexer_num_heads", "indexer_num_kv_heads", "kv_chunk_size", "q_chunk_size", "topk",
)


@register_model("indexed_moe")
class IndexedMoEAdapter(GPTAdapter):
    """Adapter for index-selected GQA over an expert layer in every block;
    the loss machinery is GPTAdapter's (same top-level parameter names).
    Every size of the family's published config is a ``model.extra`` key
    under its published name (the indexer's under ``sa_config``);
    ``experts_held: [first, count]`` is the share of the experts this
    process holds (absent: all of them)."""

    known_extra_keys = frozenset(
        {"tokenizer", "loss_impl", "ce_chunk", "ce_auto_vocab", "z_loss",
         "rope_theta", "rope_scaling", "rms_norm_eps", "norm_topk_prob", "sa_config",
         "decoder_sparse_step", "mlp_only_layers", "experts_held", *_SIZES}
    )

    def build_model(self, cfg: RunConfig) -> nn.Module:
        extra = cfg.model.extra
        unknown = sorted(set(extra) - self.known_extra_keys)
        if unknown:
            raise ValueError(
                f"model.extra keys {unknown} are not indexed_moe settings; known: "
                f"{sorted(self.known_extra_keys)}"
            )
        missing = [k for k in (*_SIZES, "sa_config") if k not in extra]
        if missing:
            raise ValueError(f"indexed_moe needs model.extra keys {missing}")
        indexer = dict(extra["sa_config"])
        if sorted(indexer) != sorted(_INDEXER_KEYS):
            raise ValueError(f"model.extra.sa_config takes exactly the keys {list(_INDEXER_KEYS)}")
        if cfg.model.remat:
            raise ValueError("indexed_moe does not support model.remat")
        if cfg.model.dropout:
            raise ValueError("indexed_moe has no dropout; set model.dropout to 0.0")
        if cfg.model.attention != "dense":
            raise ValueError(
                "indexed_moe computes its attention itself (a chosen subset of the "
                f"positions); model.attention={cfg.model.attention!r} is not supported"
            )
        if int(extra.get("decoder_sparse_step", 1)) != 1 or list(extra.get("mlp_only_layers", [])):
            raise ValueError(
                "indexed_moe puts an expert layer in every block: decoder_sparse_step "
                "must be 1 and mlp_only_layers empty"
            )
        scaling = dict(extra.get("rope_scaling") or {})
        if scaling.get("rope_type", scaling.get("type", "default")) != "default":
            raise ValueError(
                "model.extra.rope_scaling: only the default rotary is built (an "
                "mrope_section over one stream of token positions IS 1-D RoPE)"
            )
        base = super().build_model(cfg)  # the shared validation (vocab, loss)
        if base.loss_impl == "fused_ce":
            raise ValueError("indexed_moe does not run the fused CE kernel; use 'dense' or 'chunked_ce'")
        sizes = {k: int(extra[k]) for k in _SIZES}
        indexer = {k: int(v) for k, v in indexer.items()}
        for key, value in {**sizes, **indexer}.items():
            if value < 1:
                raise ValueError(f"model.extra {key} must be >= 1, got {value}")
        if base.n_heads % sizes["num_key_value_heads"]:
            raise ValueError(
                f"n_heads {base.n_heads} is no multiple of num_key_value_heads {sizes['num_key_value_heads']}"
            )
        if sizes["head_dim"] % 2 or indexer["indexer_head_dim"] % 2:
            raise ValueError("RoPE needs an even head_dim and indexer_head_dim")
        if indexer["indexer_num_kv_heads"] != 1:
            raise ValueError("indexed_moe caches ONE index key a position: indexer_num_kv_heads must be 1")
        held = extra.get("experts_held")
        if held is not None:
            held = (int(held[0]), int(held[1]))
        rope_theta = float(extra.get("rope_theta", 10000.0))
        rms_norm_eps = float(extra.get("rms_norm_eps", 1e-6))
        if rope_theta <= 0 or rms_norm_eps <= 0:
            raise ValueError("model.extra.rope_theta and rms_norm_eps must be > 0")
        tie = (
            cfg.model.tie_embeddings
            if "tie_embeddings" in cfg.model.model_fields_set
            else False
        )
        return IndexedMoE(
            vocab_size=base.vocab_size,
            block_size=base.block_size,
            d_model=base.d_model,
            n_layers=base.n_layers,
            n_heads=base.n_heads,
            tie_embeddings=tie,
            dtype=base.dtype,
            param_dtype=base.param_dtype,
            loss_impl=base.loss_impl,
            ce_chunk=base.ce_chunk,
            z_loss=base.z_loss,
            norm_topk_prob=bool(extra.get("norm_topk_prob", True)),
            experts_held=held,
            rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps,
            indexer_num_heads=indexer["indexer_num_heads"],
            indexer_head_dim=indexer["indexer_head_dim"],
            topk=indexer["topk"],
            q_chunk_size=indexer["q_chunk_size"],
            kv_chunk_size=indexer["kv_chunk_size"],
            **sizes,
        )


__all__ = ["IndexedAttention", "IndexedMoE", "IndexedMoEAdapter", "IndexedMoEBlock", "top_k_mask"]

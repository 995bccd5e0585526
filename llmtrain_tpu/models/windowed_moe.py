"""Window and global attention mixed by a layer pattern, in a parallel block
with routed experts beside averaged shared ones.

The block of the ``cohere2_moe`` language model. ONE bias-free LayerNorm a
layer feeds attention and the expert layer alike, and both are added to the
residual (``use_parallel_block``)::

    n = LayerNorm(x)                              no bias, statistics in float32
    y = x + Attention_kind(n) + Experts(n)

**Two kinds of attention, chosen per layer** by ``layer_types`` (any period;
``sliding_attention`` or ``full_attention``). Both are grouped-query
attention, ``q = n W_q`` (``n_heads`` heads of ``head_dim``), ``k = n W_k``,
``v = n W_v`` (``num_key_value_heads`` heads), no bias, no q/k norm, query
head ``j`` on K/V head ``j // group``, ``softmax(q k^T / sqrt(head_dim))``:

* a *window* layer rotates q and k (INTERLEAVED pairs, ``rope_gptj``:
  ops/rope.py, theta ``rope_theta``, the whole head) and query ``i`` sees
  key ``j`` iff ``0 <= i - j < sliding_window``;
* a *global* layer carries NO position (no rotary at all) and query ``i``
  sees every key ``j <= i``.

**Experts.** ``models/moe.py:DroplessMoE`` as it stands (sigmoid scores over
all ``num_experts``, ``num_experts_per_tok`` a token, normalised weights,
``experts_held``), plus ``num_shared_experts`` shared SwiGLU experts of the
same width whose outputs are AVERAGED (``shared_expert_combination_strategy:
average``) and added whole to the routed sum. The shared experts are held as
ONE gated MLP of width ``num_shared_experts * intermediate_size`` with its
output scaled by ``1 / num_shared_experts`` (expert ``j`` is columns
``j * intermediate_size ...`` of ``mlp_gate`` / ``mlp_up`` and those rows of
``mlp_down``): a sum over the concatenated width IS the sum of the experts'
outputs, accumulated in float32 in one product.

**Which attention code runs** is ``model.attention``: ``dense`` forms masked
``(T, T)`` scores (tests, rehearsals), ``flash`` is ``ops/flash_attention.py``
(the Pallas forward with ``window`` on the chip, grouped K/V read in place;
the XLA blockwise twin off it). A served slab of 6,144 positions and 128
heads cannot form dense scores; the two are held equal by the tests.

**Serving is paged serving, with two kinds of cache leaf** (serving/paged_kv.py
"window blocks", docs/serving.md). A global layer's ``paged_key`` /
``paged_value`` are the block pool every family has: ``(num_blocks,
block_tokens // fold, fold * width)`` under ``block_tables``. A window
layer's ``window_key`` / ``window_value`` are a second, smaller pool,
``(window_num_blocks, ...)``, under ``window_tables`` (B, ring): logical
block ``b`` of a sequence lives in ring entry ``b % ring``, ``ring =
ceil(sliding_window / block_tokens) + 1``, so the entry of the block that
fell wholly out of the window is reused in place. Keys are cached rotated.

* *decode* (one token a row): the token's K/V are written at ``table[(p //
  bt) % ring]``, the row's ``ring`` blocks are gathered (never more than the
  window table, however long the sequence), each gathered entry's absolute
  position is recovered from the row's position, and the mask is the window
  over those. The global layer gathers its whole table as every family does.
* *a slab* (prefill; ``true_len`` tells the padding): the slab attends ITS
  OWN keys, exactly (it holds every key a query may see, so a prompt longer
  than the window is right at every position), and a window layer writes only
  the positions still inside the ring at the slab's end: the last ``ring``
  blocks up to ``true_len``. Everything else, the padding included, goes to
  the null block: a padded position past ``true_len`` would otherwise land
  on a ring entry that a live block holds. A slab starts its sequence
  (offset 0): reading earlier keys through the tables is what chunked
  prefill, prefix reuse and ``verify`` would need, and the engine refuses
  all three by name for a model with window blocks (``paged_window``).
  Handed ``true_len``, the model returns the logits of the last true position
  alone, ``(B, 1, vocab)``: the head over a whole slab is 0.8 GB of float32
  nobody reads.

``paged_window`` (the window, in positions) is the ONE thing the engine reads
to know all this. The linear cursor cache (``generate()``, ``serving.mode:
simple``, speculative decoding) is refused by name.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.schemas import RunConfig
from ..ops.flash_attention import flash_attention
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
from .llama import gated_mlp
from .moe import DroplessMoE

_MASKED = jnp.finfo(jnp.float32).min
LAYER_KINDS = ("sliding_attention", "full_attention")


def masked_attention(q: jax.Array, keys: jax.Array, values: jax.Array, live: jax.Array) -> jax.Array:
    """Grouped-query attention under an explicit mask. q (B, t, kv, g, hd);
    keys / values (B, S, kv * hd) rows as the projections and the pool hold
    them; ``live`` (B, t, S). The two products go one K/V head at a time: a
    head's keys are a slice of whole lane tiles where ``hd`` is a multiple of
    128, so nothing gathered is re-tiled per head (all heads in one product
    made the compiler copy every gathered row, 537 MB a leaf at 32 x 8,192:
    PERF.md section 6, PR 45). Mask and softmax, in float32, run once over
    all heads."""
    kv, hd = q.shape[2], q.shape[-1]
    lanes = [slice(head * hd, (head + 1) * hd) for head in range(kv)]
    scores = jnp.stack([
        jnp.einsum("btgd,bsd->bgts", q[:, :, head], keys[..., lanes[head]], preferred_element_type=jnp.float32)
        for head in range(kv)
    ], axis=1) / math.sqrt(hd)  # (B, kv, g, t, S)
    probs = jax.nn.softmax(jnp.where(live[:, None, None], scores, _MASKED), axis=-1).astype(q.dtype)
    return jnp.stack(
        [jnp.einsum("bgts,bsd->btgd", probs[:, head], values[..., lanes[head]]) for head in range(kv)], axis=2
    )  # (B, t, kv, g, hd)


class PatternAttention(nn.Module):
    """One layer's attention: ``window`` > 0 a window layer (rotary, the last
    ``window`` keys), 0 a global one (no position, every earlier key)."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_layers: int
    window: int
    rope_theta: float
    attention: str
    dtype: Any
    param_dtype: Any
    decode: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0
    window_num_blocks: int = 0

    def _slab(self, q, k, v, attention_mask) -> jax.Array:
        """Every query of a slab over the slab's own keys, causal and inside
        the window. q (B, t, h, hd); k, v (B, t, kv, hd)."""
        batch, t, heads, hd = q.shape
        kv = k.shape[2]
        if self.attention == "flash":
            return flash_attention(q, k, v, attention_mask=attention_mask, window=self.window)
        col = jnp.arange(t)
        live = col[None, :] <= col[:, None]
        if self.window:
            live = live & (col[:, None] - col[None, :] < self.window)
        live = jnp.broadcast_to(live, (batch, t, t))
        if attention_mask is not None:
            # Segment semantics, as models/gpt.py:dense_attention.
            seg = attention_mask
            live = live & (seg != 0)[:, None, :] & (seg[:, :, None] == seg[:, None, :])
        out = masked_attention(
            q.reshape(batch, t, kv, heads // kv, hd), k.reshape(batch, t, kv * hd), v.reshape(batch, t, kv * hd), live
        )
        return out.reshape(batch, t, heads, hd)

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        attention_mask: jax.Array | None = None,
        *,
        positions: jax.Array | None = None,
        block_tables: jax.Array | None = None,
        window_tables: jax.Array | None = None,
        true_len: jax.Array | None = None,
    ) -> jax.Array:
        batch, t, _ = x.shape
        heads, kv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        kw = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)

        def proj(name: str, n: int) -> jax.Array:
            return nn.DenseGeneral(
                features=(n, hd),
                kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "heads", "kv")),
                name=name, **kw,
            )(x)

        q, k, v = proj("q_proj", heads), proj("k_proj", kv), proj("v_proj", kv)
        if self.decode:
            if positions is None or block_tables is None or (self.window and window_tables is None):
                raise ValueError(
                    "paged decode requires the `positions` (B,) and `block_tables` (B, max_blocks) "
                    "call arguments, and `window_tables` (B, ring) for a window layer"
                )
            pos = positions[:, None] + jnp.arange(t)[None, :]  # (B, t)
        else:
            pos = jnp.arange(t)
        if self.window:
            q, k = apply_rope(q, k, pos, theta=self.rope_theta, interleaved=True)

        scope = "window_attention" if self.window else "global_attention"
        if not self.decode:
            with jax.named_scope(scope):
                out = self._slab(q, k, v, attention_mask)
        else:
            bt, width = self.paged_block_tokens, kv * hd
            fold = paged_block_fold(bt, width)
            if self.window:
                nb, names = self.window_num_blocks, ("window_key", "window_value")
                # The ring is whatever the allocator staged (serving/paged_kv.py:
                # window_ring_blocks, the one formula); it must only not wrap inside the window.
                ring = window_tables.shape[1]
                if (ring - 1) * bt < self.window:
                    raise ValueError(
                        f"window_tables holds {ring} entries a row: blocks of {bt} reused after {ring - 1} "
                        f"would overwrite keys still inside a window of {self.window}"
                    )
                block = pos // bt
                if true_len is None:  # decode: the one token of every row is written
                    keep = jnp.ones(pos.shape, bool)
                else:  # a slab: only what the ring still holds at its end, and no padding
                    last = (true_len[:, None] - 1) // bt
                    keep = (pos < true_len[:, None]) & (block > last - ring)
                # Entry `ring` of the widened table is the null block.
                tables = jnp.concatenate([window_tables, jnp.zeros((batch, 1), window_tables.dtype)], axis=1)
                write_pos = jnp.where(keep, block % ring, ring) * bt + pos % bt
            else:
                nb, names = self.paged_num_blocks, ("paged_key", "paged_value")
                tables, write_pos = block_tables, pos
            write = paged_pool_writer(write_pos, tables, bt, width)
            leaves = []
            for name, rows in zip(names, (k, v)):
                leaf = self.variable("cache", name, jnp.zeros, (nb, bt // fold, fold * width), self.dtype)
                leaf.value = write(leaf.value, rows.astype(self.dtype).reshape(batch, t, width))
                leaves.append(leaf.value)
            with jax.named_scope(scope):
                if t > 1:
                    out = self._slab(q, k, v, None)
                else:
                    gather = window_tables if self.window else block_tables
                    s = gather.shape[1] * bt
                    keys, values = (leaf[gather].reshape(batch, s, width) for leaf in leaves)
                    if self.window:
                        # Ring entry e holds the newest logical block b' <= b with b' % ring == e.
                        entry = jnp.arange(ring)[None, :]
                        held = block - (block - entry) % ring  # (B, ring); negative: not reached yet
                        key_pos = (held[:, :, None] * bt + jnp.arange(bt)[None, None, :]).reshape(batch, 1, s)
                        row = pos[:, :, None]
                        live = (key_pos >= 0) & (key_pos <= row) & (row - key_pos < self.window)
                    else:
                        live = jnp.arange(s)[None, None, :] <= pos[:, :, None]
                    out = masked_attention(q.reshape(batch, t, kv, heads // kv, hd), keys, values, live)
                    out = out.reshape(batch, t, heads, hd)
        return nn.DenseGeneral(
            features=self.d_model,
            axis=(-2, -1),
            kernel_init=nn.with_logical_partitioning(_scaled_init(self.n_layers), ("heads", "kv", "embed")),
            name="o_proj", **kw,
        )(out.reshape(batch, t, heads, hd))


class SharedExperts(nn.Module):
    """``count`` SwiGLU experts every token goes through, averaged: one gated
    MLP over their concatenated width, its output scaled by ``1 / count``."""

    d_model: int
    d_ff: int
    count: int
    n_layers: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:
        return gated_mlp(
            h, d_model=self.d_model, d_ff=self.count * self.d_ff, n_layers=self.n_layers,
            dtype=self.dtype, param_dtype=self.param_dtype, out_scale=1.0 / self.count,
        )


class WindowedMoEBlock(nn.Module):
    d_model: int
    n_layers: int
    attn: dict[str, Any]
    moe: dict[str, Any]
    shared: dict[str, Any]
    layer_norm_eps: float
    dtype: Any
    param_dtype: Any
    cache: dict[str, Any] | None = None  # for_paged_decoding's sizes

    @nn.compact
    def __call__(self, x: jax.Array, attention_mask: jax.Array | None = None, **paged: Any) -> jax.Array:
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        act = ("batch", "length", "act_embed")
        n = nn.LayerNorm(epsilon=self.layer_norm_eps, use_bias=False, name="norm", **kw)(x)
        n = nn.with_logical_constraint(n, act)
        attended = PatternAttention(
            d_model=self.d_model, n_layers=self.n_layers, name="attn", **self.attn, **(self.cache or {}), **kw,
        )(n, attention_mask, **paged)
        routed = DroplessMoE(d_model=self.d_model, n_layers=self.n_layers, name="moe", **self.moe, **kw)(n)
        with jax.named_scope("moe_shared"):
            shared = SharedExperts(
                d_model=self.d_model, n_layers=self.n_layers, name="shared_experts", **self.shared, **kw
            )(n)
        return nn.with_logical_constraint(x + attended + routed + shared, act)


class WindowedMoE(nn.Module):
    """Decoder-only language model of :class:`WindowedMoEBlock`s, layer ``i``
    of kind ``layer_types[i]``; tied head (``tie_embeddings``), no position
    embedding."""

    vocab_size: int
    block_size: int
    d_model: int
    n_layers: int
    n_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_shared_experts: int
    sliding_window: int
    layer_types: tuple[str, ...]
    norm_topk_prob: bool = True
    experts_held: tuple[int, int] | None = None
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    tie_embeddings: bool = True
    attention: str = "dense"
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
    window_num_blocks: int = 0

    @property
    def expert_layers(self) -> int:
        """Every block holds an expert layer (serving/engine.py reads the
        ``moe_stats`` counters of a decode call where this is not 0)."""
        return self.n_layers

    @property
    def paged_window(self) -> int:
        """The window of this model's window layers, in positions: what tells
        the engine to keep a second pool and a ring table a row for them
        (0: no window layer, one pool)."""
        return self.sliding_window if "sliding_attention" in self.layer_types else 0

    def for_paged_decoding(
        self, *, num_blocks: int, block_tokens: int, state_rows: int = 0, window_num_blocks: int = 0
    ) -> "WindowedMoE":
        """Clone configured for paged continuous-batching decode (the
        GPT.for_paged_decoding contract; ``state_rows`` is offered and not
        taken). ``window_num_blocks`` sizes the window layers' own pool."""
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (got {num_blocks})")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1 (got {block_tokens})")
        if self.paged_window and window_num_blocks < 2:
            raise ValueError(
                "windowed_moe has window layers: for_paged_decoding needs window_num_blocks >= 2 "
                f"(got {window_num_blocks}), the blocks of their own pool"
            )
        return self.clone(
            decode=True, paged_num_blocks=num_blocks, paged_block_tokens=block_tokens,
            window_num_blocks=window_num_blocks,
        )

    def for_decoding(self, cache_len: int | None = None, *, ring_slack: int = 0):
        """Refused by name: the linear cursor cache has one kind of layer."""
        raise ValueError(
            "windowed_moe has no linear decode cache (generate(), serving.mode: "
            "simple, speculative decoding): window and global layers keep different "
            "caches, which only the paged pool holds — use serving.mode: continuous"
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
        window_tables: jax.Array | None = None,
        true_len: jax.Array | None = None,
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
            rope_theta=self.rope_theta, attention=self.attention,
        )
        moe = dict(
            d_ff=self.intermediate_size, n_experts=self.num_experts, top_k=self.num_experts_per_tok,
            normalize=self.norm_topk_prob, scoring="sigmoid", experts_held=self.experts_held,
        )
        shared = dict(d_ff=self.intermediate_size, count=self.num_shared_experts)
        cache = dict(
            decode=True, paged_num_blocks=self.paged_num_blocks, paged_block_tokens=self.paged_block_tokens,
            window_num_blocks=self.window_num_blocks,
        ) if self.decode else None
        paged = dict(
            positions=positions, block_tables=block_tables, window_tables=window_tables, true_len=true_len
        ) if self.decode else {}
        for layer, kind in enumerate(self.layer_types):
            window = self.sliding_window if kind == "sliding_attention" else 0
            x = WindowedMoEBlock(
                d_model=self.d_model, n_layers=self.n_layers, attn=dict(attn, window=window), moe=moe,
                shared=shared, layer_norm_eps=self.layer_norm_eps, cache=cache, name=f"block_{layer}", **kw,
            )(x, attention_mask, **paged)
        if self.decode and true_len is not None:
            # A prefill slab: the head at the last true position alone.
            x = jnp.take_along_axis(x, (true_len - 1)[:, None, None], axis=1)
        x = nn.LayerNorm(epsilon=self.layer_norm_eps, use_bias=False, name="norm_f", **kw)(x)
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
        if self.logit_scale != 1.0:
            logits = logits * self.logit_scale
        return nn.with_logical_constraint(logits, ("batch", "length", "act_vocab"))


_SIZES = (
    "num_key_value_heads", "head_dim", "intermediate_size", "num_experts", "num_experts_per_tok",
    "num_shared_experts", "sliding_window",
)
# Published keys whose one supported value says what this module computes: anything else is refused.
_FIXED = {
    "expert_selection_fn": "sigmoid", "shared_expert_combination_strategy": "average",
    "position_embedding_type": "rope_gptj", "use_parallel_block": True, "use_qk_norm": False,
    "attention_bias": False, "use_gated_activation": True, "hidden_act": "silu", "rotary_pct": 1,
    "first_k_dense_replace": 0,
}


@register_model("windowed_moe")
class WindowedMoEAdapter(GPTAdapter):
    """Adapter for window / global attention by a layer pattern over routed
    and averaged shared experts; the loss machinery is GPTAdapter's (same
    top-level parameter names). Every size of the family's published config
    is a ``model.extra`` key under its published name; ``layer_types`` has
    one entry a layer; ``experts_held: [first, count]`` is the share of the
    experts this process holds (absent: all of them)."""

    known_extra_keys = frozenset(
        {"tokenizer", "loss_impl", "ce_chunk", "ce_auto_vocab", "z_loss",
         "rope_theta", "layer_norm_eps", "norm_topk_prob", "logit_scale", "layer_types",
         "experts_held", *_SIZES, *_FIXED}
    )

    def build_model(self, cfg: RunConfig) -> nn.Module:
        extra = cfg.model.extra
        unknown = sorted(set(extra) - self.known_extra_keys)
        if unknown:
            raise ValueError(
                f"model.extra keys {unknown} are not windowed_moe settings; known: "
                f"{sorted(self.known_extra_keys)}"
            )
        missing = [k for k in (*_SIZES, "layer_types") if k not in extra]
        if missing:
            raise ValueError(f"windowed_moe needs model.extra keys {missing}")
        for key, only in _FIXED.items():
            if key in extra and extra[key] != only:
                raise ValueError(
                    f"model.extra.{key}={extra[key]!r}: windowed_moe computes {only!r} only"
                )
        if cfg.model.remat:
            raise ValueError("windowed_moe does not support model.remat")
        if cfg.model.dropout:
            raise ValueError("windowed_moe has no dropout; set model.dropout to 0.0")
        if cfg.model.attention not in ("dense", "flash"):
            raise ValueError(
                "windowed_moe attends by 'dense' masked scores or by 'flash' blocks; "
                f"model.attention={cfg.model.attention!r} (ring / Ulysses shard one kind of "
                "attention over the sequence axis) is not supported"
            )
        layer_types = tuple(str(kind) for kind in extra["layer_types"])
        bad = sorted(set(layer_types) - set(LAYER_KINDS))
        if bad:
            raise ValueError(f"model.extra.layer_types holds {bad}; a layer is one of {list(LAYER_KINDS)}")
        base = super().build_model(cfg)  # the shared validation (vocab, loss)
        if len(layer_types) != base.n_layers:
            raise ValueError(
                f"model.extra.layer_types names {len(layer_types)} layers, model.n_layers is {base.n_layers}"
            )
        if base.loss_impl == "fused_ce":
            raise ValueError("windowed_moe does not run the fused CE kernel; use 'dense' or 'chunked_ce'")
        sizes = {k: int(extra[k]) for k in _SIZES}
        for key, value in sizes.items():
            if value < 1:
                raise ValueError(f"model.extra {key} must be >= 1, got {value}")
        if base.n_heads % sizes["num_key_value_heads"]:
            raise ValueError(
                f"n_heads {base.n_heads} is no multiple of num_key_value_heads {sizes['num_key_value_heads']}"
            )
        if sizes["head_dim"] % 2:
            raise ValueError("RoPE needs an even head_dim")
        held = extra.get("experts_held")
        if held is not None:
            held = (int(held[0]), int(held[1]))
        rope_theta = float(extra.get("rope_theta", 10000.0))
        layer_norm_eps = float(extra.get("layer_norm_eps", 1e-5))
        if rope_theta <= 0 or layer_norm_eps <= 0:
            raise ValueError("model.extra.rope_theta and layer_norm_eps must be > 0")
        tie = (
            cfg.model.tie_embeddings
            if "tie_embeddings" in cfg.model.model_fields_set
            else True
        )
        return WindowedMoE(
            vocab_size=base.vocab_size,
            block_size=base.block_size,
            d_model=base.d_model,
            n_layers=base.n_layers,
            n_heads=base.n_heads,
            tie_embeddings=tie,
            attention=cfg.model.attention,
            dtype=base.dtype,
            param_dtype=base.param_dtype,
            loss_impl=base.loss_impl,
            ce_chunk=base.ce_chunk,
            z_loss=base.z_loss,
            layer_types=layer_types,
            norm_topk_prob=bool(extra.get("norm_topk_prob", True)),
            experts_held=held,
            rope_theta=rope_theta,
            layer_norm_eps=layer_norm_eps,
            logit_scale=float(extra.get("logit_scale", 1.0)),
            **sizes,
        )


__all__ = [
    "LAYER_KINDS", "PatternAttention", "SharedExperts", "WindowedMoE", "WindowedMoEAdapter", "WindowedMoEBlock",
    "masked_attention",
]

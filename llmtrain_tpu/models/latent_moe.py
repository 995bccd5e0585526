"""Latent attention (MLA) over a mixture of routed and shared experts.

The DeepSeek-V3 family's block (``model_type: deepseek_v3``, ``axk1``, ...):
attention whose keys and values are up-projections of ONE small latent row
a position, and, after ``first_k_dense_replace`` leading dense layers, an
expert layer in every block (models/moe.py:DroplessMoE beside a shared
expert). Pre-norm, RMSNorm, two residuals, no biases, untied head::

    h = x + MLA(RMSNorm(x))
    y = h + F_l(RMSNorm(h))       F_l = SwiGLU(d_ff) for l < first_k_dense_replace,
                                  else routed experts + shared expert

**MLA.** ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` gives each head
``[q_nope | q_pe]``. ``[c | k_pe] = x W_kva``; ``c_kv = RMSNorm(c)``;
``k_pe`` is one row shared by every head. ``q_pe`` and ``k_pe`` are rotated
at their absolute positions (ops/rope.py; YaRN's frequencies when the
config scales its context). Per head ``[k_nope | v] = c_kv W_kvb``;
``score = (q_nope . k_nope + q_pe . k_pe) * scale``; ``scale`` is
``(nope + rope)^-0.5`` times the square of YaRN's ``mscale_all_dim`` factor.

**The cache is the latent.** A position and layer keep ``[c_kv | rotated
k_pe]`` (``kv_lora_rank + qk_rope_head_dim`` values, zero-padded to whole
128-lane tiles: 576 -> 640), not per-head K/V: one paged pool leaf
``paged_latent``, laid out and written like every family's
(``models/gpt.py:paged_block_fold``, ``paged_pool_writer``). Two attention
paths read it:

* *materialised* (a slab: the full forward, prefill, any call of more than
  one token): ``k_nope`` and ``v`` of every head are made from the latent
  rows the call attends, keys ``nope + rope`` wide, values ``v`` wide;
* *absorbed* (a one-token call: decode): ``W_kvb = [W_uk | W_uv]`` moves to
  the query and the output, ``q_lat = q_nope W_uk^T``,
  ``score = ([q_lat | q_pe] . [c_kv | k_pe]) * scale``,
  ``o = (softmax(score) c_kv) W_uv``: every head attends the latent rows
  themselves and no per-head key or value is ever formed.

**A share of the experts.** ``experts_held = (first, count)`` gives every
expert layer the weights of that range only; it routes over all
``n_routed_experts`` and returns its own experts' part plus the shared
expert (models/moe.py). The model then computes what ONE holder of a wide
expert-parallel deployment computes; nothing stands in for the others.

Serving is paged serving (``for_paged_decoding``); the linear cursor cache
is refused by name. In a decode call the engine reads the ``moe_stats``
counters the expert layers sow (``expert_layers`` says there are some).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.schemas import RunConfig
from ..ops.rope import apply_rope, yarn_inv_freq, yarn_mscale
from ..registry.models import register_model
from .gpt import (
    _DENSE_INIT,
    _EMBED_INIT,
    GPTAdapter,
    _scaled_init,
    paged_block_fold,
    paged_pool_writer,
)
from .llama import RMSNorm, gated_mlp
from .moe import DroplessMoE


class LatentAttention(nn.Module):
    d_model: int
    n_heads: int
    n_layers: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rope_scaling: tuple[tuple[str, float], ...]  # () = none; else YaRN's keys
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
        batch, t, _ = x.shape
        heads, nope, rope_dim = self.n_heads, self.qk_nope_head_dim, self.qk_rope_head_dim
        rank, width = self.kv_lora_rank, self.kv_lora_rank + self.qk_rope_head_dim
        kw = dict(use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype)
        norm_kw = dict(eps=self.rms_norm_eps, dtype=self.dtype, param_dtype=self.param_dtype)

        c_q = nn.Dense(
            self.q_lora_rank,
            kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", None)),
            name="q_a_proj", **kw,
        )(x)
        q = nn.DenseGeneral(
            features=(heads, nope + rope_dim),
            kernel_init=nn.with_logical_partitioning(_DENSE_INIT, (None, "heads", "kv")),
            name="q_b_proj", **kw,
        )(RMSNorm(name="q_a_norm", **norm_kw)(c_q))
        kv_a = nn.Dense(
            width,
            kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", None)),
            name="kv_a_proj", **kw,
        )(x)
        c_kv = RMSNorm(name="kv_a_norm", **norm_kw)(kv_a[..., :rank])
        w_kvb = self.param(
            "kv_b_proj",
            nn.with_logical_partitioning(_DENSE_INIT, (None, "heads", "kv")),
            (rank, heads, nope + self.v_head_dim),
            self.param_dtype,
        ).astype(self.dtype)

        scaling = dict(self.rope_scaling)
        inv_freq, scale = None, 1.0 / math.sqrt(nope + rope_dim)
        if scaling:
            inv_freq = yarn_inv_freq(
                rope_dim,
                theta=self.rope_theta,
                factor=scaling["factor"],
                original_max_position_embeddings=int(scaling["original_max_position_embeddings"]),
                beta_fast=scaling["beta_fast"],
                beta_slow=scaling["beta_slow"],
            )
            # The rotary's own magnitude factor is mscale / mscale_all_dim:
            # only a ratio of 1 (every published config of the family) is built.
            scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2

        paged = self.decode
        if paged:
            if positions is None or block_tables is None:
                raise ValueError(
                    "paged decode requires the `positions` (B,) and "
                    "`block_tables` (B, max_blocks) call arguments"
                )
            pos = positions[:, None] + jnp.arange(t)[None, :]  # (B, t)
        else:
            pos = jnp.arange(t)
        q_pe, k_pe = apply_rope(
            q[..., nope:], kv_a[..., None, rank:], pos, theta=self.rope_theta, inv_freq=inv_freq
        )
        q_nope = q[..., :nope]
        latent = jnp.concatenate([c_kv, k_pe[:, :, 0]], axis=-1)  # (B, t, width)

        if paged:
            nb, bt = self.paged_num_blocks, self.paged_block_tokens
            # A row wider than one 128-lane tile is padded (with zeros) to
            # whole tiles: given 576 lanes (4.5 tiles) the TPU compiler keeps
            # the leaf `num_blocks`-minor and copies the whole pool into and
            # out of every program (read in the compiled layouts, PERF.md
            # section 6, PR 31); given 640 it keeps it row-major, in place.
            lanes = width if width < 128 else -(-width // 128) * 128
            fold = paged_block_fold(bt, lanes)
            pool = self.variable(
                "cache", "paged_latent", jnp.zeros, (nb, bt // fold, fold * lanes), self.dtype
            )
            row = jnp.pad(latent, ((0, 0), (0, 0), (0, lanes - width)))
            pool.value = paged_pool_writer(pos, block_tables, bt, lanes)(pool.value, row)
            # Logical slot index IS the absolute position: liveness is col <= row.
            s = block_tables.shape[1] * bt
            latent = pool.value[block_tables].reshape(batch, s, lanes)
            live = jnp.arange(s)[None, None, None, :] <= pos[:, None, :, None]  # (B, 1, t, S)
        else:
            live = jnp.tril(jnp.ones((t, t), bool))[None, None]
            if attention_mask is not None:
                # Segment semantics, as models/gpt.py:dense_attention.
                seg = attention_mask
                live = live & (seg != 0)[:, None, None, :] & (
                    seg[:, None, :, None] == seg[:, None, None, :]
                )

        def attend(queries: jax.Array, keys: str, key_rows: jax.Array) -> jax.Array:
            scores = jnp.einsum(
                f"bthc,{keys}->bhts", queries, key_rows, preferred_element_type=jnp.float32
            ) * scale
            scores = jnp.where(live, scores, jnp.finfo(jnp.float32).min)
            return jax.nn.softmax(scores, axis=-1).astype(self.dtype)

        with jax.named_scope("mla_attention"):
            if paged and t == 1:
                # Absorbed: the heads attend the latent rows themselves.
                q_lat = jnp.einsum("bthd,chd->bthc", q_nope, w_kvb[..., :nope])
                q_row = jnp.concatenate([q_lat, q_pe], axis=-1)
                q_row = jnp.pad(q_row, ((0, 0),) * 3 + ((0, latent.shape[-1] - width),))
                probs = attend(q_row, "bsc", latent)
                o_lat = jnp.einsum("bhts,bsc->bthc", probs, latent[..., :rank])
                out = jnp.einsum("bthc,chd->bthd", o_lat, w_kvb[..., nope:])
            else:
                # Materialised: per-head keys and values of every row attended.
                kv = jnp.einsum("bsc,chd->bshd", latent[..., :rank], w_kvb)
                shared = jnp.broadcast_to(
                    latent[:, :, None, rank:width], (*kv.shape[:3], rope_dim)
                )
                keys = jnp.concatenate([kv[..., :nope], shared], axis=-1)
                probs = attend(jnp.concatenate([q_nope, q_pe], axis=-1), "bshc", keys)
                out = jnp.einsum("bhts,bshd->bthd", probs, kv[..., nope:])
        return nn.DenseGeneral(
            features=self.d_model,
            axis=(-2, -1),
            kernel_init=nn.with_logical_partitioning(
                _scaled_init(self.n_layers), ("heads", "kv", "embed")
            ),
            name="o_proj", **kw,
        )(out)


class SharedExpert(nn.Module):
    """One SwiGLU every token passes through (``models/llama.py:gated_mlp``
    under a name of its own, beside the routed experts)."""

    d_model: int
    d_ff: int
    n_layers: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:
        return gated_mlp(
            h, d_model=self.d_model, d_ff=self.d_ff, n_layers=self.n_layers,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )


class LatentMoEBlock(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int  # 0: the block's second half is the expert layer
    n_layers: int
    attn: dict[str, Any]
    moe: dict[str, Any]
    shared_d_ff: int
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
        x = x + LatentAttention(
            d_model=self.d_model, n_heads=self.n_heads, n_layers=self.n_layers,
            rms_norm_eps=self.rms_norm_eps, decode=self.decode,
            paged_num_blocks=self.paged_num_blocks, paged_block_tokens=self.paged_block_tokens,
            name="attn", **self.attn, **kw,
        )(h, attention_mask, positions=positions, block_tables=block_tables)
        h = nn.with_logical_constraint(RMSNorm(name="mlp_norm", **norm_kw)(x), act)
        if self.d_ff:
            h = gated_mlp(h, d_model=self.d_model, d_ff=self.d_ff, n_layers=self.n_layers, **kw)
        else:
            routed = DroplessMoE(
                d_model=self.d_model, n_layers=self.n_layers, name="moe", **self.moe, **kw
            )(h)
            with jax.named_scope("moe_shared"):
                shared = SharedExpert(
                    d_model=self.d_model, d_ff=self.shared_d_ff, n_layers=self.n_layers,
                    name="shared_expert", **kw,
                )(h)
            h = routed + shared
        return nn.with_logical_constraint(x + h, act)


class LatentMoE(nn.Module):
    """Decoder-only language model of latent-attention blocks, the first
    ``first_k_dense_replace`` dense and the rest with an expert layer."""

    vocab_size: int
    block_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_group: int = 1
    topk_group: int = 1
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    experts_held: tuple[int, int] | None = None
    rope_theta: float = 10000.0
    rope_scaling: tuple[tuple[str, float], ...] = ()
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
        """How many blocks hold an expert layer (the engine reads the
        ``moe_stats`` counters of a decode call only where this is not 0)."""
        return max(0, self.n_layers - self.first_k_dense_replace)

    def for_paged_decoding(
        self, *, num_blocks: int, block_tokens: int, state_rows: int = 0
    ) -> "LatentMoE":
        """Clone configured for paged continuous-batching decode (the
        GPT.for_paged_decoding contract; ``state_rows`` is offered and not
        taken: the latent is paged like any K/V)."""
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (got {num_blocks})")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1 (got {block_tokens})")
        return self.clone(
            decode=True, paged_num_blocks=num_blocks, paged_block_tokens=block_tokens
        )

    def for_decoding(self, cache_len: int | None = None, *, ring_slack: int = 0):
        """Refused by name: the linear cursor cache holds per-head K/V."""
        raise ValueError(
            "latent_moe has no linear decode cache (generate(), serving.mode: "
            "simple, speculative decoding): the latent rows are kept only in "
            "the paged pool — use serving.mode: continuous"
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
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim, qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta, rope_scaling=self.rope_scaling,
        )
        moe = dict(
            d_ff=self.moe_intermediate_size, n_experts=self.n_routed_experts,
            top_k=self.num_experts_per_tok, n_group=self.n_group, topk_group=self.topk_group,
            normalize=self.norm_topk_prob, scale=self.routed_scaling_factor,
            experts_held=self.experts_held,
        )
        paged = dict(
            decode=True, paged_num_blocks=self.paged_num_blocks,
            paged_block_tokens=self.paged_block_tokens,
        ) if self.decode else {}
        for layer in range(self.n_layers):
            x = LatentMoEBlock(
                d_model=self.d_model, n_heads=self.n_heads, n_layers=self.n_layers,
                d_ff=self.d_ff if layer < self.first_k_dense_replace else 0,
                attn=attn, moe=moe,
                shared_d_ff=self.moe_intermediate_size * self.n_shared_experts,
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


_SIZES = (
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "moe_intermediate_size", "n_routed_experts", "num_experts_per_tok",
)
_OPTIONAL_SIZES = {"n_group": 1, "topk_group": 1, "n_shared_experts": 1, "first_k_dense_replace": 1}
_YARN_KEYS = (
    "factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
)


@register_model("latent_moe")
class LatentMoEAdapter(GPTAdapter):
    """Adapter for latent attention over routed and shared experts; the loss
    machinery is GPTAdapter's (same top-level parameter names). Every size
    of the family's published config is a ``model.extra`` key under its
    published name; ``experts_held: [first, count]`` is the share of the
    routed experts this process holds (absent: all of them)."""

    known_extra_keys = frozenset(
        {"tokenizer", "loss_impl", "ce_chunk", "ce_auto_vocab", "z_loss",
         "rope_theta", "rope_scaling", "rms_norm_eps", "norm_topk_prob", "routed_scaling_factor",
         "scoring_func", "experts_held", *_SIZES, *_OPTIONAL_SIZES}
    )

    def build_model(self, cfg: RunConfig) -> nn.Module:
        extra = cfg.model.extra
        unknown = sorted(set(extra) - self.known_extra_keys)
        if unknown:
            raise ValueError(
                f"model.extra keys {unknown} are not latent_moe settings; known: "
                f"{sorted(self.known_extra_keys)}"
            )
        missing = [k for k in _SIZES if k not in extra]
        if missing:
            raise ValueError(f"latent_moe needs model.extra keys {missing}")
        if cfg.model.remat:
            raise ValueError("latent_moe does not support model.remat")
        if cfg.model.dropout:
            raise ValueError("latent_moe has no dropout; set model.dropout to 0.0")
        if cfg.model.attention != "dense":
            raise ValueError(
                "latent_moe computes its attention itself (keys and values of "
                f"unequal width); model.attention={cfg.model.attention!r} is not supported"
            )
        if str(extra.get("scoring_func", "sigmoid")) != "sigmoid":
            raise ValueError("latent_moe routes by sigmoid scores; scoring_func must be 'sigmoid'")
        base = super().build_model(cfg)  # the shared validation (vocab, loss)
        if base.loss_impl == "fused_ce":
            raise ValueError("latent_moe does not run the fused CE kernel; use 'dense' or 'chunked_ce'")
        sizes = {k: int(extra[k]) for k in _SIZES}
        sizes.update({k: int(extra.get(k, default)) for k, default in _OPTIONAL_SIZES.items()})
        for key, value in sizes.items():
            if value < (0 if key == "first_k_dense_replace" else 1):
                raise ValueError(f"model.extra.{key} must be >= 1, got {value}")
        if sizes["qk_rope_head_dim"] % 2:
            raise ValueError(f"RoPE needs an even qk_rope_head_dim, got {sizes['qk_rope_head_dim']}")
        rope_scaling: tuple[tuple[str, float], ...] = ()
        if extra.get("rope_scaling"):
            given = dict(extra["rope_scaling"])
            if given.pop("type", "yarn") != "yarn" or sorted(given) != sorted(_YARN_KEYS):
                raise ValueError(
                    f"model.extra.rope_scaling takes type 'yarn' and the keys {list(_YARN_KEYS)}"
                )
            if float(given["mscale"]) != float(given["mscale_all_dim"]):
                raise ValueError(
                    "model.extra.rope_scaling: mscale and mscale_all_dim differ; the rotary's "
                    "own magnitude factor (their ratio) is only built as 1"
                )
            rope_scaling = tuple((k, float(given[k])) for k in _YARN_KEYS)
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
        return LatentMoE(
            vocab_size=base.vocab_size,
            block_size=base.block_size,
            d_model=base.d_model,
            n_layers=base.n_layers,
            n_heads=base.n_heads,
            d_ff=base.d_ff,
            tie_embeddings=tie,
            dtype=base.dtype,
            param_dtype=base.param_dtype,
            loss_impl=base.loss_impl,
            ce_chunk=base.ce_chunk,
            z_loss=base.z_loss,
            norm_topk_prob=bool(extra.get("norm_topk_prob", True)),
            routed_scaling_factor=float(extra.get("routed_scaling_factor", 1.0)),
            experts_held=held,
            rope_theta=rope_theta,
            rope_scaling=rope_scaling,
            rms_norm_eps=rms_norm_eps,
            **sizes,
        )


__all__ = ["LatentAttention", "LatentMoE", "LatentMoEAdapter", "LatentMoEBlock", "SharedExpert"]

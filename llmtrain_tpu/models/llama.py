"""Llama-family decoder (RMSNorm + RoPE + SwiGLU + untied head), mesh-first.

Beyond-reference model family (the reference ships GPT only,
``src/llmtrain/models/gpt.py``; SURVEY §2.1): the architecture used by
Llama/Mistral-class checkpoints —

* **RMSNorm** instead of LayerNorm: no mean subtraction, no bias; f32
  statistics for bf16 safety (same discipline as gpt_pipeline's
  ``_layernorm``).
* **Rotary position embeddings** (ops/rope.py) instead of learned
  position embeddings — applied to q/k inside attention, so the KV cache
  stores rotated keys and long-context scaling is a ``rope_theta`` knob,
  not a parameter-table resize.
* **SwiGLU MLP**: ``down(silu(gate(x)) * up(x))``, all bias-free.
* **Untied lm_head** by default (``model.tie_embeddings: false`` is the
  Llama convention; the flag still works both ways).

Everything else — GQA narrow K/V, flash/ring/ulysses attention routing,
KV-cache decode, chunked CE, remat policies, logical-axis sharding — is
the shared machinery in ``models/gpt.py``/``ops/``: attention reuses
``CausalSelfAttention`` (with ``use_bias=False, rope=True``), so there is
exactly one KV-cache and one kernel-dispatch implementation in the
package. Numerics are parity-tested against HF ``transformers``' torch
Llama in tests/test_llama.py.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.schemas import RunConfig
from ..registry.models import register_model
from .activation_policy import tag_block_input, tier_block_classes
from .gpt import (
    _DENSE_INIT,
    _EMBED_INIT,
    REMAT_POLICIES,
    CausalSelfAttention,
    GPTAdapter,
    _scaled_init,
    model_paged_kv_form,
    scaled,
)
from .gpt_moe import GPTMoEAdapter as _GPTMoEAdapter


class RMSNorm(nn.Module):
    """Root-mean-square norm, f32 statistics, scale-only (no bias).

    ``offset=True`` is the Gemma parameterization: the stored scale is a
    zero-initialized delta and the output multiplies by ``1 + scale`` —
    the identity transform at init, and the exact layout HF Gemma
    checkpoints store (models/gemma.py).
    """

    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    offset: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        # Logical axis "norm" maps to None (parallel/sharding.py): a (D,)
        # scale gains nothing from fsdp sharding, and mapping it to
        # "embed"→fsdp makes XLA reshard the residual-stream grads
        # embed-wise for the dscale reduction — an involuntary-full-
        # rematerialization path on fsdp×tensor meshes.
        init = (
            nn.initializers.zeros_init() if self.offset
            else nn.initializers.ones_init()
        )
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(init, ("norm",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        xf = x.astype(jnp.float32)
        norm = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps
        )
        mult = scale.astype(jnp.float32)
        if self.offset:
            mult = 1.0 + mult
        return (norm * mult).astype(self.dtype)


def gated_mlp(
    h: jax.Array,
    *,
    d_model: int,
    d_ff: int,
    n_layers: int,
    dtype: Any,
    param_dtype: Any,
    act: str = "silu",
    gate_scale: float = 1.0,
    out_scale: float = 1.0,
) -> jax.Array:
    """``down(act(gate(h) * gate_scale) * up(h)) * out_scale``, bias-free:
    SwiGLU with ``act="silu"``, Gemma's GeGLU with ``"gelu_tanh"``. Call it
    inside a block's ``@nn.compact`` method: the three ``nn.Dense`` layers
    (``mlp_gate``, ``mlp_up``, ``mlp_down``) become the caller's own
    children. The two scales are muP multipliers (models/falcon_h1.py),
    applied in float32; 1.0 multiplies nothing."""
    dense_kw = dict(use_bias=False, dtype=dtype, param_dtype=param_dtype)
    gate = nn.Dense(
        d_ff,
        kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "mlp")),
        name="mlp_gate",
        **dense_kw,
    )(h)
    up = nn.Dense(
        d_ff,
        kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "mlp")),
        name="mlp_up",
        **dense_kw,
    )(h)
    gate = scaled(gate, gate_scale)
    if act == "silu":
        h = nn.silu(gate) * up
    elif act == "gelu_tanh":
        # Gemma's GeGLU: HF hidden_activation gelu_pytorch_tanh.
        h = nn.gelu(gate, approximate=True) * up
    else:
        raise ValueError(
            f"mlp_act {act!r} unknown; expected 'silu' or 'gelu_tanh'"
        )
    h = nn.with_logical_constraint(h, ("batch", "length", "act_mlp"))
    h = nn.Dense(
        d_model,
        kernel_init=nn.with_logical_partitioning(
            _scaled_init(n_layers), ("mlp", "embed")
        ),
        name="mlp_down",
        **dense_kw,
    )(h)
    return scaled(h, out_scale)


class LlamaBlock(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    n_layers: int
    dropout: float
    dtype: Any
    param_dtype: Any
    attention: str = "dense"
    decode: bool = False
    cache_len: int = 0
    n_kv_heads: int = 0
    assume_packed: bool = False
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    # Qwen2 convention (models/qwen2.py): bias on q/k/v only; out_proj
    # and the MLP stay bias-free either way.
    qkv_bias: bool = False
    # Gemma conventions (models/gemma.py): tanh-GELU GeGLU MLP and the
    # (1 + scale) RMSNorm parameterization.
    mlp_act: str = "silu"
    norm_offset: bool = False
    sliding_window: int = 0  # Mistral-style window; 0 = full causal
    ring_slack: int = 0  # extra rolling-cache slots (speculative decode)
    kv_cache_dtype: str = "model"  # "int8": quantized decode cache
    # Paged block-pool decode cache (models/gpt.py CausalSelfAttention):
    # RoPE rotates by the per-row absolute positions the paged path
    # tracks, so the llama family serves continuous-batching too.
    paged: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0
    # Mixture-of-Experts MLP with SwiGLU experts (models/moe.py,
    # mlp_type="swiglu" — the Mixtral layout); 0 = dense SwiGLU.
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    router_top_k: int = 1

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        attention_mask: jax.Array | None = None,
        deterministic: bool = True,
        positions: jax.Array | None = None,
        block_tables: jax.Array | None = None,
    ) -> jax.Array:
        # Residual tag consumed by the "offload" activation tier's
        # checkpoint policy; identity under every other policy.
        x = tag_block_input(x)
        norm_kw = dict(
            eps=self.rms_norm_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            offset=self.norm_offset,
        )
        # Pin the norm outputs' sharding: without the constraint XLA's
        # backward pass reshards the residual-stream grads through a
        # full-rematerialization path on fsdp×tensor meshes (SPMD warning
        # seen in dryrun_llama).
        act = ("batch", "length", "act_embed")
        h = nn.with_logical_constraint(RMSNorm(name="attn_norm", **norm_kw)(x), act)
        x = x + CausalSelfAttention(
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_layers=self.n_layers,
            dropout=self.dropout,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            attention=self.attention,
            decode=self.decode,
            cache_len=self.cache_len,
            n_kv_heads=self.n_kv_heads,
            assume_packed=self.assume_packed,
            use_bias=False,
            qkv_bias=self.qkv_bias or None,
            rope=True,
            rope_theta=self.rope_theta,
            sliding_window=self.sliding_window,
            ring_slack=self.ring_slack,
            kv_cache_dtype=self.kv_cache_dtype,
            paged=self.paged,
            paged_num_blocks=self.paged_num_blocks,
            paged_block_tokens=self.paged_block_tokens,
            name="attn",
        )(
            h,
            attention_mask,
            deterministic=deterministic,
            positions=positions,
            block_tables=block_tables,
        )

        h = nn.with_logical_constraint(RMSNorm(name="mlp_norm", **norm_kw)(x), act)
        if self.n_experts > 0:
            from .moe import MoEMLP

            h = MoEMLP(
                d_model=self.d_model,
                d_ff=self.d_ff,
                n_experts=self.n_experts,
                n_layers=self.n_layers,
                capacity_factor=self.capacity_factor,
                aux_loss_weight=self.moe_aux_weight,
                router_top_k=self.router_top_k,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                mlp_type="swiglu",
                name="moe_mlp",
            )(h)
        else:
            h = gated_mlp(
                h,
                d_model=self.d_model,
                d_ff=self.d_ff,
                n_layers=self.n_layers,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                act=self.mlp_act,
            )
        h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        x = x + h
        return nn.with_logical_constraint(x, ("batch", "length", "act_embed"))


class Llama(nn.Module):
    """Llama-family decoder-only language model."""

    vocab_size: int
    block_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    dropout: float
    tie_embeddings: bool = False  # Llama convention: untied head
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "nothing"
    # Per-layer activation tiers (models/gpt.py GPT.activation_tiers):
    # overrides the remat fields above when set.
    activation_tiers: tuple[str, ...] | None = None
    attention: str = "dense"
    decode: bool = False
    decode_cache_len: int = 0
    loss_impl: str = "dense"
    ce_chunk: int = 8192
    # Fused lm-head + CE Pallas kernel knobs (models/gpt.py GPT fields;
    # the loss machinery is shared via GPTAdapter).
    fused_ce_block_t: int | None = None
    fused_ce_block_v: int | None = None
    pallas_interpret: bool = False
    z_loss: float = 0.0
    n_kv_heads: int = 0
    assume_packed: bool = False
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    # Qwen2 convention: bias on the q/k/v projections only.
    qkv_bias: bool = False
    # Gemma conventions: tanh-GELU GeGLU, (1 + scale) RMSNorm, and
    # sqrt(d_model)-scaled input embeddings (the tied lm_head read is
    # NOT scaled — HF Gemma semantics).
    mlp_act: str = "silu"
    norm_offset: bool = False
    embed_scale: bool = False
    # Sliding-window attention (model.extra.sliding_window, the Mistral
    # architecture knob): O(T·W) attention on the flash path.
    sliding_window: int = 0
    # Decode-cache storage dtype (model.extra.kv_cache_dtype).
    kv_cache_dtype: str = "model"
    # Extra rolling-cache slots for speculative decode rollback safety
    # (models/gpt.py CausalSelfAttention.ring_slack).
    ring_slack: int = 0
    # Paged block-pool decode cache for continuous-batching serving; set
    # via for_paged_decoding().
    paged: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0
    # Mixture-of-Experts with SwiGLU experts (model.name llama_moe — the
    # Mixtral architecture); 0 = dense SwiGLU MLPs.
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    router_top_k: int = 1

    def paged_kv_form(self, *, t: int) -> str:
        """The form of the paged read a call of ``t`` tokens a row runs: the
        engine's ``kv_form`` (models/gpt.py ``model_paged_kv_form``)."""
        return model_paged_kv_form(self, t=t)

    def for_paged_decoding(
        self, *, num_blocks: int, block_tokens: int, state_rows: int = 0
    ) -> "Llama":
        """Clone configured for paged-KV continuous-batching decode (the
        GPT.for_paged_decoding contract; serving/engine.py dispatches on
        this method's presence; ``state_rows`` is offered and not taken).
        RoPE needs no special casing — the paged attention rotates q/k by
        its per-row absolute positions — but the
        sliding-window ring and the int8 cache keep their named raise, so
        Mistral-with-window configs fall back to ``serving.mode: simple``
        with an actionable error instead of silently wrong K/V."""
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (got {num_blocks})")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1 (got {block_tokens})")
        if self.sliding_window:
            raise ValueError(
                "paged decode does not support sliding_window models yet; "
                "use for_decoding() (rolling-ring cache)"
            )
        if self.kv_cache_dtype != "model":
            raise ValueError(
                "paged decode does not support kv_cache_dtype="
                f"{self.kv_cache_dtype!r} yet; use for_decoding()"
            )
        return self.clone(
            decode=True,
            paged=True,
            remat=False,
            activation_tiers=None,
            paged_num_blocks=num_blocks,
            paged_block_tokens=block_tokens,
        )

    def for_decoding(
        self, cache_len: int | None = None, *, ring_slack: int = 0
    ) -> "Llama":
        """Clone configured for cached autoregressive decoding (same
        contract as GPT.for_decoding — generation.py dispatches on it)."""
        if cache_len is None:
            cache_len = self.block_size
        return self.clone(
            decode=True,
            remat=False,
            activation_tiers=None,
            decode_cache_len=min(cache_len, self.block_size),
            ring_slack=ring_slack,
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

        token_embedding = nn.Embed(
            self.vocab_size,
            self.d_model,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            embedding_init=nn.with_logical_partitioning(_EMBED_INIT, ("vocab", "embed")),
            name="token_embedding",
        )
        # No position embedding: RoPE rotates q/k inside attention, and at
        # decode time the cache cursor supplies absolute positions — the
        # model-level position_index variable GPT keeps (gpt.py:506-514)
        # has no Llama analogue.
        x = token_embedding(input_ids)
        if self.embed_scale:
            # HF Gemma casts the sqrt(d) normalizer to the activation
            # dtype BEFORE multiplying (a bf16 rounding the parity tests
            # would catch if skipped).
            x = x * jnp.asarray(self.d_model**0.5, dtype=x.dtype)
        x = nn.Dropout(self.dropout)(x, deterministic=deterministic)
        x = nn.with_logical_constraint(x, ("batch", "length", "act_embed"))

        if self.activation_tiers is not None:
            if len(self.activation_tiers) != self.n_layers:
                raise ValueError(
                    f"activation_tiers has {len(self.activation_tiers)} "
                    f"entries for a {self.n_layers}-layer model"
                )
            tier_classes = tier_block_classes(LlamaBlock, self.activation_tiers)
            layer_classes = [tier_classes[t] for t in self.activation_tiers]
        else:
            block_cls = LlamaBlock
            if self.remat:
                if self.remat_policy not in REMAT_POLICIES:
                    raise ValueError(
                        f"remat_policy {self.remat_policy!r} unknown; expected "
                        f"one of {sorted(REMAT_POLICIES)}"
                    )
                block_cls = nn.remat(
                    LlamaBlock,
                    static_argnums=(3,),
                    policy=REMAT_POLICIES[self.remat_policy],
                )
            layer_classes = [block_cls] * self.n_layers

        paged = self.decode and self.paged
        for layer in range(self.n_layers):
            block = layer_classes[layer](
                d_model=self.d_model,
                n_heads=self.n_heads,
                d_ff=self.d_ff,
                n_layers=self.n_layers,
                dropout=self.dropout,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                attention=self.attention,
                decode=self.decode,
                cache_len=(self.decode_cache_len or self.block_size) if self.decode else 0,
                n_kv_heads=self.n_kv_heads,
                assume_packed=self.assume_packed,
                rope_theta=self.rope_theta,
                rms_norm_eps=self.rms_norm_eps,
                qkv_bias=self.qkv_bias,
                mlp_act=self.mlp_act,
                norm_offset=self.norm_offset,
                sliding_window=self.sliding_window,
                kv_cache_dtype=self.kv_cache_dtype,
                ring_slack=self.ring_slack if self.decode else 0,
                paged=paged,
                paged_num_blocks=self.paged_num_blocks if paged else 0,
                paged_block_tokens=self.paged_block_tokens if paged else 0,
                n_experts=self.n_experts,
                capacity_factor=self.capacity_factor,
                moe_aux_weight=self.moe_aux_weight,
                router_top_k=self.router_top_k,
                name=f"block_{layer}",
            )
            if paged:
                # kwargs only on the paged path: the remat wrapper's
                # positional static_argnums contract stays untouched
                # (paged implies remat=False anyway, gpt.py precedent).
                x = block(
                    x,
                    attention_mask,
                    deterministic,
                    positions=positions,
                    block_tables=block_tables,
                )
            else:
                x = block(x, attention_mask, deterministic)

        x = RMSNorm(
            name="norm_f",
            eps=self.rms_norm_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            offset=self.norm_offset,
        )(x)

        if return_hidden:
            # Chunked-CE path: the loss contracts hidden states against the
            # vocab matrix (ops/chunked_ce.py via GPTAdapter.vocab_matrix —
            # param names match, so the adapter machinery is inherited).
            return nn.with_logical_constraint(x, ("batch", "length", "act_embed"))

        if self.tie_embeddings:
            logits = token_embedding.attend(x)
        else:
            logits = nn.Dense(
                self.vocab_size,
                use_bias=False,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "vocab")),
                name="lm_head",
            )(x)
        return nn.with_logical_constraint(logits, ("batch", "length", "act_vocab"))


@register_model("llama")
class LlamaAdapter(GPTAdapter):
    """Adapter for the Llama family.

    Inherits the GPT adapter's loss machinery wholesale — chunked CE,
    z-loss, vocab-matrix access, mesh validation — because the Llama
    module keeps the same top-level param names (``token_embedding``,
    ``lm_head``) and loss-relevant attributes.
    """

    known_extra_keys = GPTAdapter.known_extra_keys | frozenset(
        {"rope_theta", "rms_norm_eps"}
    )

    def build_model(self, cfg: RunConfig) -> nn.Module:
        if cfg.model.extra.get("fused_norm"):
            # The fused Pallas add+norm kernel is LayerNorm-shaped; the
            # llama family norms are RMSNorm and are not wired to it.
            raise ValueError(
                "model.extra.fused_norm is not supported by the llama "
                "family (RMSNorm blocks); it is a gpt-family knob"
            )
        base = super().build_model(cfg)  # runs all shared validation
        rope_theta = float(cfg.model.extra.get("rope_theta", 10000.0))
        if rope_theta <= 0:
            raise ValueError(f"model.extra.rope_theta must be > 0, got {rope_theta}")
        rms_norm_eps = float(cfg.model.extra.get("rms_norm_eps", 1e-6))
        if rms_norm_eps <= 0:
            raise ValueError(
                f"model.extra.rms_norm_eps must be > 0, got {rms_norm_eps}"
            )
        if (cfg.model.d_model // cfg.model.n_heads) % 2 != 0:
            raise ValueError(
                "RoPE needs an even head dim: d_model/n_heads = "
                f"{cfg.model.d_model // cfg.model.n_heads}"
            )
        # The schema default (tie_embeddings: true, GPT convention —
        # config/schemas.py) is wrong for this family: a config that does
        # not mention the flag gets the Llama convention (untied head);
        # an explicit value wins either way.
        tie = (
            cfg.model.tie_embeddings
            if "tie_embeddings" in cfg.model.model_fields_set
            else False
        )
        return Llama(
            vocab_size=base.vocab_size,
            block_size=base.block_size,
            d_model=base.d_model,
            n_layers=base.n_layers,
            n_heads=base.n_heads,
            d_ff=base.d_ff,
            dropout=base.dropout,
            tie_embeddings=tie,
            dtype=base.dtype,
            param_dtype=base.param_dtype,
            remat=base.remat,
            remat_policy=base.remat_policy,
            activation_tiers=base.activation_tiers,
            attention=base.attention,
            loss_impl=base.loss_impl,
            ce_chunk=base.ce_chunk,
            fused_ce_block_t=base.fused_ce_block_t,
            fused_ce_block_v=base.fused_ce_block_v,
            pallas_interpret=base.pallas_interpret,
            z_loss=base.z_loss,
            n_kv_heads=base.n_kv_heads,
            assume_packed=base.assume_packed,
            rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps,
            sliding_window=base.sliding_window,
            kv_cache_dtype=base.kv_cache_dtype,
        )


@register_model("llama_moe")
class LlamaMoEAdapter(_GPTMoEAdapter, LlamaAdapter):
    """Mixtral-class adapter: the llama family + SwiGLU-expert MoE.

    Cooperative MRO does the composition: ``GPTMoEAdapter.build_model``
    validates/clones the MoE knobs and its ``compute_loss_components``
    folds the sown load-balance aux loss; ``super().build_model`` resolves
    to ``LlamaAdapter.build_model``, so the trunk is the Llama module
    (whose blocks route the MLP through ``MoEMLP(mlp_type="swiglu")``).
    With ``model.extra.sliding_window`` this is the full Mixtral layout.
    """

    known_extra_keys = (
        _GPTMoEAdapter.known_extra_keys | LlamaAdapter.known_extra_keys
    )
    _moe_name = "llama_moe"
    _dense_name = "llama"


__all__ = ["Llama", "LlamaBlock", "RMSNorm", "LlamaAdapter", "LlamaMoEAdapter"]

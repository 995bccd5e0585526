"""Pipeline-parallel GPT: stacked-block params scheduled with GPipe.

New capability beyond the reference (no model parallelism of any kind
there — SURVEY §2.3/§2.4). Same architecture family as ``models/gpt.py``
(learned token+position embeddings, pre-norm blocks, GELU MLP, final LN,
tied lm_head — behavior spec reference models/gpt.py:99-165) but built for
stage execution: every block parameter carries a LEADING layer dim
(logical axis ``"layers"`` → mesh ``pipeline``), blocks are applied by a
``lax.scan`` over that dim, and under a mesh with ``pipeline > 1`` the
stack runs through ``parallel/pipeline.gpipe_apply`` — microbatches
rotating across stages over ICI.

Scope (validated loudly): causal sequences with padding masks applied
INSIDE attention (reference gpt.py:60-74 — each stage tick receives its
microbatch's mask slice from the executor; ``model.extra.assume_packed``
drops the operand), no dropout inside pipelined blocks, and ``pipeline``
composes with
``data`` AND ``tensor`` (Megatron column/row splits inside each stage:
qkv/fc shard their output heads/width, out/proj their input, with the two
row-parallel psums written explicitly in the stage — shard_map is manual).
``fsdp``/``sequence`` must be 1.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.schemas import RunConfig
from ..registry.models import register_model
from .base import ModelAdapter, Params, lm_loss_components
from .gpt import dense_attention, gelu_once

_INIT_STD = 0.02


def _layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    """f32-statistics layernorm over the trailing dim."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    # eps matches models/gpt.py's flax LayerNorm (1e-6, docs/parity.md) so
    # pipeline<->gpt parameter conversion (interop/pipeline_convert.py) is
    # numerically exact.
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def make_block_apply(
    *, attention: str, dtype: Any, tp_axis: str | None = None, window: int = 0
):
    """Functional pre-norm transformer block over stacked params.

    ``p`` leaves are ONE layer's slice (no leading layer dim); ``h`` is
    (B, T, D). Mirrors TransformerBlock (models/gpt.py:245-308) without
    module machinery so it can run under shard_map/scan. Shapes are read
    from the params, so the same code runs full-width or on a tensor-
    parallel shard (H/tp heads, F/tp mlp width): with ``tp_axis`` set the
    block inserts the two Megatron row-parallel psums (after out-proj and
    after mlp-proj; biases added once, after the psum).
    """

    def block_apply(
        p: dict[str, jax.Array], h: jax.Array, key_mask: jax.Array | None = None
    ) -> jax.Array:
        hn = _layernorm(h, p["ln1_scale"], p["ln1_bias"])
        # Kernels are head-major so tensor parallelism can shard whole
        # heads; local H may be a tp-shard of the global count. The fused
        # qkv layout is MHA; GQA splits into q_kernel/kv_kernel with
        # narrow K/V (layouts match models/gpt.py's projections).
        if "qkv_kernel" in p:
            qkv = jnp.einsum(
                "btd,dkhe->btkhe", hn.astype(dtype), p["qkv_kernel"].astype(dtype)
            ) + p["qkv_bias"].astype(dtype)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B, T, Hl, Dh)
        else:
            q = jnp.einsum(
                "btd,dhe->bthe", hn.astype(dtype), p["q_kernel"].astype(dtype)
            ) + p["q_bias"].astype(dtype)
            kv = jnp.einsum(
                "btd,dkhe->btkhe", hn.astype(dtype), p["kv_kernel"].astype(dtype)
            ) + p["kv_bias"].astype(dtype)
            k, v = kv[:, :, 0], kv[:, :, 1]  # (B, T, Hkv_l, Dh)
        if attention == "flash":
            from ..ops.flash_attention import flash_attention, flash_attention_qkv

            # The fused projection's output goes to the kernels whole (they
            # read q, k and v out of it in place); narrow GQA K/V are
            # consumed natively (Pallas index maps on TPU, grouped einsums
            # in the blockwise fallback).
            if "qkv_kernel" in p:
                att = flash_attention_qkv(qkv, attention_mask=key_mask, window=window)
            else:
                att = flash_attention(
                    q, k, v, attention_mask=key_mask, causal=True, window=window
                )
        else:
            if k.shape[2] != q.shape[2]:
                reps = q.shape[2] // k.shape[2]
                k = jnp.repeat(k, reps, axis=2)
                v = jnp.repeat(v, reps, axis=2)
            att = dense_attention(q, k, v, attention_mask=key_mask, window=window)
        proj = jnp.einsum(
            "bthe,hed->btd", att.astype(dtype), p["out_kernel"].astype(dtype)
        )
        if tp_axis is not None:
            proj = jax.lax.psum(proj, tp_axis)
        attn_out = proj + p["out_bias"].astype(dtype)
        if key_mask is not None:
            # Zero padded rows' attention contribution (reference
            # gpt.py:73-74, same boolean compare as models/gpt.py —
            # mask values may be segment ids).
            attn_out = attn_out * (key_mask != 0)[:, :, None].astype(attn_out.dtype)
        h = h + attn_out

        hn = _layernorm(h, p["ln2_scale"], p["ln2_bias"])
        m = hn.astype(dtype) @ p["fc_kernel"].astype(dtype) + p["fc_bias"].astype(dtype)
        # As in models/gpt.py's block: one erf evaluation an element under a
        # gradient (a stage's scan body is differentiated, and rematerialised,
        # like any other), ``nn.gelu`` itself where none is taken.
        m = gelu_once(m)
        mlp = m @ p["proj_kernel"].astype(dtype)
        if tp_axis is not None:
            mlp = jax.lax.psum(mlp, tp_axis)
        h = h + mlp + p["proj_bias"].astype(dtype)
        return h

    return block_apply


def make_stage_fn(
    *, attention: str, dtype: Any, tp_axis: str | None = None, window: int = 0
):
    """Stage program: scan ``block_apply`` over this stage's layer slice.
    ``key_mask`` is the microbatch's (B, T) padding mask (or None)."""
    block_apply = make_block_apply(
        attention=attention, dtype=dtype, tp_axis=tp_axis, window=window
    )

    def stage_fn(
        stage_params: dict[str, jax.Array],
        h: jax.Array,
        key_mask: jax.Array | None = None,
    ) -> jax.Array:
        def body(h, layer_params):
            return block_apply(layer_params, h, key_mask), None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    return stage_fn


class PipelineGPT(nn.Module):
    """Decoder-only GPT with a stacked, pipeline-shardable block stack."""

    vocab_size: int
    block_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    tie_embeddings: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention: str = "dense"
    n_microbatches: int = 4
    remat: bool = True
    # >1 selects the interleaved (Megatron-style) schedule: each stage
    # holds this many non-contiguous layer chunks and microbatches make
    # that many passes around the stage ring — bubble (S-1)/(v*M+S-1).
    n_virtual_chunks: int = 1
    # "chunked_ce" streams the LM loss over vocab chunks (ops/chunked_ce.py).
    # Works here because the lm_head applies OUTSIDE the stage shard_map,
    # on the gathered final hidden states.
    loss_impl: str = "dense"
    ce_chunk: int = 8192
    # PaLM z-loss coefficient (see models/gpt.py); 0 = off.
    z_loss: float = 0.0
    # Data is guaranteed packed (all-ones masks): skip the in-attention
    # mask (model.extra.assume_packed, same knob as models/gpt.py).
    assume_packed: bool = False
    # Sliding-window attention (model.extra.sliding_window, Mistral
    # semantics — see models/gpt.py); 0 = full causal.
    sliding_window: int = 0
    # Decode-cache storage dtype: the pipeline model never decodes
    # itself, but carries the knob so the decode-time conversion to the
    # plain GPT tree (interop/pipeline_convert.py via cli.py
    # _prepare_decode_model) preserves it.
    kv_cache_dtype: str = "model"
    # Grouped-query attention: K/V heads (0 = n_heads/MHA, 1 = MQA), the
    # same semantics and param naming family as models/gpt.py — flash
    # consumes the narrow K/V natively, dense broadcasts.
    n_kv_heads: int = 0

    def _stacked(
        self, name: str, shape: tuple[int, ...], init, axes: tuple[str, ...]
    ) -> jax.Array:
        """A per-layer-stacked parameter: leading dim n_layers on logical
        axis "layers" (→ mesh ``pipeline``); ``axes`` names the per-layer
        dims with the same logical vocabulary as models/gpt.py (so heads/
        mlp dims shard over ``tensor`` in the train state)."""
        return self.param(
            name,
            nn.with_logical_partitioning(init, ("layers", *axes)),
            (self.n_layers, *shape),
            self.param_dtype,
        )

    @nn.compact
    def __call__(
        self,
        input_ids: jax.Array,
        attention_mask: jax.Array | None = None,
        *,
        deterministic: bool = True,
        return_hidden: bool = False,
    ) -> jax.Array:
        del deterministic  # no dropout inside pipelined blocks (v1)
        # Padding masks are applied inside attention here too (reference
        # gpt.py:60-74 semantics): the executor hands each stage tick its
        # microbatch's mask slice (parallel/pipeline.py). assume_packed
        # drops the operand like the gpt flash path.
        if self.assume_packed:
            attention_mask = None
        _, seqlen = input_ids.shape
        if seqlen > self.block_size:
            raise ValueError(
                f"Input sequence length {seqlen} exceeds block size {self.block_size}."
            )

        embed_init = nn.initializers.normal(stddev=_INIT_STD)
        dense_init = nn.initializers.normal(stddev=_INIT_STD)
        scaled_init = nn.initializers.normal(
            stddev=_INIT_STD / math.sqrt(2 * self.n_layers)
        )

        token_embedding = nn.Embed(
            self.vocab_size,
            self.d_model,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            embedding_init=nn.with_logical_partitioning(embed_init, ("vocab", "embed")),
            name="token_embedding",
        )
        position_embedding = nn.Embed(
            self.block_size,
            self.d_model,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            embedding_init=nn.with_logical_partitioning(embed_init, ("position", "embed")),
            name="position_embedding",
        )
        x = token_embedding(input_ids) + position_embedding(
            jnp.arange(seqlen)[None, :]
        )
        x = nn.with_logical_constraint(x, ("batch", "length", "act_embed"))

        d, f, nh = self.d_model, self.d_ff, self.n_heads
        hd = d // nh
        ones, zeros = nn.initializers.ones_init(), nn.initializers.zeros_init()
        kvh = self.n_kv_heads or nh
        if kvh == nh:
            # Head-major fused qkv so tensor parallelism shards whole heads.
            attn_params = {
                "qkv_kernel": self._stacked(
                    "qkv_kernel", (d, 3, nh, hd), dense_init,
                    ("embed", "qkv", "heads", "kv"),
                ),
                "qkv_bias": self._stacked(
                    "qkv_bias", (3, nh, hd), zeros, ("qkv", "heads", "kv")
                ),
            }
        else:
            if nh % kvh != 0:
                raise ValueError(
                    f"n_heads ({nh}) must be divisible by n_kv_heads ({kvh})"
                )
            # Split projections, same per-layer shapes as models/gpt.py's
            # q_proj/kv_proj (the conversion in interop/pipeline_convert.py
            # maps them 1:1).
            attn_params = {
                "q_kernel": self._stacked(
                    "q_kernel", (d, nh, hd), dense_init, ("embed", "heads", "kv")
                ),
                "q_bias": self._stacked("q_bias", (nh, hd), zeros, ("heads", "kv")),
                "kv_kernel": self._stacked(
                    "kv_kernel", (d, 2, kvh, hd), dense_init,
                    ("embed", "qkv", "heads", "kv"),
                ),
                "kv_bias": self._stacked(
                    "kv_bias", (2, kvh, hd), zeros, ("qkv", "heads", "kv")
                ),
            }
        blocks = {
            "ln1_scale": self._stacked("ln1_scale", (d,), ones, ("embed",)),
            "ln1_bias": self._stacked("ln1_bias", (d,), zeros, ("embed",)),
            **attn_params,
            "out_kernel": self._stacked(
                "out_kernel", (nh, hd, d), scaled_init, ("heads", "kv", "embed")
            ),
            "out_bias": self._stacked("out_bias", (d,), zeros, ("embed",)),
            "ln2_scale": self._stacked("ln2_scale", (d,), ones, ("embed",)),
            "ln2_bias": self._stacked("ln2_bias", (d,), zeros, ("embed",)),
            "fc_kernel": self._stacked("fc_kernel", (d, f), dense_init, ("embed", "mlp")),
            "fc_bias": self._stacked("fc_bias", (f,), zeros, ("mlp",)),
            "proj_kernel": self._stacked("proj_kernel", (f, d), scaled_init, ("mlp", "embed")),
            "proj_bias": self._stacked("proj_bias", (d,), zeros, ("embed",)),
        }

        from ..parallel.pipeline import pipeline_degree
        from ..parallel.sharding import ambient_mesh

        mesh = ambient_mesh()
        n_stages = pipeline_degree(mesh)
        tp = int(mesh.shape.get("tensor", 1)) if mesh is not None else 1
        if n_stages > 1:
            from ..parallel.pipeline import BATCH_AXES, gpipe_apply

            for banned in ("fsdp", "sequence"):
                if int(mesh.shape.get(banned, 1)) != 1:
                    raise ValueError(
                        f"gpt_pipeline composes pipeline with data and tensor "
                        f"parallelism; mesh axis {banned!r} must be 1, got "
                        f"{mesh.shape[banned]}"
                    )
            if nh % tp != 0 or f % tp != 0:
                raise ValueError(
                    f"tensor parallelism needs n_heads ({nh}) and d_ff ({f}) "
                    f"divisible by the tensor axis size ({tp})"
                )
            if self.n_layers % (n_stages * self.n_virtual_chunks) != 0:
                raise ValueError(
                    f"n_layers {self.n_layers} must divide evenly into "
                    f"{n_stages} pipeline stages x {self.n_virtual_chunks} "
                    "virtual chunks"
                )
            dp = math.prod(int(mesh.shape.get(a, 1)) for a in BATCH_AXES)
            needed = dp * self.n_microbatches
            if x.shape[0] % needed != 0:
                # Batch-1 traces (the param-init probe, models/base.py:52)
                # fall back silently by design. A REAL batch must not: on a
                # pipeline:S mesh "without pipeline parallelism" means every
                # device materializes all S stages' layers — an OOM at the
                # sizes pipeline parallelism exists for, reached via a
                # warning. The Trainer pads eval batches up to
                # adapter.batch_divisor(), so this is only reachable from
                # custom callers.
                if x.shape[0] > 1:
                    raise ValueError(
                        f"gpt_pipeline: batch {x.shape[0]} is not divisible "
                        f"by data shards x microbatches ({needed}) on a "
                        f"{n_stages}-stage pipeline mesh; pad the batch with "
                        "zero-masked rows (Trainer eval does this via "
                        "ModelAdapter.batch_divisor) or adjust "
                        "model.extra.pipeline_microbatches"
                    )
                n_stages = 1
        if n_stages > 1:
            from jax.sharding import PartitionSpec as P

            tp_axis = "tensor" if tp > 1 else None
            stage_fn = make_stage_fn(
                attention=self.attention, dtype=self.dtype, tp_axis=tp_axis,
                window=self.sliding_window,
            )

            def _pspec(*tail):
                return P("pipeline", *tail)

            # Mirrors the logical axes above with "tensor" where heads/mlp
            # shard — shard_map is manual, so the specs must say it again.
            # Only when tp > 1: a size-1 (or absent) tensor axis must not
            # appear, or params become tensor-varying with no psum to
            # cancel it and the layer-scan carry types mismatch.
            tens = "tensor" if tp > 1 else None
            if kvh == nh:
                attn_specs = {
                    "qkv_kernel": _pspec(None, None, tens, None),
                    "qkv_bias": _pspec(None, tens, None),
                }
            else:
                if tp > 1 and kvh % tp != 0:
                    raise ValueError(
                        f"n_kv_heads ({kvh}) must be divisible by the mesh "
                        f"tensor axis ({tp}) — K/V heads shard over tensor "
                        "parallelism like query heads do"
                    )
                attn_specs = {
                    "q_kernel": _pspec(None, tens, None),
                    "q_bias": _pspec(tens, None),
                    "kv_kernel": _pspec(None, None, tens, None),
                    "kv_bias": _pspec(None, tens, None),
                }
            param_specs = {
                "ln1_scale": _pspec(None),
                "ln1_bias": _pspec(None),
                **attn_specs,
                "out_kernel": _pspec(tens, None, None),
                "out_bias": _pspec(None),
                "ln2_scale": _pspec(None),
                "ln2_bias": _pspec(None),
                "fc_kernel": _pspec(None, tens),
                "fc_bias": _pspec(tens),
                "proj_kernel": _pspec(tens, None),
                "proj_bias": _pspec(None),
            }
            x = gpipe_apply(
                stage_fn,
                blocks,
                x,
                mesh,
                n_microbatches=self.n_microbatches,
                remat_stage=self.remat,
                virtual_chunks=self.n_virtual_chunks,
                param_specs=param_specs,
                mask=attention_mask,
            )
        else:
            stage_fn = make_stage_fn(
                attention=self.attention, dtype=self.dtype,
                window=self.sliding_window,
            )
            fn = jax.checkpoint(stage_fn) if self.remat else stage_fn
            x = fn(blocks, x) if attention_mask is None else fn(blocks, x, attention_mask)

        ln_f_scale = self.param(
            "ln_f_scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (d,),
            self.param_dtype,
        )
        ln_f_bias = self.param(
            "ln_f_bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("embed",)),
            (d,),
            self.param_dtype,
        )
        x = _layernorm(x, ln_f_scale, ln_f_bias)

        if return_hidden:
            # Chunked-CE path: the loss contracts these against the vocab
            # matrix itself (GPTAdapter.chunked_components_from_hidden);
            # skipping the lm_head keeps [B,T,V] out of HBM. Init must run
            # with return_hidden=False so an untied lm_head still exists.
            return nn.with_logical_constraint(x, ("batch", "length", "act_embed"))

        if self.tie_embeddings:
            logits = token_embedding.attend(x)
        else:
            logits = nn.Dense(
                self.vocab_size,
                use_bias=False,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(dense_init, ("embed", "vocab")),
                name="lm_head",
            )(x)
        return nn.with_logical_constraint(logits, ("batch", "length", "act_vocab"))


@register_model("gpt_pipeline")
class PipelineGPTAdapter(ModelAdapter):
    """Adapter for the pipeline-parallel GPT.

    ``model.extra`` knobs: ``tokenizer`` ("gpt2"/"byte"/"bpe:<path>", as
    for gpt), ``pipeline_microbatches`` (default 4; per-data-shard batch
    must divide by it when pipeline > 1), ``pipeline_virtual_chunks``
    (interleaved schedule), and ``loss_impl``/``ce_chunk`` (chunked
    cross-entropy, as for gpt).
    """

    supports_pipeline = True
    known_extra_keys = frozenset(
        {
            "tokenizer",
            "loss_impl",
            "ce_chunk",
            "z_loss",
            "assume_packed",
            "n_kv_heads",
            "pipeline_microbatches",
            "pipeline_virtual_chunks",
            "sliding_window",
            "kv_cache_dtype",
        }
    )

    def build_model(self, cfg: RunConfig) -> nn.Module:
        vocab_size = cfg.model.vocab_size
        if vocab_size is None:
            tokenizer = self.build_tokenizer(cfg)
            vocab_size = getattr(tokenizer, "n_vocab", None)
            if not isinstance(vocab_size, int) or vocab_size <= 0:
                raise ValueError("tokenizer must expose a positive integer n_vocab")
        if cfg.model.dropout != 0.0:
            raise ValueError(
                "gpt_pipeline does not support dropout (v1); set model.dropout to 0.0"
            )
        if cfg.model.attention not in ("dense", "flash"):
            raise ValueError(
                f"gpt_pipeline supports attention 'dense' or 'flash', "
                f"got {cfg.model.attention!r}"
            )
        loss_impl = cfg.model.extra.get("loss_impl", "dense")
        if loss_impl == "fused_ce":
            # The Pallas kernel contracts hidden states held on the last
            # stage only; the pipeline loss runs inside the per-microbatch
            # scan where the kernel's custom_vjp is not wired. Fail loudly
            # rather than silently training something else.
            raise ValueError(
                "model.extra.loss_impl 'fused_ce' is not supported with "
                "pipeline parallelism; use 'chunked_ce'"
            )
        if loss_impl not in ("dense", "chunked_ce"):
            raise ValueError(
                f"model.extra.loss_impl {loss_impl!r} unknown; "
                "expected 'dense' or 'chunked_ce'"
            )
        z_loss = float(cfg.model.extra.get("z_loss", 0.0))
        if z_loss < 0.0:
            raise ValueError(f"model.extra.z_loss must be >= 0, got {z_loss}")
        n_kv_heads = int(cfg.model.extra.get("n_kv_heads", 0))
        if n_kv_heads < 0:
            raise ValueError(
                f"model.extra.n_kv_heads must be >= 0, got {n_kv_heads}"
            )
        if n_kv_heads and cfg.model.n_heads % n_kv_heads != 0:
            raise ValueError(
                f"model.n_heads ({cfg.model.n_heads}) must be divisible by "
                f"model.extra.n_kv_heads ({n_kv_heads})"
            )
        sliding_window = int(cfg.model.extra.get("sliding_window", 0))
        if sliding_window < 0:
            raise ValueError(
                f"model.extra.sliding_window must be >= 0, got {sliding_window}"
            )
        kv_cache_dtype = str(cfg.model.extra.get("kv_cache_dtype", "model"))
        if kv_cache_dtype not in ("model", "int8"):
            # Same config-time check as GPTAdapter: the pipeline model
            # never decodes, so a typo would otherwise surface only at
            # serve/generate conversion time, after the training run.
            raise ValueError(
                f"model.extra.kv_cache_dtype {kv_cache_dtype!r} unknown; "
                "expected 'model' or 'int8'"
            )
        return PipelineGPT(
            vocab_size=vocab_size,
            block_size=cfg.model.block_size,
            d_model=cfg.model.d_model,
            n_layers=cfg.model.n_layers,
            n_heads=cfg.model.n_heads,
            d_ff=cfg.model.d_ff,
            tie_embeddings=cfg.model.tie_embeddings,
            dtype=jnp.dtype(cfg.model.dtype),
            param_dtype=jnp.dtype(cfg.model.param_dtype),
            attention=cfg.model.attention,
            n_microbatches=self._positive_extra(cfg, "pipeline_microbatches", 4),
            remat=cfg.model.remat,
            n_virtual_chunks=self._positive_extra(cfg, "pipeline_virtual_chunks", 1),
            loss_impl=loss_impl,
            ce_chunk=self._positive_extra(cfg, "ce_chunk", 8192),
            z_loss=z_loss,
            assume_packed=bool(cfg.model.extra.get("assume_packed", False)),
            n_kv_heads=n_kv_heads,
            sliding_window=sliding_window,
            kv_cache_dtype=kv_cache_dtype,
        )

    def build_tokenizer(self, cfg: RunConfig) -> Any | None:
        from ..data.tokenizers import build_tokenizer

        return build_tokenizer(cfg.model.extra.get("tokenizer", "gpt2"))

    def batch_divisor(self, cfg: RunConfig, mesh: Any) -> int:
        """data_shards × microbatches on pipeline meshes: the row count
        every applied batch must divide by for gpipe_apply to engage."""
        from ..parallel.pipeline import BATCH_AXES, pipeline_degree

        if pipeline_degree(mesh) <= 1:
            return 1
        dp = math.prod(int(mesh.shape.get(a, 1)) for a in BATCH_AXES)
        return dp * self._positive_extra(cfg, "pipeline_microbatches", 4)

    def validate_mesh(self, cfg: RunConfig, mesh: Any) -> None:
        """Fail at startup (not at trace) when the training batch cannot
        engage the pipeline: global rows (micro_batch_size × data shards)
        divide by data_shards × microbatches iff microbatches divides
        micro_batch_size."""
        from ..parallel.pipeline import pipeline_degree

        m = self._positive_extra(cfg, "pipeline_microbatches", 4)
        if pipeline_degree(mesh) > 1 and cfg.trainer.micro_batch_size % m != 0:
            raise ValueError(
                f"trainer.micro_batch_size ({cfg.trainer.micro_batch_size}) "
                f"must be divisible by model.extra.pipeline_microbatches "
                f"({m}) on a pipeline mesh"
            )
        n_kv_heads = int(cfg.model.extra.get("n_kv_heads", 0))
        tp = int(mesh.shape.get("tensor", 1)) if mesh is not None else 1
        if n_kv_heads and tp > 1 and n_kv_heads % tp != 0:
            raise ValueError(
                f"model.extra.n_kv_heads ({n_kv_heads}) must be divisible "
                f"by the mesh tensor axis ({tp}) — K/V heads shard over "
                "tensor parallelism like query heads do"
            )

    def compute_loss_components(
        self,
        model: nn.Module,
        params: Params,
        batch: dict,
        *,
        rngs: dict[str, jax.Array] | None = None,
        deterministic: bool = True,
    ) -> tuple[jax.Array, jax.Array]:
        if getattr(model, "loss_impl", "dense") == "chunked_ce":
            from .gpt import GPTAdapter

            # Shared wiring point: nothing in the chunked path is
            # GPT-module-specific (apply(return_hidden=True) + contract
            # against the vocab matrix).
            return GPTAdapter._chunked_loss_components(
                model, params, batch, rngs=rngs, deterministic=deterministic
            )
        return lm_loss_components(
            model, params, batch, rngs=rngs, deterministic=deterministic
        )


__all__ = ["PipelineGPT", "PipelineGPTAdapter"]

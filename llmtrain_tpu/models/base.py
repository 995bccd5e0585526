"""Model-adapter plugin contract.

Parity target: reference ``src/llmtrain/models/base.py`` (ModelAdapter ABC
with build_model/build_tokenizer/compute_loss, :12-27), adapted to JAX's
functional split between module definition and parameters:

* ``build_model`` returns a Flax module (pure function of params + inputs).
* ``init_params`` is new — JAX params are explicit, not stored in the module.
* ``compute_loss`` takes ``(model, params, batch)`` and must be jit-traceable:
  shape/dtype validation happens at trace time (Python raises are fine there),
  and returned metrics are JAX scalars, not floats.
"""

from __future__ import annotations

from abc import ABC, abstractmethod
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.schemas import RunConfig

Params = Any  # PyTree of arrays
Batch = dict[str, jax.Array]
Metrics = dict[str, jax.Array]


class ModelAdapter(ABC):
    """Builds a Flax model + tokenizer and defines its training loss."""

    # Extra-dict keys this adapter understands (config/extras.py warns on
    # others). None disables the check for plugins with free-form extras.
    known_extra_keys: frozenset[str] | None = None

    # True only for models that stack their layer dim on the "layers"
    # logical axis so a mesh `pipeline` axis can shard stages
    # (models/gpt_pipeline.py). The Trainer rejects pipeline > 1 otherwise.
    supports_pipeline = False

    @abstractmethod
    def build_model(self, cfg: RunConfig) -> nn.Module:
        """Construct the (uninitialized) Flax module from config."""

    @abstractmethod
    def build_tokenizer(self, cfg: RunConfig) -> Any | None:
        """Construct the tokenizer, or None for models that need none."""

    def batch_divisor(self, cfg: RunConfig, mesh: Any) -> int:
        """Rows every applied batch must be a multiple of (default 1).

        Pipelined models return data_shards × microbatches: the Trainer
        pads eval batches up to this with zero-masked rows (exact under
        token-weighted aggregation) instead of silently dropping pipeline
        parallelism mid-eval.
        """
        return 1

    @staticmethod
    def _positive_extra(cfg: RunConfig, key: str, default: int | None) -> int | None:
        """Validated ``model.extra`` integer knob (>= 1), shared by adapters;
        a ``None`` default leaves an unset key unset."""
        value = cfg.model.extra.get(key, default)
        if value is None:
            return None
        value = int(value)
        if value < 1:
            raise ValueError(f"model.extra.{key} must be >= 1, got {value}")
        return value

    def init_params(self, model: nn.Module, cfg: RunConfig, rng: jax.Array) -> Params:
        """Initialize the parameter PyTree.

        Default: trace the module with a dummy ``(1, block_size)`` token batch.
        """
        tokens = jnp.zeros((1, cfg.model.block_size), dtype=jnp.int32)
        variables = model.init({"params": rng}, tokens, deterministic=True)
        return variables["params"]

    def compute_loss(
        self,
        model: nn.Module,
        params: Params,
        batch: Batch,
        *,
        rngs: dict[str, jax.Array] | None = None,
        deterministic: bool = True,
    ) -> tuple[jax.Array, Metrics]:
        """Pure loss function: ``(scalar loss, metrics dict of JAX scalars)``.

        Default derives the scalar from ``compute_loss_components`` (one
        forward, token-weighted mean). Adapters implement at least one of
        the two methods.
        """
        comps = self.compute_loss_components(
            model, params, batch, rngs=rngs, deterministic=deterministic
        )
        if comps is None:
            raise NotImplementedError(
                f"{type(self).__name__} must implement compute_loss or "
                "compute_loss_components"
            )
        loss_sum, tokens = comps
        loss = jnp.sum(loss_sum) / jnp.maximum(jnp.sum(tokens), 1.0)
        return loss, {"loss": loss}

    def compute_loss_components(
        self,
        model: nn.Module,
        params: Params,
        batch: Batch,
        *,
        rngs: dict[str, jax.Array] | None = None,
        deterministic: bool = True,
    ) -> tuple[jax.Array, jax.Array] | None:
        """Optional per-example ``(loss_sum, token_count)`` arrays of shape (B,).

        When an adapter implements this, the trainer derives the scalar loss
        as ``sum(loss_sum)/sum(token_count)`` and gets exact per-data-shard
        metrics (the ``*_rank_{r}`` keys, reference trainer.py:428-482) and
        token-weighted eval (reference trainer.py:243-289) from one forward.
        Returning None makes the trainer fall back to ``compute_loss``.
        """
        del model, params, batch, rngs, deterministic
        return None


def validate_lm_batch(batch: Batch) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """Trace-time validation shared by language-model adapters.

    Mirrors the reference's defensive checks (reference models/gpt.py:214-252):
    2-D input_ids/labels of equal shape, integer dtype, seq len >= 2, and an
    optional attention_mask matching input_ids.
    """
    input_ids = batch["input_ids"]
    labels = batch["labels"]
    attention_mask = batch.get("attention_mask")

    if input_ids.ndim != 2 or labels.ndim != 2:
        raise ValueError(
            f"Expected input_ids and labels to be 2D (B, T); "
            f"got {tuple(input_ids.shape)} and {tuple(labels.shape)}."
        )
    if input_ids.shape != labels.shape:
        raise ValueError(
            "Expected input_ids and labels to have the same shape; "
            f"got {tuple(input_ids.shape)} vs {tuple(labels.shape)}."
        )
    if not jnp.issubdtype(input_ids.dtype, jnp.integer) or not jnp.issubdtype(
        labels.dtype, jnp.integer
    ):
        raise ValueError(
            f"Expected integer input_ids and labels; got {input_ids.dtype} and {labels.dtype}."
        )
    if input_ids.shape[1] < 2:
        raise ValueError("Expected sequence length >= 2 for next-token loss.")

    if attention_mask is not None:
        if attention_mask.ndim != 2 or attention_mask.shape != input_ids.shape:
            raise ValueError(
                "Expected attention_mask to match input_ids shape; "
                f"got {tuple(attention_mask.shape)} vs {tuple(input_ids.shape)}."
            )
        if not (
            jnp.issubdtype(attention_mask.dtype, jnp.integer)
            or attention_mask.dtype == jnp.bool_
        ):
            raise ValueError(f"Expected bool or integer attention_mask; got {attention_mask.dtype}.")

    return input_ids, labels, attention_mask


def lm_loss_components(
    model: nn.Module,
    params: Params,
    batch: Batch,
    *,
    rngs: dict[str, jax.Array] | None = None,
    deterministic: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Shared LM forward → per-example (loss_sum, token_count).

    Honors the model's ``z_loss`` field when present (models/gpt.py).
    """
    input_ids, labels, attention_mask = validate_lm_batch(batch)
    logits = model.apply(
        {"params": params},
        input_ids,
        attention_mask=attention_mask,
        deterministic=deterministic,
        rngs=rngs,
    )
    return masked_ce_components(
        logits, labels, attention_mask, z_loss=getattr(model, "z_loss", 0.0)
    )


def masked_cross_entropy(
    logits: jax.Array, labels: jax.Array, attention_mask: jax.Array | None
) -> jax.Array:
    """Position-wise CE with mask-aware mean (reference gpt.py:256-269).

    Labels are already shifted by the data pipeline (reference hf_text.py:125),
    so no shift happens here.
    """
    loss_sum, tokens = masked_ce_components(logits, labels, attention_mask)
    return jnp.sum(loss_sum) / jnp.maximum(jnp.sum(tokens), 1.0)


def masked_ce_components(
    logits: jax.Array,
    labels: jax.Array,
    attention_mask: jax.Array | None,
    *,
    z_loss: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """Per-example ``(loss_sum, token_count)`` of shape (B,), CE in float32.

    ``z_loss > 0`` adds PaLM's softmax-normalizer regularizer
    ``z_loss * log(Z)^2`` per token (Z = sum exp(logits)) — keeps bf16
    logits from drifting large and the softmax well-conditioned. New
    capability over the reference (its loss is plain CE, gpt.py:256-269).
    """
    logits32 = logits.astype(jnp.float32)
    # One reduction serves both terms: CE = lse - logit[label], and the
    # z-loss reuses the same lse (mirrors ops/chunked_ce.py).
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)
    label_logit = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    per_token = lse - label_logit
    if z_loss > 0.0:
        per_token = per_token + z_loss * jnp.square(lse)
    if attention_mask is None:
        mask = jnp.ones_like(per_token)
    else:
        # BOOLEAN semantics (nonzero = real token): the mask may carry
        # segment ids > 1 for packed cross-document masking — they must
        # not become loss weights.
        mask = (attention_mask != 0).astype(jnp.float32)
    return jnp.sum(per_token * mask, axis=-1), jnp.sum(mask, axis=-1)

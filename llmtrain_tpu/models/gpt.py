"""Decoder-only GPT in Flax, designed mesh-first.

Parity target: reference ``src/llmtrain/models/gpt.py`` — learned token +
position embeddings (:127-128), pre-norm blocks (LN→attn→residual,
LN→MLP(GELU)→residual, :99-106), causal masking with padding-mask support
(:56-74), final LN + bias-free lm_head with optional weight tying (:142-146),
init N(0, 0.02) with residual projections scaled by 1/sqrt(2*n_layers)
(:151-165), block-size overflow raise (:41-42, :171-174), tiktoken gpt2
tokenizer + vocab sizing (:192-212), mask-aware CE loss (:214-271).

TPU-first divergences (the point of the rebuild):

* Every parameter carries *logical axis names* (``vocab``/``embed``/``heads``/
  ``kv``/``mlp``) via ``nn.with_logical_partitioning``, and activations carry
  ``nn.with_logical_constraint`` hints. Mapping logical names → mesh axes
  (data/fsdp/tensor/sequence) happens in ``llmtrain_tpu.parallel.sharding``,
  so the same module runs pure-DP, FSDP, TP, or SP without code changes.
* Attention is einsum-form with the softmax in float32 (bf16-safe on MXU);
  no (block_size, block_size) mask buffer is materialized as a parameter —
  the mask is built at trace time and fused by XLA.
* ``dtype``/``param_dtype`` split for bf16 compute over f32 master params.
* ``remat`` wraps blocks in ``nn.remat`` to trade FLOPs for HBM.
* ``attention='flash'`` routes to the Pallas kernel in ``llmtrain_tpu.ops``.
"""

from __future__ import annotations

import logging
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.activation_tiers import parse_activation_tiers
from ..config.schemas import RunConfig
from ..registry.models import register_model
from .activation_policy import (
    resolve_activation_tiers,
    tag_block_input,
    tier_block_classes,
)
from .base import (
    Batch,
    ModelAdapter,
    Params,
    lm_loss_components,
)

_EMBED_INIT = nn.initializers.normal(stddev=0.02)
_DENSE_INIT = nn.initializers.normal(stddev=0.02)

# model.extra.remat_policy values -> jax.checkpoint policies (None = the
# default: save nothing, recompute the whole block).
REMAT_POLICIES = {
    "nothing": None,
    "dots": jax.checkpoint_policies.dots_saveable,
    "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def _scaled_init(n_layers: int) -> nn.initializers.Initializer:
    """Residual-projection init, std 0.02/sqrt(2*n_layers) (reference :151-165)."""
    return nn.initializers.normal(stddev=0.02 / math.sqrt(2 * n_layers))


@jax.custom_vjp
def gelu_once(h: jax.Array) -> jax.Array:
    """The dense MLP's activation, exact (erf) GELU, which under a gradient
    is evaluated ONCE an element.

    Written plainly between two matmuls, ``nn.gelu`` looks cheap to the
    compiler, which keeps only the pre-activation ``h`` and puts the whole
    erf polynomial back into each consumer: the input of ``mlp_proj``'s
    forward product, the input of its dW product, and (with the density's
    ``exp``) the derivative in the dX product. Each of those three fusions
    is then bound by the vector unit at about 2.8 times its matmul's time
    (``docs/perf.md``, "An activation between two matmuls").

    Not differentiated (prefill, decode, eval) this is ``nn.gelu`` itself,
    so those programs stay what they were.

    Reverse mode only: a ``jax.custom_vjp`` has no forward-mode rule, so
    ``jax.jvp``, ``jacfwd``, ``hessian`` and ``linearize`` through a dense
    MLP raise ``TypeError``. Nothing in the package differentiates a model
    forwards; whoever needs to can patch ``nn.gelu`` back in, as the parity
    tests do.
    """
    return nn.gelu(h, approximate=False)


def _gelu_once_fwd(h: jax.Array):
    # One erf, in float32 whatever the compute dtype, gives both the value
    # and the derivative Phi(h) + h phi(h); each is rounded once. The barrier
    # makes the pair arrays in memory, so that no consumer recomputes them
    # from ``h``; the compiler places the evaluation in ``mlp_fc``'s own
    # fusion (tests/test_tpu_aot_compile.py::TestMLPActivationEvaluatedOnce).
    # ``erf`` and not the primal's ``erfc``: a float32 ``erf`` reaches the
    # TPU back end as one instruction, where ``erfc`` is expanded early into
    # a two-branch polynomial the compiler then splits over two fusions with
    # a float32 array between them.
    h32 = h.astype(jnp.float32)
    cdf = 0.5 * (1.0 + jax.lax.erf(h32 * math.sqrt(0.5)))
    pdf = jnp.exp(-0.5 * h32 * h32) * (1.0 / math.sqrt(2.0 * math.pi))
    return jax.lax.optimization_barrier(
        ((h32 * cdf).astype(h.dtype), (cdf + h32 * pdf).astype(h.dtype))
    )


def _gelu_once_bwd(g: jax.Array, da: jax.Array):
    return (da * g,)


gelu_once.defvjp(_gelu_once_fwd, _gelu_once_bwd)


class RowsDenseGeneral(nn.Module):
    """``nn.DenseGeneral`` over trailing input axes with the product formed
    as ONE matrix product: the same parameters (``kernel`` of shape
    ``(*inputs, *features)``, ``bias`` of shape ``features``, initialised
    value for value as the stock module initialises them), the contracted
    axes and the feature axes each merged for the product and the bias add,
    and the output in the stock module's shape, ``(..., *features)``.

    What differs is the shape the compiler sees. Handed a product, or a bias
    add, of shape ``(B, T, 3, H, 64)`` it lays T on the lanes (64 would
    half-fill them), and every consumer that wants rows of ``H * 64``
    values, the attention kernels among them, pays a transposing copy of the
    activation. Handed ``(B, T, 3*H*64)`` it keeps rows, and a reshape on
    either side moves nothing.
    """

    features: int | tuple[int, ...]
    axis: int | tuple[int, ...] = -1
    use_bias: bool = True
    dtype: Any = None
    param_dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()
    bias_init: Any = nn.initializers.zeros_init()
    dot_general: Any = None

    @nn.compact
    def __call__(self, inputs: jax.Array) -> jax.Array:
        features = (self.features,) if isinstance(self.features, int) else tuple(self.features)
        axis = (self.axis,) if isinstance(self.axis, int) else tuple(self.axis)
        kept = inputs.ndim - len(axis)
        if tuple(a % inputs.ndim for a in axis) != tuple(range(kept, inputs.ndim)):
            raise ValueError(f"axis {self.axis} is not the trailing axes of {inputs.shape}")
        in_shape = inputs.shape[kept:]

        def flat(init, flat_shape):
            # As nn.DenseGeneral: initialise flat, then shape (so fan-in and
            # fan-out are the matrix's), keeping the partitioning's names.
            def wrap(rng, shape, dtype=jnp.float32):
                value = init(rng, flat_shape, dtype)
                if isinstance(value, nn.meta.AxisMetadata):
                    return nn.meta.replace_boxed(value, jnp.reshape(value.unbox(), shape))
                return jnp.reshape(value, shape)

            return wrap

        k, f = math.prod(in_shape), math.prod(features)
        kernel = self.param(
            "kernel", flat(self.kernel_init, (k, f)), in_shape + features, self.param_dtype
        )
        bias = (
            self.param("bias", flat(self.bias_init, (f,)), features, self.param_dtype)
            if self.use_bias
            else None
        )
        inputs, kernel, bias = nn.dtypes.promote_dtype(inputs, kernel, bias, dtype=self.dtype)
        rows = inputs.reshape(inputs.shape[:kept] + (k,))
        out = (self.dot_general or jax.lax.dot_general)(
            rows, kernel.reshape(k, f), (((kept,), (0,)), ((), ()))
        )
        if bias is not None:
            out = out + bias.reshape(f)
        return out.reshape(inputs.shape[:kept] + features)


def scaled(x: jax.Array, scale: float) -> jax.Array:
    """``x * scale`` computed in float32 and cast back (a bf16 multiplier
    would carry its own rounding into every element); 1.0 returns ``x``.
    For muP multipliers (models/falcon_h1.py)."""
    if scale == 1.0:
        return x
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


logger = logging.getLogger(__name__)

_TIER_MIGRATION_LOGGED = False


def _log_tier_migration(remat_policy: str, spec: str) -> None:
    """One-time (per process) log naming the remat->tiers migration."""
    global _TIER_MIGRATION_LOGGED
    if not _TIER_MIGRATION_LOGGED:
        _TIER_MIGRATION_LOGGED = True
        logger.info(
            "model.remat: true is deprecated; mapped remat_policy %r to "
            "model.extra.activation_tiers: %r (set activation_tiers "
            "directly to silence this)",
            remat_policy,
            spec,
        )


# Deprecation shim: `model.remat: true` maps onto the tier that keeps its
# remat_policy semantics ("dots_no_batch" has no tier — it stays on the
# legacy module remat path).
_REMAT_POLICY_TO_TIER = {"nothing": "full", "dots": "selective"}


def resolve_config_activation_tiers(cfg: RunConfig) -> tuple[str, ...] | None:
    """Per-layer activation tiers for ``cfg``, backend-resolved.

    Explicit ``model.extra.activation_tiers`` wins (and conflicts with
    ``model.remat: true``); the deprecated ``model.remat: true`` migrates
    to an equivalent all-layers tier with a one-time INFO log. Returns
    None when the model should use the legacy remat fields (remat off, or
    remat_policy ``dots_no_batch``).
    """
    spec = cfg.model.extra.get("activation_tiers")
    if spec is not None:
        if cfg.model.remat:
            raise ValueError(
                "model.remat: true conflicts with model.extra."
                "activation_tiers; drop model.remat (tiers subsume it)"
            )
        tiers = parse_activation_tiers(str(spec), cfg.model.n_layers)
        return resolve_activation_tiers(tiers)
    if cfg.model.remat:
        remat_policy = str(cfg.model.extra.get("remat_policy", "nothing"))
        tier = _REMAT_POLICY_TO_TIER.get(remat_policy)
        if tier is None:
            return None
        _log_tier_migration(remat_policy, f"{tier}:*")
        return (tier,) * cfg.model.n_layers
    return None


class FusedLayerNorm(nn.Module):
    """nn.LayerNorm twin backed by the Pallas fused kernel
    (ops/fused_norm.py). Same parameter names (``scale``/``bias``),
    shapes, and logical partitioning — checkpoints are interchangeable
    with the unfused path. The optional ``residual`` argument fuses the
    preceding residual add into the same VMEM pass and returns
    ``(normed, summed)``."""

    dtype: Any
    param_dtype: Any
    epsilon: float = 1e-6
    interpret: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, residual: jax.Array | None = None):
        from ..ops.fused_norm import fused_add_layer_norm, fused_layer_norm

        d = x.shape[-1]
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (d,),
            self.param_dtype,
        )
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("embed",)),
            (d,),
            self.param_dtype,
        )
        x = x.astype(self.dtype)
        if residual is None:
            return fused_layer_norm(
                x, scale, bias, self.epsilon, 256, self.interpret
            )
        return fused_add_layer_norm(
            x,
            residual.astype(self.dtype),
            scale,
            bias,
            self.epsilon,
            256,
            self.interpret,
        )


def paged_block_fold(block_tokens: int, width: int) -> int:
    """How many positions of a block share one row of a paged pool leaf.

    A leaf is ``(num_blocks, block_tokens // fold, fold * width)`` with
    ``width = kv_width * head_dim`` (``_paged_decode_attention``). ``fold``
    is the smallest divisor of ``block_tokens`` that makes a row at least
    one 128-lane tile wide, and ``block_tokens`` where none does: 1 for
    every model with ``width >= 128``, 2 for MQA with a 64-wide head.
    """
    for fold in range(1, block_tokens):
        if block_tokens % fold == 0 and fold * width >= 128:
            return fold
    return block_tokens


# Query rows a gathered position may meet in the flat-row form of the
# paged read (``paged_kv_form``): t x heads of one call.
PAGED_ROWS_QUERY_LIMIT = 256


def paged_kv_form(*, t: int, n_heads: int, kv_heads: int, head_dim: int, block_tokens: int) -> str:
    """Which form ``_paged_decode_attention`` contracts the gathered blocks
    in, from the call's static shapes alone: ``"rows"`` or ``"heads"``.

    ``"rows"``: the gathered blocks stay the pool's own rows, ``(batch,
    gathered, kv_heads * head_dim)``, and every query head is spread over
    the row with zeros off its K/V head's lanes. Nothing of the gathered
    leaf's size is written again, at ``kv_heads`` times the products'
    FLOPs. ``"heads"``: the gathered blocks are re-tiled ``(batch,
    gathered, kv_heads, head_dim)`` for a per-head product; with
    ``kv_heads`` on the sublanes and a head narrower than a lane tile the
    compiler writes the leaf again at up to 2.67 times its size
    (``gpt2-small``'s 12 heads of 64) before any score is formed.

    What the re-tile costs is bytes a gathered position, whatever ``t``;
    what the zeros cost is MXU passes a gathered position, ``t * n_heads``
    query rows against each row of keys. A decode call (t = 1, every
    slot's whole table) is bound by the bytes; a prefill or chunk call
    (one prompt of hundreds of positions) by the passes, and its re-tile
    is one row's table. The switch is ``PAGED_ROWS_QUERY_LIMIT`` query
    rows; the batch and the table's width scale both costs alike (PERF.md
    section 6, PR 47, has the chip's readings on both sides). Where a pool
    row holds several positions (``paged_block_fold`` > 1: a row under one
    lane tile) the gathered blocks are not ``(batch, gathered, width)``
    for free, and the per-head form stays.
    """
    if paged_block_fold(block_tokens, kv_heads * head_dim) > 1:
        return "heads"
    return "rows" if t * n_heads <= PAGED_ROWS_QUERY_LIMIT else "heads"


def model_paged_kv_form(model: Any, *, t: int) -> str:
    """``paged_kv_form`` of a call of ``t`` tokens a row of a model whose
    attention layers are ``CausalSelfAttention`` (GPT, Llama, Falcon-H1),
    from the model's own fields: what serving/engine.py writes on a decode
    call's ``serve/engine.stage`` span as ``kv_form``."""
    return paged_kv_form(
        t=t, n_heads=model.n_heads, kv_heads=model.n_kv_heads or model.n_heads,
        head_dim=getattr(model, "head_dim", 0) or model.d_model // model.n_heads,
        block_tokens=model.paged_block_tokens,
    )


def paged_pool_writer(pos: jax.Array, block_tables: jax.Array, block_tokens: int, width: int):
    """``write(pool, rows)``: the ``(B, t, width)`` rows of this call's
    tokens, at absolute positions ``pos`` (B, t), set into a pool leaf
    ``(num_blocks, block_tokens // fold, fold * width)`` through the rows'
    block tables. Every paged leaf, whatever its row holds (K or V of
    ``kv_heads`` heads; one latent row, models/latent_moe.py), is written
    through this."""
    fold = paged_block_fold(block_tokens, width)
    blocks = jnp.take_along_axis(block_tables, pos // block_tokens, axis=1)  # (B, t)
    slots = pos % block_tokens
    # Distinct rows hold disjoint physical blocks (allocator invariant),
    # so the only duplicate targets are padded rows' null-block writes —
    # garbage nothing live ever reads.
    if fold == 1:
        # One position a row: whole-row scatter, in place on the pool.
        def write(pool: jax.Array, rows: jax.Array) -> jax.Array:
            return pool.at[blocks, slots].set(rows)

    else:
        # `fold` positions a row: slot s is the `width` lanes from
        # (s % fold) * width of row s // fold, written as a window.
        # The TPU compiler turns a windowed scatter into a loop of one
        # in-place update a token (a whole-row scatter it runs as one
        # op), so only rows narrower than a lane tile come here.
        where = jnp.stack(
            [blocks, slots // fold, (slots % fold) * width], axis=-1
        )
        window = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(2,),
            inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2),
        )

        def write(pool: jax.Array, rows: jax.Array) -> jax.Array:
            return jax.lax.scatter(pool, where, rows, window)

    return write


class CausalSelfAttention(nn.Module):
    d_model: int
    n_heads: int
    n_layers: int
    dropout: float
    dtype: Any
    param_dtype: Any
    attention: str = "dense"
    decode: bool = False  # autoregressive KV-cache mode (generation only)
    cache_len: int = 0  # KV-cache capacity; block_size when decode=True
    # Grouped-query attention: K/V heads (0 = n_heads, classic MHA; 1 =
    # MQA). Queries in group g attend the shared K/V head g. The flash
    # path consumes narrow K/V natively (the Pallas kernels index K/V by
    # head group — no jnp.repeat in HBM, the training-bandwidth win); the
    # decode cache stores only n_kv_heads (the serving-memory win);
    # ring/ulysses/dense broadcast K/V up to n_heads before attention.
    # n_kv_heads == n_heads keeps the MHA fused-qkv parameter tree
    # (checkpoint compatibility).
    n_kv_heads: int = 0
    # Data is guaranteed packed (all-ones masks): drop the mask operand
    # from the flash kernels — identical math, no mask streaming.
    assume_packed: bool = False
    # Llama-family knobs (models/llama.py): bias-free projections and
    # rotary position embeddings (ops/rope.py). RoPE rotates q/k after
    # projection — at decode time inside ``_decode_attention`` so the
    # rotation uses absolute positions from the cache cursor BEFORE the
    # keys are written (cached keys are stored rotated; queries at later
    # steps then compare directly). GPT defaults leave both off.
    use_bias: bool = True
    # Qwen2-style bias split (models/qwen2.py): bias on the q/k/v
    # projections only, out_proj follows ``use_bias``. None = q/k/v
    # follow ``use_bias`` too (GPT fully biased, Llama fully bias-free).
    qkv_bias: bool | None = None
    rope: bool = False
    rope_theta: float = 10000.0
    # Sliding-window attention (Mistral semantics: query i attends keys in
    # (i-window, i]). 0 = full causal. Supported on the dense/flash/decode
    # paths; ring/ulysses reject it loudly (a windowed ring schedule is a
    # different algorithm — most hops would carry dead shards).
    sliding_window: int = 0
    # Extra rolling-cache slots beyond the window (decode only).
    # Speculative decoding (speculative.py) writes up to gamma+1 positions
    # that may be ROLLED BACK; in a W-slot ring those writes would evict
    # live window entries rollback cannot restore. With W+gamma+1 slots
    # every evicted position is provably outside all future queries'
    # windows (evicted = p - C <= row - W).
    ring_slack: int = 0
    # KV-cache storage dtype (decode only): "model" keeps the compute
    # dtype; "int8" stores codes + one f32 scale per written (batch,
    # position, kv-head) — amax over head_dim — halving cache HBM vs
    # bf16 (4x vs f32). Long-generation serving memory is KV-bound, so
    # this is the cache-side sibling of weight-only quantization
    # (ops/quant.py). Dequant happens in-graph at the attention read;
    # XLA fuses it into the score einsum's operand load. Speculative
    # rollback (cursor-only) is unaffected: rolled-back slots are
    # simply rewritten, codes and scales together.
    kv_cache_dtype: str = "model"
    # Paged decode (serving/paged_kv.py): the cache is a POOL of
    # fixed-size blocks shared by every in-flight sequence instead of a
    # per-row linear buffer. The caller passes per-row absolute positions
    # and a block table mapping logical block i -> physical pool block;
    # N sequences of different lengths then share ONE jitted program
    # (continuous batching, vLLM's PagedAttention layout). Batch size
    # never shapes the cache, so join/evict needs no cache reshuffle.
    paged: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0
    # Quantized training matmuls (ops/quant.py, model.extra.matmul_precision):
    # "int8"/"int8_act"/"fp8" route every projection through
    # quant_dot_general — straight-through gradients, f32 master weights,
    # unchanged param tree. "f32" keeps the stock flax path bit-identical.
    matmul_precision: str = "f32"
    # Width of one head where it is not d_model / n_heads (models whose
    # attention is narrower than the residual stream: models/falcon_h1.py).
    # 0 = d_model // n_heads.
    head_dim: int = 0
    # Keys are multiplied by this after their projection, before RoPE and
    # before they are cached (a muP key multiplier). 1.0 = no multiply.
    key_scale: float = 1.0

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        attention_mask: jax.Array | None = None,
        *,
        deterministic: bool = True,
        positions: jax.Array | None = None,
        block_tables: jax.Array | None = None,
    ) -> jax.Array:
        head_dim = self.head_dim or self.d_model // self.n_heads
        kv_heads = self.n_kv_heads or self.n_heads
        qkv_use_bias = self.use_bias if self.qkv_bias is None else self.qkv_bias
        if self.sliding_window and self.attention in ("ring", "ulysses"):
            raise ValueError(
                f"sliding_window is not supported with attention="
                f"{self.attention!r}; use 'flash' or 'dense'"
            )
        # None under "f32": the stock flax dot path, bit-identical to a
        # build without the knob (ops/quant.quant_dot_general contract).
        from ..ops.quant import quant_dot_general

        quant_dg = quant_dot_general(self.matmul_precision)
        # The attention kernels take and give rows of H * head_dim values:
        # on their branch the projections' products keep that shape (the
        # same parameters; every other branch's program is as it was).
        # A mesh that splits the heads over devices keeps the stock
        # products: a merged 3*H*D axis sharded inside its H factor is
        # nothing GSPMD can say, and the kernels' shard_map hands each
        # device its own heads either way.
        from ..parallel.sharding import ambient_mesh

        mesh = ambient_mesh()
        heads_split = mesh is not None and mesh.shape.get("tensor", 1) > 1
        rows = self.attention == "flash" and not self.decode and not heads_split
        dense = RowsDenseGeneral if rows else nn.DenseGeneral

        if kv_heads == self.n_heads:
            qkv = dense(
                features=(3, self.n_heads, head_dim),
                axis=-1,
                use_bias=qkv_use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "qkv", "heads", "kv")),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("qkv", "heads", "kv")
                ),
                dot_general=quant_dg,
                name="qkv_proj",
            )(x)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            if self.n_heads % kv_heads != 0:
                raise ValueError(
                    f"n_heads ({self.n_heads}) must be divisible by "
                    f"n_kv_heads ({kv_heads})"
                )
            q = dense(
                features=(self.n_heads, head_dim),
                axis=-1,
                use_bias=qkv_use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "heads", "kv")),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("heads", "kv")
                ),
                dot_general=quant_dg,
                name="q_proj",
            )(x)
            kv = dense(
                features=(2, kv_heads, head_dim),
                axis=-1,
                use_bias=qkv_use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "qkv", "heads", "kv")),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("qkv", "heads", "kv")
                ),
                dot_general=quant_dg,
                name="kv_proj",
            )(x)
            k, v = kv[:, :, 0], kv[:, :, 1]
        k = scaled(k, self.key_scale)
        q = nn.with_logical_constraint(q, ("batch", "length", "act_heads", "act_kv"))
        k = nn.with_logical_constraint(k, ("batch", "length", "act_heads", "act_kv"))
        v = nn.with_logical_constraint(v, ("batch", "length", "act_heads", "act_kv"))

        if self.rope and not self.decode:
            # Global-view positions: under sequence parallelism pjit keeps
            # the arange consistent with the length-sharded activations.
            # Rotating before the GQA broadcast/attention impls is exact —
            # RoPE is per-(position, feature), independent of head layout.
            # The decode path rotates inside _decode_attention, offset by
            # the cache cursor.
            from ..ops.rope import apply_rope

            q, k = apply_rope(
                q, k, jnp.arange(q.shape[1]), theta=self.rope_theta
            )

        if (
            not self.decode
            and kv_heads != self.n_heads
            and self.attention == "dense"
        ):
            # Only dense sees full-width K/V (compute-equivalent GQA).
            # Flash consumes narrow K/V natively (Pallas index maps), ring
            # rotates the narrow shards, ulysses exchanges them narrow
            # (G x less wire traffic in each case — blockwise groups
            # queries in its einsums), and the decode path keeps the
            # narrow cache, broadcasting at read.
            reps = self.n_heads // kv_heads
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)

        if self.decode and self.paged:
            # Paged KV decode: block-pool cache shared across sequences,
            # per-row positions/block tables (continuous batching serving).
            out = self._paged_decode_attention(q, k, v, positions, block_tables)
        elif self.decode:
            # KV-cache decode: append this call's keys/values at the cache
            # cursor, attend over the filled prefix. One compiled program
            # serves both prefill (T = prompt length) and per-token steps
            # (T = 1) — new capability over the reference, whose notebook
            # generation re-runs the full forward per token.
            out = self._decode_attention(q, k, v)
        elif self.attention == "flash":
            # Padding masks are applied INSIDE attention (reference
            # gpt.py:60-64 semantics) — the Pallas kernels take the (B, T)
            # key mask directly. assume_packed drops the operand when the
            # data is provably packed (all-ones masks ≡ no mask).
            # Where q, k and v are still the one projection's output as it
            # was made (no K/V grouping, no rotation, no key multiplier),
            # the kernels take that array whole: they read the three out of
            # it in place and hand back one gradient for it, so no slice,
            # transpose or concatenation of an activation stands between
            # the projections' matmuls and the kernels.
            from ..ops.flash_attention import flash_attention, flash_attention_qkv

            key_mask = None if self.assume_packed else attention_mask
            if kv_heads == self.n_heads and not self.rope and self.key_scale == 1.0:
                out = flash_attention_qkv(
                    nn.with_logical_constraint(
                        qkv, ("batch", "length", None, "act_heads", "act_kv")
                    ),
                    attention_mask=key_mask,
                    window=self.sliding_window,
                )
            else:
                out = flash_attention(
                    q, k, v, attention_mask=key_mask, causal=True,
                    window=self.sliding_window,
                )
        elif self.attention == "ring":
            # Sequence-parallel exact attention over the mesh's `sequence`
            # axis (ops/ring_attention.py); falls back to blockwise when no
            # ambient mesh shards the sequence. Padding masks are applied
            # inside attention here too (the mask shard rotates with its
            # K/V shard); assume_packed drops the operand like flash.
            from ..ops.ring_attention import ring_or_blockwise

            out = ring_or_blockwise(
                q, k, v,
                causal=True,
                key_mask=None if self.assume_packed else attention_mask,
            )
        elif self.attention == "ulysses":
            # All-to-all sequence parallelism (ops/ulysses_attention.py):
            # the ring alternative — 2 all-to-alls instead of s ppermutes.
            # The mask arrives full-sequence on every device (replicated
            # by the shard_map in_spec — no runtime gather).
            from ..ops.ulysses_attention import ulysses_or_blockwise

            out = ulysses_or_blockwise(
                q, k, v,
                causal=True,
                key_mask=None if self.assume_packed else attention_mask,
            )
        else:
            out = dense_attention(
                q,
                k,
                v,
                attention_mask=attention_mask,
                dropout=self.dropout,
                deterministic=deterministic,
                dropout_rng_module=self,
                window=self.sliding_window,
            )

        out = dense(
            features=self.d_model,
            axis=(-2, -1),
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                _scaled_init(self.n_layers), ("heads", "kv", "embed")
            ),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), ("embed",)),
            dot_general=quant_dg,
            name="out_proj",
        )(out)
        out = nn.Dropout(self.dropout)(out, deterministic=deterministic)

        if attention_mask is not None:
            # Zero padded rows so they contribute nothing downstream
            # (reference gpt.py:73-74). Boolean compare: the mask may
            # carry segment ids > 1 (packed cross-document masking).
            out = out * (attention_mask != 0)[:, :, None].astype(out.dtype)
        return out

    def _decode_attention(self, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
        """Cached causal attention: write k/v at the cursor, read the prefix.

        q/k/v: (B, T, H, Dh) with T = tokens appended this call. The cache
        holds ``cache_len`` positions — or, under a sliding window, a
        ROLLING buffer of ``min(cache_len, window)`` slots (the Mistral
        serving layout): slot ``pos % C`` holds position ``pos``, so
        per-layer KV memory is O(window) however long the generation. A
        per-slot position buffer (stored as position+1 so the zero-init
        cache means "empty") drives the mask instead of slot order.

        Rolling-prefill caveat: a prompt longer than the window writes
        only its last C keys, so logits at INTERIOR prefill positions
        (whose windows reach dropped keys) are approximate — harmless for
        generation, which samples from the final position only; its
        window is exactly the kept set. Rows must share one sequence
        length (generation batches rectangular prompts,
        generation.py:111-120).
        """
        if self.cache_len <= 0:
            raise ValueError("decode=True requires cache_len > 0 (the block size)")
        if self.kv_cache_dtype not in ("model", "int8"):
            raise ValueError(
                f"kv_cache_dtype {self.kv_cache_dtype!r} unknown; expected "
                "'model' or 'int8'"
            )
        quant_cache = self.kv_cache_dtype == "int8"
        batch, t, n_heads, head_dim = q.shape
        kv_width = k.shape[2]  # n_kv_heads under GQA, else n_heads
        ring = (self.sliding_window + self.ring_slack) if self.sliding_window else 0
        rolling = bool(ring) and ring < self.cache_len
        cap = ring if rolling else self.cache_len
        cached_key = self.variable(
            "cache",
            "cached_key",
            jnp.zeros,
            (batch, cap, kv_width, head_dim),
            jnp.int8 if quant_cache else k.dtype,
        )
        cached_value = self.variable(
            "cache",
            "cached_value",
            jnp.zeros,
            (batch, cap, kv_width, head_dim),
            jnp.int8 if quant_cache else v.dtype,
        )
        if quant_cache:
            # One f32 scale per written (batch, slot, kv-head); zero on
            # never-written slots (dequantizes to 0.0, and the liveness
            # mask excludes those slots anyway).
            key_scale = self.variable(
                "cache", "key_scale", jnp.zeros,
                (batch, cap, kv_width, 1), jnp.float32,
            )
            value_scale = self.variable(
                "cache", "value_scale", jnp.zeros,
                (batch, cap, kv_width, 1), jnp.float32,
            )

            def _q8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
                # ONE quantization recipe in the package: the weight
                # quantizer's math, reduced over head_dim per position.
                from ..ops.quant import quantize_array

                qa = quantize_array(x, reduce_axes=(x.ndim - 1,))
                return qa.q, qa.scale

        cache_index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )

        idx = cache_index.value
        if self.rope:
            # Rotate by absolute position BEFORE the cache write: the
            # cache then holds rotated keys, and later steps' queries
            # (rotated by their own positions) compare directly.
            from ..ops.rope import apply_rope

            q, k = apply_rope(
                q, k, idx + jnp.arange(t), theta=self.rope_theta
            )
        if rolling:
            # Slot position+1 per slot; 0 = never written (zero-init safe —
            # generation.py zeroes the cache tree from an eval_shape trace).
            cached_pos1 = self.variable(
                "cache", "cached_pos1", jnp.zeros, (cap,), jnp.int32
            )
            # Only the LAST `cap` tokens of this call can survive the ring;
            # t and cap are static, so this is a static slice. Writing at
            # most `cap` tokens keeps the scatter indices duplicate-free.
            keep = min(t, cap)
            pos = idx + t - keep + jnp.arange(keep)  # absolute positions kept
            slots = pos % cap
            if quant_cache:
                kc, ks = _q8(k[:, t - keep :])
                vc, vs = _q8(v[:, t - keep :])
                cached_key.value = cached_key.value.at[:, slots].set(kc)
                cached_value.value = cached_value.value.at[:, slots].set(vc)
                key_scale.value = key_scale.value.at[:, slots].set(ks)
                value_scale.value = value_scale.value.at[:, slots].set(vs)
            else:
                cached_key.value = cached_key.value.at[:, slots].set(
                    k[:, t - keep :].astype(cached_key.value.dtype)
                )
                cached_value.value = cached_value.value.at[:, slots].set(
                    v[:, t - keep :].astype(cached_value.value.dtype)
                )
            cached_pos1.value = cached_pos1.value.at[slots].set(pos + 1)
            col_pos = cached_pos1.value - 1  # (C,): -1 = empty slot
        else:
            if quant_cache:
                kc, ks = _q8(k)
                vc, vs = _q8(v)
                cached_key.value = jax.lax.dynamic_update_slice(
                    cached_key.value, kc, (0, idx, 0, 0)
                )
                cached_value.value = jax.lax.dynamic_update_slice(
                    cached_value.value, vc, (0, idx, 0, 0)
                )
                key_scale.value = jax.lax.dynamic_update_slice(
                    key_scale.value, ks, (0, idx, 0, 0)
                )
                value_scale.value = jax.lax.dynamic_update_slice(
                    value_scale.value, vs, (0, idx, 0, 0)
                )
            else:
                cached_key.value = jax.lax.dynamic_update_slice(
                    cached_key.value, k.astype(cached_key.value.dtype), (0, idx, 0, 0)
                )
                cached_value.value = jax.lax.dynamic_update_slice(
                    cached_value.value, v.astype(cached_value.value.dtype), (0, idx, 0, 0)
                )
            col_pos = None
        cache_index.value = idx + t

        keys, values = cached_key.value, cached_value.value
        if quant_cache:
            # In-graph dequant: XLA streams the int8 codes from HBM (the
            # bandwidth win) and fuses convert+multiply into the einsum
            # operand reads.
            keys = (keys.astype(jnp.float32) * key_scale.value).astype(q.dtype)
            values = (values.astype(jnp.float32) * value_scale.value).astype(
                q.dtype
            )
        scale = 1.0 / math.sqrt(head_dim)
        # Grouped-query decode (g=1 is classic MHA): the cache holds
        # n_kv_heads (the memory win) and stays narrow at read too —
        # queries are grouped against the shared K/V heads, so the
        # per-step HBM read is G x smaller than broadcasting the cache
        # (query head k*G+g attends kv head k, matching jnp.repeat
        # semantics).
        g = n_heads // kv_width
        qg = q.reshape(batch, t, kv_width, g, head_dim)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, keys) * scale
        scores = scores.astype(jnp.float32)
        row = (idx + jnp.arange(t))[None, None, None, :, None]
        if rolling:
            # Mask by each slot's ABSOLUTE position (slot order is ring
            # order, not sequence order): live iff written, causal, and
            # within the window.
            col = col_pos[None, None, None, None, :]
            live = (col >= 0) & (col <= row) & (row - col < self.sliding_window)
        else:
            # Query at absolute position idx+i may see cache slots <= idx+i
            # (and, under a window >= cache_len, the window constraint —
            # kept for exactness even though it can only bind when the
            # model's block_size exceeds the window).
            col = jnp.arange(cap)[None, None, None, None, :]
            live = col <= row
            if self.sliding_window:
                live = live & (row - col < self.sliding_window)
        scores = jnp.where(live, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, values)
        return out.reshape(batch, t, n_heads, head_dim)

    def _paged_decode_attention(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        positions: jax.Array | None,
        block_tables: jax.Array | None,
    ) -> jax.Array:
        """Block-pool cached attention (continuous batching serving).

        The cache is a pool of ``paged_num_blocks`` blocks of
        ``paged_block_tokens`` positions each, SHARED by every in-flight
        sequence — batch size never shapes the cache, so sequences can
        join/leave the batch without a cache reshuffle. ``block_tables``
        (B, max_blocks) maps row b's logical block i to a physical pool
        block (the host-side free-list allocator in serving/paged_kv.py
        owns the mapping; physical block 0 is the reserved null block
        padded table entries point at). ``positions`` (B,) is each row's
        absolute position of the FIRST token in this call; rows at
        different depths coexist in one program — the continuous-batching
        primitive the linear cursor cache cannot express (its cursor is
        one scalar for the whole batch).

        Token t of row b writes K/V at pool[table[b, p//bt], p%bt] with
        p = positions[b]+t, then attends the gathered blocks masked by
        absolute position (col <= p) — the same liveness rule as the
        linear path, so outputs match single-sequence decode.

        **Pool layout.** A leaf is ``(num_blocks, bt // fold, fold *
        width)``, ``width = kv_width * head_dim`` and ``fold`` from
        :func:`paged_block_fold`: a block's positions in order, each one's
        heads flattened, so a block is ``bt * width`` contiguous values
        and slot ``s`` sits in row ``s // fold`` at lane ``(s % fold) *
        width``. The minor dimension must be lane-dense (at least one
        128-lane tile). The TPU compiler tiles the two minor dimensions
        8 x 128; given a leaf whose minor dimension is half a tile
        (``(nb, bt, kv_width, 64)``) it keeps the leaf in HBM with
        ``num_blocks`` minor, and every prefill, decode and verify
        program then transposes the WHOLE pool to write it, again to
        return it and again to gather it. With a lane-dense row the
        layout the compiler picks is the row-major one that the scatter,
        the block-table gather and the engine's COW copy use, and the
        donated input aliases the output in it
        (``tests/test_tpu_aot_compile.py`` holds that at the benchmark's
        pool shapes).

        **The read.** ``pool[block_tables]`` is ``(B, blocks, bt // fold,
        fold * width)``. :func:`paged_kv_form` picks, from the call's
        static shapes, how q meets it. A decode or verify call
        (``"rows"``) sees it as ``(B, s, width)``, which moves nothing,
        and spreads each query head over the row; no array of the
        gathered leaf's size is written again
        (``tests/test_tpu_aot_compile.py::
        TestDecodeReadsGatheredBlocksAsRows``). A prefill or chunk call,
        and any call on a pool whose rows fold positions, re-tiles what it
        gathered per head (``"heads"``): a relayout of one row's table.
        Both give the same sums: float32 accumulation, the same roundings
        of scores and ``probs``.
        """
        if positions is None or block_tables is None:
            raise ValueError(
                "paged decode requires the `positions` (B,) and "
                "`block_tables` (B, max_blocks) call arguments"
            )
        nb, bt = self.paged_num_blocks, self.paged_block_tokens
        if nb <= 1 or bt <= 0:
            raise ValueError(
                "paged decode requires paged_num_blocks > 1 and "
                f"paged_block_tokens > 0 (got {nb}, {bt}) — use "
                "GPT.for_paged_decoding()"
            )
        if self.sliding_window or self.kv_cache_dtype != "model":
            # Scope: full-causal, full-precision cache. RoPE is supported
            # (rotation by the per-row absolute positions below), so the
            # llama family serves paged; the sliding-window ring and the
            # int8 cache keep their named raise — for_paged_decoding()
            # pre-checks the model-level fields too.
            raise ValueError(
                "paged decode does not support sliding_window/"
                "quantized cache yet"
            )
        batch, t, n_heads, head_dim = q.shape
        kv_width = k.shape[2]
        width = kv_width * head_dim
        fold = paged_block_fold(bt, width)
        leaf_shape = (nb, bt // fold, fold * width)
        paged_key = self.variable("cache", "paged_key", jnp.zeros, leaf_shape, k.dtype)
        paged_value = self.variable(
            "cache", "paged_value", jnp.zeros, leaf_shape, v.dtype
        )
        # Absolute position of every token in this call, per row.
        pos = positions[:, None] + jnp.arange(t)[None, :]  # (B, t)
        if self.rope:
            # Rotate by PER-ROW absolute positions before the cache
            # write (the linear path's recipe at a (B, t) position grid):
            # the pool then holds rotated keys, directly comparable to
            # any later query rotated by its own positions.
            from ..ops.rope import apply_rope

            q, k = apply_rope(q, k, pos, theta=self.rope_theta)
        write = paged_pool_writer(pos, block_tables, bt, width)
        for leaf, new in ((paged_key, k), (paged_value, v)):
            leaf.value = write(
                leaf.value, new.astype(leaf.value.dtype).reshape(batch, t, width)
            )

        s = block_tables.shape[1] * bt
        scale = 1.0 / math.sqrt(head_dim)
        g = n_heads // kv_width  # grouped-query read, like the linear path
        big_neg = jnp.finfo(jnp.float32).min
        form = paged_kv_form(t=t, n_heads=n_heads, kv_heads=kv_width, head_dim=head_dim, block_tokens=bt)
        if form == "rows":
            # The gathered blocks as the pool's own rows: (B, blocks, bt,
            # width) -> (B, s, width) moves nothing. Head h's query sits on
            # the lanes of its K/V head with zeros on the others, so a
            # product over the whole row adds nothing to its score, and of
            # the value product's row it keeps its own head's lanes.
            keys = paged_key.value[block_tables].reshape(batch, s, width)
            values = paged_value.value[block_tables].reshape(batch, s, width)
            own = (jnp.arange(n_heads) // g)[:, None, None] == jnp.arange(kv_width)[:, None]
            q_rows = jnp.where(own, q[:, :, :, None, :], 0).reshape(batch, t, n_heads, width)
            scores = jnp.einsum("bqhw,bsw->bhqs", q_rows, keys) * scale
            scores = scores.astype(jnp.float32)
            # Logical slot index IS the absolute position (block i covers
            # positions [i*bt, (i+1)*bt)): causal liveness is col <= row.
            live = jnp.arange(s)[None, None, None, :] <= pos[:, None, :, None]
            probs = jax.nn.softmax(jnp.where(live, scores, big_neg), axis=-1).astype(q.dtype)
            out_rows = jnp.einsum("bhqs,bsw->bqhw", probs, values)
            out_rows = out_rows.reshape(batch, t, n_heads, kv_width, head_dim)
            return jnp.where(own, out_rows, 0).sum(axis=3)
        keys = paged_key.value[block_tables].reshape(batch, s, kv_width, head_dim)
        values = paged_value.value[block_tables].reshape(
            batch, s, kv_width, head_dim
        )
        qg = q.reshape(batch, t, kv_width, g, head_dim)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, keys) * scale
        scores = scores.astype(jnp.float32)
        # The same liveness rule on the per-head scores (b, k, g, q, s).
        row = pos[:, None, None, :, None]  # (B, 1, 1, t, 1)
        col = jnp.arange(s)[None, None, None, None, :]
        scores = jnp.where(col <= row, scores, big_neg)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, values)
        return out.reshape(batch, t, n_heads, head_dim)


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    attention_mask: jax.Array | None,
    dropout: float = 0.0,
    deterministic: bool = True,
    dropout_rng_module: nn.Module | None = None,
    window: int = 0,
) -> jax.Array:
    """Full-matrix causal attention; softmax in f32, matmuls on MXU dtype.

    q/k/v: (B, T, H, Dh). Returns (B, T, H, Dh). ``window`` > 0 restricts
    each query to its trailing ``window`` keys (Mistral sliding-window
    semantics) — the full-matrix reference for the flash kernels' skip-
    block implementation.
    """
    head_dim = q.shape[-1]
    seqlen = q.shape[1]
    scale = 1.0 / math.sqrt(head_dim)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = scores.astype(jnp.float32)

    big_neg = jnp.finfo(jnp.float32).min
    causal = jnp.tril(jnp.ones((seqlen, seqlen), dtype=jnp.bool_))
    if window:
        pos = jnp.arange(seqlen)
        causal = causal & (pos[:, None] - pos[None, :] < window)
    scores = jnp.where(causal[None, None, :, :], scores, big_neg)
    if attention_mask is not None:
        # Segment semantics (packed sequences): nonzero = real token,
        # EQUAL nonzero values = same document — a key is live for a
        # query iff it is real and in the same segment. Plain 0/1
        # padding masks are the one-segment special case (identical
        # behavior to key-only masking for real queries; padded-query
        # rows become fully masked, which the caller's output zeroing
        # already covers).
        seg = attention_mask
        live = (seg != 0)[:, None, None, :] & (
            seg[:, None, :, None] == seg[:, None, None, :]
        )
        scores = jnp.where(live, scores, big_neg)

    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout > 0.0 and not deterministic and dropout_rng_module is not None:
        keep = 1.0 - dropout
        rng = dropout_rng_module.make_rng("dropout")
        mask = jax.random.bernoulli(rng, keep, probs.shape)
        probs = jnp.where(mask, probs / keep, 0.0)

    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class TransformerBlock(nn.Module):
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
    n_kv_heads: int = 0  # grouped-query attention (see CausalSelfAttention)
    assume_packed: bool = False  # drop the flash mask operand (packed data)
    sliding_window: int = 0  # Mistral-style window; 0 = full causal
    ring_slack: int = 0  # extra rolling-cache slots (speculative decode)
    kv_cache_dtype: str = "model"  # "int8": quantized decode cache
    # Paged block-pool decode cache (see CausalSelfAttention.paged).
    paged: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0
    # Mixture-of-Experts MLP (models/moe.py); 0 = dense MLP.
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    router_top_k: int = 1
    # Quantized training matmuls (ops/quant.py): see CausalSelfAttention.
    matmul_precision: str = "f32"
    # Pallas fused residual-add + LayerNorm (ops/fused_norm.py): ln_1/ln_2
    # run in one VMEM pass each, ln_2 absorbing the attention residual
    # add. Param tree identical to the unfused path (FusedLayerNorm).
    fused_norm: bool = False
    pallas_interpret: bool = False

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
        ln_kw = dict(
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            scale_init=nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), ("embed",)),
        )
        if self.fused_norm:
            h = FusedLayerNorm(
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                interpret=self.pallas_interpret,
                name="ln_1",
            )(x)
        else:
            h = nn.LayerNorm(name="ln_1", **ln_kw)(x)
        attn_out = CausalSelfAttention(
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
            sliding_window=self.sliding_window,
            ring_slack=self.ring_slack,
            kv_cache_dtype=self.kv_cache_dtype,
            paged=self.paged,
            paged_num_blocks=self.paged_num_blocks,
            paged_block_tokens=self.paged_block_tokens,
            matmul_precision=self.matmul_precision,
            name="attn",
        )(
            h,
            attention_mask,
            deterministic=deterministic,
            positions=positions,
            block_tables=block_tables,
        )

        if self.fused_norm:
            # One kernel: x = x + attn_out; h = LN(x). The sum is both the
            # residual stream and the norm input, so it is read/written once.
            h, x = FusedLayerNorm(
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                interpret=self.pallas_interpret,
                name="ln_2",
            )(attn_out, residual=x)
        else:
            x = x + attn_out
            h = nn.LayerNorm(name="ln_2", **ln_kw)(x)
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
                matmul_precision=self.matmul_precision,
                name="moe_mlp",
            )(h)
        else:
            from ..ops.quant import quant_dot_general

            quant_dg = quant_dot_general(self.matmul_precision)
            h = nn.Dense(
                self.d_ff,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "mlp")),
                bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), ("mlp",)),
                dot_general=quant_dg,
                name="mlp_fc",
            )(h)
            h = nn.with_logical_constraint(h, ("batch", "length", "act_mlp"))
            h = gelu_once(h)
            h = nn.Dense(
                self.d_model,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(_scaled_init(self.n_layers), ("mlp", "embed")),
                bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), ("embed",)),
                dot_general=quant_dg,
                name="mlp_proj",
            )(h)
        h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        x = x + h
        return nn.with_logical_constraint(x, ("batch", "length", "act_embed"))


class GPT(nn.Module):
    """Decoder-only GPT language model."""

    vocab_size: int
    block_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    dropout: float
    tie_embeddings: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    # Rematerialization policy when remat=True (model.extra.remat_policy):
    # "nothing" (default — save no intermediates, recompute the whole
    # block) trades the most FLOPs for HBM; "dots" saves matmul outputs
    # and recomputes only the cheap elementwise ops — less recompute on
    # the MXU for a modest memory cost, often the better MFU point.
    remat_policy: str = "nothing"
    # Per-layer activation tiers (model.extra.activation_tiers), one of
    # none|selective|full|offload per block — parsed/validated by the
    # adapter (config/activation_tiers.py) and already backend-resolved
    # (offload -> full where pinned_host is missing). When set it
    # replaces the global remat/remat_policy pair above, which stays for
    # direct module users and the dots_no_batch policy.
    activation_tiers: tuple[str, ...] | None = None
    attention: str = "dense"
    decode: bool = False  # KV-cache generation mode (see for_decoding())
    decode_cache_len: int = 0  # KV-cache capacity; 0 = block_size
    # Mixture-of-Experts (models/moe.py); 0 = dense MLPs in every block.
    n_experts: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    router_top_k: int = 1
    # Loss implementation hint consumed by GPTAdapter.compute_loss_components:
    # "dense" materializes logits; "chunked_ce" streams the CE over vocab
    # chunks of ce_chunk (ops/chunked_ce.py) — the forward then returns
    # hidden states via return_hidden and never builds [B,T,V];
    # "fused_ce" computes the loss in Pallas kernels (ops/fused_ce.py) so
    # no logits tile ever reaches HBM; its tiles are chosen from the call's
    # shapes unless fused_ce_block_t / fused_ce_block_v override them.
    loss_impl: str = "dense"
    ce_chunk: int = 8192
    fused_ce_block_t: int | None = None
    fused_ce_block_v: int | None = None
    # Pallas fused residual-add + LayerNorm in every block
    # (ops/fused_norm.py); cleared on decode clones — the kernels are
    # trained-shape tuned and decode runs T=1 slices.
    fused_norm: bool = False
    # Force interpret-mode Pallas kernels (fused_ce / fused_norm) on any
    # backend — CPU parity tests run the real kernel logic under
    # emulation (model.extra.pallas_interpret).
    pallas_interpret: bool = False
    # PaLM z-loss coefficient: adds z_loss * log(Z)^2 per token to the LM
    # objective (both loss paths). 0 = off (reference behavior).
    z_loss: float = 0.0
    # Grouped-query attention: K/V heads (0 = n_heads/MHA, 1 = MQA). The
    # decode cache shrinks by n_heads/n_kv_heads (see CausalSelfAttention).
    n_kv_heads: int = 0
    # Data is guaranteed packed (all-ones masks): skip the in-attention
    # mask on the flash path (model.extra.assume_packed).
    assume_packed: bool = False
    # Sliding-window attention (model.extra.sliding_window): each query
    # attends its trailing W keys — O(T·W) attention compute on the flash
    # path. 0 = full causal.
    sliding_window: int = 0
    # Extra rolling-cache slots for speculative decode rollback safety
    # (see CausalSelfAttention.ring_slack); set via for_decoding().
    ring_slack: int = 0
    # Decode-cache storage dtype (model.extra.kv_cache_dtype): "int8"
    # halves KV-cache HBM vs bf16 (see CausalSelfAttention).
    kv_cache_dtype: str = "model"
    # Paged block-pool decode cache for continuous-batching serving
    # (see CausalSelfAttention.paged); set via for_paged_decoding().
    paged: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0
    # Quantized training matmuls (model.extra.matmul_precision, ops/quant.py):
    # "int8" quantizes projection weights per-channel with straight-through
    # gradients; "int8_act" also fake-quantizes activations; "fp8" runs
    # float8_e4m3fn matmuls where the backend supports them (the adapter
    # capability-resolves fp8 -> f32 with a warning otherwise). Embeddings
    # and the lm_head stay in the compute dtype — they are the
    # quality-sensitive ends of the stack and a rounding error of the
    # matmul byte budget. Param tree and checkpoints are unchanged.
    matmul_precision: str = "f32"

    def paged_kv_form(self, *, t: int) -> str:
        """The form of the paged read a call of ``t`` tokens a row runs: the
        engine's ``kv_form`` (``model_paged_kv_form``)."""
        return model_paged_kv_form(self, t=t)

    def for_paged_decoding(
        self, *, num_blocks: int, block_tokens: int, state_rows: int = 0
    ) -> "GPT":
        """Clone configured for paged-KV continuous-batching decode.
        ``state_rows`` is the engine's offer of per-sequence state rows
        (serving/paged_kv.py); a model without recurrent state takes none.

        The cache becomes a pool of ``num_blocks`` blocks of
        ``block_tokens`` positions each, shared by every in-flight
        sequence; callers pass per-row ``positions`` and ``block_tables``
        to ``apply`` (serving/engine.py owns the jitted step). Same
        parameter structure as training (params transfer 1:1). Physical
        block 0 is the null block padded table entries point at, so the
        pool must hold at least 2 blocks.
        """
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (got {num_blocks})")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1 (got {block_tokens})")
        # (No rope check: GPT has no rope field — rotary embeddings live
        # on CausalSelfAttention for the llama-family modules, and the
        # paged path rotates by per-row positions; Llama.for_paged_decoding
        # is the llama-family twin of this entrypoint.)
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
            fused_norm=False,
            paged_num_blocks=num_blocks,
            paged_block_tokens=block_tokens,
        )

    def for_decoding(
        self, cache_len: int | None = None, *, ring_slack: int = 0
    ) -> "GPT":
        """Clone configured for cached autoregressive decoding.

        Same parameter structure (params transfer 1:1); remat is dropped —
        it trades FLOPs for training memory and would re-run cache writes.
        ``cache_len`` sizes the per-layer KV cache to the actual output
        length (capped at ``block_size``) so short generations don't pay
        O(block_size) HBM and attention per step. ``ring_slack`` widens a
        windowed model's rolling cache for speculative-rollback safety
        (speculative.py passes gamma+1).
        """
        if cache_len is None:
            cache_len = self.block_size
        return self.clone(
            decode=True,
            remat=False,
            activation_tiers=None,
            fused_norm=False,
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
        position_embedding = nn.Embed(
            self.block_size,
            self.d_model,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            embedding_init=nn.with_logical_partitioning(_EMBED_INIT, ("position", "embed")),
            name="position_embedding",
        )

        if self.decode and self.paged:
            # Per-ROW absolute positions from the caller: rows at different
            # depths share one program (continuous batching). No cursor
            # variable — the scheduler owns each sequence's position.
            if positions is None:
                raise ValueError(
                    "paged decode requires the `positions` (B,) argument"
                )
            pos_ids = positions[:, None] + jnp.arange(seqlen)[None, :]
        elif self.decode:
            # Positions continue from the cache cursor across apply() calls.
            position_index = self.variable(
                "cache", "position_index", lambda: jnp.zeros((), jnp.int32)
            )
            pos_ids = (position_index.value + jnp.arange(seqlen))[None, :]
            position_index.value = position_index.value + seqlen
        else:
            pos_ids = jnp.arange(seqlen)[None, :]
        x = token_embedding(input_ids) + position_embedding(pos_ids)
        x = nn.Dropout(self.dropout)(x, deterministic=deterministic)
        x = nn.with_logical_constraint(x, ("batch", "length", "act_embed"))

        if self.activation_tiers is not None:
            if len(self.activation_tiers) != self.n_layers:
                raise ValueError(
                    f"activation_tiers has {len(self.activation_tiers)} "
                    f"entries for a {self.n_layers}-layer model"
                )
            tier_classes = tier_block_classes(
                TransformerBlock, self.activation_tiers
            )
            layer_classes = [tier_classes[t] for t in self.activation_tiers]
        else:
            block_cls = TransformerBlock
            if self.remat:
                if self.remat_policy not in REMAT_POLICIES:
                    # Direct module users; the adapter validates at config time.
                    raise ValueError(
                        f"remat_policy {self.remat_policy!r} unknown; expected "
                        f"one of {sorted(REMAT_POLICIES)}"
                    )
                # argnums include the module at 0; 3 = `deterministic`, a
                # trace-time bool that must stay static through the remat boundary.
                # policy=None is nn.remat's own default (save nothing).
                block_cls = nn.remat(
                    TransformerBlock,
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
                sliding_window=self.sliding_window,
                ring_slack=self.ring_slack if self.decode else 0,
                kv_cache_dtype=self.kv_cache_dtype,
                paged=paged,
                paged_num_blocks=self.paged_num_blocks if paged else 0,
                paged_block_tokens=self.paged_block_tokens if paged else 0,
                n_experts=self.n_experts,
                capacity_factor=self.capacity_factor,
                moe_aux_weight=self.moe_aux_weight,
                router_top_k=self.router_top_k,
                matmul_precision=self.matmul_precision,
                fused_norm=self.fused_norm,
                pallas_interpret=self.pallas_interpret,
                name=f"block_{layer}",
            )
            if paged:
                # kwargs only on the paged path: the remat wrapper's
                # positional static_argnums contract stays untouched.
                x = block(
                    x,
                    attention_mask,
                    deterministic,
                    positions=positions,
                    block_tables=block_tables,
                )
            else:
                x = block(x, attention_mask, deterministic)

        x = nn.LayerNorm(
            name="ln_f",
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            scale_init=nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), ("embed",)),
        )(x)

        if return_hidden:
            # Chunked-CE path (ops/chunked_ce.py): the loss contracts the
            # hidden states against the vocab matrix itself; skipping the
            # lm_head here is what keeps [B,T,V] out of HBM. NOTE: an
            # untied model must still initialize lm_head params, so init
            # runs with return_hidden=False (adapter handles this).
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


@register_model("gpt")
class GPTAdapter(ModelAdapter):
    """Model adapter for the decoder-only GPT implementation."""

    known_extra_keys = frozenset(
        {"tokenizer", "loss_impl", "ce_chunk", "z_loss", "n_kv_heads",
         "assume_packed", "remat_policy", "sliding_window",
         "kv_cache_dtype", "matmul_precision", "ce_auto_vocab",
         "activation_tiers", "fused_ce_block_t", "fused_ce_block_v",
         "fused_norm", "pallas_interpret"}
    )

    def build_model(self, cfg: RunConfig) -> nn.Module:
        vocab_size = cfg.model.vocab_size
        if vocab_size is None:
            tokenizer = self.build_tokenizer(cfg)
            tokenizer_vocab_size = getattr(tokenizer, "n_vocab", None)
            if not isinstance(tokenizer_vocab_size, int) or tokenizer_vocab_size <= 0:
                raise ValueError("GPT tokenizer must expose a positive integer n_vocab.")
            vocab_size = tokenizer_vocab_size
        ce_auto_vocab = self._positive_extra(cfg, "ce_auto_vocab", 32768)
        # Selection authority lives in ops/fused_ce.py (shared with the
        # autotune planner): explicit knob wins (unknown raises, fused_ce
        # without Pallas degrades to chunked_ce with a one-time warning);
        # unset auto-selects a streamed CE at vocab >= ce_auto_vocab —
        # the [B,T,V] logits tensor is the top memory-bound op in the
        # 50k-vocab roofline table (docs/perf.md).
        from ..ops.fused_ce import resolve_loss_impl
        from ..ops.fused_norm import resolve_fused_norm

        pallas_interpret = bool(cfg.model.extra.get("pallas_interpret", False))
        loss_impl = resolve_loss_impl(
            cfg.model.extra.get("loss_impl"),
            vocab_size=vocab_size,
            ce_auto_vocab=ce_auto_vocab,
            interpret=pallas_interpret,
        )
        fused_norm = resolve_fused_norm(
            bool(cfg.model.extra.get("fused_norm", False)),
            interpret=pallas_interpret,
        )
        ce_chunk = self._positive_extra(cfg, "ce_chunk", 8192)
        fused_ce_block_t = self._positive_extra(cfg, "fused_ce_block_t", None)
        fused_ce_block_v = self._positive_extra(cfg, "fused_ce_block_v", None)
        z_loss = float(cfg.model.extra.get("z_loss", 0.0))
        if z_loss < 0.0:
            raise ValueError(f"model.extra.z_loss must be >= 0, got {z_loss}")
        n_kv_heads = int(cfg.model.extra.get("n_kv_heads", 0))
        if n_kv_heads < 0:
            raise ValueError(f"model.extra.n_kv_heads must be >= 0, got {n_kv_heads}")
        if n_kv_heads and cfg.model.n_heads % n_kv_heads != 0:
            raise ValueError(
                f"model.n_heads ({cfg.model.n_heads}) must be divisible by "
                f"model.extra.n_kv_heads ({n_kv_heads})"
            )
        remat_policy = str(cfg.model.extra.get("remat_policy", "nothing"))
        if remat_policy not in REMAT_POLICIES:
            # Validated here (not only at trace under remat=True) so a
            # typo'd policy fails at config time even when remat is off.
            raise ValueError(
                f"model.extra.remat_policy {remat_policy!r} unknown; "
                f"expected one of {sorted(REMAT_POLICIES)}"
            )
        activation_tiers = resolve_config_activation_tiers(cfg)
        if cfg.model.attention in ("flash", "ring", "ulysses") and cfg.model.dropout > 0.0:
            raise ValueError(
                f"attention={cfg.model.attention!r} does not support "
                "attention-probability dropout; set model.dropout to 0.0 or "
                "use attention='dense'"
            )
        kv_cache_dtype = str(cfg.model.extra.get("kv_cache_dtype", "model"))
        if kv_cache_dtype not in ("model", "int8"):
            raise ValueError(
                f"model.extra.kv_cache_dtype {kv_cache_dtype!r} unknown; "
                "expected 'model' or 'int8'"
            )
        sliding_window = int(cfg.model.extra.get("sliding_window", 0))
        if sliding_window < 0:
            raise ValueError(
                f"model.extra.sliding_window must be >= 0, got {sliding_window}"
            )
        if sliding_window and cfg.model.attention in ("ring", "ulysses"):
            raise ValueError(
                "model.extra.sliding_window is not supported with "
                f"attention={cfg.model.attention!r}; use 'flash' or 'dense'"
            )
        # Validated like loss_impl (unknown raises at config time) then
        # capability-resolved: fp8 on a backend without float8 matmuls
        # degrades to f32 with a one-time warning (ops/quant.py).
        from ..ops.quant import resolve_matmul_precision

        matmul_precision = resolve_matmul_precision(
            str(cfg.model.extra.get("matmul_precision", "f32"))
        )
        return GPT(
            vocab_size=vocab_size,
            block_size=cfg.model.block_size,
            d_model=cfg.model.d_model,
            n_layers=cfg.model.n_layers,
            n_heads=cfg.model.n_heads,
            d_ff=cfg.model.d_ff,
            dropout=cfg.model.dropout,
            tie_embeddings=cfg.model.tie_embeddings,
            dtype=jnp.dtype(cfg.model.dtype),
            param_dtype=jnp.dtype(cfg.model.param_dtype),
            remat=cfg.model.remat,
            attention=cfg.model.attention,
            loss_impl=loss_impl,
            ce_chunk=ce_chunk,
            fused_ce_block_t=fused_ce_block_t,
            fused_ce_block_v=fused_ce_block_v,
            fused_norm=fused_norm,
            pallas_interpret=pallas_interpret,
            z_loss=z_loss,
            n_kv_heads=n_kv_heads,
            assume_packed=bool(cfg.model.extra.get("assume_packed", False)),
            remat_policy=remat_policy,
            activation_tiers=activation_tiers,
            sliding_window=sliding_window,
            kv_cache_dtype=kv_cache_dtype,
            matmul_precision=matmul_precision,
        )

    def build_tokenizer(self, cfg: RunConfig) -> Any | None:
        """tiktoken gpt2 by default (reference models/gpt.py:210-212);
        ``model.extra.tokenizer: "byte"`` selects the offline byte-level
        tokenizer (no network egress at startup)."""
        from ..data.tokenizers import build_tokenizer

        return build_tokenizer(cfg.model.extra.get("tokenizer", "gpt2"))

    def validate_mesh(self, cfg: RunConfig, mesh: Any) -> None:
        """Mesh-dependent checks the Trainer runs before compiling.

        GQA's narrow K/V heads carry the same ``heads`` logical axis as
        queries, so they must divide over the ``tensor`` mesh axis or
        pjit fails with an opaque sharding error.
        """
        n_kv_heads = int(cfg.model.extra.get("n_kv_heads", 0))
        tp = int(mesh.shape.get("tensor", 1))
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
        batch: Batch,
        *,
        rngs: dict[str, jax.Array] | None = None,
        deterministic: bool = True,
    ) -> tuple[jax.Array, jax.Array]:
        if getattr(model, "loss_impl", "dense") in ("chunked_ce", "fused_ce"):
            return self._chunked_loss_components(
                model, params, batch, rngs=rngs, deterministic=deterministic
            )
        return lm_loss_components(
            model, params, batch, rngs=rngs, deterministic=deterministic
        )

    @staticmethod
    def vocab_matrix(model: nn.Module, params: Params) -> jax.Array:
        """The (V, d) output-projection matrix, for losses that contract
        hidden states against it directly (ops/chunked_ce.py)."""
        if model.tie_embeddings:
            w_vocab = params["token_embedding"]["embedding"]
        else:
            w_vocab = params["lm_head"]["kernel"]
        # Trainer-held params are boxed with partitioning metadata
        # (nn.with_logical_partitioning); model.apply unboxes internally but
        # direct access must do it explicitly. No-op on plain arrays.
        w_vocab = nn.meta.unbox(w_vocab)
        if not model.tie_embeddings:
            w_vocab = w_vocab.T  # (d, V) -> (V, d)
        return w_vocab

    @classmethod
    def chunked_components_from_hidden(
        cls,
        model: nn.Module,
        params: Params,
        hidden: jax.Array,
        labels: jax.Array,
        attention_mask: jax.Array | None,
    ) -> tuple[jax.Array, jax.Array]:
        """Streamed/fused-CE components from already-computed hidden
        states — the single wiring point for every adapter's
        hidden-contraction loss path (gpt_moe reuses it after its
        mutable-collection apply). Dispatches on ``model.loss_impl``:
        fused_ce runs the Pallas kernel (ops/fused_ce.py), everything
        else the lax.scan streamer (ops/chunked_ce.py)."""
        if getattr(model, "loss_impl", "dense") == "fused_ce":
            from ..ops.fused_ce import fused_ce_components

            return fused_ce_components(
                hidden,
                cls.vocab_matrix(model, params),
                labels,
                attention_mask,
                block_t=getattr(model, "fused_ce_block_t", None),
                block_v=getattr(model, "fused_ce_block_v", None),
                z_loss=getattr(model, "z_loss", 0.0),
                interpret=bool(getattr(model, "pallas_interpret", False)),
            )
        from ..ops.chunked_ce import chunked_ce_components

        return chunked_ce_components(
            hidden,
            cls.vocab_matrix(model, params),
            labels,
            attention_mask,
            chunk=model.ce_chunk,
            z_loss=getattr(model, "z_loss", 0.0),
        )

    @classmethod
    def _chunked_loss_components(
        cls,
        model: nn.Module,
        params: Params,
        batch: Batch,
        *,
        rngs: dict[str, jax.Array] | None,
        deterministic: bool,
    ) -> tuple[jax.Array, jax.Array]:
        """Same loss as the dense path, streamed over vocab chunks
        (ops/chunked_ce.py) so [B,T,V] never materializes."""
        from ..models.base import validate_lm_batch

        input_ids, labels, attention_mask = validate_lm_batch(batch)
        hidden = model.apply(
            {"params": params},
            input_ids,
            attention_mask=attention_mask,
            deterministic=deterministic,
            rngs=rngs,
            return_hidden=True,
        )
        return cls.chunked_components_from_hidden(
            model, params, hidden, labels, attention_mask
        )


__all__ = ["GPT", "TransformerBlock", "CausalSelfAttention", "GPTAdapter", "dense_attention"]

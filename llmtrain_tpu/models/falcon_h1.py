"""Falcon-H1 family: attention and a Mamba-2 mixer side by side in every block.

One normalised input feeds BOTH branches; their outputs are scaled and
summed into the residual, then a SwiGLU follows (Falcon-H1, TII 2025;
``model_type: falcon_h1``)::

    u = RMSNorm(h)
    a = Attention(u * attention_in_multiplier)         keys * key_multiplier, RoPE, GQA
    m = Mamba2(u * ssm_in_multiplier)                  conv -> SSD scan -> gated grouped norm
    h = h + a * attention_out_multiplier + m * ssm_out_multiplier
    h = h + SwiGLU(RMSNorm(h))                         gate * mlp_multipliers[0], out * [1]

with muP multipliers on the embedding, the five parts of the mixer's input
projection (``ssm_multipliers``: z, x, B, C, dt) and the logits. Multipliers
are applied in float32 (``models/gpt.py:scaled``).

What is shared: ``RMSNorm`` and ``gated_mlp`` of ``models/llama.py``,
``ops/rope.py`` and ``CausalSelfAttention`` with its paged path (``head_dim``
and ``key_scale`` were added there for this family). What is new: the mixer
(:class:`Mamba2Mixer` over ``ops/ssd.py``) and its serving state.

**Serving state.** Attention's K/V lives in the paged block pool like every
family's. The mixer's recurrent state — the conv's last ``d_conv - 1``
inputs and the SSM state — cannot be paged: it is one fixed-size value per
sequence. In paged decode mode the mixer keeps two more leaves a layer in
the ``cache`` collection, ``state_conv (rows, d_conv - 1, conv_dim)`` in the
compute dtype and ``state_ssm (rows, heads, head_dim, d_state)`` in float32,
``rows`` as the engine offers them (``for_paged_decoding(state_rows=...)``;
row 0 is the null row of padded batch rows). The caller names each batch
row's state row (``state_rows``) in every call, a row belonging to one
request from admission to retirement (serving/paged_kv.py). A call at
position 0 starts from zeros whatever the row held; any other continues
from the row. A slab (prefill, any chunk of one) reads and writes its own
rows; a one-token call (decode) updates the WHOLE ``state_ssm`` leaf
elementwise, rows not in the batch multiplied by 1 and given 0, so the
donated leaf is updated in place and no row is gathered or scattered.

Not supported, each refused by name: the linear cache (``for_decoding``:
``generate()``, ``serving.mode: simple``), a quantized KV cache, remat.
A padding mask does not reach the mixer: rows are read as packed.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.schemas import RunConfig
from ..ops.ssd import ssd_chunked_scan, ssd_step, ssm_conv
from ..registry.models import register_model
from .gpt import (
    _DENSE_INIT,
    _EMBED_INIT,
    CausalSelfAttention,
    GPTAdapter,
    _scaled_init,
    model_paged_kv_form,
    scaled,
)
from .llama import RMSNorm, gated_mlp

_VEC = ("norm",)  # logical axis of every small vector: replicated


def _vec(init):
    return nn.with_logical_partitioning(init, _VEC)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a log-uniform draw in [0.001, 0.1] (Mamba-2)."""
    dt = jnp.exp(
        jax.random.uniform(key, shape, jnp.float32)
        * (math.log(0.1) - math.log(0.001))
        + math.log(0.001)
    )
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _conv_init(key, shape, dtype=jnp.float32):
    bound = 1.0 / math.sqrt(shape[0])  # torch's Conv1d default, fan_in = width
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Mamba2Mixer(nn.Module):
    """in_proj -> [z | x B C | dt]; causal conv + SiLU over ``x B C``; the
    SSD recurrence; ``out_proj(GroupedRMSNorm(y * silu(z)))``."""

    d_model: int
    d_ssm: int
    d_state: int
    n_heads: int
    n_groups: int
    d_conv: int
    chunk: int
    n_layers: int
    eps: float
    in_scale: float
    part_scales: tuple[float, ...]  # z, x, B, C, dt
    dtype: Any
    param_dtype: Any
    decode: bool = False
    state_rows: int = 0

    @nn.compact
    def __call__(
        self,
        u: jax.Array,  # (B, T, d) the block's normalised input
        *,
        positions: jax.Array | None = None,  # (B,) position of u[:, 0]
        state_rows: jax.Array | None = None,  # (B,) each row's state row
        true_len: jax.Array | None = None,  # (B,) real positions of a slab
    ) -> jax.Array:
        d_ssm, n, heads, groups = self.d_ssm, self.d_state, self.n_heads, self.n_groups
        p = d_ssm // heads
        gn = groups * n
        conv_dim = d_ssm + 2 * gn
        bsz, length, _ = u.shape

        proj = nn.Dense(
            2 * d_ssm + 2 * gn + heads,
            use_bias=False,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", "mlp")),
            name="in_proj",
        )(scaled(u, self.in_scale))
        mup = jnp.concatenate(
            [
                jnp.full((size,), s, jnp.float32)
                for size, s in zip((d_ssm, d_ssm, gn, gn, heads), self.part_scales)
            ]
        )
        proj = proj.astype(jnp.float32) * mup
        z = proj[..., :d_ssm].astype(self.dtype)
        xbc = proj[..., d_ssm : d_ssm + conv_dim].astype(self.dtype)
        dt_raw = proj[..., d_ssm + conv_dim :].astype(self.dtype)

        conv_w = self.param(
            "conv_weight",
            nn.with_logical_partitioning(_conv_init, ("norm", "mlp")),
            (self.d_conv, conv_dim),
            self.param_dtype,
        )
        conv_b = self.param(
            "conv_bias", _vec(nn.initializers.zeros_init()), (conv_dim,), self.param_dtype
        )
        dt_bias = self.param("dt_bias", _vec(_dt_bias_init), (heads,), self.param_dtype)
        a_log = self.param("A_log", _vec(_a_log_init), (heads,), self.param_dtype)
        d_skip = self.param(
            "D", _vec(nn.initializers.ones_init()), (heads,), self.param_dtype
        )
        a = -jnp.exp(a_log.astype(jnp.float32))
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias.astype(jnp.float32))

        def split(act: jax.Array):
            """silu(conv) output (..., conv_dim) float32 -> x, B, C."""
            act = nn.silu(act).astype(self.dtype)
            lead = act.shape[:-1]
            return (
                act[..., :d_ssm].reshape(*lead, heads, p),
                act[..., d_ssm : d_ssm + gn].reshape(*lead, groups, n),
                act[..., d_ssm + gn :].reshape(*lead, groups, n),
            )

        skip = d_skip.astype(jnp.float32)[:, None]
        if not self.decode:
            zeros = jnp.zeros((bsz, self.d_conv - 1, conv_dim), self.dtype)
            x, b_mat, c_mat = split(ssm_conv(xbc, conv_w, conv_b, zeros)[0])
            y, _ = ssd_chunked_scan(
                x, dt, a, b_mat, c_mat, chunk=self.chunk, dtype=self.dtype
            )
            y = y + skip * x.astype(jnp.float32)
        else:
            rows = self.state_rows
            if rows < 2:
                raise ValueError(
                    "paged decode needs state_rows >= 2 (row 0 is the null "
                    "row) — use FalconH1.for_paged_decoding(state_rows=...)"
                )
            conv_var = self.variable(
                "cache", "state_conv", jnp.zeros,
                (rows, self.d_conv - 1, conv_dim), self.dtype,
            )
            ssm_var = self.variable(
                "cache", "state_ssm", jnp.zeros, (rows, heads, p, n), jnp.float32
            )
            if state_rows is None or positions is None:
                if not self.is_initializing():
                    raise ValueError(
                        "paged decode of a model with recurrent state needs "
                        "the `state_rows` (B,) and `positions` (B,) arguments"
                    )
                state_rows = jnp.zeros((bsz,), jnp.int32)
                positions = jnp.zeros((bsz,), jnp.int32)
            if length > 1:
                # A slab: each row starts from its own state row (zeros at
                # position 0), and leaves there what its last REAL token left.
                fresh = positions == 0
                conv0 = jnp.where(fresh[:, None, None], 0, conv_var.value[state_rows])
                ssm0 = jnp.where(
                    fresh[:, None, None, None], 0.0, ssm_var.value[state_rows]
                )
                act, conv_new = ssm_conv(xbc, conv_w, conv_b, conv0, true_len)
                x, b_mat, c_mat = split(act)
                y, final = ssd_chunked_scan(
                    x, dt, a, b_mat, c_mat, chunk=self.chunk,
                    initial_state=ssm0, true_len=true_len, dtype=self.dtype,
                )
                y = y + skip * x.astype(jnp.float32)
                conv_var.value = conv_var.value.at[state_rows].set(conv_new)
                ssm_var.value = ssm_var.value.at[state_rows].set(final)
            else:
                # One token a row, computed over the whole table of state
                # rows: a batch row's small inputs are scattered to its
                # state row, every other row gets dt = 0 (state times 1,
                # plus 0), and only y is gathered back.
                active = (
                    jnp.zeros((rows,), bool).at[state_rows].set(True).at[0].set(False)
                )

                def to_rows(v: jax.Array) -> jax.Array:
                    return jnp.zeros((rows,) + v.shape[1:], v.dtype).at[state_rows].set(v)

                keep = to_rows(positions != 0) | ~active
                conv_old = conv_var.value
                act, conv_new = ssm_conv(
                    to_rows(xbc[:, 0])[:, None],
                    conv_w,
                    conv_b,
                    jnp.where(keep[:, None, None], conv_old, 0),
                )
                conv_var.value = jnp.where(active[:, None, None], conv_new, conv_old)
                x, b_mat, c_mat = split(act[:, 0])
                dt_rows = jnp.where(active[:, None], to_rows(dt[:, 0]), 0.0)
                new, y_rows = ssd_step(
                    ssm_var.value, x, dt_rows, a, b_mat, c_mat, keep=keep
                )
                ssm_var.value = new
                y_rows = y_rows + skip * x.astype(jnp.float32)
                y = y_rows[state_rows][:, None]

        # Gate first (norm_before_gate false), statistics a group, float32.
        scale = self.param(
            "norm_scale", _vec(nn.initializers.ones_init()), (d_ssm,), self.param_dtype
        )
        v = y.reshape(bsz, length, d_ssm) * nn.silu(z.astype(jnp.float32))
        vg = v.reshape(bsz, length, groups, d_ssm // groups)
        vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True) + self.eps)
        v = (vg.reshape(bsz, length, d_ssm) * scale.astype(jnp.float32)).astype(self.dtype)
        return nn.Dense(
            self.d_model,
            use_bias=False,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                _scaled_init(self.n_layers), ("mlp", "embed")
            ),
            name="out_proj",
        )(v)


class FalconH1Block(nn.Module):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    n_layers: int
    dropout: float
    dtype: Any
    param_dtype: Any
    rope_theta: float
    rms_norm_eps: float
    mamba_d_ssm: int
    mamba_d_state: int
    mamba_n_heads: int
    mamba_n_groups: int
    mamba_d_conv: int
    mamba_chunk_size: int
    key_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: tuple[float, ...]
    mlp_multipliers: tuple[float, ...]
    attention: str = "dense"
    decode: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0
    paged_state_rows: int = 0

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        attention_mask: jax.Array | None = None,
        deterministic: bool = True,
        positions: jax.Array | None = None,
        block_tables: jax.Array | None = None,
        state_rows: jax.Array | None = None,
        true_len: jax.Array | None = None,
    ) -> jax.Array:
        norm_kw = dict(eps=self.rms_norm_eps, dtype=self.dtype, param_dtype=self.param_dtype)
        act = ("batch", "length", "act_embed")
        u = nn.with_logical_constraint(RMSNorm(name="input_norm", **norm_kw)(x), act)
        attn = CausalSelfAttention(
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_layers=self.n_layers,
            dropout=self.dropout,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            attention=self.attention,
            decode=self.decode,
            n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            key_scale=self.key_multiplier,
            use_bias=False,
            rope=True,
            rope_theta=self.rope_theta,
            paged=self.decode,
            paged_num_blocks=self.paged_num_blocks,
            paged_block_tokens=self.paged_block_tokens,
            name="attn",
        )(
            scaled(u, self.attention_in_multiplier),
            attention_mask,
            deterministic=deterministic,
            positions=positions,
            block_tables=block_tables,
        )
        mixed = Mamba2Mixer(
            d_model=self.d_model,
            d_ssm=self.mamba_d_ssm,
            d_state=self.mamba_d_state,
            n_heads=self.mamba_n_heads,
            n_groups=self.mamba_n_groups,
            d_conv=self.mamba_d_conv,
            chunk=self.mamba_chunk_size,
            n_layers=self.n_layers,
            eps=self.rms_norm_eps,
            in_scale=self.ssm_in_multiplier,
            part_scales=self.ssm_multipliers,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            decode=self.decode,
            state_rows=self.paged_state_rows,
            name="mamba",
        )(u, positions=positions, state_rows=state_rows, true_len=true_len)
        x = (
            x.astype(jnp.float32)
            + attn.astype(jnp.float32) * self.attention_out_multiplier
            + mixed.astype(jnp.float32) * self.ssm_out_multiplier
        ).astype(self.dtype)
        x = nn.with_logical_constraint(x, act)
        h = nn.with_logical_constraint(RMSNorm(name="mlp_norm", **norm_kw)(x), act)
        h = gated_mlp(
            h,
            d_model=self.d_model,
            d_ff=self.d_ff,
            n_layers=self.n_layers,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            gate_scale=self.mlp_multipliers[0],
            out_scale=self.mlp_multipliers[1],
        )
        h = nn.Dropout(self.dropout)(h, deterministic=deterministic)
        return nn.with_logical_constraint(x + h, act)


class FalconH1(nn.Module):
    """Falcon-H1 decoder-only language model."""

    vocab_size: int
    block_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    dropout: float
    n_kv_heads: int
    head_dim: int
    mamba_d_ssm: int
    mamba_d_state: int
    mamba_n_heads: int
    mamba_n_groups: int
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple[float, ...] = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    tie_embeddings: bool = False
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention: str = "dense"
    # The loss machinery GPTAdapter shares reads these.
    loss_impl: str = "dense"
    ce_chunk: int = 8192
    z_loss: float = 0.0
    # Decoding is paged decoding (the family has no linear cache); set via
    # for_paged_decoding().
    decode: bool = False
    paged_num_blocks: int = 0
    paged_block_tokens: int = 0
    paged_state_rows: int = 0

    @property
    def state_scan_chunk(self) -> int:
        """Positions one chunk of the prefill scan covers (the engine counts
        ``scan_chunks`` of a prefill call with it)."""
        return self.mamba_chunk_size

    def paged_kv_form(self, *, t: int) -> str:
        """The form of the paged read a call of ``t`` tokens a row runs: the
        engine's ``kv_form`` (models/gpt.py ``model_paged_kv_form``)."""
        return model_paged_kv_form(self, t=t)

    def for_paged_decoding(
        self, *, num_blocks: int, block_tokens: int, state_rows: int = 0
    ) -> "FalconH1":
        """Clone configured for paged continuous-batching decode (the
        GPT.for_paged_decoding contract). ``state_rows`` is how many rows
        every layer's two state leaves hold, the null row 0 included."""
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (got {num_blocks})")
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1 (got {block_tokens})")
        if state_rows < 2:
            raise ValueError(
                "a model with recurrent state needs state_rows >= 2 (the "
                f"null row and one a sequence), got {state_rows}"
            )
        return self.clone(
            decode=True,
            paged_num_blocks=num_blocks,
            paged_block_tokens=block_tokens,
            paged_state_rows=state_rows,
        )

    def for_decoding(self, cache_len: int | None = None, *, ring_slack: int = 0):
        """Refused by name: the linear cursor cache has no recurrent state."""
        raise ValueError(
            "falcon_h1 has no linear decode cache (generate(), serving.mode: "
            "simple, speculative decoding): the mixer's recurrent state is "
            "kept only as state rows of the paged engine — use "
            "serving.mode: continuous"
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
        state_rows: jax.Array | None = None,
        true_len: jax.Array | None = None,
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
        x = scaled(token_embedding(input_ids), self.embedding_multiplier)
        x = nn.Dropout(self.dropout)(x, deterministic=deterministic)
        x = nn.with_logical_constraint(x, ("batch", "length", "act_embed"))
        paged = self.decode
        for layer in range(self.n_layers):
            block = FalconH1Block(
                d_model=self.d_model,
                n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim,
                d_ff=self.d_ff,
                n_layers=self.n_layers,
                dropout=self.dropout,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                rope_theta=self.rope_theta,
                rms_norm_eps=self.rms_norm_eps,
                mamba_d_ssm=self.mamba_d_ssm,
                mamba_d_state=self.mamba_d_state,
                mamba_n_heads=self.mamba_n_heads,
                mamba_n_groups=self.mamba_n_groups,
                mamba_d_conv=self.mamba_d_conv,
                mamba_chunk_size=self.mamba_chunk_size,
                key_multiplier=self.key_multiplier,
                attention_in_multiplier=self.attention_in_multiplier,
                attention_out_multiplier=self.attention_out_multiplier,
                ssm_in_multiplier=self.ssm_in_multiplier,
                ssm_out_multiplier=self.ssm_out_multiplier,
                ssm_multipliers=tuple(self.ssm_multipliers),
                mlp_multipliers=tuple(self.mlp_multipliers),
                attention=self.attention,
                decode=paged,
                paged_num_blocks=self.paged_num_blocks if paged else 0,
                paged_block_tokens=self.paged_block_tokens if paged else 0,
                paged_state_rows=self.paged_state_rows if paged else 0,
                name=f"block_{layer}",
            )
            if paged:
                x = block(
                    x, attention_mask, deterministic, positions=positions,
                    block_tables=block_tables, state_rows=state_rows, true_len=true_len,
                )
            else:
                x = block(x, attention_mask, deterministic)
        x = RMSNorm(
            name="norm_f", eps=self.rms_norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(x)
        if return_hidden:
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
        logits = scaled(logits, self.lm_head_multiplier)
        return nn.with_logical_constraint(logits, ("batch", "length", "act_vocab"))


_REQUIRED = ("n_kv_heads", "head_dim", "mamba_d_ssm", "mamba_d_state", "mamba_n_heads", "mamba_n_groups")
_SCALARS = (
    "embedding_multiplier", "key_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
    "lm_head_multiplier",
)


@register_model("falcon_h1")
class FalconH1Adapter(GPTAdapter):
    """Adapter for the Falcon-H1 family; the loss machinery is GPTAdapter's
    (same top-level parameter names). Every size and multiplier of the
    published config is a ``model.extra`` key under its published name."""

    known_extra_keys = frozenset(
        {"tokenizer", "loss_impl", "ce_chunk", "ce_auto_vocab", "z_loss", "kv_cache_dtype",
         "rope_theta", "rms_norm_eps", "mamba_d_conv", "mamba_chunk_size",
         "ssm_multipliers", "mlp_multipliers", *_REQUIRED, *_SCALARS}
    )

    def build_model(self, cfg: RunConfig) -> nn.Module:
        extra = cfg.model.extra
        unknown = sorted(set(extra) - self.known_extra_keys)
        if unknown:
            raise ValueError(
                f"model.extra keys {unknown} are not falcon_h1 settings; known: "
                f"{sorted(self.known_extra_keys)}"
            )
        missing = [k for k in _REQUIRED if k not in extra]
        if missing:
            raise ValueError(f"falcon_h1 needs model.extra keys {missing}")
        if cfg.model.remat:
            raise ValueError("falcon_h1 does not support model.remat")
        if str(extra.get("kv_cache_dtype", "model")) != "model":
            raise ValueError(
                "falcon_h1 keeps its KV cache in the model's dtype; "
                f"model.extra.kv_cache_dtype={extra['kv_cache_dtype']!r} is not supported"
            )
        base = super().build_model(cfg)  # the shared validation (vocab, loss, GQA)
        if base.loss_impl == "fused_ce":
            raise ValueError(
                "falcon_h1 does not run the fused CE kernel; use loss_impl "
                "'dense' or 'chunked_ce'"
            )
        sizes = {k: int(extra[k]) for k in _REQUIRED}
        sizes["mamba_d_conv"] = int(extra.get("mamba_d_conv", 4))
        sizes["mamba_chunk_size"] = int(extra.get("mamba_chunk_size", 128))
        for key, value in sizes.items():
            if value < 1:
                raise ValueError(f"model.extra.{key} must be >= 1, got {value}")
        if sizes["head_dim"] % 2:
            raise ValueError(f"RoPE needs an even head_dim, got {sizes['head_dim']}")
        if sizes["mamba_d_ssm"] % sizes["mamba_n_heads"]:
            raise ValueError("mamba_d_ssm must be a multiple of mamba_n_heads")
        if sizes["mamba_n_heads"] % sizes["mamba_n_groups"] or sizes["mamba_d_ssm"] % sizes["mamba_n_groups"]:
            raise ValueError("mamba_n_heads and mamba_d_ssm must be multiples of mamba_n_groups")
        lists = {}
        for key, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            value = tuple(float(v) for v in extra.get(key, (1.0,) * n))
            if len(value) != n:
                raise ValueError(f"model.extra.{key} takes {n} numbers, got {len(value)}")
            lists[key] = value
        rope_theta = float(extra.get("rope_theta", 1e11))
        rms_norm_eps = float(extra.get("rms_norm_eps", 1e-5))
        if rope_theta <= 0 or rms_norm_eps <= 0:
            raise ValueError("model.extra.rope_theta and rms_norm_eps must be > 0")
        tie = (
            cfg.model.tie_embeddings
            if "tie_embeddings" in cfg.model.model_fields_set
            else False
        )
        return FalconH1(
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
            attention=base.attention,
            loss_impl=base.loss_impl,
            ce_chunk=base.ce_chunk,
            z_loss=base.z_loss,
            rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps,
            **sizes,
            **lists,
            **{k: float(extra.get(k, 1.0)) for k in _SCALARS},
        )

    @staticmethod
    def vocab_matrix(model: nn.Module, params: Any) -> jax.Array:
        """(V, d) with the logits' multiplier folded in, so the streamed CE
        contracts hidden states against what the dense head computes."""
        return scaled(GPTAdapter.vocab_matrix(model, params), model.lm_head_multiplier)


__all__ = ["FalconH1", "FalconH1Block", "Mamba2Mixer", "FalconH1Adapter"]

"""Mixture-of-Experts MLPs, mesh-first: two dispatches over one router
projection and one layout of expert weights (leading logical axis ``expert``).

* :class:`MoEMLP` — top-1 Switch or top-2 GShard routing, a fixed capacity,
  tokens over capacity DROPPED (the rest of this docstring). ``gpt_moe`` and
  ``llama_moe`` train with it.
* :class:`DroplessMoE` — any ``top_k``, sigmoid scores, group-limited
  selection, normalised and scaled weights, no capacity and no dropped
  token: token-expert pairs are sorted by expert and the experts HELD are
  run as grouped matrix products (``jax.lax.ragged_dot``). The layer is told
  which experts it holds (``experts_held``) and computes their part of the
  result (models/latent_moe.py serves with it).

**MoEMLP.**

New capability beyond the reference (dense MLP only, reference
models/gpt.py:94-97), designed the TPU/XLA way (GShard/Switch pattern):
routing is expressed as dense one-hot dispatch/combine einsums over a
(tokens, experts, capacity) layout, and expert parallelism falls out of
sharding annotations — expert weights carry the logical ``expert`` axis and
dispatched activations carry ``act_expert``; with a mesh whose ``expert``
axis is > 1, XLA's SPMD partitioner inserts the token all-to-alls. No
hand-written collectives.

Semantics:

* ``router_top_k=1`` (Switch Transformer): each token goes to its argmax
  expert, output scaled by the raw router probability.
* ``router_top_k=2`` (GShard): each token also goes to its second-choice
  expert; the two RAW router probabilities are renormalized to sum to 1
  (before any capacity drop — a dropped choice contributes zero without
  inflating the survivor), and second choices queue BEHIND all first
  choices for capacity (first-choice priority).
* fixed expert capacity ``ceil(capacity_factor * k * T / n_experts)`` per
  sequence; tokens over capacity are dropped — they pass through the
  residual connection unchanged (output 0 from the MoE layer for that
  choice).
* load-balance auxiliary loss ``aux_weight * E^2 * mean_e(f_e * P_e)``
  (f from first choices) sown into the ``losses`` collection; the gpt_moe
  adapter folds it into the training objective. ``sow`` is a no-op when
  the collection isn't mutable, so eval/generation paths need no changes.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

_DENSE_INIT = nn.initializers.normal(stddev=0.02)


def _scaled_init(n_layers: int) -> nn.initializers.Initializer:
    return nn.initializers.normal(stddev=0.02 / math.sqrt(2 * n_layers))


def _router(n_experts: int, precision: Any = None) -> nn.Dense:
    """The router projection both dispatches share (call it inside a
    ``@nn.compact`` method: it becomes the caller's child ``router``).
    Float32 whatever the model computes in: a choice among experts must
    not be made in bf16. (On a TPU a float32 product still runs as bf16
    passes unless ``precision`` asks for more.)"""
    return nn.Dense(
        n_experts,
        use_bias=False,
        dtype=jnp.float32,
        param_dtype=jnp.float32,
        precision=precision,
        kernel_init=nn.with_logical_partitioning(_DENSE_INIT, ("embed", None)),
        name="router",
    )


def _swiglu_expert_weights(
    module: nn.Module, n_experts: int, d_model: int, d_ff: int, n_layers: int, param_dtype: Any
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``wg``, ``wu`` ``(experts, d, ff)`` and ``wo`` ``(experts, ff, d)`` of
    SwiGLU experts, declared on ``module`` with the ``expert`` logical axis
    leading (both dispatches; ``experts`` is how many the layer holds)."""
    wide = nn.with_logical_partitioning(_DENSE_INIT, ("expert", "embed", "mlp"))
    wg = module.param("wg", wide, (n_experts, d_model, d_ff), param_dtype)
    wu = module.param("wu", wide, (n_experts, d_model, d_ff), param_dtype)
    wo = module.param(
        "wo",
        nn.with_logical_partitioning(_scaled_init(n_layers), ("expert", "mlp", "embed")),
        (n_experts, d_ff, d_model),
        param_dtype,
    )
    return wg, wu, wo


def _expert_matmul(x: jax.Array, w: jax.Array, mode: str, spec: str) -> jax.Array:
    """Expert-batched matmul, optionally quantized (ops/quant.py).

    ``x`` (E, B, C, d_in) against stacked expert kernels ``w``
    (E, d_in, d_out) -> (E, B, C, d_out). ``mode`` "f32" keeps the
    original einsum (bit-identical to the pre-quantization build); the
    quantized modes route through ``quant_dot_general`` with the same
    contraction expressed as dot_general dimension numbers (batch dim E,
    contracting dim d_in) — per-(expert, output-unit) int8 scales,
    straight-through gradients to the f32 master weights. Only the
    expert kernels quantize: router and dispatch/combine one-hots are
    routing decisions, not matmul bandwidth, and stay f32.
    """
    if mode == "f32":
        return jnp.einsum(spec, x, w)
    from ..ops.quant import quant_dot_general

    return quant_dot_general(mode)(x, w, (((3,), (1,)), ((0,), (0,))))


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense MLP inside a transformer block."""

    d_model: int
    d_ff: int
    n_experts: int
    n_layers: int
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    router_top_k: int = 1
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    # Expert MLP flavor: "gelu" (GPT family, biased two-matmul MLP) or
    # "swiglu" (Mixtral/llama family: silu(x·wg) * (x·wu) → wo, bias-free
    # — the same block shape as models/llama.py's dense SwiGLU).
    mlp_type: str = "gelu"
    # Quantized expert matmuls (ops/quant.py): see _expert_matmul.
    matmul_precision: str = "f32"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        batch, seqlen, d_model = x.shape
        n_exp = self.n_experts
        k = self.router_top_k
        if k not in (1, 2):
            raise ValueError(
                f"router_top_k must be 1 or 2 on the capacity-and-drop path "
                f"(MoEMLP: gpt_moe, llama_moe), got {k}; more experts a token "
                "need the dropless dispatch (DroplessMoE, model.name latent_moe)"
            )
        if k > n_exp:
            raise ValueError(f"router_top_k {k} exceeds n_experts {n_exp}")
        capacity = max(1, int(math.ceil(self.capacity_factor * k * seqlen / n_exp)))

        # Router in float32: softmax over tiny expert dim must not run bf16.
        router_logits = _router(n_exp)(x.astype(jnp.float32))
        gates = jax.nn.softmax(router_logits, axis=-1)  # (B, T, E) f32

        # Per-choice dispatch with first-choice capacity priority: choice c
        # tokens queue behind every earlier choice's (post-cut) enqueues.
        remaining = gates
        queued = jnp.zeros((batch, n_exp), jnp.float32)  # tokens enqueued per expert
        choices = []  # (mask_post_cut, raw_prob, kept, position) per choice
        first_choice_mask = None  # pre-cut first-choice one-hot, for the aux loss
        for _ in range(k):
            mask_pre = jax.nn.one_hot(
                jnp.argmax(remaining, axis=-1), n_exp, dtype=jnp.float32
            )
            if first_choice_mask is None:
                first_choice_mask = mask_pre
            pos = (jnp.cumsum(mask_pre, axis=1) + queued[:, None, :]) * mask_pre
            mask_post = mask_pre * (pos <= capacity)
            raw_prob = jnp.sum(remaining * mask_pre, axis=-1)  # (B, T) pre-drop
            kept = jnp.sum(mask_post, axis=-1)  # (B, T) 1.0 unless dropped
            position = jnp.sum(pos * mask_post, axis=-1) - 1.0
            choices.append((mask_post, raw_prob, kept, position))
            queued = queued + mask_post.sum(axis=1)
            remaining = remaining * (1.0 - mask_pre)

        # Load-balance loss from FIRST choices: E * sum_e f_e * P_e per
        # sequence (fraction of tokens routed to e times mean router prob
        # of e), scaled so a perfectly uniform router gives aux_weight*1.0.
        density = first_choice_mask.mean(axis=1)  # (B, E)
        density_proxy = gates.mean(axis=1)  # (B, E)
        aux = self.aux_loss_weight * n_exp * n_exp * jnp.mean(density * density_proxy)
        self.sow("losses", "moe_aux", aux)

        # Combine weights: k=1 keeps the raw Switch probability; k>1
        # renormalizes the RAW router probabilities to sum to 1 (GShard) —
        # BEFORE capacity drops, so a congested neighbor zeroes a dropped
        # choice's contribution without inflating the surviving one.
        if k == 1:
            weights = [p * kp for _, p, kp, _ in choices]
        else:
            denom = jnp.maximum(sum(p for _, p, _, _ in choices), 1e-9)
            weights = [p / denom * kp for _, p, kp, _ in choices]

        # One-hot over capacity slots; dropped tokens (position 0 -> -1) map
        # to all-zero rows.
        dispatch = jnp.zeros((batch, seqlen, n_exp, capacity), jnp.float32)
        combine = jnp.zeros((batch, seqlen, n_exp, capacity), jnp.float32)
        for (mask_i, _, _, position_i), weight_i in zip(choices, weights):
            position_oh = jax.nn.one_hot(
                position_i.astype(jnp.int32), capacity, dtype=jnp.float32
            )
            dispatch_i = mask_i[..., None] * position_oh[:, :, None, :]  # (B,T,E,C)
            dispatch = dispatch + dispatch_i
            combine = combine + dispatch_i * weight_i[:, :, None, None]

        # Dispatch tokens: (B,T,E,C) x (B,T,D) -> (E,B,C,D). The E dim is
        # expert-sharded, B stays data-sharded (act_expert_group) — the
        # resharding between the two layouts is the all-to-all.
        expert_in = jnp.einsum(
            "btec,btd->ebcd", dispatch.astype(x.dtype), x.astype(x.dtype)
        )
        expert_in = nn.with_logical_constraint(
            expert_in, ("act_expert", "act_expert_group", None, "act_embed")
        )

        if self.mlp_type == "swiglu":
            wg, wu, wo = _swiglu_expert_weights(
                self, n_exp, d_model, self.d_ff, self.n_layers, self.param_dtype
            )
            gate = _expert_matmul(
                expert_in, wg.astype(self.dtype), self.matmul_precision,
                "ebcd,edf->ebcf",
            )
            up = _expert_matmul(
                expert_in, wu.astype(self.dtype), self.matmul_precision,
                "ebcd,edf->ebcf",
            )
            h = nn.silu(gate) * up
            h = nn.with_logical_constraint(
                h, ("act_expert", "act_expert_group", None, "act_mlp")
            )
            expert_out = _expert_matmul(
                h, wo.astype(self.dtype), self.matmul_precision, "ebcf,efd->ebcd"
            )
        elif self.mlp_type == "gelu":
            wi = self.param(
                "wi",
                nn.with_logical_partitioning(_DENSE_INIT, ("expert", "embed", "mlp")),
                (n_exp, d_model, self.d_ff),
                self.param_dtype,
            )
            bi = self.param(
                "bi",
                nn.with_logical_partitioning(nn.initializers.zeros_init(), ("expert", "mlp")),
                (n_exp, self.d_ff),
                self.param_dtype,
            )
            wo = self.param(
                "wo",
                nn.with_logical_partitioning(
                    _scaled_init(self.n_layers), ("expert", "mlp", "embed")
                ),
                (n_exp, self.d_ff, d_model),
                self.param_dtype,
            )
            bo = self.param(
                "bo",
                nn.with_logical_partitioning(nn.initializers.zeros_init(), ("expert", "embed")),
                (n_exp, d_model),
                self.param_dtype,
            )

            h = _expert_matmul(
                expert_in, wi.astype(self.dtype), self.matmul_precision,
                "ebcd,edf->ebcf",
            )
            h = h + bi.astype(self.dtype)[:, None, None, :]
            h = nn.with_logical_constraint(h, ("act_expert", "act_expert_group", None, "act_mlp"))
            h = nn.gelu(h, approximate=False)
            expert_out = _expert_matmul(
                h, wo.astype(self.dtype), self.matmul_precision, "ebcf,efd->ebcd"
            )
            expert_out = expert_out + bo.astype(self.dtype)[:, None, None, :]
        else:
            raise ValueError(
                f"mlp_type {self.mlp_type!r} unknown; expected 'gelu' or 'swiglu'"
            )
        expert_out = nn.with_logical_constraint(
            expert_out, ("act_expert", "act_expert_group", None, "act_embed")
        )

        # Combine back to (B, T, D); dropped tokens get 0 (residual carries them).
        out = jnp.einsum("btec,ebcd->btd", combine.astype(x.dtype), expert_out)
        return nn.with_logical_constraint(out, ("batch", "length", "act_embed"))


def group_limited_top_k(
    scores: jax.Array, *, top_k: int, n_group: int, topk_group: int
) -> tuple[jax.Array, jax.Array]:
    """The ``top_k`` experts of each token, chosen inside its best groups.

    ``scores`` (N, E) float32, experts in ``n_group`` consecutive groups. A
    group's score is the sum of its two highest expert scores; the
    ``topk_group`` best groups stay, every other expert is masked, and the
    ``top_k`` highest of what is left are chosen (the DeepSeek-V3 family's
    group-limited selection without the bias correction). Returns the
    chosen experts' ids (N, top_k) int32 and their UNMASKED scores.
    ``n_group == 1`` is plain top-k.
    """
    n, n_exp = scores.shape
    choice = scores
    if n_group > 1:
        grouped = scores.reshape(n, n_group, n_exp // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # (N, groups)
        kept = jax.lax.top_k(group_score, topk_group)[1]  # (N, topk_group)
        stays = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        choice = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(n, n_exp)
    _, picked = jax.lax.top_k(choice, top_k)
    return picked.astype(jnp.int32), jnp.take_along_axis(scores, picked, axis=-1)


class DroplessMoE(nn.Module):
    """Routed SwiGLU experts with no capacity: every chosen pair whose
    expert this layer holds is computed.

    ``scores = sigmoid(x W_router)`` over ALL ``n_experts`` (float32;
    ``scoring = "softmax"``: ``softmax(x W_router)`` over all of them);
    :func:`group_limited_top_k` picks ``top_k`` a token; their weights are
    ``scale * s_i / sum_chosen s_j`` (``normalize``; else ``scale * s_i``),
    the sum over all chosen experts, held here or not.

    ``experts_held = (first, count)``: the layer holds the weights of
    experts ``first .. first + count - 1`` only (``wg``, ``wu``, ``wo`` have
    ``count`` leading rows; ``None`` = all) and returns the part of the
    result those experts give. The router, the groups, the ``top_k`` and
    the normalisation are those of the whole layer. What the absent experts
    would add is another holder's to compute; nothing here stands in for it.

    **Dispatch.** The ``N * top_k`` token-expert pairs are sorted by expert
    (pairs of absent experts last), each pair's token row is gathered, and
    the held experts run as three grouped matrix products over the sorted
    rows (``jax.lax.ragged_dot``: on the TPU one kernel that walks the
    groups; rows past the last group are not computed and are masked here).
    The results return to pair order and are summed under their weights in
    float32. No ``(tokens, experts, capacity)`` array exists.

    **Counters.** Where the ``moe_stats`` collection is mutable (the paged
    engine's decode call) the layer sows ``counts``, int32 ``[expert_pairs,
    experts_hit]``: pairs routed to held experts, and held experts with at
    least one pair (serving/engine.py:EXPERT_COUNTERS adds them over layers).
    """

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    n_layers: int
    n_group: int = 1
    topk_group: int = 1
    normalize: bool = True
    scale: float = 1.0
    scoring: str = "sigmoid"
    experts_held: tuple[int, int] | None = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        lead, d_model = x.shape[:-1], x.shape[-1]
        first, count = self.experts_held or (0, self.n_experts)
        k = self.top_k
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring {self.scoring!r} unknown; expected 'sigmoid' or 'softmax'")
        if not 0 < k <= self.n_experts:
            raise ValueError(f"top_k {k} must lie in 1..n_experts ({self.n_experts})")
        if self.n_experts % self.n_group or not 0 < self.topk_group <= self.n_group:
            raise ValueError(
                f"n_experts {self.n_experts} must divide into n_group {self.n_group} "
                f"groups, of which topk_group {self.topk_group} stay"
            )
        if self.topk_group * (self.n_experts // self.n_group) < k:
            raise ValueError(f"top_k {k} experts do not fit in {self.topk_group} groups")
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(
                f"experts_held {(first, count)} is no range of the {self.n_experts} experts"
            )
        tokens = x.reshape(-1, d_model)
        n = tokens.shape[0]

        with jax.named_scope("moe_router"):
            router = _router(self.n_experts, jax.lax.Precision.HIGHEST)
            logits = router(tokens.astype(jnp.float32))
            scores = jax.nn.sigmoid(logits) if self.scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
            picked, weights = group_limited_top_k(
                scores, top_k=k, n_group=self.n_group, topk_group=self.topk_group
            )
            if self.normalize:
                weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
            weights = weights * self.scale
            # Pairs in (token, choice) order; absent experts sort last.
            local = picked.reshape(-1) - first
            held = (local >= 0) & (local < count)
            local = jnp.where(held, local, count)
            order = jnp.argsort(local, stable=True)
            sorted_local = local[order]
            # (by comparison with every pair: one fusion, where a binary search is a loop)
            bounds = jnp.searchsorted(
                sorted_local, jnp.arange(count + 1), side="left", method="compare_all"
            )
            sizes = (bounds[1:] - bounds[:-1]).astype(jnp.int32)  # (count,)
            counts = jnp.stack([jnp.sum(held, dtype=jnp.int32), jnp.sum(sizes > 0, dtype=jnp.int32)])
            self.sow("moe_stats", "counts", counts, reduce_fn=jnp.add,
                     init_fn=lambda: jnp.zeros((2,), jnp.int32))

        wg, wu, wo = _swiglu_expert_weights(
            self, count, d_model, self.d_ff, self.n_layers, self.param_dtype
        )
        with jax.named_scope("moe_experts"):
            rows = tokens.astype(self.dtype)[order // k]  # (N * k, d)

            def grouped(a: jax.Array, w: jax.Array) -> jax.Array:
                return jax.lax.ragged_dot(a, w.astype(self.dtype), sizes)

            h = nn.silu(grouped(rows, wg)) * grouped(rows, wu)
            out = grouped(h, wo)
            out = jnp.where((sorted_local < count)[:, None], out, 0)
            # Back to pair order, then each token's pairs under their weights.
            back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k, dtype=order.dtype))
            pairs = out[back].reshape(n, k, d_model).astype(jnp.float32)
            mixed = jnp.einsum("nk,nkd->nd", weights, pairs)  # an absent expert's pair is a zero row
        return mixed.astype(self.dtype).reshape(*lead, d_model)


__all__ = ["DroplessMoE", "MoEMLP", "group_limited_top_k"]

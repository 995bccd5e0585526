"""Plain reference of the A.X-K1 family (``model_type: axk1``; the layer
equations are the DeepSeek-V3 family's): float32 ``jax.numpy``, nothing else.

Pre-norm blocks, RMSNorm, two residuals, no biases, an untied head::

    h = x + MLA(RMSNorm(x))
    y = h + F_l(RMSNorm(h))     F_l = SwiGLU(intermediate_size) for l < first_k_dense_replace,
                                else the expert layer

    MLA:  c_q = RMSNorm(x W_qa) ; q = c_q W_qb -> heads of [q_nope | q_pe]
          [c | k_pe] = x W_kva ; c_kv = RMSNorm(c) ; k_pe is ONE row for all heads
          q_pe, k_pe rotated at their positions (rotate-half; YaRN's blended frequencies)
          [k_nope | v] = c_kv W_kvb per head
          score = (q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-0.5 * m^2 ,  m = 0.1 * mscale_all_dim * ln(factor) + 1
          out = concat_heads(causal_softmax(score) v) W_o
    experts:  s = sigmoid(x W_g) over ALL published experts, in groups of consecutive experts;
          a group's score is the sum of its two highest s; the topk_group best groups stay,
          the num_experts_per_tok highest s inside them are chosen (group-limited selection
          without bias correction: the configuration's ``assumed.topk_method``);
          w_i = routed_scaling_factor * s_i / sum_chosen s_j
          y = sum_{i chosen AND held here} w_i E_i(x) + Shared(x) ,  E(x) = W_down(silu(W_gate x) * W_up x)

Every head's keys and values are materialised for every position: no cache,
no absorption of ``W_kvb`` into the query, no grouped products (each held
expert runs over ALL tokens under its weight, zero where it was not chosen).
Matmuls run under ``jax.default_matmul_precision("highest")``. It imports
nothing of the program.

**A share.** The configuration holds ``experts_held = [first, count]`` of
the published experts (``n_routed_experts`` is that count;
``published.n_routed_experts`` the router's width): the router, the groups,
the chosen experts and the normalisation are the whole layer's, and what
the absent experts would add is left out, here as in the program.

**Weights that do not fit.** At the published widths the float32 weights of
the cut are 14 GB. Every weight is a pure function of ``(seed key, leaf,
layer)``, a routed expert's of ``(seed key, leaf, layer, expert)`` (so a
share draws the SAME expert the whole layer would) and the two vocabulary
matrices of ``(seed key, leaf, slice of the vocabulary)``: ``init_weights``
returns only a handle and :func:`served_token_gaps` makes one layer's
float32 weights at a time, runs every sequence through it with the heads in
blocks, and reads the head in slices of the vocabulary. The sequences are
PACKED into rows of the context length (a token attends the earlier tokens
of its own sequence only), so a layer is one compiled shape whatever the
lengths.

What the harness needs to know of the family is here too: the program's
model section, context and vocabulary, and the bytes a decode call must
move (:func:`weight_bytes`, :func:`expert_bytes`,
:func:`kv_bytes_per_position`).
"""

from __future__ import annotations

import math
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # float8_e4m3fn
VOCAB_SLICES = 8  # the vocabulary matrices are keyed, made and read in this many slices
TOKENS_PER_BATCH = 8192  # packed rows go through a layer this many tokens at a time
HEAD_BLOCK = 8  # attention heads go through the scores this many at a time
HEAD_ROWS = 16384  # served positions go through a slice of the head this many at a time


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative Python int (seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed % 2**32))
    key = jax.random.fold_in(key, np.uint32(seed // 2**32))
    return jax.random.fold_in(key, np.uint32(stream))


# ------------------------------------------------------------------ sizes


def dims(cfg: dict) -> dict[str, int]:
    first, count = (int(v) for v in cfg["experts_held"])
    experts = int(cfg["published"]["n_routed_experts"])
    if count != int(cfg["n_routed_experts"]) or first < 0 or first + count > experts:
        raise ValueError(f"experts_held {cfg['experts_held']} is not n_routed_experts of the {experts} experts")
    return {
        "d": int(cfg["hidden_size"]), "layers": int(cfg["num_hidden_layers"]), "vocab": int(cfg["vocab_size"]),
        "ff": int(cfg["intermediate_size"]), "h": int(cfg["num_attention_heads"]),
        "ql": int(cfg["q_lora_rank"]), "r": int(cfg["kv_lora_rank"]), "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "dense": int(cfg["first_k_dense_replace"]), "eff": int(cfg["moe_intermediate_size"]),
        "shared": int(cfg["n_shared_experts"]), "experts": experts, "first": first, "held": count,
        "k": int(cfg["num_experts_per_tok"]), "groups": int(cfg["n_group"]), "kept": int(cfg["topk_group"]),
    }


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < int(cfg["first_k_dense_replace"])


def layer_shapes(cfg: dict, layer: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """One layer's leaves but its routed experts: name -> (shape, how drawn)."""
    s = dims(cfg)
    d, h = s["d"], s["h"]
    shapes = {
        "attn_norm.g": ((d,), "scale"),
        "q_a.w": ((d, s["ql"]), "matrix"), "q_a_norm.g": ((s["ql"],), "scale"),
        "q_b.w": ((s["ql"], h * (s["nope"] + s["rope"])), "matrix"),
        "kv_a.w": ((d, s["r"] + s["rope"]), "matrix"), "kv_a_norm.g": ((s["r"],), "scale"),
        "kv_b.w": ((s["r"], h * (s["nope"] + s["v"])), "matrix"),
        "o.w": ((h * s["v"], d), "matrix"),
        "mlp_norm.g": ((d,), "scale"),
    }
    if is_dense(cfg, layer):
        ff = s["ff"]
        shapes.update({"gate.w": ((d, ff), "matrix"), "up.w": ((d, ff), "matrix"), "down.w": ((ff, d), "matrix")})
    else:
        ff = s["eff"] * s["shared"]
        shapes.update({
            "router.w": ((d, s["experts"]), "matrix"),
            "shared.gate.w": ((d, ff), "matrix"), "shared.up.w": ((d, ff), "matrix"),
            "shared.down.w": ((ff, d), "matrix"),
        })
    return shapes


_GLOBAL = ("embed", "head", "final_norm.g")  # leaf numbers 0, 1, 2
# Leaf numbers of a layer's leaves are fixed by name, dense or not.
_LEAF = {name: len(_GLOBAL) + i for i, name in enumerate((
    "attn_norm.g", "q_a.w", "q_a_norm.g", "q_b.w", "kv_a.w", "kv_a_norm.g", "kv_b.w", "o.w", "mlp_norm.g",
    "gate.w", "up.w", "down.w", "router.w", "shared.gate.w", "shared.up.w", "shared.down.w",
    "experts.gate.w", "experts.up.w", "experts.down.w",
))}
_FLOAT32_ALWAYS = ("router.w",)  # the program keeps the router's weights in float32 (the file's ``assumed``)


def _draw(key: jax.Array, shape: tuple[int, ...], kind: str) -> jax.Array:
    """The initialiser (the configuration's ``assumed``), always float32."""
    if kind == "matrix":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    raise ValueError(kind)


def _leaf_key(key: jax.Array, leaf: int, index: Any = 0) -> jax.Array:
    """``index`` (a layer, a slice of the vocabulary) may be traced."""
    return jax.random.fold_in(jax.random.fold_in(key, np.uint32(leaf)), jnp.asarray(index, jnp.uint32))


def make_expert(cfg: dict, key: jax.Array, layer: Any, expert: Any) -> dict:
    """One routed expert's three matrices, by its PUBLISHED index (float32)."""
    s = dims(cfg)
    shapes = {"experts.gate.w": (s["d"], s["eff"]), "experts.up.w": (s["d"], s["eff"]),
              "experts.down.w": (s["eff"], s["d"])}
    return {
        name: _draw(jax.random.fold_in(_leaf_key(key, _LEAF[name], layer), jnp.asarray(expert, jnp.uint32)),
                    shape, "matrix")
        for name, shape in shapes.items()
    }


def make_layer(cfg: dict, key: jax.Array, layer: int, dtype: Any = jnp.float32,
               held: tuple[int, int] | None = None) -> dict:
    """One layer's weights alone (traceable; ``layer`` is a Python int: it
    decides the layer's kind). An expert layer's routed experts are stacked
    on a leading axis, ``held = (first, count)`` of them (the file's own)."""
    out = {
        name: _draw(_leaf_key(key, _LEAF[name], layer), shape, kind).astype(
            jnp.float32 if name in _FLOAT32_ALWAYS else dtype)
        for name, (shape, kind) in layer_shapes(cfg, layer).items()
    }
    if not is_dense(cfg, layer):
        first, count = held or (dims(cfg)["first"], dims(cfg)["held"])
        experts = jax.lax.map(
            lambda e: jax.tree.map(lambda x: x.astype(dtype), make_expert(cfg, key, layer, e)),
            first + jnp.arange(count, dtype=jnp.uint32),
        )
        out.update(experts)
    return out


def vocab_slice_rows(cfg: dict) -> int:
    vocab = int(cfg["vocab_size"])
    if vocab % VOCAB_SLICES:
        raise ValueError(f"vocab_size {vocab} is not a multiple of {VOCAB_SLICES}")
    return vocab // VOCAB_SLICES


def embed_slice(cfg: dict, key: jax.Array, index: Any) -> jax.Array:
    """Rows ``[index * rows, (index + 1) * rows)`` of the embedding, float32."""
    return _draw(_leaf_key(key, 0, index), (vocab_slice_rows(cfg), int(cfg["hidden_size"])), "matrix")


def head_slice(cfg: dict, key: jax.Array, index: Any) -> jax.Array:
    """Columns of the same range of the untied head ``(d, vocab)``, float32."""
    return _draw(_leaf_key(key, 1, index), (int(cfg["hidden_size"]), vocab_slice_rows(cfg)), "matrix")


def _assemble(make_slice, shape: tuple[int, int], axis: int, dtype: Any) -> jax.Array:
    """A vocabulary matrix written slice by slice into a buffer of ``dtype``."""
    rows = shape[axis] // VOCAB_SLICES

    def body(i, buf):
        at = (i * rows, 0) if axis == 0 else (0, i * rows)
        return jax.lax.dynamic_update_slice(buf, make_slice(i).astype(dtype), at)

    return jax.lax.fori_loop(0, VOCAB_SLICES, body, jnp.zeros(shape, dtype))


def make_weights(cfg: dict, key: jax.Array, dtype: Any = jnp.float32,
                 held: tuple[int, int] | None = None) -> dict:
    """Every weight from ``key`` (traceable: call it under ``jax.jit``), drawn
    in float32 and cast, so the bf16 weights a server holds are the rounding
    of the float32 weights the reference makes. ``held`` overrides the
    file's share (tests: the uncut layer, another share)."""
    s = dims(cfg)
    return {
        "embed": _assemble(lambda i: embed_slice(cfg, key, i), (s["vocab"], s["d"]), 0, dtype),
        "head": _assemble(lambda i: head_slice(cfg, key, i), (s["d"], s["vocab"]), 1, dtype),
        "final_norm.g": _draw(_leaf_key(key, 2), (s["d"],), "scale").astype(dtype),
        "layers": [make_layer(cfg, key, i, dtype, held) for i in range(s["layers"])],
    }


def init_weights(cfg: dict, seed: int, dtype: Any = jnp.float32) -> dict:
    """The reference's own copy is a HANDLE: the key every weight is a pure
    function of. ``dtype`` is accepted for the interface and ignored."""
    return {"key": seed_key(seed, 1)}


# ------------------------------------------- what the harness asks the family


def context_length(cfg: dict) -> int:
    return int(cfg["max_position_embeddings"])


def vocab_size(cfg: dict) -> int:
    return int(cfg["vocab_size"])


_EXTRA_KEYS = (
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
    "num_experts_per_tok", "n_group", "topk_group", "n_shared_experts", "first_k_dense_replace", "norm_topk_prob",
    "routed_scaling_factor", "scoring_func", "rope_theta", "rope_scaling", "rms_norm_eps", "experts_held",
)


def program_model(cfg: dict, seq_len: int | None = None) -> dict:
    """The ``model`` section of the program's run config: the file's
    ``program.model`` block plus the published sizes under the program's
    field names (``model.extra`` keeps the published names; the router's
    width is the PUBLISHED count of experts, the share is ``experts_held``)."""
    model = dict(cfg["program"]["model"])
    extra = dict(model.get("extra", {}))
    extra.update({k: cfg[k] for k in _EXTRA_KEYS})
    extra["n_routed_experts"] = dims(cfg)["experts"]
    model["extra"] = extra
    model.update(
        block_size=int(seq_len or cfg["max_position_embeddings"]), d_model=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]), n_heads=int(cfg["num_attention_heads"]),
        d_ff=int(cfg["intermediate_size"]), vocab_size=int(cfg["vocab_size"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )
    return model


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "reference/axk1.py: train_flops_per_token: the configuration has no training cell "
        "(16 B a parameter of the smallest cut do not fit one chip: PERF.md section 4)"
    )


def expert_params(cfg: dict) -> int:
    s = dims(cfg)
    return 3 * s["d"] * s["eff"]


def layer_params(cfg: dict, layer: int) -> int:
    """A layer's parameters OUTSIDE its routed experts."""
    return sum(math.prod(shape) for shape, _ in layer_shapes(cfg, layer).values())


def total_params(cfg: dict) -> int:
    s = dims(cfg)
    layers = sum(layer_params(cfg, i) + (0 if is_dense(cfg, i) else s["held"] * expert_params(cfg))
                 for i in range(s["layers"]))
    return layers + 2 * s["vocab"] * s["d"] + s["d"]


def weight_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of weights EVERY decode call must read: every layer outside its
    routed experts (the router's in float32) and the head; of the embedding
    only the rows of the call's tokens, counted as nothing. The routed
    experts a call touches are counted by :func:`expert_bytes`."""
    s = dims(cfg)
    routers = (s["layers"] - s["dense"]) * s["d"] * s["experts"]
    values = sum(layer_params(cfg, i) for i in range(s["layers"])) + s["vocab"] * s["d"] + s["d"]
    return (values - routers) * bytes_per_value + routers * 4


def expert_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """One routed expert's three matrices: read whole by a call in which any
    token picked it."""
    return expert_params(cfg) * bytes_per_value


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """The latent row ``[c_kv | k_pe]`` of every layer for one position."""
    s = dims(cfg)
    return s["layers"] * (s["r"] + s["rope"]) * bytes_per_value


# ------------------------------------------------------------------ names


def program_tree(w: dict, cfg: dict) -> dict:
    """The arrays of :func:`make_weights` under the names and shapes
    ``llmtrain_tpu.models.latent_moe`` gives its parameters (a renaming and
    reshaping only)."""
    s = dims(cfg)
    h, nope, rope, v = s["h"], s["nope"], s["rope"], s["v"]
    tree: dict[str, Any] = {
        "token_embedding": {"embedding": w["embed"]},
        "lm_head": {"kernel": w["head"]},
        "norm_f": {"scale": w["final_norm.g"]},
    }
    for i, lw in enumerate(w["layers"]):
        block = {
            "attn_norm": {"scale": lw["attn_norm.g"]},
            "attn": {
                "q_a_proj": {"kernel": lw["q_a.w"]}, "q_a_norm": {"scale": lw["q_a_norm.g"]},
                "q_b_proj": {"kernel": lw["q_b.w"].reshape(s["ql"], h, nope + rope)},
                "kv_a_proj": {"kernel": lw["kv_a.w"]}, "kv_a_norm": {"scale": lw["kv_a_norm.g"]},
                "kv_b_proj": lw["kv_b.w"].reshape(s["r"], h, nope + v),
                "o_proj": {"kernel": lw["o.w"].reshape(h, v, s["d"])},
            },
            "mlp_norm": {"scale": lw["mlp_norm.g"]},
        }
        if "gate.w" in lw:
            block.update({"mlp_gate": {"kernel": lw["gate.w"]}, "mlp_up": {"kernel": lw["up.w"]},
                          "mlp_down": {"kernel": lw["down.w"]}})
        else:
            block["moe"] = {"router": {"kernel": lw["router.w"]}, "wg": lw["experts.gate.w"],
                            "wu": lw["experts.up.w"], "wo": lw["experts.down.w"]}
            block["shared_expert"] = {"mlp_gate": {"kernel": lw["shared.gate.w"]},
                                      "mlp_up": {"kernel": lw["shared.up.w"]},
                                      "mlp_down": {"kernel": lw["shared.down.w"]}}
        tree[f"block_{i}"] = block
    return tree


# ---------------------------------------------------------------- forward


def _round_to(x: jax.Array, precision: str) -> jax.Array:
    """``x`` rounded to the control's type and back."""
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown control precision {precision!r}")


def _mm(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    return jnp.matmul(_round_to(a, precision), _round_to(b, precision))


def _rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def yarn(cfg: dict) -> tuple[jax.Array, float]:
    """(inverse frequency of each rotary pair, softmax scale). Pair ``i`` of
    the ``qk_rope_head_dim / 2`` turns ``theta^(-2i/dim)`` radians a position;
    under YaRN, pairs that make fewer than ``beta_slow`` turns over the
    ORIGINAL context are slowed by ``factor``, pairs that make more than
    ``beta_fast`` are kept, and a linear ramp over the pair index blends the
    two between the (floored, ceiled) indices where those turn counts fall."""
    s = dims(cfg)
    dim, theta = s["rope"], float(cfg["rope_theta"])
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    scale = (s["nope"] + s["rope"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if not rs:
        return freq, scale
    if rs["type"] != "yarn" or float(rs["mscale"]) != float(rs["mscale_all_dim"]):
        raise ValueError("only rope_scaling of type yarn with mscale == mscale_all_dim is written down here")
    factor, original = float(rs["factor"]), float(rs["original_max_position_embeddings"])

    def pair_of(turns: float) -> float:  # the pair index that makes `turns` turns over the original context
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(rs["beta_slow"]))), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    m = 0.1 * float(rs["mscale_all_dim"]) * math.log(factor) + 1.0 if factor > 1 else 1.0
    return freq * (1.0 - ramp) + freq / factor * ramp, scale * m * m


def _rope(x: jax.Array, pos: jax.Array, inv_freq: jax.Array) -> jax.Array:
    """Rotate-half over the last axis; x (B, T, ..., dim), pos (B, T)."""
    ang = pos.astype(jnp.float32)[..., None] * inv_freq  # (B, T, dim / 2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def routing(scores: jax.Array, cfg: dict) -> jax.Array:
    """(N, experts) sigmoid scores -> (N, experts) weights: ``routed_scaling_factor
    * s_i / sum_chosen s_j`` at the chosen experts, 0 elsewhere."""
    s = dims(cfg)
    n = scores.shape[0]
    grouped = scores.reshape(n, s["groups"], -1)
    group_score = jnp.sum(jnp.sort(grouped, -1)[..., -2:], -1)  # its two highest (a group of one: that one)
    best = jnp.argsort(-group_score, axis=-1, stable=True)[:, : s["kept"]]  # ties to the lower index
    stays = jnp.zeros_like(group_score, bool).at[jnp.arange(n)[:, None], best].set(True)
    masked = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(n, -1)
    order = jnp.argsort(-masked, axis=-1, stable=True)[:, : s["k"]]
    chosen = jnp.zeros_like(scores, bool).at[jnp.arange(n)[:, None], order].set(True)
    picked = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return picked * float(cfg["routed_scaling_factor"])


def layer_forward(lw: dict, h: jax.Array, pos: jax.Array, seg: jax.Array, cfg: dict,
                  precision: str = "f32", held: tuple[int, int] | None = None) -> jax.Array:
    """One block over (B, T, d) float32 hidden states. ``pos`` (B, T) is each
    token's position in its own sequence and ``seg`` (B, T) names that
    sequence: a token attends the tokens of its sequence at or before it."""
    s = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    b, t, d = h.shape
    heads, nope, rope, v = s["h"], s["nope"], s["rope"], s["v"]
    lw = {k: w.astype(jnp.float32) for k, w in lw.items()}
    inv_freq, scale = yarn(cfg)

    x = _rms(h, lw["attn_norm.g"], eps)
    q = _mm(_rms(_mm(x, lw["q_a.w"], precision), lw["q_a_norm.g"], eps), lw["q_b.w"], precision)
    q = q.reshape(b, t, heads, nope + rope)
    kv_a = _mm(x, lw["kv_a.w"], precision)
    c_kv = _rms(kv_a[..., : s["r"]], lw["kv_a_norm.g"], eps)
    k_pe = _rope(kv_a[..., s["r"]:], pos, inv_freq)  # (B, T, rope): one row for all heads
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv_freq)], -1)
    kv = _mm(c_kv, lw["kv_b.w"], precision).reshape(b, t, heads, nope + v)
    live = (seg[:, :, None] == seg[:, None, :]) & jnp.tril(jnp.ones((t, t), bool))[None]

    def head_block(qkv):
        qb, kb = qkv  # (B, T, hb, nope + rope), (B, T, hb, nope + v)
        keys = jnp.concatenate([kb[..., :nope], jnp.broadcast_to(k_pe[:, :, None], kb.shape[:3] + (rope,))], -1)
        scores = _mm(qb.transpose(0, 2, 1, 3), keys.transpose(0, 2, 3, 1), precision) * scale  # (B, hb, T, T)
        probs = jax.nn.softmax(jnp.where(live[:, None], scores, -jnp.inf), -1)
        return _mm(probs, kb[..., nope:].transpose(0, 2, 1, 3), precision)  # (B, hb, T, v)

    hb = math.gcd(heads, HEAD_BLOCK)
    blocks = lambda a: jnp.moveaxis(a.reshape(b, t, heads // hb, hb, a.shape[-1]), 2, 0)  # noqa: E731
    out = jax.lax.map(head_block, (blocks(q), blocks(kv)))  # (blocks, B, hb, T, v)
    out = out.transpose(1, 3, 0, 2, 4).reshape(b, t, heads * v)
    h = h + _mm(out, lw["o.w"], precision)

    x = _rms(h, lw["mlp_norm.g"], eps)

    def swiglu(gate, up, down, x_):
        return _mm(jax.nn.silu(_mm(x_, gate, precision)) * _mm(x_, up, precision), down, precision)

    if "gate.w" in lw:
        return h + swiglu(lw["gate.w"], lw["up.w"], lw["down.w"], x)
    first, count = held or (s["first"], s["held"])
    tokens = x.reshape(b * t, d)
    weights = routing(jax.nn.sigmoid(_mm(tokens, lw["router.w"], precision)), cfg)  # (N, experts)

    def add_expert(i, acc):  # every token through held expert i, under its weight (0 where not chosen)
        e = jax.tree.map(lambda w: w[i], {k: lw[f"experts.{k}.w"] for k in ("gate", "up", "down")})
        w_i = jax.lax.dynamic_index_in_dim(weights, first + i, axis=1, keepdims=True)
        return acc + w_i * swiglu(e["gate"], e["up"], e["down"], tokens)

    routed = jax.lax.fori_loop(0, count, add_expert, jnp.zeros_like(tokens)) if count else 0.0  # a holder of none
    shared = swiglu(lw["shared.gate.w"], lw["shared.up.w"], lw["shared.down.w"], tokens)
    return h + (routed + shared).reshape(b, t, d)


def _embedding(key: jax.Array, cfg: dict) -> jax.Array:
    return jnp.concatenate([embed_slice(cfg, key, i) for i in range(VOCAB_SLICES)], 0)


def _whole(w: dict, cfg: dict) -> dict:
    """Every weight at once, for sizes that fit: the handle's key turned
    into :func:`make_weights`' tree (a tree passes through)."""
    return make_weights(cfg, w["key"]) if "key" in w else w


def hidden_states(w: dict, ids: jax.Array, cfg: dict, precision: str = "f32",
                  held: tuple[int, int] | None = None) -> jax.Array:
    """(B, T) token ids -> (B, T, d) final-norm hidden states, float32; every
    row one sequence from position 0."""
    w = _whole(w, cfg)
    h = w["embed"].astype(jnp.float32)[ids]
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    seg = jnp.ones(ids.shape, jnp.int32)
    for lw in w["layers"]:
        h = layer_forward(lw, h, pos, seg, cfg, precision, held)
    return _rms(h, w["final_norm.g"].astype(jnp.float32), float(cfg["rms_norm_eps"]))


def logits_fn(w: dict, ids: jax.Array, cfg: dict, precision: str = "f32",
              held: tuple[int, int] | None = None) -> jax.Array:
    """``w`` is :func:`init_weights`' handle or :func:`make_weights`' tree."""
    w = _whole(w, cfg)
    return _mm(hidden_states(w, ids, cfg, precision, held), w["head"].astype(jnp.float32), precision)


# ------------------------------------------------------- serving yardstick


def pack(lengths: list[int], size: int) -> list[list[int]]:
    """Sequences (by index) into rows of ``size`` positions, longest first
    into the first row that still has room."""
    rows: list[tuple[int, list[int]]] = []
    for i in sorted(range(len(lengths)), key=lambda j: -lengths[j]):
        if lengths[i] > size:
            raise ValueError(f"a sequence of {lengths[i]} positions exceeds the context ({size})")
        for r, (used, members) in enumerate(rows):
            if used + lengths[i] <= size:
                rows[r] = (used + lengths[i], members + [i])
                break
        else:
            rows.append((lengths[i], [i]))
    return [members for _, members in rows]


def served_token_gaps(w: dict, cfg: dict, sequences: list[tuple[np.ndarray, np.ndarray]],
                      precision: str = "f32", pad_to: tuple[int, ...] = ()) -> dict[str, Any]:
    """The two numbers ``reference/gpt2.py:served_token_gaps`` returns (and
    with ``precision`` below f32 the control's two, read at the same
    positions), for a model whose float32 weights do not fit the chip: the
    sequences, packed into rows of the context length (``pad_to`` is not
    needed: a packed row has one shape), go through ONE layer at a time,
    that layer's float32 weights made alone; then the final norm and the
    head, a slice of the vocabulary at a time, at the served positions only.
    Only per-position gaps leave the device."""
    key = w["key"]
    s = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    streams = ("f32",) if precision == "f32" else ("f32", precision)
    size = context_length(cfg)
    rows_per_batch = max(1, TOKENS_PER_BATCH // size)

    # Packed rows: token ids, each token's position in its sequence, its sequence (0 = padding).
    packed = pack([len(p) + len(t) for p, t in sequences], size)
    packed += [[]] * (-len(packed) % rows_per_batch)
    ids = np.zeros((len(packed), size), np.int32)
    pos = np.zeros((len(packed), size), np.int32)
    seg = np.zeros((len(packed), size), np.int32)
    start: dict[int, tuple[int, int]] = {}  # sequence -> (row, column of its first token)
    for r, members in enumerate(packed):
        at = 0
        for i in members:
            seq = np.concatenate(sequences[i]).astype(np.int32)
            ids[r, at : at + len(seq)], pos[r, at : at + len(seq)], seg[r, at : at + len(seq)] = (
                seq, np.arange(len(seq)), i + 1)
            start[i] = (r, at)
            at += len(seq)
    batches = [slice(b, b + rows_per_batch) for b in range(0, len(packed), rows_per_batch)]

    clock = [time.perf_counter()]

    def phase(name: str, *arrays) -> None:
        """One line a phase, so a run that is cut says where it was."""
        jax.block_until_ready(arrays)
        now = time.perf_counter()
        print(f"[reference axk1] {name}: {now - clock[0]:.1f}s", flush=True)
        clock[0] = now

    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda k: _embedding(k, cfg))(key)
        look_up = jax.jit(lambda e, i: e[i])
        hidden = {st: [look_up(embed, jnp.asarray(ids[b])) for b in batches] for st in streams}
        del embed
        phase(f"{len(sequences)} sequences packed into {len(packed)} rows of {size}, embedded", hidden)
        donate = (1,) if jax.default_backend() == "tpu" else ()
        make = jax.jit(lambda k, layer: make_layer(cfg, k, layer), static_argnames=("layer",))
        run = jax.jit(lambda lw, h, p, g, prec: layer_forward(lw, h, p, g, cfg, prec),
                      static_argnames=("prec",), donate_argnums=donate)
        where = [(jnp.asarray(pos[b]), jnp.asarray(seg[b])) for b in batches]
        for layer in range(s["layers"]):
            lw = make(key, layer=layer)
            for st in streams:
                hidden[st] = [run(lw, h, p, g, prec=st) for h, (p, g) in zip(hidden[st], where)]
            del lw
            phase(f"layer {layer} ({' and '.join(streams)})", hidden)

        # Position p predicts token p + 1: served token j of a sequence sits at len(prompt) + j.
        g_final = jax.jit(lambda k: _draw(_leaf_key(k, 2), (s["d"],), "scale"))(key)
        final_norm = jax.jit(lambda h: _rms(h, g_final, eps))
        r_idx, c_idx, owner, first = [], [], [], []
        for i, (prompt, served) in enumerate(sequences):
            r, at = start[i]
            r_idx += [r] * len(served)
            c_idx += range(at + len(prompt) - 1, at + len(prompt) - 1 + len(served))
            owner += [int(t) for t in served]
            first += [j == 0 for j in range(len(served))]
        at_served = {}
        for st in streams:
            normed = np.concatenate([np.asarray(final_norm(h)) for h in hidden[st]], 0)
            at_served[st] = normed[r_idx, c_idx]
            hidden[st] = None
        del hidden
        phase("final norm, served positions to the host")
        rows = vocab_slice_rows(cfg)

        @partial(jax.jit, static_argnames=("prec",))
        def head_pass(k, index, h_ref, h_low, tok, carry, prec):
            best, picked, low_best, ref_at_low = carry
            hs = head_slice(cfg, k, index)
            ref = jnp.matmul(h_ref, hs)
            low = ref if prec == "f32" else _mm(h_low, hs, prec)
            local = tok - index * rows
            inside = (local >= 0) & (local < rows)
            mine = jnp.take_along_axis(ref, jnp.clip(local, 0, rows - 1)[:, None], -1)[:, 0]
            arg = jnp.argmax(low, -1)
            low_here = jnp.take_along_axis(low, arg[:, None], -1)[:, 0]
            ref_here = jnp.take_along_axis(ref, arg[:, None], -1)[:, 0]
            better = low_here > low_best  # strict: the first of equal maxima, as one argmax over the vocabulary
            return (jnp.maximum(best, jnp.max(ref, -1)), jnp.where(inside, mine, picked),
                    jnp.where(better, low_here, low_best), jnp.where(better, ref_here, ref_at_low))

        # Chunks of HEAD_ROWS positions, the last padded: one compiled shape.
        total = len(owner)
        pad = -total % HEAD_ROWS
        h_ref = np.pad(at_served["f32"], [(0, pad), (0, 0)])
        h_low = np.pad(at_served[streams[-1]], [(0, pad), (0, 0)])
        served_tok = np.pad(np.asarray(owner, np.int32), (0, pad))
        served_gap, low_gap = [], []
        for begin in range(0, total + pad, HEAD_ROWS):
            sl = slice(begin, begin + HEAD_ROWS)
            part = tuple(jnp.asarray(x[sl]) for x in (h_ref, h_low, served_tok))
            carry = tuple(jnp.full((HEAD_ROWS,), -jnp.inf, jnp.float32) for _ in range(4))
            for index in range(VOCAB_SLICES):
                carry = head_pass(key, np.int32(index), *part, carry, prec=precision)
            best, picked, _, ref_at_low = carry
            served_gap.append(np.asarray(best - picked, np.float64))
            low_gap.append(np.asarray(best - ref_at_low, np.float64))
    served_gap, low_gap = np.concatenate(served_gap)[:total], np.concatenate(low_gap)[:total]
    phase(f"head over {total} served positions")
    first = np.asarray(first)
    return {
        "widest_gap": float(served_gap.max()),
        "first_mean_gap": float(served_gap[first].mean()),
        "control_widest_gap": float(low_gap.max()),
        "control_first_mean_gap": float(low_gap[first].mean()),
        "tokens": int(len(served_gap)),
    }

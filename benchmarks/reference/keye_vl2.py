"""Plain reference of the Keye-VL-2.0 language model (``model_type:
KeyeVL2``): float32 ``jax.numpy``, nothing else.

Pre-norm blocks, RMSNorm (eps ``rms_norm_eps``), two residuals, no biases,
an untied head. With ``h = RMSNorm(x)`` and position ``t``::

    1  q_t = RMSNorm_hd(W_q h_t) per head ; k_t = RMSNorm_hd(W_k h_t) per head ; v_t = W_v h_t
       rotate-half RoPE over the whole head, theta ``rope_theta`` (mrope_section with the three
       position components equal, which is what one stream of tokens gives, IS 1-D RoPE)
    2  indexer:  qI_{t,j} = (W_qI h_t)_j for the indexer's heads j ; kI_s = LayerNorm(W_kI h_s), ONE head ;
       w_t = W_w h_t ; RoPE (same theta) over the whole index head of qI and kI
       index score  I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      for s <= t
    3  S_t = the ``topk`` positions s <= t with the highest I[t, s] (ties: the lower s); all of them while t < topk
    4  o_{t,a} = sum_{s in S_t} softmax_{s in S_t}(q_{t,a} . k_{s,g(a)} / sqrt(head_dim)) v_{s,g(a)} ; x <- x + W_o o_t
    5  experts on h' = RMSNorm(x):  p = softmax(W_r h') over ALL published experts ; the num_experts_per_tok
       largest (ties: the lower index), weights p_e / sum of the chosen (norm_topk_prob) ;
       x <- x + sum_{e chosen AND held here} weight_e W_down^e (silu(W_gate^e h') * W_up^e h')
    6  final RMSNorm, head

No cache, no kernel, no grouped product: full index scores, an exact top-k
MASK (one sort a row, ties by a running count), a masked softmax, and each
held expert over ALL tokens under its weight (zero where it was not chosen).
Queries go through steps 2-4 in blocks of ``QUERY_BLOCK`` so that a row of
6,656 positions fits (what ``q_chunk_size`` is read as: the configuration's
``assumed``); a block sees every position. Matmuls run under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program.

**A share.** The configuration holds ``experts_held = [first, count]`` of
the published experts (``num_experts`` is that count, ``published.num_experts``
the router's width): router, top-k and normalisation are the whole layer's,
and what the absent experts would add is left out, here as in the program.

**Memory that does not grow with the window.** Every weight is a pure
function of ``(seed key, leaf, layer)``, a routed expert's of ``(seed key,
leaf, layer, published expert index)``, the two vocabulary matrices of
``(seed key, leaf, slice)``. ``init_weights`` returns a handle;
:func:`served_token_gaps` packs the sequences into rows of the context
length and sends them through in GROUPS of a fixed number of positions: all
layers for one group (each layer's float32 weights made alone, 388 MB at the
published widths), then the head at every position of the group, and only
two float32 numbers a position leave the device. A window that finishes
three times as many requests runs three times as many groups in the same
memory. One line a group says where it is.

What the harness needs to know of the family is here too: the program's
model section, context and vocabulary, the bytes a decode call must move
(:func:`weight_bytes`, :func:`expert_bytes`, :func:`kv_bytes_per_position`,
:func:`index_bytes_per_position`) and the operations a prefill call needs
(:func:`prefill_flops`).
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
VOCAB_SLICES = 8  # the vocabulary matrices are keyed and made in this many slices
GROUP_POSITIONS = 8192  # packed rows go through all layers this many positions at a time
QUERY_BLOCK = 512  # queries go through index scores, selection and attention this many at a time


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative Python int (seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed % 2**32))
    key = jax.random.fold_in(key, np.uint32(seed // 2**32))
    return jax.random.fold_in(key, np.uint32(stream))


# ------------------------------------------------------------------ sizes


def dims(cfg: dict) -> dict[str, int]:
    first, count = (int(v) for v in cfg["experts_held"])
    experts = int(cfg["published"]["num_experts"])
    if count != int(cfg["num_experts"]) or first < 0 or first + count > experts:
        raise ValueError(f"experts_held {cfg['experts_held']} is not num_experts of the {experts} experts")
    sa = cfg["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the index key is ONE head a position: indexer_num_kv_heads must be 1")
    return {
        "d": int(cfg["hidden_size"]), "layers": int(cfg["num_hidden_layers"]), "vocab": int(cfg["vocab_size"]),
        "h": int(cfg["num_attention_heads"]), "kv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "ih": int(sa["indexer_num_heads"]), "id": int(sa["indexer_head_dim"]), "topk": int(sa["topk"]),
        "eff": int(cfg["moe_intermediate_size"]), "experts": experts, "first": first, "held": count,
        "k": int(cfg["num_experts_per_tok"]),
    }


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """One layer's leaves but its routed experts: name -> (shape, how drawn)."""
    s = dims(cfg)
    d, h, kv, hd = s["d"], s["h"], s["kv"], s["hd"]
    return {
        "attn_norm.g": ((d,), "scale"),
        "q.w": ((d, h * hd), "matrix"), "k.w": ((d, kv * hd), "matrix"), "v.w": ((d, kv * hd), "matrix"),
        "q_norm.g": ((hd,), "scale"), "k_norm.g": ((hd,), "scale"),
        "o.w": ((h * hd, d), "matrix"),
        "index_q.w": ((d, s["ih"] * s["id"]), "matrix"), "index_k.w": ((d, s["id"]), "matrix"),
        "index_k_norm.g": ((s["id"],), "scale"), "index_k_norm.b": ((s["id"],), "shift"),
        "index_w.w": ((d, s["ih"]), "matrix"),
        "mlp_norm.g": ((d,), "scale"),
        "router.w": ((d, s["experts"]), "matrix"),
    }


_GLOBAL = ("embed", "head", "final_norm.g")  # leaf numbers 0, 1, 2
_LEAF = {name: len(_GLOBAL) + i for i, name in enumerate((
    "attn_norm.g", "q.w", "k.w", "v.w", "q_norm.g", "k_norm.g", "o.w", "index_q.w", "index_k.w",
    "index_k_norm.g", "index_k_norm.b", "index_w.w", "mlp_norm.g", "router.w",
    "experts.gate.w", "experts.up.w", "experts.down.w",
))}
_FLOAT32_ALWAYS = ("router.w",)  # the program keeps the router's weights in float32 (the file's ``assumed``)
_NORMS = ("attn_norm.g", "q_norm.g", "k_norm.g", "index_k_norm.g", "index_k_norm.b", "mlp_norm.g")


def _draw(key: jax.Array, shape: tuple[int, ...], kind: str) -> jax.Array:
    """The initialiser (the configuration's ``assumed``), always float32."""
    if kind in ("matrix", "shift"):
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


def make_layer(cfg: dict, key: jax.Array, layer: Any, dtype: Any = jnp.float32,
               held: tuple[int, int] | None = None) -> dict:
    """One layer's weights alone (traceable). The routed experts are stacked
    on a leading axis, ``held = (first, count)`` of them (the file's own)."""
    out = {
        name: _draw(_leaf_key(key, _LEAF[name], layer), shape, kind).astype(
            jnp.float32 if name in _FLOAT32_ALWAYS else dtype)
        for name, (shape, kind) in layer_shapes(cfg).items()
    }
    first, count = held or (dims(cfg)["first"], dims(cfg)["held"])
    out.update(jax.lax.map(
        lambda e: jax.tree.map(lambda x: x.astype(dtype), make_expert(cfg, key, layer, e)),
        first + jnp.arange(count, dtype=jnp.uint32),
    ))
    return out


def vocab_slice_rows(cfg: dict) -> int:
    vocab = int(cfg["vocab_size"])
    if vocab % VOCAB_SLICES:
        raise ValueError(f"vocab_size {vocab} is not a multiple of {VOCAB_SLICES}")
    return vocab // VOCAB_SLICES


def _vocab_matrix(cfg: dict, key: jax.Array, leaf: int, dtype: Any = jnp.float32) -> jax.Array:
    """The embedding ``(vocab, d)`` (leaf 0) or the head ``(d, vocab)`` (leaf 1),
    slice by slice of the vocabulary."""
    rows, d = vocab_slice_rows(cfg), int(cfg["hidden_size"])
    shape = (rows, d) if leaf == 0 else (d, rows)
    slices = [_draw(_leaf_key(key, leaf, i), shape, "matrix").astype(dtype) for i in range(VOCAB_SLICES)]
    return jnp.concatenate(slices, axis=leaf)


def make_weights(cfg: dict, key: jax.Array, dtype: Any = jnp.float32,
                 held: tuple[int, int] | None = None) -> dict:
    """Every weight from ``key`` (traceable: call it under ``jax.jit``), drawn
    in float32 and cast, so the bf16 weights a server holds are the rounding
    of the float32 weights the reference makes. ``held`` overrides the
    file's share (tests: the uncut layer, another share)."""
    s = dims(cfg)
    return {
        "embed": _vocab_matrix(cfg, key, 0, dtype),
        "head": _vocab_matrix(cfg, key, 1, dtype),
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
    "num_key_value_heads", "head_dim", "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
    "sa_config", "rope_theta", "rope_scaling", "rms_norm_eps", "decoder_sparse_step", "mlp_only_layers",
    "experts_held",
)


def program_model(cfg: dict, seq_len: int | None = None) -> dict:
    """The ``model`` section of the program's run config: the file's
    ``program.model`` block plus the published sizes under the program's
    field names (``model.extra`` keeps the published names; the router's
    width is the PUBLISHED count of experts, the share is ``experts_held``)."""
    model = dict(cfg["program"]["model"])
    extra = dict(model.get("extra", {}))
    extra.update({k: cfg[k] for k in _EXTRA_KEYS})
    extra["num_experts"] = dims(cfg)["experts"]
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
        "reference/keye_vl2.py: train_flops_per_token: the configuration has no training cell "
        "(the config gives no training objective for the indexer: PERF.md section 4)"
    )


def expert_params(cfg: dict) -> int:
    s = dims(cfg)
    return 3 * s["d"] * s["eff"]


def layer_params(cfg: dict) -> int:
    """A layer's parameters OUTSIDE its routed experts."""
    return sum(math.prod(shape) for shape, _ in layer_shapes(cfg).values())


def total_params(cfg: dict) -> int:
    s = dims(cfg)
    return s["layers"] * (layer_params(cfg) + s["held"] * expert_params(cfg)) + 2 * s["vocab"] * s["d"] + s["d"]


def weight_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of weights EVERY decode call must read: every layer outside its
    routed experts (the router's in float32) and the head; of the embedding
    only the rows of the call's tokens, counted as nothing. The routed
    experts a call touches are counted by :func:`expert_bytes`."""
    s = dims(cfg)
    routers = s["layers"] * s["d"] * s["experts"]
    values = s["layers"] * layer_params(cfg) + s["vocab"] * s["d"] + s["d"]
    return (values - routers) * bytes_per_value + routers * 4


def expert_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """One routed expert's three matrices: read whole by a call in which any
    token picked it."""
    return expert_params(cfg) * bytes_per_value


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of every K/V head of every layer for one position: what a
    query reads of each position it ATTENDS."""
    s = dims(cfg)
    return s["layers"] * 2 * s["kv"] * s["hd"] * bytes_per_value


def index_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """The index key of every layer for one position: what a query reads of
    each live position to SCORE it."""
    s = dims(cfg)
    return s["layers"] * s["id"] * bytes_per_value


def prefill_flops(cfg: dict, prompt_tokens: int, index_pairs: int, selected_pairs: int) -> float:
    """Operations a prefill of ``prompt_tokens`` true tokens needs (a
    multiply-add is two): every token through every layer's matrices outside
    the experts and through the pairs routed to experts held here (their
    EXPECTED number under even routing, ``k * held / experts`` a token: the
    program counts expert pairs in decode calls only); ``index_pairs``
    (query, position) index dots of every indexer head; ``selected_pairs``
    (query, attended position) scores and values of every head; the head for
    ONE position (the one the first token is sampled at)."""
    s = dims(cfg)
    matrices = layer_params(cfg) - sum(math.prod(layer_shapes(cfg)[n][0]) for n in _NORMS)
    pairs_held = s["k"] * s["held"] / s["experts"]
    a_token = 2.0 * (matrices + pairs_held * expert_params(cfg))
    index = 2.0 * s["ih"] * s["id"] * index_pairs
    attention = 4.0 * s["h"] * s["hd"] * selected_pairs
    return s["layers"] * (prompt_tokens * a_token + index + attention) + 2.0 * s["d"] * s["vocab"]


# ------------------------------------------------------------------ names


def program_tree(w: dict, cfg: dict) -> dict:
    """The arrays of :func:`make_weights` under the names and shapes
    ``llmtrain_tpu.models.indexed_moe`` gives its parameters (a renaming and
    reshaping only)."""
    s = dims(cfg)
    d, h, kv, hd = s["d"], s["h"], s["kv"], s["hd"]
    tree: dict[str, Any] = {
        "token_embedding": {"embedding": w["embed"]},
        "lm_head": {"kernel": w["head"]},
        "norm_f": {"scale": w["final_norm.g"]},
    }
    for i, lw in enumerate(w["layers"]):
        tree[f"block_{i}"] = {
            "attn_norm": {"scale": lw["attn_norm.g"]},
            "attn": {
                "q_proj": {"kernel": lw["q.w"].reshape(d, h, hd)}, "q_norm": {"scale": lw["q_norm.g"]},
                "k_proj": {"kernel": lw["k.w"].reshape(d, kv, hd)}, "k_norm": {"scale": lw["k_norm.g"]},
                "v_proj": {"kernel": lw["v.w"].reshape(d, kv, hd)},
                "index_q_proj": {"kernel": lw["index_q.w"].reshape(d, s["ih"], s["id"])},
                "index_k_proj": {"kernel": lw["index_k.w"]},
                "index_k_norm": {"scale": lw["index_k_norm.g"], "bias": lw["index_k_norm.b"]},
                "index_weight_proj": {"kernel": lw["index_w.w"]},
                "o_proj": {"kernel": lw["o.w"].reshape(h, hd, d)},
            },
            "mlp_norm": {"scale": lw["mlp_norm.g"]},
            "moe": {"router": {"kernel": lw["router.w"]}, "wg": lw["experts.gate.w"],
                    "wu": lw["experts.up.w"], "wo": lw["experts.down.w"]},
        }
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


def _layer_norm(x: jax.Array, g: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half over the last axis; x (B, T, ..., dim), pos (B, T)."""
    dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos.astype(jnp.float32)[..., None] * inv_freq  # (B, T, dim / 2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)
    half = dim // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def top_k_mask(scores: jax.Array, k: int) -> jax.Array:
    """(N, S) scores, ``-inf`` where a position may not be seen -> (N, S)
    bool: the ``k`` highest of each row, of equal scores the LOWER column
    first; every seeable position of a row that has at most ``k``."""
    seen = scores > -jnp.inf
    if scores.shape[-1] <= k:
        return seen
    kth = jnp.sort(scores, axis=-1)[:, scores.shape[-1] - k][:, None]  # the k-th highest
    above, ties = scores > kth, scores == kth
    room = k - jnp.sum(above, -1, keepdims=True)  # how many of the ties are in
    return (above | (ties & (jnp.cumsum(ties, -1) <= room))) & seen


def routing(probs: jax.Array, cfg: dict) -> jax.Array:
    """(N, experts) softmax probabilities -> (N, experts) weights: ``p_e /
    sum_chosen p`` at the ``num_experts_per_tok`` largest (ties: the lower
    index), 0 elsewhere."""
    n = probs.shape[0]
    order = jnp.argsort(-probs, axis=-1, stable=True)[:, : dims(cfg)["k"]]
    chosen = jnp.zeros_like(probs, bool).at[jnp.arange(n)[:, None], order].set(True)
    picked = jnp.where(chosen, probs, 0.0)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return picked


def layer_forward(lw: dict, h: jax.Array, pos: jax.Array, seg: jax.Array, cfg: dict,
                  precision: str = "f32", held: tuple[int, int] | None = None) -> jax.Array:
    """One block over (B, T, d) float32 hidden states. ``pos`` (B, T) is each
    token's position in its own sequence and ``seg`` (B, T) names that
    sequence: a token sees the tokens of its sequence at or before it."""
    s = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    b, t, d = h.shape
    heads, kv, hd, ih, idim = s["h"], s["kv"], s["hd"], s["ih"], s["id"]
    lw = {k: w.astype(jnp.float32) for k, w in lw.items()}

    x = _rms(h, lw["attn_norm.g"], eps)
    q = _rope(_rms(_mm(x, lw["q.w"], precision).reshape(b, t, heads, hd), lw["q_norm.g"], eps), pos, theta)
    k = _rope(_rms(_mm(x, lw["k.w"], precision).reshape(b, t, kv, hd), lw["k_norm.g"], eps), pos, theta)
    v = _mm(x, lw["v.w"], precision).reshape(b, t, kv, hd)
    q_i = _rope(_mm(x, lw["index_q.w"], precision).reshape(b, t, ih, idim), pos, theta)
    k_i = _rope(_layer_norm(_mm(x, lw["index_k.w"], precision), lw["index_k_norm.g"], lw["index_k_norm.b"], eps),
                pos, theta)  # (B, T, id): one key a position
    w_i = _mm(x, lw["index_w.w"], precision)  # (B, T, ih)
    keys = jnp.repeat(k, heads // kv, axis=2).transpose(0, 2, 3, 1)  # (B, h, hd, T): head a reads K/V head a // group
    values = jnp.repeat(v, heads // kv, axis=2).transpose(0, 2, 1, 3)  # (B, h, T, hd)
    col = jnp.arange(t)

    def query_block(args):
        q_b, qi_b, wi_b, seg_b, row = args  # (B, Q, h, hd), (B, Q, ih, id), (B, Q, ih), (B, Q), (Q,)
        seen = (seg_b[:, :, None] == seg[:, None, :]) & (col[None, None, :] <= row[None, :, None])  # (B, Q, T)
        dots = _mm(qi_b.transpose(0, 2, 1, 3), k_i[:, None].transpose(0, 1, 3, 2), precision)  # (B, ih, Q, T)
        index = jnp.sum(wi_b.transpose(0, 2, 1)[..., None] * jax.nn.relu(dots), axis=1)  # (B, Q, T)
        index = jnp.where(seen, index, -jnp.inf)
        chosen = top_k_mask(index.reshape(-1, t), s["topk"]).reshape(index.shape)
        scores = _mm(q_b.transpose(0, 2, 1, 3), keys, precision) / math.sqrt(hd)  # (B, h, Q, T)
        probs = jax.nn.softmax(jnp.where(chosen[:, None], scores, -jnp.inf), -1)
        return _mm(probs, values, precision).transpose(0, 2, 1, 3)  # (B, Q, h, hd)

    # A packed row holds several sequences: a token's COLUMN bounds what it sees, its position rotates it.
    block = min(t, QUERY_BLOCK)
    pad = -t % block
    blocks = lambda a: jnp.moveaxis(  # noqa: E731
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(b, (t + pad) // block, block, *a.shape[2:]), 1, 0)
    rows = jnp.pad(col, (0, pad)).reshape(-1, block)
    out = jax.lax.map(query_block, (blocks(q), blocks(q_i), blocks(w_i), blocks(seg), rows))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, heads * hd)[:, :t]
    h = h + _mm(out, lw["o.w"], precision)

    x = _rms(h, lw["mlp_norm.g"], eps)
    first, count = held or (s["first"], s["held"])
    tokens = x.reshape(b * t, d)
    weights = routing(jax.nn.softmax(_mm(tokens, lw["router.w"], precision), -1), cfg)  # (N, experts)

    def add_expert(i, acc):  # every token through held expert i, under its weight (0 where not chosen)
        e = {k: lw[f"experts.{k}.w"][i] for k in ("gate", "up", "down")}
        w_e = jax.lax.dynamic_index_in_dim(weights, first + i, axis=1, keepdims=True)
        y = _mm(jax.nn.silu(_mm(tokens, e["gate"], precision)) * _mm(tokens, e["up"], precision), e["down"], precision)
        return acc + w_e * y

    if not count:  # a holder of none
        return h
    return h + jax.lax.fori_loop(0, count, add_expert, jnp.zeros_like(tokens)).reshape(b, t, d)


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
    positions). The sequences are packed into rows of the context length
    (``pad_to`` is not needed: a packed row has one shape) and go through in
    groups of ``GROUP_POSITIONS`` positions: every layer for one group, its
    float32 weights made alone, then the final norm and the head at EVERY
    position of the group; of a group only two float32 a position leave the
    device (how far the logit of the token that follows lies below the best,
    and how far the reference's logit at the control's argmax does), and the
    served positions are picked from them on the host. What the device holds
    does not depend on how many sequences there are."""
    key = w["key"]
    s = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    size = context_length(cfg)
    rows_per_group = max(1, GROUP_POSITIONS // size)

    # Packed rows: token ids, each token's position in its sequence, its sequence (0 = padding).
    packed = pack([len(p) + len(t) for p, t in sequences], size)
    packed += [[]] * (-len(packed) % rows_per_group)
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
    follows = np.roll(ids, -1, axis=1)  # position p predicts the token at p + 1

    clock = [time.perf_counter()]

    def phase(name: str, *arrays) -> None:
        """One line a phase, so a run that is cut says where it was."""
        jax.block_until_ready(arrays)
        now = time.perf_counter()
        print(f"[reference keye_vl2] {name}: {now - clock[0]:.1f}s", flush=True)
        clock[0] = now

    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda k: _vocab_matrix(cfg, k, 0))(key)
        head = jax.jit(lambda k: _vocab_matrix(cfg, k, 1))(key)
        g_final = jax.jit(lambda k: _draw(_leaf_key(k, 2), (s["d"],), "scale"))(key)
        make = jax.jit(lambda k, layer: make_layer(cfg, k, layer))
        donate = (1,) if jax.default_backend() == "tpu" else ()
        run = jax.jit(lambda lw, h, p, g, prec: layer_forward(lw, h, p, g, cfg, prec),
                      static_argnames=("prec",), donate_argnums=donate)

        look_up = jax.jit(lambda e, i: e[i])

        @partial(jax.jit, static_argnames=("prec",))
        def gaps(head, g_final, h_ref, h_low, nxt, prec):
            ref = jnp.matmul(_rms(h_ref, g_final, eps), head)  # (rows, size, vocab)
            best = jnp.max(ref, -1)
            served = best - jnp.take_along_axis(ref, nxt[..., None], -1)[..., 0]
            if prec == "f32":
                return served, served
            low = _mm(_rms(h_low, g_final, eps), head, prec)
            at_low = jnp.take_along_axis(ref, jnp.argmax(low, -1)[..., None], -1)[..., 0]
            return served, best - at_low

        phase(f"{len(sequences)} sequences packed into {len(packed)} rows of {size}; embedding and head made", embed, head)
        served_gap = np.zeros(ids.shape, np.float64)
        low_gap = np.zeros(ids.shape, np.float64)
        streams = ("f32",) if precision == "f32" else ("f32", precision)
        for begin in range(0, len(packed), rows_per_group):
            sl = slice(begin, begin + rows_per_group)
            where = (jnp.asarray(pos[sl]), jnp.asarray(seg[sl]))
            hidden = {st: look_up(embed, jnp.asarray(ids[sl])) for st in streams}  # a buffer a stream: `run` donates
            for layer in range(s["layers"]):
                lw = make(key, np.uint32(layer))
                for st in streams:
                    hidden[st] = run(lw, hidden[st], *where, prec=st)
                del lw
            out = gaps(head, g_final, hidden["f32"], hidden[streams[-1]], jnp.asarray(follows[sl]), prec=precision)
            served_gap[sl], low_gap[sl] = (np.asarray(o, np.float64) for o in out)
            del hidden, out
            phase(f"rows {begin}-{begin + rows_per_group - 1} of {len(packed)}: {s['layers']} layers "
                  f"({' and '.join(streams)}) and the head")

    # Served token j of a sequence is predicted at column start + len(prompt) - 1 + j.
    r_idx, c_idx, first = [], [], []
    for i, (prompt, served) in enumerate(sequences):
        r, at = start[i]
        r_idx += [r] * len(served)
        c_idx += range(at + len(prompt) - 1, at + len(prompt) - 1 + len(served))
        first += [j == 0 for j in range(len(served))]
    served_gap, low_gap, first = served_gap[r_idx, c_idx], low_gap[r_idx, c_idx], np.asarray(first)
    return {
        "widest_gap": float(served_gap.max()),
        "first_mean_gap": float(served_gap[first].mean()),
        "control_widest_gap": float(low_gap.max()),
        "control_first_mean_gap": float(low_gap[first].mean()),
        "tokens": int(len(served_gap)),
    }

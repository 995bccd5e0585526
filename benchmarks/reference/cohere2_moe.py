"""Plain reference of the ``cohere2_moe`` language model (Command A+):
float32 ``jax.numpy``, nothing else.

A PARALLEL block: one bias-free LayerNorm a layer (eps ``layer_norm_eps``)
feeds attention and the expert layer, and both are added to the residual.
With ``n = LN(x)`` and position ``t``::

    1  q_t = W_q n_t (num_attention_heads heads of head_dim) ; k_t = W_k n_t ; v_t = W_v n_t
       (num_key_value_heads heads) ; no bias, no q/k norm ; query head a reads K/V head a // group
    2  a ``sliding_attention`` layer rotates q and k over the whole head, INTERLEAVED pairs
       (rope_gptj: dimensions (0,1), (2,3), ...), theta ``rope_theta``, and query t sees keys s
       with 0 <= t - s < sliding_window ; a ``full_attention`` layer rotates NOTHING and sees
       every s <= t
    3  o_{t,a} = sum_s softmax_s(q_{t,a} . k_{s,g(a)} / sqrt(head_dim)) v_{s,g(a)} ; A_t = W_o o_t
    4  experts on the SAME n:  s = sigmoid(W_r n) over ALL published experts ; the
       num_experts_per_tok largest (ties: the lower index), weights s_e / sum of the chosen
       (norm_topk_prob) ; R_t = sum_{e chosen AND held here} weight_e E_e(n_t),
       E(x) = W_down (silu(W_gate x) * W_up x), width intermediate_size
    5  shared:  S_t = (1 / num_shared_experts) sum_j S_j(n_t), the shared experts of the same
       shape, AVERAGED and added whole (the configuration's ``assumed``: an inference)
    6  x <- x + A_t + R_t + S_t ; after the last layer a final LN and the head, the embedding
       transposed (tie_word_embeddings), times logit_scale

No cache, no kernel, no grouped product. Two things keep a window's worth of
positions inside a traced run's time (ISSUE 45; both stay plain masks and
plain matmuls): queries go through step 3 in blocks of ``QUERY_BLOCK`` over
the keys their mask can reach (a window layer's block sees the
``sliding_window + QUERY_BLOCK`` columns up to its end, a global layer's
every column up to its end), and a held expert runs over the rows that CHOSE
it, sorted first and taken ``EXPERT_ROWS`` at a time until every chooser has
been through (exact for any routing; the rows past the last chooser carry
weight 0). Matmuls run under ``jax.default_matmul_precision("highest")``. It
imports nothing of the program.

**A share.** The configuration holds ``experts_held = [first, count]`` of the
published experts (``num_experts`` is that count, ``published.num_experts``
the router's width): router, top-k and normalisation are the whole layer's,
and what the absent experts would add is left out, here as in the program.
The shared experts are whole on every holder.

**Device memory that does not grow with the window.** Every weight is a pure
function of ``(seed key, leaf, layer)``, an expert's of ``(seed key, leaf,
layer, published expert index)`` (shared experts: their own leaves and
index), the embedding of ``(seed key, slice)``. ``init_weights`` returns a
handle; :func:`served_token_gaps` packs the sequences into rows of the
context length, keeps their hidden states ON THE HOST, and sends them
through ONE LAYER AT A TIME, that layer's float32 weights made alone (4.6 GB
at the published widths), ``GROUP_POSITIONS`` positions a call; then the
final norm and the head (the embedding, kept on the device, transposed), a
slice of the vocabulary at a time, at the served positions only. The device
holds the embedding, one layer and two groups whatever the window finished. One line a phase says where it is.

What the harness needs to know of the family is here too: the program's
model section, context and vocabulary, the bytes a decode call must move
(:func:`weight_bytes`, :func:`expert_bytes`, :func:`kv_bytes_per_position`),
the operations a prefill call needs (:func:`prefill_flops`) and the
operations and bytes of the flash forward kernel on the serving path
(:func:`flash_forward_cost`).
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
VOCAB_SLICES = 8  # the embedding is keyed and made in this many slices
GROUP_POSITIONS = 8192  # packed rows go through a layer this many positions a call
QUERY_BLOCK = 512  # queries go through attention this many at a time
EXPERT_ROWS = 640  # a held expert takes its choosers this many rows at a time (about 512 of a group's 8,192 choose one)
HEAD_ROWS = 4096  # served positions go through the head this many at a time
KINDS = ("sliding_attention", "full_attention")


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative Python int (seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed % 2**32))
    key = jax.random.fold_in(key, np.uint32(seed // 2**32))
    return jax.random.fold_in(key, np.uint32(stream))


# ------------------------------------------------------------------ sizes


def dims(cfg: dict) -> dict[str, Any]:
    first, count = (int(v) for v in cfg["experts_held"])
    experts = int(cfg["published"]["num_experts"])
    if count != int(cfg["num_experts"]) or first < 0 or first + count > experts:
        raise ValueError(f"experts_held {cfg['experts_held']} is not num_experts of the {experts} experts")
    # The file keeps the published pattern whole; the layers run are its first num_hidden_layers entries.
    kinds = tuple(cfg["layer_types"])[: int(cfg["num_hidden_layers"])]
    if len(kinds) != int(cfg["num_hidden_layers"]) or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types {kinds} must name num_hidden_layers layers, each one of {KINDS}")
    return {
        "d": int(cfg["hidden_size"]), "layers": len(kinds), "kinds": kinds, "vocab": int(cfg["vocab_size"]),
        "h": int(cfg["num_attention_heads"]), "kv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "window": int(cfg["sliding_window"]), "eff": int(cfg["intermediate_size"]),
        "experts": experts, "first": first, "held": count, "k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["num_shared_experts"]),
    }


def layer_window(cfg: dict, layer: int) -> int:
    """The window of layer ``layer``: ``sliding_window``, or 0 for a global layer."""
    s = dims(cfg)
    return s["window"] if s["kinds"][layer] == "sliding_attention" else 0


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """One layer's leaves but its experts: name -> (shape, how drawn)."""
    s = dims(cfg)
    d, h, kv, hd = s["d"], s["h"], s["kv"], s["hd"]
    return {
        "norm.g": ((d,), "scale"),
        "q.w": ((d, h * hd), "matrix"), "k.w": ((d, kv * hd), "matrix"), "v.w": ((d, kv * hd), "matrix"),
        "o.w": ((h * hd, d), "matrix"),
        "router.w": ((d, s["experts"]), "matrix"),
    }


_GLOBAL = ("embed", "final_norm.g")  # leaf numbers 0, 1
_LEAF = {name: len(_GLOBAL) + i for i, name in enumerate((
    "norm.g", "q.w", "k.w", "v.w", "o.w", "router.w",
    "experts.gate.w", "experts.up.w", "experts.down.w",
    "shared.gate.w", "shared.up.w", "shared.down.w",
))}
_FLOAT32_ALWAYS = ("router.w",)  # the program keeps the router's weights in float32 (the file's ``assumed``)
_PARTS = ("gate", "up", "down")


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


def make_expert(cfg: dict, key: jax.Array, layer: Any, expert: Any, group: str = "experts") -> dict:
    """One expert's three matrices (float32): a routed one by its PUBLISHED
    index (``group`` "experts"), a shared one by its own (``group`` "shared")."""
    s = dims(cfg)
    shapes = {"gate": (s["d"], s["eff"]), "up": (s["d"], s["eff"]), "down": (s["eff"], s["d"])}
    return {
        f"{group}.{part}.w": _draw(
            jax.random.fold_in(_leaf_key(key, _LEAF[f"{group}.{part}.w"], layer), jnp.asarray(expert, jnp.uint32)),
            shapes[part], "matrix")
        for part in _PARTS
    }


def make_layer(cfg: dict, key: jax.Array, layer: Any, dtype: Any = jnp.float32,
               held: tuple[int, int] | None = None) -> dict:
    """One layer's weights alone (traceable). The routed experts are stacked
    on a leading axis, ``held = (first, count)`` of them (the file's own);
    the shared experts likewise, all of them."""
    s = dims(cfg)
    out = {
        name: _draw(_leaf_key(key, _LEAF[name], layer), shape, kind).astype(
            jnp.float32 if name in _FLOAT32_ALWAYS else dtype)
        for name, (shape, kind) in layer_shapes(cfg).items()
    }
    first, count = held or (s["first"], s["held"])
    for group, indices in (("experts", first + jnp.arange(count, dtype=jnp.uint32)),
                           ("shared", jnp.arange(s["shared"], dtype=jnp.uint32))):
        out.update(jax.lax.map(
            lambda e, group=group: jax.tree.map(lambda x: x.astype(dtype), make_expert(cfg, key, layer, e, group)),
            indices,
        ))
    return out


def vocab_slice_rows(cfg: dict) -> int:
    vocab = int(cfg["vocab_size"])
    if vocab % VOCAB_SLICES:
        raise ValueError(f"vocab_size {vocab} is not a multiple of {VOCAB_SLICES}")
    return vocab // VOCAB_SLICES


def embedding_slice(cfg: dict, key: jax.Array, index: Any) -> jax.Array:
    """Rows ``index * rows ...`` of the embedding ``(vocab, d)`` (float32);
    the head is its transpose (``tie_word_embeddings``)."""
    return _draw(_leaf_key(key, 0, index), (vocab_slice_rows(cfg), int(cfg["hidden_size"])), "matrix")


def _embedding(cfg: dict, key: jax.Array, dtype: Any = jnp.float32) -> jax.Array:
    return jnp.concatenate([embedding_slice(cfg, key, i).astype(dtype) for i in range(VOCAB_SLICES)], axis=0)


def make_weights(cfg: dict, key: jax.Array, dtype: Any = jnp.float32,
                 held: tuple[int, int] | None = None) -> dict:
    """Every weight from ``key`` (traceable: call it under ``jax.jit``), drawn
    in float32 and cast, so the bf16 weights a server holds are the rounding
    of the float32 weights the reference makes. ``held`` overrides the
    file's share (tests: the uncut layer, another share)."""
    s = dims(cfg)
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the family ties its head to the embedding: tie_word_embeddings must be true")
    return {
        "embed": _embedding(cfg, key, dtype),
        "final_norm.g": _draw(_leaf_key(key, 1), (s["d"],), "scale").astype(dtype),
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
    "num_key_value_heads", "head_dim", "intermediate_size", "num_experts_per_tok", "num_shared_experts",
    "norm_topk_prob", "sliding_window", "layer_types", "rope_theta", "layer_norm_eps", "logit_scale",
    "experts_held", "expert_selection_fn", "shared_expert_combination_strategy", "position_embedding_type",
    "use_parallel_block", "use_qk_norm", "attention_bias", "use_gated_activation", "hidden_act", "rotary_pct",
    "first_k_dense_replace",
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
    extra["layer_types"] = list(dims(cfg)["kinds"])
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
        "reference/cohere2_moe.py: train_flops_per_token: the configuration has no training cell "
        "(its smallest cut inside the guide's floors is 50 GB of training state: PERF.md section 4)"
    )


def expert_params(cfg: dict) -> int:
    s = dims(cfg)
    return 3 * s["d"] * s["eff"]


def layer_params(cfg: dict) -> int:
    """A layer's parameters OUTSIDE its routed experts: norm, attention,
    router and the shared experts (whole on every holder)."""
    s = dims(cfg)
    return sum(math.prod(shape) for shape, _ in layer_shapes(cfg).values()) + s["shared"] * expert_params(cfg)


def total_params(cfg: dict) -> int:
    """What this share holds; the tied head is the embedding, counted once."""
    s = dims(cfg)
    return s["layers"] * (layer_params(cfg) + s["held"] * expert_params(cfg)) + s["vocab"] * s["d"] + s["d"]


def weight_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of weights EVERY decode call must read: every layer outside its
    routed experts (the router's in float32) and the head (the tied
    embedding, whole); of the embedding AS a look-up only the rows of the
    call's tokens, counted as nothing. The routed experts a call touches are
    counted by :func:`expert_bytes`."""
    s = dims(cfg)
    routers = s["layers"] * s["d"] * s["experts"]
    values = s["layers"] * layer_params(cfg) + s["vocab"] * s["d"] + s["d"]
    return (values - routers) * bytes_per_value + routers * 4


def expert_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """One routed expert's three matrices: read whole by a call in which any
    token picked it."""
    return expert_params(cfg) * bytes_per_value


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of every K/V head of ONE layer for one position (the window
    and the global layers attend different numbers of positions: a reader
    multiplies by each kind's count, :func:`layer_counts`)."""
    s = dims(cfg)
    return 2 * s["kv"] * s["hd"] * bytes_per_value


def layer_counts(cfg: dict) -> tuple[int, int]:
    """``(window layers, global layers)`` of the configuration as run."""
    kinds = dims(cfg)["kinds"]
    window = sum(kind == "sliding_attention" for kind in kinds)
    return window, len(kinds) - window


def prefill_flops(cfg: dict, prompt_tokens: int, window_pairs: int, causal_pairs: int) -> float:
    """Operations a prefill of ``prompt_tokens`` true tokens needs (a
    multiply-add is two): every token through every layer's matrices outside
    the routed experts (the shared experts among them) and through the pairs
    routed to experts held here (their EXPECTED number under even routing,
    ``k * held / experts`` a token: the program counts expert pairs in decode
    calls only); scores and values of every head over ``window_pairs``
    (query, key) pairs in each window layer and ``causal_pairs`` in each
    global one; the head for ONE position (the one the first token is
    sampled at)."""
    s = dims(cfg)
    matrices = layer_params(cfg) - s["d"]  # the norm's scale is no matrix
    pairs_held = s["k"] * s["held"] / s["experts"]
    a_token = 2.0 * (matrices + pairs_held * expert_params(cfg))
    window_layers, global_layers = layer_counts(cfg)
    attention = 4.0 * s["h"] * s["hd"] * (window_layers * window_pairs + global_layers * causal_pairs)
    return s["layers"] * prompt_tokens * a_token + attention + 2.0 * s["d"] * s["vocab"]


def flash_forward_cost(cfg: dict, prompt_tokens: int, window_pairs: int, causal_pairs: int,
                       bytes_per_value: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of the flash forward kernel's calls in ONE
    prefill of ``prompt_tokens`` true tokens, all layers (the conventions of
    lib/kernel_costs.py: what the algorithm needs, counted once; every
    operand read once and every output written once): two matmuls over each
    attended (query, key) pair of every head, band-limited in the window
    layers; q and the output at the query heads' width, k and v at the K/V
    heads', the float32 log-sum a row and head."""
    s = dims(cfg)
    window_layers, global_layers = layer_counts(cfg)
    flops = 4.0 * s["h"] * s["hd"] * (window_layers * window_pairs + global_layers * causal_pairs)
    a_layer = prompt_tokens * ((2 * s["h"] + 2 * s["kv"]) * s["hd"] * bytes_per_value + s["h"] * 4)
    return flops, float(s["layers"] * a_layer)


# ------------------------------------------------------------------ names


def program_tree(w: dict, cfg: dict) -> dict:
    """The arrays of :func:`make_weights` under the names and shapes
    ``llmtrain_tpu.models.windowed_moe`` gives its parameters (a renaming
    and reshaping only: the shared experts side by side as one gated MLP,
    expert ``j`` in columns ``j * intermediate_size ...`` of gate and up and
    in those rows of down)."""
    s = dims(cfg)
    d, h, kv, hd, eff = s["d"], s["h"], s["kv"], s["hd"], s["eff"]
    tree: dict[str, Any] = {
        "token_embedding": {"embedding": w["embed"]},
        "norm_f": {"scale": w["final_norm.g"]},
    }
    for i, lw in enumerate(w["layers"]):
        side_by_side = lambda m: jnp.moveaxis(m, 0, 1).reshape(d, s["shared"] * eff)  # noqa: E731
        tree[f"block_{i}"] = {
            "norm": {"scale": lw["norm.g"]},
            "attn": {
                "q_proj": {"kernel": lw["q.w"].reshape(d, h, hd)},
                "k_proj": {"kernel": lw["k.w"].reshape(d, kv, hd)},
                "v_proj": {"kernel": lw["v.w"].reshape(d, kv, hd)},
                "o_proj": {"kernel": lw["o.w"].reshape(h, hd, d)},
            },
            "moe": {"router": {"kernel": lw["router.w"]}, "wg": lw["experts.gate.w"],
                    "wu": lw["experts.up.w"], "wo": lw["experts.down.w"]},
            "shared_experts": {
                "mlp_gate": {"kernel": side_by_side(lw["shared.gate.w"])},
                "mlp_up": {"kernel": side_by_side(lw["shared.up.w"])},
                "mlp_down": {"kernel": lw["shared.down.w"].reshape(s["shared"] * eff, d)},
            },
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


def _layer_norm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g


def rope_interleaved(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate pairs ``(2i, 2i + 1)`` of the last axis by ``pos * theta^(-2i / dim)``;
    x (B, T, heads, dim), pos (B, T)."""
    dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos.astype(jnp.float32)[..., None, None] * inv_freq  # (B, T, 1, dim / 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang), even * jnp.sin(ang) + odd * jnp.cos(ang)], -1)
    return turned.reshape(x.shape)


def attention_mask(rows: jax.Array, cols: jax.Array, seg_rows: jax.Array, seg_cols: jax.Array,
                   window: int) -> jax.Array:
    """(B, Q, S) bool: query at column ``rows`` (Q,) of a packed row sees the
    key at column ``cols`` (S,) iff it belongs to the same sequence
    (``seg_rows`` (B, Q), ``seg_cols`` (B, S); 0 is padding), lies at or
    before it and, in a window layer, fewer than ``window`` behind it. Inside
    one sequence a difference of columns IS the difference of positions."""
    behind = rows[:, None] - cols[None, :]
    seen = (behind >= 0) & ((behind < window) if window else True)
    return seen[None] & (seg_rows[:, :, None] == seg_cols[:, None, :]) & (seg_cols != 0)[:, None, :]


def routing(scores: jax.Array, cfg: dict) -> jax.Array:
    """(N, experts) sigmoid scores -> (N, experts) weights: ``s_e /
    sum_chosen s`` at the ``num_experts_per_tok`` largest (ties: the lower
    index), 0 elsewhere."""
    n = scores.shape[0]
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, : dims(cfg)["k"]]
    chosen = jnp.zeros_like(scores, bool).at[jnp.arange(n)[:, None], order].set(True)
    picked = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return picked


def _expert(e: dict, x: jax.Array, precision: str) -> jax.Array:
    return _mm(jax.nn.silu(_mm(x, e["gate"], precision)) * _mm(x, e["up"], precision), e["down"], precision)


def attention(lw: dict, n: jax.Array, pos: jax.Array, seg: jax.Array, cfg: dict, window: int,
              precision: str = "f32") -> jax.Array:
    """Steps 1-3 over (B, T, d) normed states: ``A`` (B, T, d)."""
    s = dims(cfg)
    b, t, _ = n.shape
    heads, kv, hd = s["h"], s["kv"], s["hd"]
    group = heads // kv
    q = _mm(n, lw["q.w"], precision).reshape(b, t, heads, hd)
    k = _mm(n, lw["k.w"], precision).reshape(b, t, kv, hd)
    v = _mm(n, lw["v.w"], precision).reshape(b, t, kv, hd)
    if window:
        theta = float(cfg["rope_theta"])
        q, k = rope_interleaved(q, pos, theta), rope_interleaved(k, pos, theta)
    q = q.reshape(b, t, kv, group, hd)
    block = min(t, QUERY_BLOCK)
    reach = window + block if window else 0  # columns a window layer's block can see, up to its end
    out = []
    for q0 in range(0, t, block):
        q1 = min(t, q0 + block)
        k0 = max(0, q1 - reach) if window else 0
        rows, cols = jnp.arange(q0, q1), jnp.arange(k0, q1)
        seen = attention_mask(rows, cols, seg[:, q0:q1], seg[:, k0:q1], window)  # (B, Q, S)
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", _round_to(q[:, q0:q1], precision), _round_to(k[:, k0:q1], precision)
        ) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen[:, None, None], scores, -jnp.inf), -1)
        probs = jnp.where(seen[:, None, None], probs, 0.0)  # a padding row sees nothing: zeros, not NaN
        out.append(jnp.einsum("bkgqs,bskd->bqkgd", _round_to(probs, precision), _round_to(v[:, k0:q1], precision)))
    out = jnp.concatenate(out, axis=1).reshape(b, t, heads * hd)
    return _mm(out, lw["o.w"], precision)


def experts(lw: dict, n: jax.Array, cfg: dict, precision: str = "f32",
            held: tuple[int, int] | None = None) -> tuple[jax.Array, jax.Array]:
    """Steps 4 and 5 over (B, T, d) normed states: ``(R, S)``, the held
    routed experts' part and the averaged shared experts'."""
    s = dims(cfg)
    b, t, d = n.shape
    tokens = n.reshape(b * t, d)
    count_rows = tokens.shape[0]
    first, count = held or (s["first"], s["held"])
    weights = routing(jax.nn.sigmoid(_mm(tokens, lw["router.w"], precision)), cfg)  # (N, experts)
    rows_a_pass = min(EXPERT_ROWS, count_rows)

    def add_expert(i, acc):
        e = {part: lw[f"experts.{part}.w"][i] for part in _PARTS}
        w_e = jax.lax.dynamic_index_in_dim(weights, first + i, axis=1, keepdims=False)  # (N,)
        choosers_first = jnp.argsort(w_e <= 0, stable=True)  # the rows that chose expert i, in order, then the rest
        passes = -(-jnp.sum(w_e > 0) // rows_a_pass)

        def add_rows(j, acc):
            # The last pass may reach past the end: it is moved back and takes rows twice, which
            # the second time add what the first did not (`fresh`).
            start = jnp.minimum(j * rows_a_pass, count_rows - rows_a_pass)
            idx = jax.lax.dynamic_slice_in_dim(choosers_first, start, rows_a_pass)
            fresh = (start + jnp.arange(rows_a_pass)) >= j * rows_a_pass
            y = _expert(e, tokens[idx], precision) * jnp.where(fresh, w_e[idx], 0.0)[:, None]
            return acc.at[idx].add(y)

        return jax.lax.fori_loop(0, passes, add_rows, acc)

    routed = jnp.zeros_like(tokens)
    if count:  # (a holder of none computes no routed part)
        routed = jax.lax.fori_loop(0, count, add_expert, routed)

    def add_shared(j, acc):
        return acc + _expert({part: lw[f"shared.{part}.w"][j] for part in _PARTS}, tokens, precision)

    shared = jax.lax.fori_loop(0, s["shared"], add_shared, jnp.zeros_like(tokens)) / s["shared"]
    return routed.reshape(b, t, d), shared.reshape(b, t, d)


def layer_forward(lw: dict, h: jax.Array, pos: jax.Array, seg: jax.Array, cfg: dict, window: int,
                  precision: str = "f32", held: tuple[int, int] | None = None) -> jax.Array:
    """One block over (B, T, d) float32 hidden states. ``pos`` (B, T) is each
    token's position in its own sequence and ``seg`` (B, T) names that
    sequence (0: padding): a token sees the tokens of its sequence at or
    before it, inside ``window`` if the layer has one (0: a global layer)."""
    lw = {k: w.astype(jnp.float32) for k, w in lw.items()}
    n = _layer_norm(h, lw["norm.g"], float(cfg["layer_norm_eps"]))
    routed, shared = experts(lw, n, cfg, precision, held)
    return h + attention(lw, n, pos, seg, cfg, window, precision) + routed + shared


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
    for layer, lw in enumerate(w["layers"]):
        h = layer_forward(lw, h, pos, seg, cfg, layer_window(cfg, layer), precision, held)
    return _layer_norm(h, w["final_norm.g"].astype(jnp.float32), float(cfg["layer_norm_eps"]))


def logits_fn(w: dict, ids: jax.Array, cfg: dict, precision: str = "f32",
              held: tuple[int, int] | None = None) -> jax.Array:
    """``w`` is :func:`init_weights`' handle or :func:`make_weights`' tree."""
    w = _whole(w, cfg)
    head = w["embed"].astype(jnp.float32).T
    return _mm(hidden_states(w, ids, cfg, precision, held), head, precision) * float(cfg["logit_scale"])


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
    (``pad_to`` is not needed: a packed row has one shape); their hidden
    states live on the HOST and go through one layer at a time, that layer's
    float32 weights made alone, ``GROUP_POSITIONS`` positions a call (the
    next group is sent while the last computes); then the final norm and the
    head, a slice of the vocabulary at a time, at the served positions only.
    What the device holds does not depend on how many sequences there are."""
    key = w["key"]
    s = dims(cfg)
    eps = float(cfg["layer_norm_eps"])
    streams = ("f32",) if precision == "f32" else ("f32", precision)
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
    groups = [slice(g, g + rows_per_group) for g in range(0, len(packed), rows_per_group)]

    clock = [time.perf_counter()]

    def phase(name: str, *arrays) -> None:
        """One line a phase, so a run that is cut says where it was."""
        jax.block_until_ready(arrays)
        now = time.perf_counter()
        print(f"[reference cohere2_moe] {name}: {now - clock[0]:.1f}s", flush=True)
        clock[0] = now

    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda k: _embedding(cfg, k))(key)
        look_up = jax.jit(lambda e, i: e[i])
        first_states = [np.asarray(look_up(embed, jnp.asarray(ids[g]))) for g in groups]
        hidden = {st: list(first_states) for st in streams}  # on the host; a stream's list is its own
        del first_states  # (the embedding stays: the tied head is its transpose)
        phase(f"{len(sequences)} sequences packed into {len(packed)} rows of {size}, embedded")
        donate = (1,) if jax.default_backend() == "tpu" else ()
        make = jax.jit(lambda k, layer: make_layer(cfg, k, layer))
        run = jax.jit(lambda lw, h, p, g, window, prec: layer_forward(lw, h, p, g, cfg, window, prec),
                      static_argnames=("window", "prec"), donate_argnums=donate)
        for layer in range(s["layers"]):
            lw = make(key, np.uint32(layer))
            window = layer_window(cfg, layer)
            for st in streams:
                waiting = None  # (group index, its result still on the device)
                for gi, g in enumerate(groups):
                    out = run(lw, jnp.asarray(hidden[st][gi]), jnp.asarray(pos[g]), jnp.asarray(seg[g]),
                              window=window, prec=st)
                    if waiting is not None:
                        hidden[st][waiting[0]] = np.asarray(waiting[1])
                    waiting = (gi, out)
                hidden[st][waiting[0]] = np.asarray(waiting[1])
                del waiting, out
            del lw
            phase(f"layer {layer} ({s['kinds'][layer]}; {' and '.join(streams)}), {len(groups)} groups")

        # Position p predicts token p + 1: served token j of a sequence sits at len(prompt) + j.
        r_idx, c_idx, owner, first = [], [], [], []
        for i, (prompt, served) in enumerate(sequences):
            r, at = start[i]
            r_idx += [r] * len(served)
            c_idx += range(at + len(prompt) - 1, at + len(prompt) - 1 + len(served))
            owner += [int(t) for t in served]
            first += [j == 0 for j in range(len(served))]
        r_idx, c_idx = np.asarray(r_idx), np.asarray(c_idx)
        at_served = {st: np.zeros((len(owner), s["d"]), np.float32) for st in streams}
        for gi, g in enumerate(groups):  # a group's served positions out of that group's own array
            mine = (r_idx >= g.start) & (r_idx < g.stop)
            for st in streams:
                at_served[st][mine] = hidden[st][gi][r_idx[mine] - g.start, c_idx[mine]]
        del hidden
        g_final = jax.jit(lambda k: _draw(_leaf_key(k, 1), (s["d"],), "scale"))(key)
        rows = vocab_slice_rows(cfg)
        scale = float(cfg["logit_scale"])

        @partial(jax.jit, static_argnames=("prec",))
        def head_pass(embed, index, h_ref, h_low, tok, carry, prec):
            best, picked, low_best, ref_at_low = carry
            hs = jax.lax.dynamic_slice_in_dim(embed, index * rows, rows, axis=0).T  # the tied head's columns of this slice
            ref = jnp.matmul(_layer_norm(h_ref, g_final, eps), hs) * scale
            low = ref if prec == "f32" else _mm(_layer_norm(h_low, g_final, eps), hs, prec) * scale
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
        chunk = min(HEAD_ROWS, total)
        pad = -total % chunk
        h_ref = np.pad(at_served["f32"], [(0, pad), (0, 0)])
        h_low = np.pad(at_served[streams[-1]], [(0, pad), (0, 0)])
        served_tok = np.pad(np.asarray(owner, np.int32), (0, pad))
        served_gap, low_gap = [], []
        for begin in range(0, total + pad, chunk):
            sl = slice(begin, begin + chunk)
            part = tuple(jnp.asarray(x[sl]) for x in (h_ref, h_low, served_tok))
            carry = tuple(jnp.full((chunk,), -jnp.inf, jnp.float32) for _ in range(4))
            for index in range(VOCAB_SLICES):
                carry = head_pass(embed, np.int32(index), *part, carry, prec=precision)
            best, picked, _, ref_at_low = carry
            served_gap.append(np.asarray(best - picked, np.float64))
            low_gap.append(np.asarray(best - ref_at_low, np.float64))
    served_gap, low_gap = np.concatenate(served_gap)[:total], np.concatenate(low_gap)[:total]
    phase(f"final norm and head over {total} served positions")
    first = np.asarray(first)
    return {
        "widest_gap": float(served_gap.max()),
        "first_mean_gap": float(served_gap[first].mean()),
        "control_widest_gap": float(low_gap.max()),
        "control_first_mean_gap": float(low_gap[first].mean()),
        "tokens": int(len(served_gap)),
    }

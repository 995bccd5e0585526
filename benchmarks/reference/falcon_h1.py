"""Plain reference of the Falcon-H1 family: float32 ``jax.numpy``, nothing else.

Every block feeds ONE normalised input to an attention branch and a Mamba-2
branch in parallel and adds both, scaled, to the residual; a SwiGLU follows
(tiiuae/Falcon-H1-34B-Instruct ``config.json``; the equations are in the
configuration file's ``assumed`` list where no key of the config gives them)::

    h0 = embed[ids] * embedding_multiplier
    u  = RMSNorm_in(h)
    q, k, v = (u * attention_in_multiplier) W_q, W_k, W_v ; k *= key_multiplier ; RoPE (rotate-half)
    a  = W_o . causal_softmax(q k^T / sqrt(head_dim)) v                 (GQA)
    p  = ((u * ssm_in_multiplier) W_in) * mup_vector      [z | x | B | C | dt], ssm_multipliers over the five parts
    xBC = silu(causal_depthwise_conv1d([x|B|C])) ; dt = softplus(dt + dt_bias) ; A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t ;  y_t = S_t C_t + D x_t
    m  = W_out . GroupedRMSNorm(y * silu(z))
    h  = h + a * attention_out_multiplier + m * ssm_out_multiplier
    h  = h + (W_down . (silu((RMSNorm_ff(h) W_gate) * mlp_multipliers[0]) * (RMSNorm_ff(h) W_up))) * mlp_multipliers[1]
    logits = (RMSNorm_f(h) W_head) * lm_head_multiplier

The recurrence is a plain ``lax.scan`` over time steps: no chunks, no cache,
no batching tricks. Matmuls run under ``jax.default_matmul_precision
("highest")``. It imports nothing of the program.

**Weights that do not fit.** At the published widths the float32 weights
are 17.6 GB. Every weight is a pure function of ``(seed key, leaf, layer)``
and the two vocabulary matrices of ``(seed key, leaf, slice of the
vocabulary)``, so :func:`init_weights` returns only a handle (the key) and
:func:`served_token_gaps` makes one layer's float32 weights at a time, runs
every sequence through it, and reads the head in slices of the vocabulary.
:func:`make_weights` (traceable; the program's own copy in its compute
dtype) writes the vocabulary matrices slice by slice into a buffer of the
target dtype, so no float32 copy of either is ever alive.

What the harness needs to know of the family is here too: the program's
model section, context and vocabulary, and the bytes a decode call must move
(:func:`weight_bytes`, :func:`state_bytes_per_row`,
:func:`kv_bytes_per_position`).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # float8_e4m3fn
VOCAB_SLICES = 32  # the vocabulary matrices are keyed, made and read in this many slices
TOKENS_PER_BATCH = 16384  # sequences of one padded length go through a layer this many tokens at a time
HEAD_ROWS = 16384  # served positions go through a slice of the head this many at a time


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative Python int (seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed % 2**32))
    key = jax.random.fold_in(key, np.uint32(seed // 2**32))
    return jax.random.fold_in(key, np.uint32(stream))


# ------------------------------------------------------------------ sizes


def dims(cfg: dict) -> dict[str, int]:
    d_ssm = int(cfg.get("mamba_d_ssm") or int(cfg["mamba_expand"]) * int(cfg["hidden_size"]))
    heads = int(cfg["mamba_n_heads"])
    gn = int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])
    return {
        "d": int(cfg["hidden_size"]), "layers": int(cfg["num_hidden_layers"]), "vocab": int(cfg["vocab_size"]),
        "ff": int(cfg["intermediate_size"]), "hq": int(cfg["num_attention_heads"]),
        "hkv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "d_ssm": d_ssm, "heads": heads, "p": d_ssm // heads, "groups": int(cfg["mamba_n_groups"]),
        "n": int(cfg["mamba_d_state"]), "gn": gn, "conv_dim": d_ssm + 2 * gn, "k": int(cfg["mamba_d_conv"]),
        "proj": 2 * d_ssm + 2 * gn + heads,
    }


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """One layer's leaves: name -> (shape, how it is drawn)."""
    s = dims(cfg)
    d, q, kv = s["d"], s["hq"] * s["hd"], s["hkv"] * s["hd"]
    return {
        "in_norm.g": ((d,), "scale"),
        "q.w": ((d, q), "matrix"), "k.w": ((d, kv), "matrix"), "v.w": ((d, kv), "matrix"),
        "o.w": ((q, d), "matrix"),
        "in_proj.w": ((d, s["proj"]), "matrix"),
        "conv.w": ((s["k"], s["conv_dim"]), "conv"), "conv.b": ((s["conv_dim"],), "matrix"),
        "dt_bias": ((s["heads"],), "dt_bias"), "A_log": ((s["heads"],), "a_log"), "D": ((s["heads"],), "one"),
        "ssm_norm.g": ((s["d_ssm"],), "scale"),
        "out_proj.w": ((s["d_ssm"], d), "matrix"),
        "ff_norm.g": ((d,), "scale"),
        "gate.w": ((d, s["ff"]), "matrix"), "up.w": ((d, s["ff"]), "matrix"), "down.w": ((s["ff"], d), "matrix"),
    }


_GLOBAL = ("embed", "head", "final_norm.g")  # leaf numbers 0, 1, 2; a layer's leaves follow


def _draw(key: jax.Array, shape: tuple[int, ...], kind: str) -> jax.Array:
    """The initialiser (the configuration's ``assumed``), always float32."""
    if kind == "matrix":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "conv":
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if kind == "dt_bias":  # inverse softplus of a log-uniform draw in [0.001, 0.1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * math.log(100.0) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "a_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    raise ValueError(kind)


def _leaf_key(key: jax.Array, leaf: int, index: Any = 0) -> jax.Array:
    """``index`` (a layer, a slice of the vocabulary) may be traced."""
    return jax.random.fold_in(jax.random.fold_in(key, np.uint32(leaf)), jnp.asarray(index, jnp.uint32))


def make_layer(cfg: dict, key: jax.Array, layer: int, dtype: Any = jnp.float32) -> dict:
    """One layer's weights alone (traceable)."""
    return {
        name: _draw(_leaf_key(key, len(_GLOBAL) + i, layer), shape, kind).astype(dtype)
        for i, (name, (shape, kind)) in enumerate(layer_shapes(cfg).items())
    }


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
    """A vocabulary matrix written slice by slice into a buffer of ``dtype``:
    only one float32 slice is alive at a time."""
    rows = shape[axis] // VOCAB_SLICES

    def body(i, buf):
        at = (i * rows, 0) if axis == 0 else (0, i * rows)
        return jax.lax.dynamic_update_slice(buf, make_slice(i).astype(dtype), at)

    return jax.lax.fori_loop(0, VOCAB_SLICES, body, jnp.zeros(shape, dtype))


def make_weights(cfg: dict, key: jax.Array, dtype: Any = jnp.float32) -> dict:
    """Every weight from ``key`` (traceable: call it under ``jax.jit``), drawn
    in float32 and cast, so the bf16 weights a server holds are the rounding
    of the float32 weights the reference makes."""
    s = dims(cfg)
    return {
        "embed": _assemble(lambda i: embed_slice(cfg, key, i), (s["vocab"], s["d"]), 0, dtype),
        "head": _assemble(lambda i: head_slice(cfg, key, i), (s["d"], s["vocab"]), 1, dtype),
        "final_norm.g": _draw(_leaf_key(key, 2), (s["d"],), "scale").astype(dtype),
        "layers": [make_layer(cfg, key, i, dtype) for i in range(s["layers"])],
    }


def init_weights(cfg: dict, seed: int, dtype: Any = jnp.float32) -> dict:
    """The reference's own copy is a HANDLE: the key every weight is a pure
    function of. ``dtype`` is accepted for the interface and ignored: the
    reference computes in float32."""
    return {"key": seed_key(seed, 1)}


# ------------------------------------------- what the harness asks the family


def context_length(cfg: dict) -> int:
    return int(cfg["max_position_embeddings"])


def vocab_size(cfg: dict) -> int:
    return int(cfg["vocab_size"])


_EXTRA_KEYS = (
    "head_dim", "rope_theta", "rms_norm_eps", "mamba_d_state", "mamba_n_heads", "mamba_n_groups", "mamba_d_conv",
    "mamba_chunk_size", "embedding_multiplier", "key_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
    "lm_head_multiplier",
)


def program_model(cfg: dict, seq_len: int | None = None) -> dict:
    """The ``model`` section of the program's run config: the file's
    ``program.model`` block plus the published sizes and multipliers under
    the program's field names (``model.extra`` keeps the published names)."""
    model = dict(cfg["program"]["model"])
    extra = dict(model.get("extra", {}))
    extra.update({k: cfg[k] for k in _EXTRA_KEYS})
    extra.update(n_kv_heads=int(cfg["num_key_value_heads"]), mamba_d_ssm=dims(cfg)["d_ssm"])
    model["extra"] = extra
    model.update(
        block_size=int(seq_len or cfg["max_position_embeddings"]), d_model=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]), n_heads=int(cfg["num_attention_heads"]),
        d_ff=int(cfg["intermediate_size"]), vocab_size=int(cfg["vocab_size"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )
    return model


def layer_params(cfg: dict) -> int:
    return sum(math.prod(shape) for shape, _ in layer_shapes(cfg).values())


def total_params(cfg: dict) -> int:
    s = dims(cfg)
    return s["layers"] * layer_params(cfg) + 2 * s["vocab"] * s["d"] + s["d"]


def weight_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of weights ONE decode call must read: every layer and the head;
    of the embedding only the rows of the call's tokens, counted as nothing."""
    s = dims(cfg)
    return (s["layers"] * layer_params(cfg) + s["vocab"] * s["d"] + s["d"]) * bytes_per_value


def state_bytes_per_row(cfg: dict, conv_bytes: int = 2, ssm_bytes: int = 4) -> int:
    """Recurrent state of one sequence over all layers: the conv's last
    ``d_conv - 1`` inputs and the SSM state."""
    s = dims(cfg)
    per_layer = (s["k"] - 1) * s["conv_dim"] * conv_bytes + s["heads"] * s["p"] * s["n"] * ssm_bytes
    return s["layers"] * per_layer


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of every layer for one position."""
    s = dims(cfg)
    return 2 * s["layers"] * s["hkv"] * s["hd"] * bytes_per_value


# ------------------------------------------------------------------ names


def program_tree(w: dict, cfg: dict) -> dict:
    """The arrays of :func:`make_weights` under the names and shapes
    ``llmtrain_tpu.models.falcon_h1`` gives its parameters (a renaming and
    reshaping only)."""
    s = dims(cfg)
    d, hq, hkv, hd = s["d"], s["hq"], s["hkv"], s["hd"]
    if hq == hkv:
        raise ValueError("the family has grouped-query attention: num_key_value_heads < num_attention_heads")
    tree: dict[str, Any] = {
        "token_embedding": {"embedding": w["embed"]},
        "lm_head": {"kernel": w["head"]},
        "norm_f": {"scale": w["final_norm.g"]},
    }
    for i, lw in enumerate(w["layers"]):
        tree[f"block_{i}"] = {
            "input_norm": {"scale": lw["in_norm.g"]},
            "attn": {
                "q_proj": {"kernel": lw["q.w"].reshape(d, hq, hd)},
                "kv_proj": {"kernel": jnp.stack(
                    [lw["k.w"].reshape(d, hkv, hd), lw["v.w"].reshape(d, hkv, hd)], axis=1)},
                "out_proj": {"kernel": lw["o.w"].reshape(hq, hd, d)},
            },
            "mamba": {
                "in_proj": {"kernel": lw["in_proj.w"]},
                "conv_weight": lw["conv.w"], "conv_bias": lw["conv.b"],
                "dt_bias": lw["dt_bias"], "A_log": lw["A_log"], "D": lw["D"],
                "norm_scale": lw["ssm_norm.g"],
                "out_proj": {"kernel": lw["out_proj.w"]},
            },
            "mlp_norm": {"scale": lw["ff_norm.g"]},
            "mlp_gate": {"kernel": lw["gate.w"]},
            "mlp_up": {"kernel": lw["up.w"]},
            "mlp_down": {"kernel": lw["down.w"]},
        }
    return tree


# ---------------------------------------------------------------- forward


def _round_to(x: jax.Array, precision: str) -> jax.Array:
    """``x`` rounded to the control's type and back (straight-through)."""
    if precision == "f32":
        return x
    if precision == "bf16":
        low = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        low = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown control precision {precision!r}")
    return x + jax.lax.stop_gradient(low - x)


def _mm(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    return jnp.matmul(_round_to(a, precision), _round_to(b, precision))


def _rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE over the whole head; x is (B, T, H, hd), positions 0..T-1."""
    t, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    half = hd // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def mup_vector(cfg: dict) -> jax.Array:
    s = dims(cfg)
    sizes = (s["d_ssm"], s["d_ssm"], s["gn"], s["gn"], s["heads"])
    return jnp.concatenate([jnp.full((n,), float(m), jnp.float32) for n, m in zip(sizes, cfg["ssm_multipliers"])])


def layer_forward(lw: dict, h: jax.Array, cfg: dict, precision: str = "f32") -> jax.Array:
    """One block over (B, T, d) float32 hidden states."""
    s = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    b, t, _ = h.shape
    hq, hkv, hd = s["hq"], s["hkv"], s["hd"]
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    u = _rms(h, lw["in_norm.g"], eps)

    # -- attention branch
    ua = u * float(cfg["attention_in_multiplier"])
    q = _mm(ua, lw["q.w"], precision).reshape(b, t, hq, hd)
    k = (_mm(ua, lw["k.w"], precision) * float(cfg["key_multiplier"])).reshape(b, t, hkv, hd)
    v = _mm(ua, lw["v.w"], precision).reshape(b, t, hkv, hd)
    q, k = _rope(q, float(cfg["rope_theta"])), _rope(k, float(cfg["rope_theta"]))
    g = hq // hkv  # query head j reads kv head j // g
    qg = q.reshape(b, t, hkv, g, hd).transpose(0, 2, 3, 1, 4)  # (b, hkv, g, t, hd)
    kt = k.transpose(0, 2, 3, 1)[:, :, None]  # (b, hkv, 1, hd, t)
    scores = _mm(qg, kt, precision) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = _mm(jax.nn.softmax(scores, -1), v.transpose(0, 2, 1, 3)[:, :, None], precision)
    attn = _mm(out.transpose(0, 3, 1, 2, 4).reshape(b, t, hq * hd), lw["o.w"], precision)

    # -- Mamba-2 branch
    p = _mm(u * float(cfg["ssm_in_multiplier"]), lw["in_proj.w"], precision) * mup_vector(cfg)
    z, xbc, dt = jnp.split(p, [s["d_ssm"], s["d_ssm"] + s["conv_dim"]], -1)
    padded = jnp.pad(xbc, [(0, 0), (s["k"] - 1, 0), (0, 0)])  # the conv state of a new sequence is zero
    conv = sum(padded[:, j : j + t] * lw["conv.w"][j] for j in range(s["k"])) + lw["conv.b"]
    xbc = jax.nn.silu(conv)
    x, bm, cm = jnp.split(xbc, [s["d_ssm"], s["d_ssm"] + s["gn"]], -1)
    x = _round_to(x, precision).reshape(b, t, s["heads"], s["p"])
    per_group = s["heads"] // s["groups"]  # heads 0..per_group-1 read group 0's B and C, and so on
    bm = jnp.repeat(_round_to(bm, precision).reshape(b, t, s["groups"], s["n"]), per_group, axis=2)
    cm = jnp.repeat(_round_to(cm, precision).reshape(b, t, s["groups"], s["n"]), per_group, axis=2)
    dt = jax.nn.softplus(dt + lw["dt_bias"])  # (b, t, heads); not clamped from above
    a = -jnp.exp(lw["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp  # (b, heads, p), (b, heads, n), (b, heads, n), (b, heads)
        state = jnp.exp(dt_t * a)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], -1)

    state0 = jnp.zeros((b, s["heads"], s["p"], s["n"]), jnp.float32)
    _, y = jax.lax.scan(step, state0, tuple(jnp.moveaxis(v_, 1, 0) for v_ in (x, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + lw["D"][:, None] * x  # (b, t, heads, p)
    gated = y.reshape(b, t, s["d_ssm"]) * jax.nn.silu(z)  # gate first: norm_before_gate false
    grouped = gated.reshape(b, t, s["groups"], s["d_ssm"] // s["groups"])
    grouped = grouped * jax.lax.rsqrt(jnp.mean(jnp.square(grouped), -1, keepdims=True) + eps)
    mixed = _mm(grouped.reshape(b, t, s["d_ssm"]) * lw["ssm_norm.g"], lw["out_proj.w"], precision)

    h = h + attn * float(cfg["attention_out_multiplier"]) + mixed * float(cfg["ssm_out_multiplier"])
    f = _rms(h, lw["ff_norm.g"], eps)
    gate = jax.nn.silu(_mm(f, lw["gate.w"], precision) * float(cfg["mlp_multipliers"][0]))
    return h + _mm(gate * _mm(f, lw["up.w"], precision), lw["down.w"], precision) * float(cfg["mlp_multipliers"][1])


def _embedding(key: jax.Array, cfg: dict) -> jax.Array:
    return jnp.concatenate([embed_slice(cfg, key, i) for i in range(VOCAB_SLICES)], 0)


def _whole(w: dict, cfg: dict) -> dict:
    """Every weight at once, for sizes that fit: the handle's key turned
    into :func:`make_weights`' tree (a tree passes through)."""
    return make_weights(cfg, w["key"]) if "key" in w else w


def hidden_states(w: dict, ids: jax.Array, cfg: dict, precision: str = "f32") -> jax.Array:
    """(B, T) token ids -> (B, T, d) final-norm hidden states, float32."""
    w = _whole(w, cfg)
    h = w["embed"].astype(jnp.float32)[ids] * float(cfg["embedding_multiplier"])
    for lw in w["layers"]:
        h = layer_forward(lw, h, cfg, precision)
    return _rms(h, w["final_norm.g"].astype(jnp.float32), float(cfg["rms_norm_eps"]))


def logits_fn(w: dict, ids: jax.Array, cfg: dict, precision: str = "f32") -> jax.Array:
    """``w`` is :func:`init_weights`' handle or :func:`make_weights`' tree."""
    w = _whole(w, cfg)
    h = hidden_states(w, ids, cfg, precision)
    return _mm(h, w["head"].astype(jnp.float32), precision) * float(cfg["lm_head_multiplier"])


def loss_sum(w: dict, ids: jax.Array, labels: jax.Array, mask: jax.Array, cfg: dict,
             precision: str = "f32") -> jax.Array:
    """Sum over unmasked positions of the cross-entropy (labels already shifted)."""
    logits = logits_fn(w, ids, cfg, precision)
    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum((lse - picked) * mask)


# ------------------------------------------------------- serving yardstick


def served_token_gaps(w: dict, cfg: dict, sequences: list[tuple[np.ndarray, np.ndarray]],
                      precision: str = "f32", pad_to: tuple[int, ...] = ()) -> dict[str, Any]:
    """The two numbers ``reference/gpt2.py:served_token_gaps`` returns (and
    with ``precision`` below f32 the control's two, read at the same
    positions), for a model whose float32 weights do not fit the chip: the
    sequences, padded to the smallest of ``pad_to`` (and the context) that
    holds each, go through ONE layer at a time, that layer's float32 weights
    made alone; then the final norm and the head, a slice of the vocabulary
    at a time, at the served positions only. Only per-position gaps leave
    the device."""
    key = w["key"]
    s = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    streams = ("f32",) if precision == "f32" else ("f32", precision)
    sizes = sorted({*pad_to, context_length(cfg)})

    # Sequences of one padded length share batches.
    by_size: dict[int, list[int]] = {}
    for i, (prompt, served) in enumerate(sequences):
        n = len(prompt) + len(served)
        by_size.setdefault(next(size for size in sizes if size >= n), []).append(i)
    batches: list[tuple[np.ndarray, list[int]]] = []
    for size, members in sorted(by_size.items()):
        rows = max(1, TOKENS_PER_BATCH // size)
        for start in range(0, len(members), rows):
            group = members[start : start + rows]
            ids = np.zeros((rows, size), np.int32)  # always `rows` rows: one compiled shape a padded length
            for r, i in enumerate(group):
                seq = np.concatenate(sequences[i]).astype(np.int32)
                ids[r, : len(seq)] = seq
            batches.append((ids, group))

    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda k: _embedding(k, cfg))(key)
        look_up = jax.jit(lambda e, ids: e[ids] * float(cfg["embedding_multiplier"]))
        hidden = {st: [look_up(embed, jnp.asarray(ids)) for ids, _ in batches] for st in streams}
        del embed
        make = jax.jit(lambda k, layer: make_layer(cfg, k, layer))
        run = jax.jit(lambda lw, h, prec: layer_forward(lw, h, cfg, prec), static_argnames=("prec",),
                      donate_argnums=(1,) if jax.default_backend() == "tpu" else ())
        for layer in range(s["layers"]):
            lw = make(key, np.uint32(layer))
            for st in streams:
                hidden[st] = [run(lw, h, prec=st) for h in hidden[st]]
            del lw

        # Position p predicts token p + 1: served token j of a sequence sits at len(prompt) + j.
        # The served positions are picked on the host, so the device sees one shape a padded length.
        g_final = jax.jit(lambda k: _draw(_leaf_key(k, 2), (s["d"],), "scale"))(key)
        final_norm = jax.jit(lambda h: _rms(h, g_final, eps))
        owner, first = [], []
        at = {st: [] for st in streams}
        for b, (_, group) in enumerate(batches):
            r_idx, p_idx = [], []
            for r, i in enumerate(group):
                prompt, served = sequences[i]
                r_idx += [r] * len(served)
                p_idx += range(len(prompt) - 1, len(prompt) - 1 + len(served))
                owner += [int(t) for t in served]
                first += [j == 0 for j in range(len(served))]
            for st in streams:
                at[st].append(np.asarray(final_norm(hidden[st][b]))[r_idx, p_idx])
                hidden[st][b] = None
        del hidden
        rows = vocab_slice_rows(cfg)
        mult = float(cfg["lm_head_multiplier"])

        @partial(jax.jit, static_argnames=("prec",))
        def head_pass(k, index, h_ref, h_low, tok, carry, prec):
            best, picked, low_best, ref_at_low = carry
            hs = head_slice(cfg, k, index)
            ref = jnp.matmul(h_ref, hs) * mult
            low = ref if prec == "f32" else _mm(h_low, hs, prec) * mult
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
        h_ref = np.pad(np.concatenate(at["f32"], 0), [(0, pad), (0, 0)])
        h_low = np.pad(np.concatenate(at[streams[-1]], 0), [(0, pad), (0, 0)])
        served_tok = np.pad(np.asarray(owner, np.int32), (0, pad))
        served_gap, low_gap = [], []
        for start in range(0, total + pad, HEAD_ROWS):
            sl = slice(start, start + HEAD_ROWS)
            part = tuple(jnp.asarray(v[sl]) for v in (h_ref, h_low, served_tok))
            carry = tuple(jnp.full((HEAD_ROWS,), -jnp.inf, jnp.float32) for _ in range(4))
            for index in range(VOCAB_SLICES):
                carry = head_pass(key, np.int32(index), *part, carry, prec=precision)
            best, picked, _, ref_at_low = carry
            served_gap.append(np.asarray(best - picked, np.float64))
            low_gap.append(np.asarray(best - ref_at_low, np.float64))
    served_gap, low_gap = np.concatenate(served_gap)[:total], np.concatenate(low_gap)[:total]
    first = np.asarray(first)
    return {
        "widest_gap": float(served_gap.max()),
        "first_mean_gap": float(served_gap[first].mean()),
        "control_widest_gap": float(low_gap.max()),
        "control_first_mean_gap": float(low_gap[first].mean()),
        "tokens": int(len(served_gap)),
    }

"""Plain reference of the GPT-2 family: float32 ``jax.numpy``, nothing else.

Follows the published description (Radford et al. 2019; the layout of
``openai-community/gpt2``): learned token + position embeddings, pre-norm
blocks of causal multi-head attention and a 4x MLP, a final LayerNorm and
a head tied to the token embedding. No kernels, no cache, no batching
tricks; matmuls run under ``jax.default_matmul_precision("highest")``
because a TPU otherwise computes float32 matmuls in bf16 passes.

Departures from the published model, both read from the configuration
file (which lists them under ``deviations``): ``activation_function`` and
``layer_norm_epsilon`` are whatever the file says — the program under
test has exact-erf GELU and 1e-6 hard-coded.

What the harness needs to know of the family is here too, so that runners
and metric readers name no key of a GPT-2 config: the program's model
section (:func:`program_model`), the context and vocabulary
(:func:`context_length`, :func:`vocab_size`) and the operations and bytes
the algorithm needs (:func:`train_flops_per_token` and the rest).

It imports nothing of the program and takes nothing the program made: the
weights come from :func:`init_weights` (one jitted call from the seed; the
runner hands the SAME arrays, renamed by :func:`program_tree`, to the
program). ``precision`` selects the control: ``"bf16"`` or ``"fp8"``
rounds every matmul operand to that type first.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # float8_e4m3fn


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative Python int (seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed % 2**32))
    key = jax.random.fold_in(key, np.uint32(seed // 2**32))
    return jax.random.fold_in(key, np.uint32(stream))


def weight_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, ff, n, v, p = (
        int(cfg["n_embd"]), int(cfg["n_inner"]), int(cfg["n_layer"]),
        int(cfg["vocab_size"]), int(cfg["n_positions"]),
    )
    return {
        "wte": (v, d), "wpe": (p, d),
        "ln_1.g": (n, d), "ln_1.b": (n, d),
        "attn.c_attn.w": (n, d, 3 * d), "attn.c_attn.b": (n, 3 * d),
        "attn.c_proj.w": (n, d, d), "attn.c_proj.b": (n, d),
        "ln_2.g": (n, d), "ln_2.b": (n, d),
        "mlp.c_fc.w": (n, d, ff), "mlp.c_fc.b": (n, ff),
        "mlp.c_proj.w": (n, ff, d), "mlp.c_proj.b": (n, d),
        "ln_f.g": (d,), "ln_f.b": (d,),
    }


def make_weights(cfg: dict, key: jax.Array, dtype: Any = jnp.float32) -> dict:
    """Every weight from ``key`` (traceable: call it under ``jax.jit``).
    Layers are stacked on a leading axis. Always drawn in float32 and then
    cast, so the bf16 weights a server holds are the rounding of the
    float32 weights the reference holds."""
    shapes = weight_shapes(cfg)
    resid_scale = 1.0 / math.sqrt(2.0 * int(cfg["n_layer"]))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        x = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shapes[name], jnp.float32)
        if name.endswith("c_proj.w"):
            x = x * resid_scale
        if name.endswith(".g"):
            x = 1.0 + x
        out[name] = x.astype(dtype)
    return out


def init_weights(cfg: dict, seed: int, dtype: Any = jnp.float32) -> dict:
    """The reference's own copy: on the device, in ONE jitted call."""
    return jax.jit(lambda key: make_weights(cfg, key, dtype))(seed_key(seed, 1))


# ------------------------------------------- what the harness asks the family


def context_length(cfg: dict) -> int:
    return int(cfg["n_positions"])


def vocab_size(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def program_model(cfg: dict, seq_len: int | None = None) -> dict:
    """The ``model`` section of the program's run config for this
    configuration: the file's ``program.model`` block plus the published
    sizes under the program's own field names."""
    model = dict(cfg["program"]["model"])
    model["extra"] = dict(model.get("extra", {}))
    model.update(
        block_size=int(seq_len or cfg["n_positions"]), d_model=int(cfg["n_embd"]),
        n_layers=int(cfg["n_layer"]), n_heads=int(cfg["n_head"]), d_ff=int(cfg["n_inner"]),
        vocab_size=int(cfg["vocab_size"]),
    )
    return model


def matmul_params(cfg: dict) -> int:
    """Per layer qkv (3d^2) + out (d^2) + mlp (2*d*ff); plus the (tied)
    vocabulary matrix V*d."""
    d, ff = int(cfg["n_embd"]), int(cfg["n_inner"])
    return int(cfg["n_layer"]) * (4 * d * d + 2 * d * ff) + int(cfg["vocab_size"]) * d


def total_params(cfg: dict) -> int:
    """Every parameter, the tied head counted once."""
    return sum(math.prod(shape) for shape in weight_shapes(cfg).values())


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """The usual 6N + 12*L*T*d (PaLM, appendix B): each matmul parameter
    costs 2 FLOP forward and 4 backward per token; attention's QK^T and PV
    cost 4*T*d forward per token and layer at full (not causal) T, times 3
    with the backward. N is :func:`matmul_params`: every parameter that
    enters a matmul, ONCE — the tied token embedding counts once (as the
    lm-head; its lookup is free); position embeddings, biases and norm
    parameters do not count. Recomputation never counts. A causal kernel
    needs only half the attention term, so this number flatters a causal
    model's MFU by at most 6*L*T*d/(6N+12*L*T*d)."""
    return 6.0 * matmul_params(cfg) + 12.0 * int(cfg["n_layer"]) * seq_len * int(cfg["n_embd"])


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    """K and V of every layer for one position (heads * head_dim = n_embd)."""
    return 2 * int(cfg["n_layer"]) * int(cfg["n_embd"]) * bytes_per_value


# ------------------------------------------------------------------ names


def program_tree(w: dict, cfg: dict) -> dict:
    """The same arrays under the names and shapes ``llmtrain_tpu.models.gpt``
    gives its parameters (a renaming and reshaping only; works on arrays and
    on anything with ``.reshape`` and indexing)."""
    n_head = int(cfg["n_head"])
    d = int(cfg["n_embd"])
    hd = d // n_head
    tree: dict[str, Any] = {
        "token_embedding": {"embedding": w["wte"]},
        "position_embedding": {"embedding": w["wpe"]},
        "ln_f": {"scale": w["ln_f.g"], "bias": w["ln_f.b"]},
    }
    for i in range(int(cfg["n_layer"])):
        tree[f"block_{i}"] = {
            "ln_1": {"scale": w["ln_1.g"][i], "bias": w["ln_1.b"][i]},
            "ln_2": {"scale": w["ln_2.g"][i], "bias": w["ln_2.b"][i]},
            "attn": {
                "qkv_proj": {
                    "kernel": w["attn.c_attn.w"][i].reshape(d, 3, n_head, hd),
                    "bias": w["attn.c_attn.b"][i].reshape(3, n_head, hd),
                },
                "out_proj": {
                    "kernel": w["attn.c_proj.w"][i].reshape(n_head, hd, d),
                    "bias": w["attn.c_proj.b"][i],
                },
            },
            "mlp_fc": {"kernel": w["mlp.c_fc.w"][i], "bias": w["mlp.c_fc.b"][i]},
            "mlp_proj": {"kernel": w["mlp.c_proj.w"][i], "bias": w["mlp.c_proj.b"][i]},
        }
    return tree


def leaf_norms(w: dict, cfg: dict) -> dict[str, float]:
    """L2 norm of every leaf, keyed by the program's parameter path
    (``block_3/attn/qkv_proj/kernel``), from a stacked reference tree."""
    per_layer = {
        name: np.asarray(
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=tuple(range(1, x.ndim))))
        )
        if x.ndim > 1 and name not in ("wte", "wpe")
        else np.asarray(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
        for name, x in w.items()
    }
    # program_tree only renames: run it over the per-layer norm vectors.
    class _Norm:
        def __init__(self, v):
            self.v = np.asarray(v)

        def __getitem__(self, i):
            return _Norm(self.v[i])

        def reshape(self, *_):
            return self

    tree = program_tree({k: _Norm(v) for k, v in per_layer.items()}, cfg)
    flat: dict[str, float] = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, f"{path}/{k}" if path else k)
        else:
            flat[path] = float(node.v)

    walk(tree, "")
    return flat


# ---------------------------------------------------------------- forward


def _round_to(x: jax.Array, precision: str) -> jax.Array:
    """``x`` rounded to the control's type and back. Straight-through: the
    gradient passes as if nothing was rounded (a tangent pushed through a
    float8 cast would itself underflow to zero)."""
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


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _act(x, name: str):
    if name == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if name == "gelu_new":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"activation_function {name!r} is not in the GPT-2 family")


def hidden_states(w: dict, ids: jax.Array, cfg: dict, precision: str = "f32") -> jax.Array:
    """(B, T) token ids -> (B, T, d) final-norm hidden states, float32."""
    n_head, d = int(cfg["n_head"]), int(cfg["n_embd"])
    hd = d // n_head
    eps, act = float(cfg["layer_norm_epsilon"]), str(cfg["activation_function"])
    b, t = ids.shape
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = w["wte"][ids] + w["wpe"][:t][None]
    causal = jnp.tril(jnp.ones((t, t), bool))
    layer_names = [k for k in w if k not in ("wte", "wpe", "ln_f.g", "ln_f.b")]

    def layer(x, lw):
        h = _layer_norm(x, lw["ln_1.g"], lw["ln_1.b"], eps)
        qkv = _mm(h, lw["attn.c_attn.w"], precision) + lw["attn.c_attn.b"]
        q, k, v = (
            z.reshape(b, t, n_head, hd).transpose(0, 2, 1, 3) for z in jnp.split(qkv, 3, -1)
        )
        s = _mm(q, k.transpose(0, 1, 3, 2), precision) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        a = _mm(jax.nn.softmax(s, -1), v, precision)
        a = a.transpose(0, 2, 1, 3).reshape(b, t, d)
        x = x + _mm(a, lw["attn.c_proj.w"], precision) + lw["attn.c_proj.b"]
        h = _layer_norm(x, lw["ln_2.g"], lw["ln_2.b"], eps)
        h = _act(_mm(h, lw["mlp.c_fc.w"], precision) + lw["mlp.c_fc.b"], act)
        x = x + _mm(h, lw["mlp.c_proj.w"], precision) + lw["mlp.c_proj.b"]
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, {k: w[k] for k in layer_names})
    return _layer_norm(x, w["ln_f.g"], w["ln_f.b"], eps)


def logits_fn(w: dict, ids: jax.Array, cfg: dict, precision: str = "f32") -> jax.Array:
    h = hidden_states(w, ids, cfg, precision)
    return _mm(h, w["wte"].astype(jnp.float32).T, precision)


def loss_sum(w: dict, ids: jax.Array, labels: jax.Array, mask: jax.Array, cfg: dict,
             precision: str = "f32") -> jax.Array:
    """Sum over unmasked positions of the cross-entropy (labels arrive
    already shifted, as the program's data pipeline delivers them)."""
    logits = logits_fn(w, ids, cfg, precision)
    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum((lse - picked) * mask)


# ------------------------------------------------------- serving yardstick


def served_token_gaps(w: dict, cfg: dict, sequences: list[tuple[np.ndarray, np.ndarray]],
                      precision: str = "f32", pad_to: tuple[int, ...] = ()) -> dict[str, Any]:
    """For each (prompt, served tokens): run the reference ONCE over prompt +
    served tokens and read, at every served position, how far the served
    token's logit lies below the reference's best. Two numbers: the widest
    such gap over all served tokens, and the mean gap over each sequence's
    FIRST served token — with seeded random weights and a tied head greedy
    decoding soon repeats one token with a wide margin, so the token that
    follows the random prompt is the close call that shows a precision. With
    ``precision`` below f32 it ALSO returns the control's two numbers, read
    at the SAME positions: the gap of the token the lower precision itself
    puts first there (teacher forced on the served tokens; no decoding).

    A sequence is padded to the smallest of ``pad_to`` (and the context
    length) that holds it: a few shapes, a few compiles. Only per-position
    gaps leave the device."""

    @partial(jax.jit, static_argnames=("prec",))
    def run(w, ids, nxt, prec):
        with jax.default_matmul_precision("highest"):
            ref = logits_fn(w, ids, cfg, "f32")
            low = ref if prec == "f32" else logits_fn(w, ids, cfg, prec)
        best = jnp.max(ref, -1)
        pick = lambda tok: jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]  # noqa: E731
        return best - pick(nxt), best - pick(jnp.argmax(low, -1))

    sizes = sorted({*pad_to, context_length(cfg)})
    gaps, control_gaps = [], []
    for prompt, served in sequences:
        seq = np.concatenate([prompt, served]).astype(np.int32)
        n = len(seq)
        ids = np.zeros((1, next(size for size in sizes if size >= n)), np.int32)
        ids[0, :n] = seq
        nxt = np.roll(ids, -1, axis=1)  # position p predicts token p+1
        served_gap, low_gap = run(w, jnp.asarray(ids), jnp.asarray(nxt), precision)
        pos = np.arange(len(prompt) - 1, n - 1)  # served token k sits at len(prompt)+k
        gaps.append(np.asarray(served_gap[0], np.float64)[pos])
        control_gaps.append(np.asarray(low_gap[0], np.float64)[pos])
    return {
        "widest_gap": float(max(g.max() for g in gaps)),
        "first_mean_gap": float(np.mean([g[0] for g in gaps])),
        "control_widest_gap": float(max(g.max() for g in control_gaps)),
        "control_first_mean_gap": float(np.mean([g[0] for g in control_gaps])),
        "tokens": int(sum(len(g) for g in gaps)),
    }


# ------------------------------------------------------ training yardstick


def lr_at(update: int, hyper: dict) -> float:
    """Learning rate of 0-indexed optimizer update ``update``: linear from 0
    over ``warmup_steps`` updates, then cosine to 0 at ``max_steps``."""
    warm, total, base = int(hyper["warmup_steps"]), int(hyper["max_steps"]), float(hyper["lr"])
    if update < warm:
        return base * update / warm
    progress = min(max((update - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * 0.5 * (1.0 + math.cos(math.pi * progress))


def train_reference(cfg: dict, seed: int, batches: list[dict[str, np.ndarray]], hyper: dict,
                    rows_per_block: int = 4, precision: str = "f32") -> dict[str, Any]:
    """Follow the first ``len(batches)`` optimizer steps in float32.

    Each batch holds ``input_ids``, ``labels`` and ``attention_mask`` of
    shape (accum, rows, T). The step is the one the configuration states:
    loss = mean over accumulation micro-batches of the token-mean loss of
    each; gradient = mean of the micro-batch gradients, clipped to global
    norm ``max_grad_norm``; AdamW (0.9, 0.999, 1e-8, decoupled decay on
    every parameter). Returns each step's loss, the per-leaf norm of the
    first clipped gradient, and the per-leaf norm of the parameters' change
    after the last step, both keyed by the program's parameter paths.
    """
    b1, b2, eps = 0.9, 0.999, 1e-8
    wd, clip = float(hyper["weight_decay"]), float(hyper["max_grad_norm"])

    @jax.jit
    def block_grad(w, ids, labels, mask):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss_sum)(w, ids, labels, mask, cfg, precision)

    @jax.jit
    def accumulate(acc, g, scale):
        return jax.tree.map(lambda a, x: a + x * scale, acc, g)

    @jax.jit
    def apply(w, mu, nu, g, lr, t):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-30)), g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        def upd(p, m, v):
            mh, vh = m / (1 - b1**t), v / (1 - b2**t)
            return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
        return jax.tree.map(upd, w, mu, nu), mu, nu, g

    w0 = init_weights(cfg, seed)
    w = w0
    mu = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)
    losses, first_grad = [], None
    for step, batch in enumerate(batches):
        accum, rows, _ = batch["input_ids"].shape
        grads = jax.tree.map(jnp.zeros_like, w)
        loss = 0.0
        for a in range(accum):
            tokens = float(np.sum(batch["attention_mask"][a] != 0))
            for r in range(0, rows, rows_per_block):
                sl = slice(r, r + rows_per_block)
                ls, g = block_grad(
                    w, jnp.asarray(batch["input_ids"][a, sl]), jnp.asarray(batch["labels"][a, sl]),
                    jnp.asarray((batch["attention_mask"][a, sl] != 0).astype(np.float32)),
                )
                grads = accumulate(grads, g, 1.0 / (tokens * accum))
                loss += float(ls) / (tokens * accum)
        losses.append(loss)
        w, mu, nu, clipped = apply(w, mu, nu, grads, lr_at(step, hyper), float(step + 1))
        if step == 0:
            first_grad = leaf_norms(clipped, cfg)
    delta = jax.tree.map(lambda a, b: a - b, w, w0)
    return {"losses": losses, "grad_norms": first_grad, "delta_norms": leaf_norms(delta, cfg)}

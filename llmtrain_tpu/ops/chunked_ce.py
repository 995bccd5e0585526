"""Chunked (vocab-blocked) cross-entropy: CE loss without [B,T,V] tensors.

The dense LM loss path (models/base.py:masked_ce_components) materializes
the full logits tensor and, in the backward, its softmax gradient — at
GPT-2's V=50257 and a batch of 64x512 that is the single largest
HBM resident of the train step (reference behavior spec: gpt.py:256-269;
the reference materializes the same tensors via F.cross_entropy).

This op computes the identical per-token loss by streaming over vocab
chunks with a running logsumexp (`lax.scan`), and a `custom_vjp` whose
backward RECOMPUTES each chunk's logits to accumulate dhidden and dW —
so peak memory is O(B*T*chunk) instead of O(B*T*V), trading one extra
hidden@W pass for the saved bandwidth (the flash-attention trade, applied
to the lm_head).

Matmuls run in the model dtype with f32 accumulation
(``preferred_element_type``) — MXU-friendly on TPU; the streaming
statistics and gradients accumulate in f32.

Select per run with ``model.extra.loss_impl: chunked_ce`` (models/gpt.py);
chunk size via ``model.extra.ce_chunk`` (default 8192, a multiple of the
128-lane TPU tile).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

DEFAULT_CHUNK = 8192


def _pad_vocab(w: jax.Array, chunk: int) -> tuple[jax.Array, int]:
    """Pad [V, d] to a chunk multiple; returns (padded [n*chunk, d], n)."""
    v = w.shape[0]
    n_chunks = -(-v // chunk)
    pad = n_chunks * chunk - v
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    return w, n_chunks


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def chunked_ce_per_token(
    hidden: jax.Array,
    w_vocab: jax.Array,
    labels: jax.Array,
    chunk: int = DEFAULT_CHUNK,
    compute_dtype: jnp.dtype | None = None,
    z_loss: float = 0.0,
) -> jax.Array:
    """Per-token CE loss, f32, shape (B, T).

    hidden: (B, T, d) post-final-norm activations. w_vocab: (V, d) in
    embedding layout (tied ``token_embedding.embedding`` directly; untied
    ``lm_head.kernel`` transposed). labels: (B, T) int ids. ``z_loss``
    adds PaLM's ``z_loss * log(Z)^2`` per token — free here, the
    streaming logsumexp is already computed.
    """
    loss, _ = _forward(hidden, w_vocab, labels, chunk, compute_dtype, z_loss)
    return loss


def _forward(hidden, w_vocab, labels, chunk, compute_dtype, z_loss):
    v = w_vocab.shape[0]
    dt = compute_dtype or hidden.dtype
    w_pad, n_chunks = _pad_vocab(w_vocab, chunk)
    w_chunks = w_pad.reshape(n_chunks, chunk, w_pad.shape[-1])

    h = hidden.astype(dt)

    def scan_chunk(carry, xs):
        m, s = carry  # running max / scaled sum-exp, (B, T) f32
        w_c, base = xs
        logits = jnp.einsum(
            "btd,vd->btv", h, w_c.astype(dt), preferred_element_type=jnp.float32
        )
        # Padded vocab rows must not contribute to the partition function.
        col_ok = (base + jnp.arange(chunk)) < v
        logits = jnp.where(col_ok[None, None, :], logits, -jnp.inf)
        m_c = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_c)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[..., None]), axis=-1
        )
        return (m_new, s), None

    b, t = labels.shape
    init = (
        jnp.full((b, t), -jnp.inf, jnp.float32),
        jnp.zeros((b, t), jnp.float32),
    )
    bases = jnp.arange(n_chunks) * chunk
    (m, s), _ = jax.lax.scan(scan_chunk, init, (w_chunks, bases))
    lse = m + jnp.log(s)

    label_emb = jnp.take(w_vocab, labels, axis=0).astype(dt)  # (B, T, d)
    label_logit = jnp.einsum(
        "btd,btd->bt", h, label_emb, preferred_element_type=jnp.float32
    )
    per_token = lse - label_logit
    if z_loss > 0.0:
        per_token = per_token + z_loss * jnp.square(lse)
    return per_token, lse


def _fwd(hidden, w_vocab, labels, chunk, compute_dtype, z_loss):
    loss, lse = _forward(hidden, w_vocab, labels, chunk, compute_dtype, z_loss)
    return loss, (hidden, w_vocab, labels, lse)


def _bwd(chunk, compute_dtype, z_loss, res, g):
    hidden, w_vocab, labels, lse = res
    v, d = w_vocab.shape
    dt = compute_dtype or hidden.dtype
    w_pad, n_chunks = _pad_vocab(w_vocab, chunk)
    w_chunks = w_pad.reshape(n_chunks, chunk, d)

    h = hidden.astype(dt)
    gf = g.astype(jnp.float32)  # (B, T)
    # d(per_token)/d(lse) = 1 (CE) + 2*z*lse (z-loss); both flow through
    # the softmax. The -label_logit term keeps coefficient -1.
    g_lse = gf * (1.0 + 2.0 * z_loss * lse) if z_loss > 0.0 else gf

    def scan_chunk(dh, xs):
        w_c, base = xs
        logits = jnp.einsum(
            "btd,vd->btv", h, w_c.astype(dt), preferred_element_type=jnp.float32
        )
        col_ok = (base + jnp.arange(chunk)) < v
        logits = jnp.where(col_ok[None, None, :], logits, -jnp.inf)
        # d(lse)/d(logit) = softmax; weight by the incoming cotangent.
        gp = jnp.exp(logits - lse[..., None]) * g_lse[..., None]  # (B, T, chunk)
        dh = dh + jnp.einsum(
            "btv,vd->btd", gp, w_c.astype(dt), preferred_element_type=jnp.float32
        )
        dw_c = jnp.einsum(
            "btv,btd->vd", gp, h, preferred_element_type=jnp.float32
        )
        return dh, dw_c

    bases = jnp.arange(n_chunks) * chunk
    dh, dw_chunks = jax.lax.scan(
        scan_chunk, jnp.zeros(hidden.shape, jnp.float32), (w_chunks, bases)
    )
    dw = dw_chunks.reshape(n_chunks * chunk, d)[:v]

    # The -label_logit term: dhidden -= g * W[label]; dW[label] -= g * hidden.
    label_emb = jnp.take(w_vocab, labels, axis=0).astype(jnp.float32)
    dh = dh - gf[..., None] * label_emb
    scatter = (-gf[..., None] * hidden.astype(jnp.float32)).reshape(-1, d)
    dw = dw.at[labels.reshape(-1)].add(scatter)

    return dh.astype(hidden.dtype), dw.astype(w_vocab.dtype), None


chunked_ce_per_token.defvjp(_fwd, _bwd)


def chunked_ce_components(
    hidden: jax.Array,
    w_vocab: jax.Array,
    labels: jax.Array,
    attention_mask: jax.Array | None,
    *,
    chunk: int = DEFAULT_CHUNK,
    z_loss: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """Per-example ``(loss_sum, token_count)`` of shape (B,) — the drop-in
    counterpart of models/base.py:masked_ce_components, same mask-aware
    semantics (reference gpt.py:256-269), computed without full logits."""
    per_token = chunked_ce_per_token(hidden, w_vocab, labels, chunk, None, z_loss)
    if attention_mask is None:
        mask = jnp.ones_like(per_token)
    else:
        # Boolean semantics: segment ids > 1 (packed cross-document
        # masking) must not become loss weights.
        mask = (attention_mask != 0).astype(jnp.float32)
    return jnp.sum(per_token * mask, axis=-1), jnp.sum(mask, axis=-1)


__all__ = ["chunked_ce_per_token", "chunked_ce_components", "DEFAULT_CHUNK"]

"""Fused lm-head + cross-entropy Pallas kernel: logits never touch HBM.

`ops/chunked_ce.py` already shrinks the loss from O(B*T*V) to
O(B*T*chunk) by streaming vocab chunks through XLA — but each chunk's
logits block is still an XLA-materialized intermediate that round-trips
HBM. This module closes the remaining gap with a blockwise Pallas TPU
kernel that computes per-token CE (+ PaLM z-loss) directly from
``(hidden [B,T,d], w_vocab [V,d], labels)``:

* **forward** tiles over (token-block × vocab-block) with the online
  logsumexp/max recurrence held in VMEM — the flash-attention trick
  applied to the lm-head:  ``m' = max(m, max(logits));
  s' = s*exp(m-m') + sum(exp(logits-m'))``; ``lse = m + log(s)``.
  The label logit is picked up for free while the tile is resident
  (a one-hot column-hit mask — no gather).
* **backward** RECOMPUTES each vocab tile's logits in-kernel and
  accumulates ``dhidden`` (vocab-innermost grid) and ``dW``
  (token-innermost grid) into f32 revisited output blocks, using
  ``dlogit = softmax * g_lse - onehot(label) * g``.

Neither pass ever writes a logits tile to HBM: the only [*, V]-shaped
traffic left in the step is the weight matrix itself.

Selection: ``model.extra.loss_impl: fused_ce`` (models/gpt.py). OFF the
chip the explicit knob degrades to chunked_ce with a once-per-process
warning (the ``fp8_supported()`` pattern from ops/quant.py) and
``model.extra.pallas_interpret: true`` forces the ``interpret=True``
emulation path so CPU runs — including tier-1 parity tests — execute the
real kernel logic. ON platform ``tpu`` nothing degrades: interpret mode
is an error there, and the run report names the impl that executed.

On a mesh of more than one device ``fused_ce_per_token`` wraps itself in
``shard_map`` (tokens over the batch and sequence axes, the ``[V, d]``
operand gathered, its gradient summed over the token shards) — GSPMD
cannot partition a Mosaic kernel. A vocab-sharded lm-head (``tensor`` >
1) is rejected at plan time (autotune/plan.py).

Block sizes via ``model.extra.fused_ce_block_t`` / ``fused_ce_block_v``
(defaults 256 / 512: a (512, d) f32 weight tile plus the (256, 512)
logits tile stay well under the ~16 MB/core VMEM budget up to d≈4k).
"""

from __future__ import annotations

import logging
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import BATCH_AXES, kernel_mesh, shard_axes

logger = logging.getLogger(__name__)

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_V = 512

# Finite stand-in for -inf: masked lanes must stay orderable and
# exp()-able without spawning inf-inf = NaN in the recurrence (same
# constant as ops/pallas_attention.py).
_NEG_INF = -1e30

LOSS_IMPLS = ("dense", "chunked_ce", "fused_ce")

_FALLBACK_WARNED: set[str] = set()
_AUTO_LOGGED: set[str] = set()


def pallas_ce_supported() -> bool:
    """True when the compiled (non-interpret) Pallas kernels can run.

    Mosaic lowering is TPU-only in this tree — same backend gate as
    ops/flash_attention.py:_use_pallas. CPU/GPU callers get the kernels
    via ``interpret=True`` (tests, bench) or fall back to chunked_ce.
    """
    return jax.default_backend() == "tpu"


def check_pallas_interpret(interpret: bool) -> None:
    """``model.extra.pallas_interpret`` exists so CPU runs execute the
    kernel logic; on the chip it would run the emulation in place of the
    compiled kernel under the kernel's name — an error, not a mode."""
    if interpret and pallas_ce_supported():
        raise ValueError(
            "model.extra.pallas_interpret: true on platform tpu — interpret "
            "mode is the CPU emulation of the Pallas kernels; remove the "
            "key to run the compiled kernels on the chip"
        )


def resolve_loss_impl(
    requested: str | None,
    *,
    vocab_size: int,
    ce_auto_vocab: int,
    interpret: bool = False,
) -> str:
    """The single selection authority for ``model.extra.loss_impl``.

    Explicit knob always wins (unknown value raises); ``fused_ce`` OFF
    the chip without interpret mode degrades to chunked_ce with a
    once-per-process warning (the fp8-fallback contract from
    ops/quant.py) — on platform tpu the compiled kernel always runs and
    interpret mode raises (:func:`check_pallas_interpret`). Unset
    auto-selects at
    ``vocab_size >= ce_auto_vocab``: fused on TPU, chunked elsewhere.
    Used by the GPT adapter family at build time and by the autotune
    planner so `llmtrain plan` verdicts assume the same impl training
    will materialize.
    """
    check_pallas_interpret(interpret)
    if requested is not None:
        if requested not in LOSS_IMPLS:
            raise ValueError(
                f"model.extra.loss_impl {requested!r} unknown; "
                f"expected one of {', '.join(LOSS_IMPLS)}"
            )
        if requested == "fused_ce" and not (pallas_ce_supported() or interpret):
            if "fused_ce" not in _FALLBACK_WARNED:
                _FALLBACK_WARNED.add("fused_ce")
                logger.warning(
                    "loss_impl: fused_ce requested but backend %r has no "
                    "Pallas TPU support; falling back to chunked_ce "
                    "(set model.extra.pallas_interpret: true to force the "
                    "interpret-mode kernel)",
                    jax.default_backend(),
                )
            return "chunked_ce"
        return requested
    if vocab_size >= ce_auto_vocab:
        impl = "fused_ce" if (pallas_ce_supported() or interpret) else "chunked_ce"
        if impl not in _AUTO_LOGGED:
            _AUTO_LOGGED.add(impl)
            logger.info(
                "loss_impl auto-selected: %s (vocab_size %d >= "
                "model.extra.ce_auto_vocab %d and loss_impl unset; pass "
                "loss_impl: dense to override)",
                impl,
                vocab_size,
                ce_auto_vocab,
            )
        return impl
    return "dense"


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    pad = rows - x.shape[0]
    if pad:
        cfg = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, cfg)
    return x


# ---------------------------------------------------------------------------
# forward kernel: grid (token-blocks, vocab-blocks), vocab innermost.
# The three (1, BT) outputs live at a fixed index per token-block and are
# revisited across the vocab dimension — the repo's established
# accumulate-across-innermost-grid-dim idiom (ops/pallas_attention.py
# _bwd_dkdv_kernel): zero/init at j == 0, finalize at j == n_vb - 1.
# ---------------------------------------------------------------------------


def _fwd_kernel(h_ref, w_ref, lab_ref, lse_ref, s_ref, ll_ref, *, block_v, vocab, n_vb):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        lse_ref[0] = jnp.full_like(lse_ref[0], _NEG_INF)
        s_ref[0] = jnp.zeros_like(s_ref[0])
        ll_ref[0] = jnp.zeros_like(ll_ref[0])

    h = h_ref[...]  # (BT, d)
    w = w_ref[...]  # (BV, d)
    logits = lax.dot_general(
        h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (BT, BV)
    col = j * block_v + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < vocab, logits, _NEG_INF)

    m_old = lse_ref[0]  # running max until the last step rewrites it as lse
    s_old = s_ref[0]
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=1))
    s_new = s_old * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(logits - m_new[:, None]), axis=1
    )
    # Label logit while the tile is resident: exactly one column hits.
    hit = col == lab_ref[0][:, None]
    ll_ref[0] += jnp.sum(jnp.where(hit, logits, 0.0), axis=1)
    lse_ref[0] = m_new
    s_ref[0] = s_new

    @pl.when(j == n_vb - 1)
    def _finalize():
        lse_ref[0] = m_new + jnp.log(s_new)


def _dlogit_tile(h, w, labels, lse, g_lse, g, col, vocab):
    """Recompute one logits tile and its cotangent dlogit (f32, BT x BV).

    dlogit = softmax(logits) * g_lse - onehot(label) * g; masked vocab
    columns produce exp(-1e30 - lse) == 0 and can never match a label.
    """
    logits = lax.dot_general(
        h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    logits = jnp.where(col < vocab, logits, _NEG_INF)
    gp = jnp.exp(logits - lse[:, None]) * g_lse[:, None]
    return gp - jnp.where(col == labels[:, None], g[:, None], 0.0)


def _bwd_dh_kernel(
    h_ref, w_ref, lab_ref, lse_ref, gl_ref, g_ref, dh_ref, *, block_v, vocab
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    h = h_ref[...]
    w = w_ref[...]
    col = j * block_v + lax.broadcasted_iota(jnp.int32, (h.shape[0], w.shape[0]), 1)
    gp = _dlogit_tile(h, w, lab_ref[0], lse_ref[0], gl_ref[0], g_ref[0], col, vocab)
    dh_ref[...] += lax.dot_general(
        gp, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _bwd_dw_kernel(
    h_ref, w_ref, lab_ref, lse_ref, gl_ref, g_ref, dw_ref, *, block_v, vocab
):
    # Grid (vocab-blocks, token-blocks): token dim innermost so the dW
    # tile is the revisited accumulator.
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    h = h_ref[...]
    w = w_ref[...]
    col = pl.program_id(0) * block_v + lax.broadcasted_iota(
        jnp.int32, (h.shape[0], w.shape[0]), 1
    )
    gp = _dlogit_tile(h, w, lab_ref[0], lse_ref[0], gl_ref[0], g_ref[0], col, vocab)
    dw_ref[...] += lax.dot_general(
        gp, h, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _prep(hidden, w_vocab, labels, block_t, block_v, compute_dtype):
    """Flatten + pad operands to block multiples; returns the kernel view."""
    b, t = labels.shape
    v, d = w_vocab.shape
    n = b * t
    dt = compute_dtype or hidden.dtype
    n_tb = _cdiv(n, block_t)
    n_vb = _cdiv(v, block_v)
    h = _pad_rows(hidden.reshape(n, d).astype(dt), n_tb * block_t)
    w = _pad_rows(w_vocab.astype(dt), n_vb * block_v)
    # Padded token rows get label -1: hits no column, so their label
    # accumulator stays 0 and no backward one-hot term fires.
    lab = _pad_rows(labels.reshape(n).astype(jnp.int32), n_tb * block_t)
    lab = jnp.where(
        jnp.arange(n_tb * block_t) < n, lab, jnp.int32(-1)
    ).reshape(1, n_tb * block_t)
    return h, w, lab, n, v, d, n_tb, n_vb


def _row_spec(block_t):
    # (1, BT) blocks over a (1, N) array: the singleton leading dim keeps
    # per-token vectors legal under Mosaic's 2-D tiling rules (same trick
    # as the (1, 1, BQ) carries in ops/pallas_attention.py).
    return pl.BlockSpec((1, block_t), lambda i, j: (0, i))


# The enclosing scope takes the ``jvp(...)`` / ``transpose(jvp(...))`` wrapping
# that a ``custom_vjp`` puts on the first scope under it, so the kernels'
# own ``name=`` reaches the HLO instruction (and the profiler trace) clean.
@jax.named_scope("fused_ce")
def _forward(hidden, w_vocab, labels, block_t, block_v, compute_dtype, z_loss, interpret):
    h, w, lab, n, v, d, n_tb, n_vb = _prep(
        hidden, w_vocab, labels, block_t, block_v, compute_dtype
    )
    row = jax.ShapeDtypeStruct((1, n_tb * block_t), jnp.float32)
    lse2, _, ll2 = pl.pallas_call(
        partial(_fwd_kernel, block_v=block_v, vocab=v, n_vb=n_vb),
        grid=(n_tb, n_vb),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            _row_spec(block_t),
        ],
        out_specs=[_row_spec(block_t)] * 3,
        out_shape=[row, row, row],
        interpret=interpret,
        name="fused_ce_fwd",
    )(h, w, lab)
    b, t = labels.shape
    lse = lse2[0, :n]
    per_token = lse - ll2[0, :n]
    if z_loss > 0.0:
        per_token = per_token + z_loss * jnp.square(lse)
    return per_token.reshape(b, t), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce_local(
    hidden, w_vocab, labels, block_t, block_v, compute_dtype, z_loss, interpret
):
    """The kernels on ONE device's operands (differentiable)."""
    loss, _ = _forward(
        hidden, w_vocab, labels, block_t, block_v, compute_dtype, z_loss, interpret
    )
    return loss


def _fwd(hidden, w_vocab, labels, block_t, block_v, compute_dtype, z_loss, interpret):
    loss, lse = _forward(
        hidden, w_vocab, labels, block_t, block_v, compute_dtype, z_loss, interpret
    )
    return loss, (hidden, w_vocab, labels, lse)


@jax.named_scope("fused_ce")
def _bwd(block_t, block_v, compute_dtype, z_loss, interpret, res, g):
    hidden, w_vocab, labels, lse = res
    h, w, lab, n, v, d, n_tb, n_vb = _prep(
        hidden, w_vocab, labels, block_t, block_v, compute_dtype
    )
    gf = g.reshape(n).astype(jnp.float32)
    # d(per_token)/d(lse) = 1 (CE) + 2*z*lse (z-loss); the -label_logit
    # term keeps coefficient -1 via the one-hot in _dlogit_tile.
    g_lse = gf * (1.0 + 2.0 * z_loss * lse) if z_loss > 0.0 else gf
    n_pad = n_tb * block_t
    # Pad cotangents with 0 so padded token rows contribute nothing.
    lse_p = _pad_rows(lse, n_pad).reshape(1, n_pad)
    gl_p = _pad_rows(g_lse, n_pad).reshape(1, n_pad)
    g_p = _pad_rows(gf, n_pad).reshape(1, n_pad)

    row_in = _row_spec(block_t)
    dh = pl.pallas_call(
        partial(_bwd_dh_kernel, block_v=block_v, vocab=v),
        grid=(n_tb, n_vb),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            row_in,
            row_in,
            row_in,
            row_in,
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        interpret=interpret,
        name="fused_ce_bwd_dh",
    )(h, w, lab, lse_p, gl_p, g_p)

    col_in = pl.BlockSpec((1, block_t), lambda j, i: (0, i))
    dw = pl.pallas_call(
        partial(_bwd_dw_kernel, block_v=block_v, vocab=v),
        grid=(n_vb, n_tb),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            col_in,
            col_in,
            col_in,
            col_in,
        ],
        out_specs=pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n_vb * block_v, d), jnp.float32),
        interpret=interpret,
        name="fused_ce_bwd_dw",
    )(h, w, lab, lse_p, gl_p, g_p)

    b, t = labels.shape
    dh = dh[:n].reshape(b, t, -1).astype(hidden.dtype)
    return dh, dw[:v].astype(w_vocab.dtype), None


_fused_ce_local.defvjp(_fwd, _bwd)


def fused_ce_per_token(
    hidden: jax.Array,
    w_vocab: jax.Array,
    labels: jax.Array,
    block_t: int = DEFAULT_BLOCK_T,
    block_v: int = DEFAULT_BLOCK_V,
    compute_dtype: jnp.dtype | None = None,
    z_loss: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """Per-token CE loss, f32, shape (B, T) — drop-in for
    ops/chunked_ce.py:chunked_ce_per_token, computed by the Pallas
    kernels above. Same operand layout: ``w_vocab`` is (V, d) embedding
    layout (tied ``token_embedding.embedding`` directly, untied
    ``lm_head.kernel`` transposed).

    Under a multi-device mesh each chip runs the kernels on its own token
    shard against the gathered ``w_vocab``; shard_map's transpose sums
    ``dW`` over the token shards."""

    def local(h, w, lab):
        return _fused_ce_local(
            h, w, lab, block_t, block_v, compute_dtype, z_loss, interpret
        )

    mesh = kernel_mesh()
    if mesh is None:
        return local(hidden, w_vocab, labels)
    b, t = labels.shape
    tok = P(shard_axes(mesh, BATCH_AXES, b), shard_axes(mesh, ("sequence",), t))
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(*tok, None), P(None, None), tok),
        out_specs=tok,
        check_vma=False,
    )(hidden, w_vocab, labels)


def fused_ce_components(
    hidden: jax.Array,
    w_vocab: jax.Array,
    labels: jax.Array,
    attention_mask: jax.Array | None,
    *,
    block_t: int = DEFAULT_BLOCK_T,
    block_v: int = DEFAULT_BLOCK_V,
    z_loss: float = 0.0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-example ``(loss_sum, token_count)`` of shape (B,) — same
    mask-aware contract as chunked_ce_components / masked_ce_components
    (segment ids > 1 from packing are boolean-ized, not loss weights)."""
    per_token = fused_ce_per_token(
        hidden, w_vocab, labels, block_t, block_v, None, z_loss, interpret
    )
    if attention_mask is None:
        mask = jnp.ones_like(per_token)
    else:
        mask = (attention_mask != 0).astype(jnp.float32)
    return jnp.sum(per_token * mask, axis=-1), jnp.sum(mask, axis=-1)


__all__ = [
    "fused_ce_per_token",
    "fused_ce_components",
    "resolve_loss_impl",
    "check_pallas_interpret",
    "pallas_ce_supported",
    "LOSS_IMPLS",
    "DEFAULT_BLOCK_T",
    "DEFAULT_BLOCK_V",
]

"""Fused lm-head + cross-entropy Pallas kernel: logits never touch HBM.

`ops/chunked_ce.py` already shrinks the loss from O(B*T*V) to
O(B*T*chunk) by streaming vocab chunks through XLA — but each chunk's
logits block is still an XLA-materialized intermediate that round-trips
HBM. This module closes the remaining gap with a blockwise Pallas TPU
kernel pair that computes per-token CE (+ PaLM z-loss) directly from
``(hidden [B,T,d], w_vocab [V,d], labels)``.

Both kernels hold a logits tile TRANSPOSED, ``(block_v, block_t)`` float32:
vocabulary on sublanes, tokens on lanes. A reduction over the vocabulary
leaves a ``(1, block_t)`` row, which is the layout of every per-token
operand, carry and output, so no value changes layout between grid steps.

* **forward** (``fused_ce_fwd``), grid ``(token blocks, vocab blocks)``,
  vocab innermost: the online logsumexp/max recurrence of flash attention
  applied to the lm-head, ``m' = max(m, max(logits));
  s' = s*exp(m-m') + sum(exp(logits-m'))``; ``lse = m + log(s)``. The
  running max, the running sum and the label's logit (a row-hit mask, no
  gather) are ``(1, block_t)`` VMEM scratch; the ``lse`` and label-logit
  rows are written once, at the last vocab block. Padded vocabulary rows
  are masked only in the block that holds them. A step walks its token
  tile in unrolled chunks of one lane tile: a chunk's exponentials wait on
  its own column maxima only and run under the next chunk's product.
* **backward** (``fused_ce_bwd_dw``), ONE kernel on the same grid: a step
  RECOMPUTES its logits tile once, forms ``dlogit = softmax * g_lse -
  onehot(label) * g`` once (float32) and feeds both products.
  ``dh += dlogit^T W`` accumulates in its revisited ``(block_t, d)``
  output block (zeroed at the first vocab block). ``dW += dlogit h`` is
  visited once per TOKEN block, ``n_vb`` steps apart, so it is accumulated
  in HBM: the float32 ``(V_padded, d)`` array enters and leaves the call
  as an aliased pair of BlockSpecs, ``N / block_t`` reads and writes of
  the whole array that hide under the products when the token tile is
  large. The chip prefetches a step's input blocks while earlier steps'
  outputs are still being written, so the vocabulary axis has ONE block
  (dW then is a revisited accumulator too) or at least
  ``_MIN_RMW_BLOCKS``; ``_choose_tiles`` keeps to that and ``_bwd``
  refuses an override that does not.

Neither pass ever writes a logits tile to HBM: the only [*, V]-shaped
traffic left in the step is the weight matrix and its gradient. The
backward runs three vocabulary-sized products where a kernel that kept its
logits would run two, so its share of the two-product roofline cannot pass
two thirds.

Tiles: ``_choose_tiles`` picks ``(block_t, block_v)`` per kernel from N, V,
d and the operands' width, the first measured-good pair whose VMEM sum
(``_vmem_bytes``: every BlockSpec operand twice for the pipeline's two
buffers, the scratch carries, ``_TILE_TEMPS`` float32 copies of the logits
tile) fits ``_VMEM_LIMIT_BYTES``, which is handed to Mosaic as
``vmem_limit_bytes``. ``model.extra.fused_ce_block_t`` /
``fused_ce_block_v`` override both kernels' tiles (unset = chosen from
shapes). The pair and its bytes are logged once a process and shape.

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
"""

from __future__ import annotations

import logging
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import BATCH_AXES, kernel_mesh, shard_axes

logger = logging.getLogger(__name__)

# Mosaic's default scoped limit (16 MiB) does not hold the tiles below; the
# v5e has 128 MiB a core. The chooser keeps its own estimate under this.
_VMEM_LIMIT_BYTES = 64 * 2**20
_TILE_TEMPS = 3
# Candidate tiles, best first as the v5e measured them at (32 x 1,024, 768,
# 50,257) bf16 (PERF.md section 6, PR 41).
_TOKEN_TILES = {"fwd": (4096, 2048, 1024, 512, 256, 128), "bwd": (2048, 1024, 512, 256, 128)}
_VOCAB_TILES = {"fwd": (256, 128), "bwd": (512, 256, 128)}
# Fewest vocabulary blocks (other than one) between two visits of a dW block
# that the backward reads back from HBM: with the pipeline's two buffers a
# side, the write of step k has landed before step k + 3 prefetches.
_MIN_RMW_BLOCKS = 4
# Tokens a product of the forward's unrolled inner loop covers: one lane tile.
_FWD_LANE_CHUNK = 128

# Finite stand-in for -inf: masked lanes must stay orderable and
# exp()-able without spawning inf-inf = NaN in the recurrence (same
# constant as ops/pallas_attention.py).
_NEG_INF = -1e30

LOSS_IMPLS = ("dense", "chunked_ce", "fused_ce")

_FALLBACK_WARNED: set[str] = set()
_AUTO_LOGGED: set[str] = set()
_TILES_LOGGED: set[tuple] = set()


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


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    pad = rows - x.shape[0]
    if pad:
        cfg = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, cfg)
    return x


# ---------------------------------------------------------------------------
# tiles: chosen from what the call can see (N, V, d, the operands' width)
# under the VMEM budget handed to Mosaic as ``vmem_limit_bytes``.
# ---------------------------------------------------------------------------


def _vmem_bytes(kind: str, block_t: int, block_v: int, d: int, width: int) -> int:
    """VMEM bytes one grid step of the ``kind`` ("fwd" / "bwd") kernel holds
    at tiles ``(block_t, block_v)``, hidden width ``d`` and operands of
    ``width`` bytes, counted so that the chip's compiler has never asked for
    more (``tests/test_tpu_aot_compile.py`` compiles the chosen tiles at d 768
    to 8,192): every BlockSpec operand twice (the pipeline's two buffers),
    the scratch carries, ``_TILE_TEMPS`` float32 copies of the (block_v,
    block_t) logits tile (logits, the exponentials or dlogits, a select; the
    forward walks it a lane tile at a time and needs less) and, in the
    backward, each gradient block once more per value the body forms of it
    (dh's product before it is added; dW's product and its sum)."""
    operands = 2 * (block_t + block_v) * d * width
    row = 8 * block_t * 4  # a (1, block_t) row fills whole (8, 128) tiles
    tile = _TILE_TEMPS * block_t * block_v * 4
    if kind == "fwd":
        # labels in, lse and label logit out, three scratch carries.
        return operands + tile + (2 * 3 + 3) * row
    # dh: the revisited output twice and its product; dW: in and out twice
    # each, its product and the sum; four rows in.
    grads = 3 * block_t * d * 4 + 6 * block_v * d * 4
    return operands + grads + tile + 2 * 4 * row


def _choose_tiles(kind: str, n: int, v: int, d: int, width: int) -> tuple[int, int]:
    """``(block_t, block_v)`` of the ``kind`` kernel for N tokens, V vocabulary
    rows, hidden width d and ``width``-byte operands: the first pair, in the
    order the v5e measured them at the train cell's shape (PERF.md section 6,
    PR 41), whose ``_vmem_bytes`` fit ``_VMEM_LIMIT_BYTES``.

    The token tile goes first and as large as fits: it sets how often the
    weight streams (forward) and how often dW is read and written back
    (backward), N / block_t times each; a wider d shrinks it. A token tile
    that would pad N by more than 1/32 is passed over for a smaller one.
    The backward's vocabulary axis gets ONE block or at least
    ``_MIN_RMW_BLOCKS``: fewer would revisit an HBM-accumulated dW block
    while its last write may still be in flight (``_bwd``).
    """
    lanes = 128
    for block_t in _TOKEN_TILES[kind]:
        block_t = min(block_t, _round_up(n, lanes))
        if block_t > lanes and (_round_up(n, block_t) - n) * 32 > n:
            continue
        for block_v in _VOCAB_TILES[kind]:
            block_v = min(block_v, _round_up(v, lanes))
            if kind == "bwd" and 1 < _cdiv(v, block_v) < _MIN_RMW_BLOCKS:
                continue
            if _vmem_bytes(kind, block_t, block_v, d, width) <= _VMEM_LIMIT_BYTES:
                return block_t, block_v
    raise ValueError(
        f"fused_ce: no {kind} tile fits {_VMEM_LIMIT_BYTES >> 20} MiB of VMEM at "
        f"d={d}, {width}-byte operands; use loss_impl: chunked_ce"
    )


def _tiles(kind, n, v, d, width, block_t, block_v) -> tuple[int, int]:
    """The overrides where given (``fused_ce_block_t`` / ``_v``, both
    kernels), else the chosen pair; logged once a process and shape."""
    chosen = block_t is None or block_v is None
    if chosen:
        auto_t, auto_v = _choose_tiles(kind, n, v, d, width)
        block_t, block_v = block_t or auto_t, block_v or auto_v
    key = (kind, n, v, d, width, block_t, block_v)
    if key not in _TILES_LOGGED:
        _TILES_LOGGED.add(key)
        logger.info(
            "fused_ce %s tiles: %d tokens x %d vocabulary rows (%s), %.1f MiB of "
            "VMEM under a limit of %d MiB, at N=%d V=%d d=%d, %d-byte operands",
            kind, block_t, block_v,
            "chosen from shapes" if chosen else "overridden",
            _vmem_bytes(kind, block_t, block_v, d, width) / 2**20,
            _VMEM_LIMIT_BYTES >> 20, n, v, d, width,
        )
    return block_t, block_v


def _compiler_params(*semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT_BYTES
    )


# ---------------------------------------------------------------------------
# Both kernels hold the logits tile TRANSPOSED: (block_v, block_t), vocabulary
# on sublanes and tokens on lanes. A reduction over the vocabulary is then
# element-wise over vregs plus one 8-sublane fold, and leaves a (1, block_t)
# row: the layout of the per-token operands, of the carries and of the
# outputs, so no value changes layout between grid steps.
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))  # (m, k) x (n, k) -> (m, n)
_NN = (((1,), (0,)), ((), ()))  # (m, k) x (k, n) -> (m, n)
_TN = (((0,), (0,)), ((), ()))  # (k, m) x (k, n) -> (m, n)


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _logits_tile(w, h, rows_left, masked):
    """Float32 logits ``w @ h^T`` of a (vocabulary rows, tokens) tile and the
    rows' index within it. ``rows_left`` is the vocabulary's end counted from
    the tile's first row; ``masked`` (static) says whether any row can lie
    past it. A row then meets the end, and a label, after ONE (1, tokens)
    subtraction and not an add over the whole tile."""
    logits = _dot(w, h, _NT)
    row = lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    if masked:
        logits = jnp.where(row < rows_left, logits, _NEG_INF)
    return logits, row


# forward: grid (token blocks, vocabulary blocks), vocabulary innermost. The
# running max, the running sum and the label's logit are (1, block_t) VMEM
# scratch in the reductions' own orientation; the two output rows are written
# once, at the last vocabulary block.
def _fwd_kernel(
    h_ref, w_ref, lab_ref, lse_ref, ll_ref, m_s, s_s, ll_s, *, block_v, vocab, n_vb
):
    j = pl.program_id(1)
    block_t = h_ref.shape[0]
    chunk = _FWD_LANE_CHUNK if block_t % _FWD_LANE_CHUNK == 0 else block_t

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        s_s[...] = jnp.zeros_like(s_s)
        ll_s[...] = jnp.zeros_like(ll_s)

    def body(masked):
        first_row = j * block_v
        w = w_ref[...]
        m_old, s_old, ll_old = m_s[...], s_s[...], ll_s[...]
        m_new, s_new, ll_new = [], [], []
        # Tokens in chunks of one lane tile, unrolled, the carries read and
        # written once as whole rows: a chunk's exponentials wait only on its
        # own column maxima and run under the next chunk's product.
        for start in range(0, block_t, chunk):
            cols = slice(start, start + chunk)
            logits, row = _logits_tile(w, h_ref[cols, :], vocab - first_row, masked)
            m = jnp.maximum(m_old[:, cols], jnp.max(logits, axis=0, keepdims=True))
            s_new.append(
                s_old[:, cols] * jnp.exp(m_old[:, cols] - m)
                + jnp.sum(jnp.exp(logits - m), axis=0, keepdims=True)
            )
            # Label logit while the tile is resident: at most one row hits.
            hit = row == lab_ref[:, cols] - first_row
            ll_new.append(
                ll_old[:, cols] + jnp.sum(jnp.where(hit, logits, 0.0), axis=0, keepdims=True)
            )
            m_new.append(m)
        m_s[...] = jnp.concatenate(m_new, axis=1)
        s_s[...] = jnp.concatenate(s_new, axis=1)
        ll_s[...] = jnp.concatenate(ll_new, axis=1)

    # The vocabulary mask only where rows are padded: the last block, when V
    # is no multiple of the tile (one product a tile does not hide it).
    if vocab % block_v == 0:
        body(False)
    elif n_vb == 1:
        body(True)
    else:
        pl.when(j < n_vb - 1)(partial(body, False))
        pl.when(j == n_vb - 1)(partial(body, True))

    @pl.when(j == n_vb - 1)
    def _finalize():
        lse_ref[...] = m_s[...] + jnp.log(s_s[...])
        ll_ref[...] = ll_s[...]


# backward: ONE kernel, grid (token blocks, vocabulary blocks), vocabulary
# innermost. A step recomputes its logits tile once, forms
# dlogit = softmax * g_lse - onehot(label) * g once (float32; masked rows give
# exp(-1e30 - lse) == 0 and never match a label) and feeds both products.
# dh's (block_t, d) block is the revisited accumulator of the inner axis. dW's
# (block_v, d) block is visited once per token block, n_vb steps apart: it is
# accumulated in HBM, read through ``dw_in_ref`` and written through
# ``dw_ref``, the same (aliased) float32 array. The mask sits in every block
# (three products hide it, and ONE body compiles in half the time of two).
def _bwd_kernel(
    h_ref, w_ref, lab_ref, lse_ref, gl_ref, g_ref, dw_in_ref, dh_ref, dw_ref,
    *, block_v, vocab, n_vb,
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    first_row = j * block_v
    h = h_ref[...]
    w = w_ref[...]
    logits, row = _logits_tile(w, h, vocab - first_row, vocab % block_v != 0)
    dlogit = jnp.exp(logits - lse_ref[...]) * gl_ref[...] - jnp.where(
        row == lab_ref[...] - first_row, g_ref[...], 0.0
    )
    dh_ref[...] += _dot(dlogit, w, _TN)
    dw = _dot(dlogit, h, _NN)
    if n_vb > 1:
        dw_sum = dw_in_ref[...] + dw
        dw_ref[...] = dw_sum
        # For the interpreter, which keeps an aliased pair as two arrays and
        # would hand every visit the zeros the input began as; on the chip a
        # store into an input's VMEM buffer that goes nowhere.
        dw_in_ref[...] = dw_sum
    else:
        # ONE vocabulary block: its index never changes, so the pipeline
        # neither re-reads the input nor writes the output between steps.
        # The output block is then a revisited accumulator like dh's.
        @pl.when(i == 0)
        def _first():
            dw_ref[...] = dw

        @pl.when(i > 0)
        def _rest():
            dw_ref[...] += dw


def _prep(hidden, w_vocab, labels, kind, block_t, block_v, compute_dtype):
    """Flatten + pad operands to block multiples; returns the kernel view."""
    b, t = labels.shape
    v, d = w_vocab.shape
    n = b * t
    dt = jnp.dtype(compute_dtype or hidden.dtype)
    block_t, block_v = _tiles(kind, n, v, d, dt.itemsize, block_t, block_v)
    n_tb = _cdiv(n, block_t)
    n_vb = _cdiv(v, block_v)
    h = _pad_rows(hidden.reshape(n, d).astype(dt), n_tb * block_t)
    w = _pad_rows(w_vocab.astype(dt), n_vb * block_v)
    # Padded token rows get label -1: hits no row, so their label
    # accumulator stays 0 and no backward one-hot term fires.
    lab = _pad_rows(labels.reshape(n).astype(jnp.int32), n_tb * block_t)
    lab = jnp.where(
        jnp.arange(n_tb * block_t) < n, lab, jnp.int32(-1)
    ).reshape(1, n_tb * block_t)
    return h, w, lab, n, v, d, block_t, block_v, n_tb, n_vb


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
    h, w, lab, n, v, d, block_t, block_v, n_tb, n_vb = _prep(
        hidden, w_vocab, labels, "fwd", block_t, block_v, compute_dtype
    )
    row = jax.ShapeDtypeStruct((1, n_tb * block_t), jnp.float32)
    lse2, ll2 = pl.pallas_call(
        partial(_fwd_kernel, block_v=block_v, vocab=v, n_vb=n_vb),
        grid=(n_tb, n_vb),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            _row_spec(block_t),
        ],
        out_specs=[_row_spec(block_t)] * 2,
        out_shape=[row, row],
        scratch_shapes=[pltpu.VMEM((1, block_t), jnp.float32)] * 3,
        compiler_params=_compiler_params("parallel", "arbitrary"),
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
    h, w, lab, n, v, d, block_t, block_v, n_tb, n_vb = _prep(
        hidden, w_vocab, labels, "bwd", block_t, block_v, compute_dtype
    )
    if not interpret and 1 < n_vb < _MIN_RMW_BLOCKS and n_tb > 1:
        # The chip prefetches a step's input blocks while earlier steps'
        # outputs are still being written: a dW block read back fewer than
        # _MIN_RMW_BLOCKS steps after its write may read stale sums. The
        # chooser never picks such tiles; an override can.
        raise ValueError(
            f"fused_ce: fused_ce_block_v={block_v} gives the vocabulary axis "
            f"{n_vb} blocks at V={v}; the backward accumulates dW in HBM and "
            f"needs 1 block or at least {_MIN_RMW_BLOCKS}. Unset the key."
        )
    gf = g.reshape(n).astype(jnp.float32)
    # d(per_token)/d(lse) = 1 (CE) + 2*z*lse (z-loss); the -label_logit
    # term keeps coefficient -1 via the one-hot in the kernel.
    g_lse = gf * (1.0 + 2.0 * z_loss * lse) if z_loss > 0.0 else gf
    n_pad = n_tb * block_t
    # Pad cotangents with 0 so padded token rows contribute nothing.
    lse_p = _pad_rows(lse, n_pad).reshape(1, n_pad)
    gl_p = _pad_rows(g_lse, n_pad).reshape(1, n_pad)
    g_p = _pad_rows(gf, n_pad).reshape(1, n_pad)

    # h and dh walk the token blocks, W and dW (in and out) the vocabulary's.
    tok_spec = pl.BlockSpec((block_t, d), lambda i, j: (i, 0))
    voc_spec = pl.BlockSpec((block_v, d), lambda i, j: (j, 0))
    dw_shape = jax.ShapeDtypeStruct((n_vb * block_v, d), jnp.float32)
    dh, dw = pl.pallas_call(
        partial(_bwd_kernel, block_v=block_v, vocab=v, n_vb=n_vb),
        grid=(n_tb, n_vb),
        in_specs=[tok_spec, voc_spec] + [_row_spec(block_t)] * 4 + [voc_spec],
        out_specs=[tok_spec, voc_spec],
        out_shape=[jax.ShapeDtypeStruct((n_pad, d), jnp.float32), dw_shape],
        input_output_aliases={6: 1},
        compiler_params=_compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
        name="fused_ce_bwd_dw",
    )(h, w, lab, lse_p, gl_p, g_p, jnp.zeros(dw_shape.shape, dw_shape.dtype))

    b, t = labels.shape
    dh = dh[:n].reshape(b, t, -1).astype(hidden.dtype)
    return dh, dw[:v].astype(w_vocab.dtype), None


_fused_ce_local.defvjp(_fwd, _bwd)


def fused_ce_per_token(
    hidden: jax.Array,
    w_vocab: jax.Array,
    labels: jax.Array,
    block_t: int | None = None,
    block_v: int | None = None,
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
    block_t: int | None = None,
    block_v: int | None = None,
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
]

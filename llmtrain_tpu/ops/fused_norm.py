"""Fused residual-add + LayerNorm Pallas kernel (fwd + bwd).

The r06 attribution tables show the per-block pre-norms as pure
elementwise HBM round-trips: XLA reads the residual stream, writes the
sum, reads it back for the norm, writes the normed copy — twice per
layer. This kernel fuses ``s = x + residual; y = LN(s)`` into one VMEM
pass per token block and returns both ``y`` (for the sublayer) and
``s`` (the new residual stream), so the stream is read and written once.

Backward is the standard per-token LayerNorm gradient, recomputed from
the saved sum + per-token (mean, rstd):

    xhat  = (s - mean) * rstd
    dxhat = dy * scale
    ds    = rstd * (dxhat - mean_d(dxhat) - xhat * mean_d(dxhat * xhat))

``dscale``/``dbias`` accumulate into a revisited (1, d) output block
across the token-block grid (the same accumulate-across-grid idiom as
ops/pallas_attention.py and ops/fused_ce.py). ``dx == dresidual == ds``
(+ the incoming gradient on the returned sum), so the residual branch
costs nothing extra.

Wired per-block in models/gpt.py behind ``model.extra.fused_norm``;
``model.extra.pallas_interpret: true`` runs the emulated kernel on CPU
(tier-1 parity tests) and is an error on platform tpu. On a mesh of more
than one device both entry points wrap themselves in ``shard_map``
(tokens over the batch and sequence axes, ``scale``/``bias`` gathered,
their gradients summed over the token shards). Parameter names/shapes match ``nn.LayerNorm``
(``scale``/``bias`` of shape (d,)) so checkpoints are interchangeable
with the unfused path.
"""

from __future__ import annotations

import logging
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import BATCH_AXES, kernel_mesh, shard_axes

logger = logging.getLogger(__name__)

DEFAULT_BLOCK_T = 256
_FALLBACK_WARNED: set[str] = set()


def resolve_fused_norm(requested: bool, *, interpret: bool = False) -> bool:
    """fp8-style degrade OFF the chip: fused_norm without Pallas TPU
    support (warn-once) reverts to the unfused nn.LayerNorm path. On
    platform tpu the compiled kernel always runs and interpret mode
    raises (ops/fused_ce.py:check_pallas_interpret)."""
    from .fused_ce import check_pallas_interpret, pallas_ce_supported

    check_pallas_interpret(interpret)
    if requested and not (pallas_ce_supported() or interpret):
        if "fused_norm" not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add("fused_norm")
            logger.warning(
                "model.extra.fused_norm requested but backend %r has no "
                "Pallas TPU support; using the unfused LayerNorm path "
                "(set model.extra.pallas_interpret: true to force the "
                "interpret-mode kernel)",
                jax.default_backend(),
            )
        return False
    return bool(requested)


def _fwd_kernel(x_ref, res_ref, sc_ref, b_ref, y_ref, s_ref, m_ref, r_ref, *, eps):
    s = x_ref[...].astype(jnp.float32)
    if res_ref is not None:
        s = s + res_ref[...].astype(jnp.float32)
    mu = jnp.mean(s, axis=1)
    var = jnp.mean(jnp.square(s - mu[:, None]), axis=1)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (s - mu[:, None]) * rstd[:, None]
    y_ref[...] = (xhat * sc_ref[0][None, :] + b_ref[0][None, :]).astype(y_ref.dtype)
    if s_ref is not None:
        s_ref[...] = s.astype(s_ref.dtype)
    m_ref[0] = mu
    r_ref[0] = rstd


def _bwd_kernel(s_ref, sc_ref, m_ref, r_ref, gy_ref, dx_ref, dsc_ref, db_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dsc_ref[0] = jnp.zeros_like(dsc_ref[0])
        db_ref[0] = jnp.zeros_like(db_ref[0])

    s = s_ref[...].astype(jnp.float32)
    gy = gy_ref[...].astype(jnp.float32)
    mu = m_ref[0]
    rstd = r_ref[0]
    xhat = (s - mu[:, None]) * rstd[:, None]
    dxhat = gy * sc_ref[0][None, :].astype(jnp.float32)
    m1 = jnp.mean(dxhat, axis=1)
    m2 = jnp.mean(dxhat * xhat, axis=1)
    dx_ref[...] = (rstd[:, None] * (dxhat - m1[:, None] - xhat * m2[:, None])).astype(
        dx_ref.dtype
    )
    dsc_ref[0] += jnp.sum(gy * xhat, axis=0)
    db_ref[0] += jnp.sum(gy, axis=0)


def _pad_tokens(x, n_pad):
    pad = n_pad - x.shape[0]
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x


# The enclosing scope takes the ``jvp(...)`` / ``transpose(jvp(...))`` wrapping
# that a ``custom_vjp`` puts on the first scope under it, so the kernels'
# own ``name=`` reaches the HLO instruction (and the profiler trace) clean.
@jax.named_scope("fused_norm")
def _run_forward(x, residual, scale, bias, eps, block_t, interpret):
    shape = x.shape
    d = shape[-1]
    n = 1
    for dim in shape[:-1]:
        n *= dim
    n_tb = -(-n // block_t)
    n_pad = n_tb * block_t
    x2 = _pad_tokens(x.reshape(n, d), n_pad)
    operands = [x2]
    with_res = residual is not None
    if with_res:
        operands.append(_pad_tokens(residual.reshape(n, d), n_pad))
    operands += [scale.reshape(1, d), bias.reshape(1, d)]

    def kernel(*refs):
        if with_res:
            x_r, res_r, sc_r, b_r, y_r, s_r, m_r, r_r = refs
        else:
            x_r, sc_r, b_r, y_r, m_r, r_r = refs
            res_r = s_r = None
        _fwd_kernel(x_r, res_r, sc_r, b_r, y_r, s_r, m_r, r_r, eps=eps)

    tok = pl.BlockSpec((block_t, d), lambda i: (i, 0))
    param = pl.BlockSpec((1, d), lambda i: (0, 0))
    row = pl.BlockSpec((1, block_t), lambda i: (0, i))
    row_shape = jax.ShapeDtypeStruct((1, n_pad), jnp.float32)
    out_specs = [tok] + ([tok] if with_res else []) + [row, row]
    out_shape = [jax.ShapeDtypeStruct((n_pad, d), x.dtype)]
    if with_res:
        out_shape.append(jax.ShapeDtypeStruct((n_pad, d), x.dtype))
    out_shape += [row_shape, row_shape]
    outs = pl.pallas_call(
        kernel,
        grid=(n_tb,),
        in_specs=[tok] + ([tok] if with_res else []) + [param, param],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_norm_fwd",
    )(*operands)
    if with_res:
        y, s, mu, rstd = outs
    else:
        y, mu, rstd = outs
        s = y  # unused slot; the saved sum is x itself below
    return shape, n, y[:n].reshape(shape), s[:n].reshape(shape), mu, rstd


@jax.named_scope("fused_norm")
def _run_backward(s2, scale, mu, rstd, gy, shape, n, eps, block_t, interpret):
    d = shape[-1]
    n_tb = -(-n // block_t)
    n_pad = n_tb * block_t
    # Padded gy rows are zero: they add nothing to dscale/dbias and their
    # dx rows are sliced away.
    gy2 = _pad_tokens(gy.reshape(n, d), n_pad)
    tok = pl.BlockSpec((block_t, d), lambda i: (i, 0))
    param = pl.BlockSpec((1, d), lambda i: (0, 0))
    row = pl.BlockSpec((1, block_t), lambda i: (0, i))
    dx, dsc, db = pl.pallas_call(
        _bwd_kernel,
        grid=(n_tb,),
        in_specs=[tok, param, row, row, tok],
        out_specs=[tok, param, param],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, d), gy.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
        name="fused_norm_bwd",
    )(s2, scale.reshape(1, d), mu, rstd, gy2)
    return dx[:n].reshape(shape), dsc[0].astype(scale.dtype), db[0]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _layer_norm_local(x, scale, bias, eps, block_t, interpret):
    _, _, y, _, _, _ = _run_forward(x, None, scale, bias, eps, block_t, interpret)
    return y


def _ln_fwd(x, scale, bias, eps, block_t, interpret):
    shape, n, y, _, mu, rstd = _run_forward(
        x, None, scale, bias, eps, block_t, interpret
    )
    n_pad = -(-n // block_t) * block_t
    s2 = _pad_tokens(x.reshape(n, shape[-1]), n_pad)
    return y, (s2, scale, mu, rstd, shape, n)


def _ln_bwd(eps, block_t, interpret, res, gy):
    s2, scale, mu, rstd, shape, n = res
    dx, dsc, db = _run_backward(
        s2, scale, mu, rstd, gy, shape, n, eps, block_t, interpret
    )
    return dx.astype(gy.dtype), dsc, db.astype(scale.dtype)


_layer_norm_local.defvjp(_ln_fwd, _ln_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _add_layer_norm_local(x, residual, scale, bias, eps, block_t, interpret):
    _, _, y, s, _, _ = _run_forward(x, residual, scale, bias, eps, block_t, interpret)
    return y, s


def _aln_fwd(x, residual, scale, bias, eps, block_t, interpret):
    shape, n, y, s, mu, rstd = _run_forward(
        x, residual, scale, bias, eps, block_t, interpret
    )
    n_pad = -(-n // block_t) * block_t
    s2 = _pad_tokens(s.reshape(n, shape[-1]), n_pad)
    return (y, s), (s2, scale, mu, rstd, shape, n)


def _aln_bwd(eps, block_t, interpret, res, g):
    gy, gs = g
    s2, scale, mu, rstd, shape, n = res
    ds, dsc, db = _run_backward(
        s2, scale, mu, rstd, gy, shape, n, eps, block_t, interpret
    )
    # The returned sum feeds the residual stream: its cotangent flows
    # straight through the add to both inputs.
    dx = (ds + gs).astype(gy.dtype)
    return dx, dx, dsc, db.astype(scale.dtype)


_add_layer_norm_local.defvjp(_aln_fwd, _aln_bwd)


def _token_sharded(local, n_act: int, *args):
    """Run ``local(*acts, scale, bias)`` per token shard under the ambient
    mesh: the leading ``n_act`` (B, T, d) activations — and as many
    outputs — shard over the batch/sequence axes, the (d,) params arrive
    gathered and shard_map's transpose sums their gradients over the
    token shards."""
    mesh = kernel_mesh()
    x = args[0]
    if mesh is None or x.ndim != 3:
        return local(*args)
    tok = P(
        shard_axes(mesh, BATCH_AXES, x.shape[0]),
        shard_axes(mesh, ("sequence",), x.shape[1]),
        None,
    )
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(tok,) * n_act + (P(None), P(None)),
        out_specs=tok if n_act == 1 else (tok,) * n_act,
        check_vma=False,
    )(*args)


def fused_layer_norm(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    eps: float = 1e-6,
    block_t: int = DEFAULT_BLOCK_T,
    interpret: bool = False,
) -> jax.Array:
    """LayerNorm over the last axis — the no-residual flavor (block
    input norm ln_1 / final ln_f sites)."""
    return _token_sharded(
        lambda x_, s_, b_: _layer_norm_local(x_, s_, b_, eps, block_t, interpret),
        1, x, scale, bias,
    )


def fused_add_layer_norm(
    x: jax.Array,
    residual: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    eps: float = 1e-6,
    block_t: int = DEFAULT_BLOCK_T,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``(LN(x + residual), x + residual)`` in one HBM pass — the
    post-attention pre-MLP site: the first output feeds the sublayer,
    the second is the updated residual stream."""
    return _token_sharded(
        lambda x_, r_, s_, b_: _add_layer_norm_local(
            x_, r_, s_, b_, eps, block_t, interpret
        ),
        2, x, residual, scale, bias,
    )


__all__ = [
    "fused_layer_norm",
    "fused_add_layer_norm",
    "resolve_fused_norm",
    "DEFAULT_BLOCK_T",
]

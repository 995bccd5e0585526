"""int8/fp8 quantization: inference weight compression AND a training matmul path.

Beyond-reference capability (the reference has no quantization path; its
serving story is the f32 notebook forward). Two entry points share one
quantization recipe (:func:`quantize_array`):

**Inference (weight-only int8)** — :func:`quantize_tree` rewrites a param
tree's big leaves into :class:`QuantizedArray` containers; ``__jax_array__``
dequantizes in-graph so XLA keeps the int8 buffer in HBM and fuses the
``convert+multiply`` into the consuming matmul's operand read. Rationale:
single-stream decode is weight-bandwidth bound (docs/perf.md "Serving
bandwidth model"), so halving weight bytes is worth ~1% logit error — and TPU
v5e reads int8 natively.

**Training (quantized matmuls, ``model.extra.matmul_precision``)** —
:func:`quant_dot_general` builds a ``lax.dot_general`` replacement that
flax ``Dense``/``DenseGeneral`` modules consume via their ``dot_general=``
hook, and :class:`QuantDense` is the standalone drop-in. Modes:

* ``"int8"`` — weights quantized to symmetric per-channel int8 at each
  step's current value (just-in-time amax scaling over the contracting
  axes, so the scales group by output unit) and dequantized in-graph;
  activations stay in the compute dtype.
* ``"int8_act"`` — additionally fake-quantizes the activations
  per-channel over their contracting axes (int8 x int8 numerics).
* ``"fp8"`` — both operands cast to ``float8_e4m3fn`` with per-tensor
  just-in-time scaling into the e4m3 dynamic range, matmul accumulated
  in f32 via ``preferred_element_type`` (TransformerEngine-style).
  Requires backend support: :func:`fp8_supported` probes it once and
  :func:`resolve_matmul_precision` falls back to ``"f32"`` with a
  one-time warning when absent.
* ``"f32"`` — the unmodified flax/lax path (returns ``None`` so the
  module uses its default ``dot_general``).

Gradients are straight-through (``jax.custom_vjp``): quantization is an
identity in the backward pass, so gradients are exact f32 with respect
to the quantized operands — master weights, grad accumulation, the
optimizer, ZeRO sharding, and checkpoint contracts are all untouched
(the param tree never stores codes during training). Loss parity with
the f32 trajectory is *tested*, not assumed: tests/test_quant_train.py
trains N steps quantized-vs-f32 from one init and fails when the
trajectories diverge beyond the documented rtol (docs/perf.md "Quantized
training").

Scales are symmetric per-channel (no zero-point): dequant stays a single
fused multiply and 0.0 is exact, which LayerNorm-heavy stacks care about.
For :func:`quantize_tree` the per-channel rule is: ``embedding`` tables
one scale per row; all other kernels max over the largest leading axis
(the contraction dim in every layout we ship).
"""

from __future__ import annotations

import functools
import logging
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, tree_util

Params = Any  # PyTree of arrays

logger = logging.getLogger(__name__)

_INT8_MAX = 127.0


@tree_util.register_pytree_node_class
class QuantizedArray:
    """int8 codes + broadcastable f32 scales, posing as the original array.

    Registered as a pytree *container*: under ``jit``/``tree.map`` it
    flattens into its two array children, so jitted programs carry the
    int8 buffer (not a dequantized copy) across the host→device boundary
    and through donation. ``__jax_array__`` makes every consuming jnp op
    dequantize in-graph to ``dtype`` (the weight's original dtype).
    """

    def __init__(self, q: jax.Array, scale: jax.Array, dtype: Any):
        self.q = q
        self.scale = scale
        self._dtype = jnp.dtype(dtype)

    # --- array protocol -------------------------------------------------
    @property
    def shape(self) -> tuple[int, ...]:
        return self.q.shape

    @property
    def dtype(self):
        return self._dtype

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def size(self) -> int:
        return self.q.size

    @property
    def nbytes(self) -> int:
        """Actual storage cost: int8 codes + scale floats."""
        return int(self.q.size * 1 + self.scale.size * self.scale.dtype.itemsize)

    def dequantize(self) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.scale).astype(self._dtype)

    def __jax_array__(self) -> jax.Array:
        return self.dequantize()

    def astype(self, dtype) -> "QuantizedArray":
        """Retarget the *dequantized* dtype; codes and scales are shared."""
        return QuantizedArray(self.q, self.scale, dtype)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"QuantizedArray(shape={self.shape}, dtype={self._dtype.name}, "
            f"scale_shape={self.scale.shape})"
        )

    # --- pytree protocol ------------------------------------------------
    def tree_flatten(self):
        return (self.q, self.scale), self._dtype

    @classmethod
    def tree_unflatten(cls, dtype, children):
        return cls(children[0], children[1], dtype)


def quantize_array(w: jax.Array, *, reduce_axes: tuple[int, ...]) -> QuantizedArray:
    """Symmetric per-channel int8: ``scale = amax/127`` over ``reduce_axes``."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axes, keepdims=True)
    # All-zero channels (e.g. a fresh LoRA B factor) get scale 1.0: the
    # codes are all 0 and dequantize exactly to 0.0 either way, without
    # a 0/0 NaN in the division below.
    scale = jnp.where(amax == 0.0, 1.0, amax / _INT8_MAX)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -_INT8_MAX, _INT8_MAX)
    return QuantizedArray(q.astype(jnp.int8), scale, w.dtype)


def _is_embedding_path(path) -> bool:
    for k in path:
        name = getattr(k, "key", None) or getattr(k, "name", None)
        if name is not None and "embedding" in str(name):
            return True
    return False


def _is_bias_path(path) -> bool:
    """Multi-dim bias leaves (Qwen2's (3, H, dh) fused qkv bias) pass the
    ndim gate but are exactly the quality-sensitive additive params the
    'biases stay float' contract promises to preserve."""
    if not path:
        return False
    name = getattr(path[-1], "key", None) or getattr(path[-1], "name", None)
    return name is not None and str(name) == "bias"


def quantize_tree(params: Params, *, min_size: int = 4096) -> Params:
    """Quantize every weight matrix in a param tree to int8.

    A leaf is quantized iff it is floating, at least 2-D, and has
    ``size >= min_size`` — norms, biases and tiny projections stay in
    their original dtype (they are a rounding error of the byte budget
    and the quality-sensitive part of the stack). Embedding tables get
    per-row scales; all other kernels per-output-unit scales (max over
    every axis but the last).

    The result is a same-structure tree whose big leaves are
    :class:`QuantizedArray` containers — directly consumable by
    ``model.apply``, ``generation.generate``, ``speculative_generate``
    and the Trainer's eval ``params_override``.
    """

    def _leaf(path, a):
        if isinstance(a, QuantizedArray):
            raise ValueError("quantize_tree: tree is already quantized")
        if not hasattr(a, "ndim") or a.ndim < 2:
            return a
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        if a.size < min_size:
            return a
        if _is_bias_path(path):
            return a
        if _is_embedding_path(path):
            reduce_axes: tuple[int, ...] = (a.ndim - 1,)
        else:
            leading = a.shape[:-1]
            reduce_axes = (leading.index(max(leading)),)
        return quantize_array(a, reduce_axes=reduce_axes)

    return tree_util.tree_map_with_path(
        _leaf, params, is_leaf=lambda x: isinstance(x, QuantizedArray)
    )


def dequantize_tree(params: Params) -> Params:
    """Materialize a quantized tree back to plain arrays (testing/export)."""
    return jax.tree.map(
        lambda a: a.dequantize() if isinstance(a, QuantizedArray) else a,
        params,
        is_leaf=lambda x: isinstance(x, QuantizedArray),
    )


def quant_stats(params: Params) -> dict[str, int | float]:
    """Byte accounting for a (possibly) quantized tree.

    ``bytes_dense`` is what the same tree would occupy with every
    quantized leaf restored to its original dtype — the compression
    ratio decode cares about, since weight bytes streamed per token is
    the single-stream bottleneck.
    """
    leaves = jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedArray)
    )
    n_q = sum(1 for a in leaves if isinstance(a, QuantizedArray))
    bytes_actual = 0
    bytes_dense = 0
    params_q = 0
    params_total = 0
    for a in leaves:
        params_total += int(a.size)
        if isinstance(a, QuantizedArray):
            params_q += int(a.size)
            bytes_actual += a.nbytes
            bytes_dense += int(a.size * a.dtype.itemsize)
        else:
            nbytes = int(a.size * a.dtype.itemsize)
            bytes_actual += nbytes
            bytes_dense += nbytes
    return {
        "quantized_leaves": n_q,
        "quantized_params": params_q,
        "total_params": params_total,
        "bytes": bytes_actual,
        "bytes_dense": bytes_dense,
        "compression": (bytes_dense / bytes_actual) if bytes_actual else 1.0,
    }


# ==========================================================================
# Training path: quantized matmuls with straight-through gradients.
# ==========================================================================

#: Accepted ``model.extra.matmul_precision`` values. "int8_act" is the
#: activations-too variant of "int8" (the knob's documented surface is
#: f32|int8|fp8; int8_act is the opt-in extension).
MATMUL_PRECISIONS = ("f32", "int8", "int8_act", "fp8")

# float8_e4m3fn dynamic range: the per-tensor scale maps each operand's
# amax onto this so the cast saturates instead of overflowing to inf.
_E4M3_MAX = 448.0


@functools.lru_cache(maxsize=1)
def fp8_supported() -> bool:
    """True when the installed jax + backend can run a float8_e4m3fn matmul.

    Probed once per process with a tiny end-to-end dot (dtype existing is
    not enough — a backend can expose the dtype but reject the HLO).
    Lazy: no jax compute happens at import time.
    """
    if not hasattr(jnp, "float8_e4m3fn"):
        return False
    try:
        a = jnp.ones((4, 4), jnp.float8_e4m3fn)
        out = lax.dot_general(
            a, a, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return bool(jax.device_get(out)[0, 0] == 4.0)
    except Exception:  # noqa: BLE001 — any backend rejection means "no"
        return False


_FALLBACK_WARNED: set[str] = set()


def resolve_matmul_precision(mode: str) -> str:
    """Validate a ``matmul_precision`` knob value and resolve capability.

    Unknown values raise (config-time, like ``loss_impl``); ``"fp8"``
    degrades to ``"f32"`` with a one-time warning when the backend can't
    run float8 matmuls — the clean-fallback contract: the run proceeds,
    the precision claim does not.
    """
    if mode not in MATMUL_PRECISIONS:
        raise ValueError(
            f"matmul_precision {mode!r} unknown; expected one of "
            f"{list(MATMUL_PRECISIONS)}"
        )
    if mode == "fp8" and not fp8_supported():
        if "fp8" not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add("fp8")
            logger.warning(
                "matmul_precision=fp8 requested but this jax/backend cannot "
                "run float8_e4m3fn matmuls; falling back to f32"
            )
        return "f32"
    return mode


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def fake_quant(w: jax.Array, reduce_axes: tuple[int, ...]) -> jax.Array:
    """Quantize-dequantize ``w`` to symmetric per-channel int8 (STE).

    Forward is exactly :func:`quantize_array` followed by dequant — the
    value the matmul consumes has int8 numerics (just-in-time amax
    scaling over ``reduce_axes``, per-output-unit scales for a kernel
    whose contracting dims are reduced). Backward is the identity
    (straight-through): the gradient flows to the f32 master weight
    untouched, so optimizer/ZeRO/checkpoint contracts never see codes.
    """
    return quantize_array(w, reduce_axes=reduce_axes).dequantize()


def _fake_quant_fwd(w, reduce_axes):
    return fake_quant(w, reduce_axes), None


def _fake_quant_bwd(reduce_axes, _res, g):
    return (g,)


fake_quant.defvjp(_fake_quant_fwd, _fake_quant_bwd)


def _fp8_dot_impl(lhs: jax.Array, rhs: jax.Array, dimension_numbers) -> jax.Array:
    """f32-accumulated float8_e4m3fn dot with per-tensor JIT scaling."""
    out_dtype = jnp.promote_types(lhs.dtype, rhs.dtype)
    lhs32 = lhs.astype(jnp.float32)
    rhs32 = rhs.astype(jnp.float32)
    # amax -> e4m3 range; the floor keeps all-zero operands at scale ~1
    # territory instead of 0/0 (mirrors quantize_array's zero guard).
    ls = jnp.maximum(jnp.max(jnp.abs(lhs32)), 1e-30) / _E4M3_MAX
    rs = jnp.maximum(jnp.max(jnp.abs(rhs32)), 1e-30) / _E4M3_MAX
    l8 = (lhs32 / ls).astype(jnp.float8_e4m3fn)
    r8 = (rhs32 / rs).astype(jnp.float8_e4m3fn)
    out = lax.dot_general(
        l8, r8, dimension_numbers, preferred_element_type=jnp.float32
    )
    return (out * (ls * rs)).astype(out_dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fp8_dot(lhs: jax.Array, rhs: jax.Array, dimension_numbers) -> jax.Array:
    """fp8 forward, exact straight-through backward.

    The whole dot is wrapped (not just the casts) because differentiating
    a dot with float8 operands would hand XLA an fp8 transpose — the
    backward here is the plain f32 ``dot_general`` vjp on the saved
    full-precision operands, i.e. exact master-weight gradients.
    """
    return _fp8_dot_impl(lhs, rhs, dimension_numbers)


def _fp8_dot_fwd(lhs, rhs, dimension_numbers):
    return _fp8_dot_impl(lhs, rhs, dimension_numbers), (lhs, rhs)


def _fp8_dot_bwd(dimension_numbers, res, g):
    lhs, rhs = res
    _, vjp = jax.vjp(
        lambda l, r: lax.dot_general(l, r, dimension_numbers), lhs, rhs
    )
    return vjp(g)


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def quant_dot_general(mode: str) -> Callable | None:
    """A ``lax.dot_general`` replacement implementing ``mode``.

    Returns ``None`` for ``"f32"`` so callers can pass the result
    directly to flax's ``Dense(dot_general=...)`` hook — ``None`` selects
    the module's stock path, keeping f32 bit-identical to a build without
    this feature. ``mode`` must already be capability-resolved
    (:func:`resolve_matmul_precision`); an fp8 dot on an unsupported
    backend raises at trace time rather than silently degrading.
    """
    if mode not in MATMUL_PRECISIONS:
        raise ValueError(
            f"matmul_precision {mode!r} unknown; expected one of "
            f"{list(MATMUL_PRECISIONS)}"
        )
    if mode == "f32":
        return None

    def dot_general(
        lhs: jax.Array,
        rhs: jax.Array,
        dimension_numbers,
        precision=None,
        preferred_element_type=None,
    ) -> jax.Array:
        if mode == "fp8":
            del precision, preferred_element_type
            return _fp8_dot(lhs, rhs, dimension_numbers)
        (lhs_contract, rhs_contract), _ = dimension_numbers
        rhs_q = fake_quant(rhs, tuple(rhs_contract))
        if mode == "int8_act":
            lhs = fake_quant(lhs, tuple(lhs_contract))
        return lax.dot_general(
            lhs,
            rhs_q,
            dimension_numbers,
            precision=precision,
            preferred_element_type=preferred_element_type,
        )

    return dot_general


class QuantDense:
    """Drop-in ``nn.Dense`` with the quantized training matmul.

    Same parameter tree as ``nn.Dense`` ({"kernel", "bias"}), f32 master
    params, straight-through gradients — a checkpoint trained through
    ``QuantDense`` loads into ``nn.Dense`` verbatim and vice versa. The
    model families thread ``matmul_precision`` into their existing
    Dense/DenseGeneral modules via ``dot_general=quant_dot_general(mode)``
    instead (no param-tree change at all); this class is the standalone
    building block for code outside those families.

    Implemented as a thin factory over ``nn.Dense`` (imported lazily so
    ops/ keeps its no-flax-at-import property for kernel-only consumers).
    """

    def __new__(cls, *args: Any, matmul_precision: str = "int8", **kwargs: Any):
        from flax import linen as nn

        return nn.Dense(
            *args, **kwargs, dot_general=quant_dot_general(matmul_precision)
        )

"""The chip's compiler, without the chip: the main path's Pallas kernels
at GPT-2-small widths compiled for a *described* ``v5e:2x2`` topology.

Interpret-mode tests validate kernel math; only the TPU compiler refuses
a slice not aligned to the tiling, a kernel over its VMEM budget, or a
Mosaic call GSPMD is asked to partition ("Mosaic kernels cannot be
automatically partitioned" — what ``attention: flash`` on a mesh raised
before the kernels wrapped themselves in ``shard_map``). Nothing runs:
shapes in, an executable out, ``as_text()`` checked for the custom call.

The topology is described INSIDE a module-scoped fixture, never at import
(only one process may load libtpu; xdist workers all import this file),
and every compile happens in the test's own process. This is the only
test file that describes a TPU topology.
"""

from __future__ import annotations

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

B, T, H, D = 8, 512, 12, 64
D_MODEL, VOCAB = 768, 50257


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """``{data: 2, fsdp: 2}`` over the four described chips, with the
    trainer's full axis-name set (size-1 axes included)."""
    from llmtrain_tpu.distributed import MESH_AXES

    devices = np.array(topo.devices).reshape(2, 2, 1, 1, 1, 1)
    return Mesh(devices, MESH_AXES)


@pytest.fixture(autouse=True)
def _as_on_chip(monkeypatch):
    """Steer the kernel dispatch onto its platform-``tpu`` branch (the
    process's real backend is the CPU) and keep these compiles out of the
    persistent cache: an entry written for a described chip cannot be read
    back without one and would warn on every later run."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _qkv(sharding, *, kv_heads=H):
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((B, T, kv_heads, D), jnp.bfloat16, sharding=sharding)
    return q, kv, kv


def _flash_loss(window=0):
    from llmtrain_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v, mask=None):
        out = flash_attention(q, k, v, attention_mask=mask, window=window)
        return jnp.sum(out.astype(jnp.float32))

    return loss


class TestFlashAttentionOneChip:
    def test_forward(self, one_chip):
        from llmtrain_tpu.ops.flash_attention import flash_attention

        assert "tpu_custom_call" in _compile(flash_attention, *_qkv(one_chip))

    def test_forward_backward(self, one_chip):
        text = _compile(jax.grad(_flash_loss(), argnums=(0, 1, 2)), *_qkv(one_chip))
        # fwd + the two fused backward kernels (dq, dk/dv).
        assert text.count("tpu_custom_call") >= 3

    def test_masked_forward_backward(self, one_chip):
        mask = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip)
        text = _compile(
            jax.grad(_flash_loss(), argnums=(0, 1, 2)), *_qkv(one_chip), mask
        )
        assert text.count("tpu_custom_call") >= 3

    def test_gqa_sliding_window_forward_backward(self, one_chip):
        text = _compile(
            jax.grad(_flash_loss(window=256), argnums=(0, 1, 2)),
            *_qkv(one_chip, kv_heads=4),
        )
        assert text.count("tpu_custom_call") >= 3

    def test_untileable_length_is_an_error_not_blockwise(self, one_chip):
        from llmtrain_tpu.ops.flash_attention import flash_attention

        q = jax.ShapeDtypeStruct((B, 200, H, D), jnp.bfloat16, sharding=one_chip)
        with pytest.raises(ValueError, match="multiple of 128"):
            jax.jit(flash_attention).lower(q, q, q)


class TestFlashAttentionAtTheCellsShape:
    """``gpt2-small.train-64k``'s own attention call, (32, 1024, 12, 64)
    bf16, with the tiles ``_auto_block`` picks for it, and the other shapes
    the tile choice must not break: a 128-wide head with 4 K/V heads, and
    the segment mask with a sliding window."""

    @staticmethod
    def _grad_text(sharding, *, b=32, t=1024, h=12, hkv=12, d=64, masked=False, window=0):
        q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=sharding)
        kv = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=sharding)
        args = (q, kv, kv)
        if masked:
            args += (jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=sharding),)
        return _compile(jax.grad(_flash_loss(window), argnums=(0, 1, 2)), *args)

    def test_three_kernels_a_narrow_residual_and_no_replicated_statistics(self, one_chip):
        text = self._grad_text(one_chip)
        assert _custom_call_names(text) == {
            "flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkdv",
        }
        # The residual between forward and backward is one float32 a row
        # and head...
        assert re.search(r"f32\[32,12,1,1024\]", text)
        # ...and the lane-replicated (rows, 128) statistics never leave VMEM.
        assert not re.search(r"f32\[[\d,]*1024,128\]", text)

    @pytest.mark.parametrize(
        "shape",
        [
            dict(b=8, h=20, hkv=4, d=128),
            dict(b=8, masked=True, window=256),
            dict(b=8, h=20, hkv=4, d=128, masked=True, window=300),
        ],
        ids=["head128-gqa", "masked-windowed", "head128-gqa-masked-windowed"],
    )
    def test_other_shapes_compile_with_the_tiles_picked_for_them(self, one_chip, shape):
        assert _custom_call_names(self._grad_text(one_chip, **shape)) == {
            "flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkdv",
        }


class TestAttentionBlockHandsOffRows:
    """``jax.grad`` of the GPT-2-small block's attention, projections
    included, at the train cell's shape: between the projections' matmuls
    and the three kernels no array of an activation's size is copied,
    transposed, sliced or concatenated, forward or backward (the parent paid
    twelve 50 MB ``copy``s a layer and micro-batch there, 6.3% of the step).
    The kernels read q, k and v out of the qkv projection's own
    ``(B, T, 3*H*D)`` output and write one gradient of that shape."""

    B, T, H, D = 32, 1024, 12, 64

    @classmethod
    def _grad_text(cls, sharding, params_sharding=None, *, b=B, h=H, d=D, **module):
        import flax.linen as nn

        from llmtrain_tpu.models.gpt import CausalSelfAttention

        d_model = h * d
        attn = CausalSelfAttention(
            d_model=d_model, n_heads=h, n_layers=12, dropout=0.0, attention="flash",
            dtype=jnp.bfloat16, param_dtype=jnp.float32, assume_packed=True, **module,
        )
        x = jax.ShapeDtypeStruct((b, cls.T, d_model), jnp.bfloat16, sharding=sharding)
        boxed = jax.eval_shape(
            lambda: attn.init(jax.random.key(0), jnp.zeros((1, 128, d_model), jnp.bfloat16))
        )
        placed = params_sharding(boxed) if params_sharding else jax.tree.map(
            lambda _: sharding, nn.meta.unbox(boxed)
        )
        params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            nn.meta.unbox(boxed), placed,
        )

        def loss(p, x, weight):
            return jnp.sum((attn.apply(p, x) * weight).astype(jnp.float32))

        return _compile(jax.grad(loss, argnums=(0, 1)), params, x, x)

    @classmethod
    def _moved_activations(cls, text: str, elements: int):
        """Instructions outside fusions that only move ``elements`` values or
        more: what a transposing ``copy`` is, or a slice or concatenation of
        the projection's output."""
        movers = {"copy", "transpose", "slice", "concatenate", "dynamic-slice", "pad"}
        found = []
        for _, body in _unfused_computations(text):
            for op, type_text, _, line in _hlo_instructions(body):
                if op in movers and _elements(type_text) >= elements:
                    found.append(line.strip()[:160])
        return found

    def test_no_activation_sized_copy_on_either_side_of_the_three_kernels(self, one_chip):
        text = self._grad_text(one_chip)
        assert _custom_call_names(text) == {
            "flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkdv",
        }
        assert self._moved_activations(text, self.B * self.T * self.H * self.D) == []
        # Nothing head-major or folded is left, in any order of its axes...
        assert not re.search(
            r"bf16\[(32,12,1024,64|32,1024,12,64|384,1024,64)\]\S* (copy|transpose)\(", text
        )
        # ...the forward reads the projection's output whole, and the two
        # backward kernels write ONE gradient of its shape, in place.
        assert re.search(
            r"flash_attention_fwd[\w.]* = [^\n]*operand_layout_constraints=\{bf16\[32,1024,2304\]", text
        )
        assert re.search(r"flash_attention_bwd_dkdv[\w.]* = bf16\[32,1024,2304\]", text)

    def test_a_rotated_block_with_grouped_heads_of_128_hands_the_kernels_rows(self, one_chip):
        """The llama shape: RoPE on q and k, 4 K/V heads under 8 query heads
        of 128. The kernels take q, k and v apart as ``(B, T, H*D)`` rows, a
        query head's block mapped to its group's K/V block. (The rotation
        itself is still re-laid-out by the compiler around its ``(..., 2,
        D/2)`` shape, as at the parent: ``PERF.md`` section 7.)"""
        text = self._grad_text(
            one_chip, b=8, h=8, d=128, n_kv_heads=4, rope=True, use_bias=False
        )
        assert _custom_call_names(text) == {
            "flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkdv",
        }
        assert re.search(
            r"flash_attention_fwd[\w.]* = [^\n]*operand_layout_constraints=\{"
            r"bf16\[8,1024,1024\]\{2,1,0\}, bf16\[8,1024,512\]\{2,1,0\}, bf16\[8,1024,512\]",
            text,
        )

    def test_heads_split_over_a_tensor_axis_still_find_whole_lane_blocks(self, topo):
        """``{data: 2, tensor: 2}``: each chip's kernels see its own six
        heads as three lane blocks of the local ``(B/2, T, 3*6*64)`` array."""
        import flax.linen as nn

        from llmtrain_tpu.distributed import MESH_AXES
        from llmtrain_tpu.parallel.sharding import DEFAULT_LOGICAL_AXIS_RULES

        mesh = Mesh(np.array(topo.devices).reshape(2, 1, 2, 1, 1, 1), MESH_AXES)

        def place(boxed):
            with nn.logical_axis_rules(DEFAULT_LOGICAL_AXIS_RULES):
                specs = nn.logical_to_mesh(nn.get_partition_spec(boxed))
            return jax.tree.map(lambda spec: NamedSharding(mesh, spec), specs)

        with mesh, nn.logical_axis_rules(DEFAULT_LOGICAL_AXIS_RULES):
            text = self._grad_text(NamedSharding(mesh, P("data")), place)
        assert _custom_call_names(text) == {
            "flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkdv",
        }
        assert re.search(
            r"flash_attention_fwd[\w.]* = [^\n]*operand_layout_constraints=\{bf16\[16,1024,1152\]", text
        )
        assert re.search(r"flash_attention_bwd_dkdv[\w.]* = bf16\[16,1024,1152\]", text)


class TestMLPActivationEvaluatedOnce:
    """``jax.grad`` through the GPT-2-small block at the train cell's shape
    (32 x 1,024 x 768, d_ff 3,072, bf16 over float32 parameters): the MLP's
    erf GELU is evaluated in ONE fusion a block, ``mlp_fc``'s own, which
    writes the value ``a`` and the derivative ``g``; the three products that
    read them (``mlp_proj`` forward, its dW, the dX through it) hold no
    ``erf``, ``divide`` or ``exponential``. Written plainly, as the parent
    wrote it, the compiler keeps only ``h`` and evaluates the polynomial in
    all three (36 fusions in the 12-layer model, each bound by the vector
    unit at about 2.8 times its matmul's time). The inference forward does
    not change."""

    B, T, D_MODEL, D_FF = 32, 1024, 768, 3072
    WIDE = "bf16[32,1024,3072]"

    @classmethod
    @functools.cache  # two tests read the gradient's program: one compile
    def _block_text(cls, sharding, *, grad: bool, activation=None):
        import flax.linen as nn

        from llmtrain_tpu.models import gpt

        block = gpt.TransformerBlock(
            d_model=cls.D_MODEL, n_heads=12, d_ff=cls.D_FF, n_layers=12, dropout=0.0, attention="flash",
            dtype=jnp.bfloat16, param_dtype=jnp.float32, assume_packed=True,
        )
        boxed = jax.eval_shape(
            lambda: block.init(jax.random.key(0), jnp.zeros((1, 128, cls.D_MODEL), jnp.bfloat16))
        )
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), nn.meta.unbox(boxed)
        )
        x = jax.ShapeDtypeStruct((cls.B, cls.T, cls.D_MODEL), jnp.bfloat16, sharding=sharding)

        def loss(p, x, weight):
            # Not linear in the block's output, or `mlp_proj`'s forward product is dead code.
            y = block.apply(p, x, deterministic=False).astype(jnp.float32)
            return jnp.sum(y * y * weight)

        def forward(p, x):
            return block.apply(p, x, deterministic=True)

        with pytest.MonkeyPatch.context() as patch:
            if activation is not None:
                patch.setattr(gpt, "gelu_once", activation)
            if grad:
                return _compile(jax.grad(loss, argnums=(0, 1)), params, x, x)
            return _compile(forward, params, x)

    @staticmethod
    def _plain(h):
        import flax.linen as nn

        return nn.gelu(h, approximate=False)

    @classmethod
    def _fused_ops(cls, text: str, name: str) -> list[str]:
        """Opcodes of a fused computation, nested fusions' included."""
        ops = []
        for op, _, called, _ in _hlo_instructions(_computation(text, name)):
            ops.append(op)
            if op in ("fusion", "call") and called:
                ops.extend(cls._fused_ops(text, called))
        return ops

    @classmethod
    def _matmul_fusions(cls, text: str):
        """``(line, fused computation's text, opcodes)`` of every fusion,
        outside fusions, that holds a ``convolution`` (how the chip's compiler
        writes a matmul) or the erf polynomial."""
        for _, body in _unfused_computations(text):
            for op, _, called, line in _hlo_instructions(body):
                if op == "fusion":
                    ops = cls._fused_ops(text, called)
                    if "convolution" in ops or cls._holds_erf(ops):
                        yield line, _computation(text, called), ops

    @staticmethod
    def _holds_erf(ops: list[str]) -> bool:
        # One instruction in float32; expanded to a rational polynomial
        # (2 divides, about 30 multiplies) when written in bf16 or as erfc.
        return "erf" in ops or ("divide" in ops and ops.count("multiply") > 20)

    def test_one_fusion_a_block_holds_erf_and_the_three_consumers_are_bare(self, one_chip):
        text = self._block_text(one_chip, grad=True)
        fusions = list(self._matmul_fusions(text))
        with_erf = [fusion for fusion in fusions if self._holds_erf(fusion[2])]
        assert len(with_erf) == 1, [line.strip()[:200] for line, _, _ in with_erf]
        line, fused, ops = with_erf[0]
        # It is `mlp_fc`'s own fusion: the MXU's product hides under the vector unit's pass...
        assert "mlp_fc/dot_general" in fused
        assert (ops.count("convolution"), ops.count("erf"), ops.count("exponential")) == (1, 1, 1)
        # ...and writes `a` and `g`, and neither `h` nor anything in float32.
        result = line.split(" = ", 1)[1].split(" fusion(", 1)[0]
        assert re.findall(r"\w+\[[\d,]*\]", result) == [self.WIDE] * 2, result
        # The three products that read `a` or `g`: `mlp_proj` forward, its dW, the dX through it.
        readers = [(line, ops) for line, fused, ops in fusions if "mlp_proj/dot_general" in fused]
        assert len(readers) == 3
        for line, ops in readers:
            assert not {"erf", "divide", "exponential"} & set(ops), line.strip()[:200]
            assert ops.count("multiply") <= 3, line.strip()[:200]

    def test_two_wide_arrays_cross_to_the_backward_and_neither_is_the_preactivation(self, one_chip):
        text = self._block_text(one_chip, grad=True)
        # Every array of the hidden width that exists in memory (outside fusions)...
        wide = [
            (op, called, line) for _, body in _unfused_computations(text)
            for op, result, called, line in _hlo_instructions(body)
            if op not in ("get-tuple-element", "bitcast", "parameter", "tuple")
            for _ in re.findall(re.escape(self.WIDE), result)
        ]
        forward = [(op, called, line) for op, called, line in wide if "transpose(jvp" not in line]
        backward = [(op, called, line) for op, called, line in wide if "transpose(jvp" in line]
        # ...is one of the forward's two, both outputs of the one fusion with erf, or the backward's dh.
        assert len(forward) == 2 and len({called for _, called, _ in forward}) == 1
        assert len(backward) == 1
        ops = self._fused_ops(text, forward[0][1])
        assert "erf" in ops
        # `h` is the convolution plus its bias: each output is a value formed AFTER erf.
        body = _computation(text, forward[0][1])
        root = re.search(r"ROOT %[\w.\-]+ = [^\n]* tuple\(([^)]*)\)", body).group(1)
        operands = {
            name: re.findall(r"%([\w.\-]+)", line.split(" = ", 1)[1].split("(", 1)[1])
            for name, line in (
                (line.split(" = ", 1)[0].strip().removeprefix("ROOT ").lstrip("%"), line)
                for line in body.splitlines()[1:] if " = " in line
            )
        }
        erf = next(name for name in operands if re.match(r"erf[.\d]*$", name))

        def reaches(name, seen):
            if name == erf:
                return True
            seen.add(name)
            return any(reaches(o, seen) for o in operands.get(name, []) if o not in seen)

        for out in re.findall(r"%([\w.\-]+)", root):
            assert reaches(out, set()), f"{out} does not come after erf: is it h?"

    def test_the_plain_composition_evaluates_it_in_three_fusions(self, one_chip):
        """The yardstick of the test above: the parent's block, by the same count."""
        text = self._block_text(one_chip, grad=True, activation=self._plain)
        with_erf = [ops for _, _, ops in self._matmul_fusions(text) if self._holds_erf(ops)]
        assert len(with_erf) == 3 and all("convolution" in ops for ops in with_erf)

    def test_a_pipeline_stage_evaluates_it_in_its_fc_product_too(self, one_chip):
        """``gpt_pipeline``'s stage (a ``scan`` over stacked layers) runs the
        same ``gelu_once``: one fusion of the forward loop's body holds erf,
        with the ``fc`` product, and writes ``a`` and ``g`` for the stack of
        residuals; the backward loop's body holds none. (Plain, a scan already
        keeps value and derivative, since its residuals are arrays, but from a
        stand-alone pass over the expanded ``erfc`` with two exponentials.)"""
        from llmtrain_tpu.models.gpt_pipeline import make_stage_fn

        layers, heads = 2, 12

        def leaf(*shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct((layers, *shape), dtype, sharding=one_chip)

        d, ff = self.D_MODEL, self.D_FF
        params = {
            "ln1_scale": leaf(d), "ln1_bias": leaf(d), "ln2_scale": leaf(d), "ln2_bias": leaf(d),
            "qkv_kernel": leaf(d, 3, heads, d // heads), "qkv_bias": leaf(3, heads, d // heads),
            "out_kernel": leaf(heads, d // heads, d), "out_bias": leaf(d),
            "fc_kernel": leaf(d, ff), "fc_bias": leaf(ff), "proj_kernel": leaf(ff, d), "proj_bias": leaf(d),
        }
        x = jax.ShapeDtypeStruct((self.B, self.T, d), jnp.bfloat16, sharding=one_chip)
        stage = make_stage_fn(attention="flash", dtype=jnp.bfloat16)

        def loss(p, x, weight):
            y = stage(p, x).astype(jnp.float32)
            return jnp.sum(y * y * weight)

        text = _compile(jax.grad(loss, argnums=(0, 1)), params, x, x)
        with_erf = [fusion for fusion in self._matmul_fusions(text) if self._holds_erf(fusion[2])]
        assert len(with_erf) == 1, [line.strip()[:200] for line, _, _ in with_erf]
        line, _, ops = with_erf[0]
        assert (ops.count("convolution"), ops.count("erf"), ops.count("exponential")) == (1, 1, 1)
        result = line.split(" = ", 1)[1].split(" fusion(", 1)[0]
        assert re.findall(r"\w+\[[\d,]*\]", result) == [self.WIDE] * 2, result

    def test_the_inference_forward_is_the_plain_compositions_program(self, one_chip):
        """PR 26's digest: ``metadata={...}`` dropped, the instruction list hashed."""
        import hashlib

        def digest(text):
            # Instructions only: the module's header tables name the traced Python functions.
            text = re.sub(r", metadata=\{[^{}]*\}", "", text)
            return hashlib.sha256("\n".join(line for *_, line in _hlo_instructions(text)).encode()).hexdigest()

        served = self._block_text(one_chip, grad=False)
        assert " erf(" not in served and "opt-barrier" not in served
        assert digest(served) == digest(self._block_text(one_chip, grad=False, activation=self._plain))


class TestFusedCEOneChip:
    @staticmethod
    def _operands(sharding, dtype):
        h = jax.ShapeDtypeStruct((B, T, D_MODEL), dtype, sharding=sharding)
        w = jax.ShapeDtypeStruct((VOCAB, D_MODEL), dtype, sharding=sharding)
        lab = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=sharding)
        return h, w, lab

    @staticmethod
    def _loss(h, w, lab):
        from llmtrain_tpu.ops.fused_ce import fused_ce_per_token

        return jnp.sum(fused_ce_per_token(h, w, lab))

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
    def test_forward(self, one_chip, dtype):
        assert "tpu_custom_call" in _compile(self._loss, *self._operands(one_chip, dtype))

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
    def test_grad(self, one_chip, dtype):
        text = _compile(
            jax.grad(self._loss, argnums=(0, 1)), *self._operands(one_chip, dtype)
        )
        # The forward and the ONE backward kernel (dh and dW together).
        assert text.count("tpu_custom_call") >= 2

    @pytest.mark.parametrize(
        "n, d, vocab, dtype",
        [
            (32 * 1024, 768, 50257, jnp.bfloat16),
            (8 * 1024, 1600, 50257, jnp.bfloat16),
            (4 * 2048, 4096, 128256, jnp.bfloat16),
            (2 * 2048, 8192, 128256, jnp.bfloat16),
            (2 * 1024, 4096, 32000, jnp.float32),
        ],
        ids=["train-cell", "gpt2-xl", "llama-8b", "llama-70b", "d4096-f32"],
    )
    def test_the_chosen_tiles_fit_the_chips_vmem(self, one_chip, n, d, vocab, dtype):
        """``_choose_tiles`` keeps its own estimate under the limit it hands
        Mosaic; only the chip's compiler says whether the estimate holds."""
        h = jax.ShapeDtypeStruct((1, n, d), dtype, sharding=one_chip)
        w = jax.ShapeDtypeStruct((vocab, d), dtype, sharding=one_chip)
        lab = jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=one_chip)
        text = _compile(jax.grad(self._loss, argnums=(0, 1)), h, w, lab)
        assert _custom_call_names(text) == {"fused_ce_fwd", "fused_ce_bwd_dw"}
        # No [N, V]-shaped array in HBM, in any dtype.
        assert not re.search(rf"\[{n},{vocab}\]|\[{vocab},{n}\]", text)


class TestFusedNormOneChip:
    @staticmethod
    def _operands(sharding):
        x = jax.ShapeDtypeStruct((B, T, D_MODEL), jnp.bfloat16, sharding=sharding)
        p = jax.ShapeDtypeStruct((D_MODEL,), jnp.float32, sharding=sharding)
        return x, p

    def test_layer_norm_grad(self, one_chip):
        from llmtrain_tpu.ops.fused_norm import fused_layer_norm

        def loss(x, scale, bias):
            return jnp.sum(fused_layer_norm(x, scale, bias).astype(jnp.float32))

        x, p = self._operands(one_chip)
        text = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, p, p)
        assert text.count("tpu_custom_call") >= 2

    def test_add_layer_norm_grad(self, one_chip):
        from llmtrain_tpu.ops.fused_norm import fused_add_layer_norm

        def loss(x, res, scale, bias):
            y, s = fused_add_layer_norm(x, res, scale, bias)
            return jnp.sum(y.astype(jnp.float32)) + jnp.sum(s.astype(jnp.float32))

        x, p = self._operands(one_chip)
        text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), x, x, p, p)
        assert text.count("tpu_custom_call") >= 2


def _custom_call_names(text: str) -> set[str]:
    """Instruction names (numeric suffix dropped) of the program's
    ``tpu_custom_call`` instructions: what the profiler's ``XLA Ops`` line
    shows and ``benchmarks/lib/trace.py:op_label`` reduces."""
    return {
        re.sub(r"(\.\d+)+$", "", m.group(1))
        for m in re.finditer(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    }


def _ce_grad(one_chip):
    return _compile(
        jax.grad(TestFusedCEOneChip._loss, argnums=(0, 1)),
        *TestFusedCEOneChip._operands(one_chip, jnp.bfloat16),
    )


def _flash_grad(one_chip):
    return _compile(jax.grad(_flash_loss(), argnums=(0, 1, 2)), *_qkv(one_chip))


def _norm_grad(one_chip):
    from llmtrain_tpu.ops.fused_norm import fused_add_layer_norm

    def loss(x, res, scale, bias):
        y, s = fused_add_layer_norm(x, res, scale, bias)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(s.astype(jnp.float32))

    x, p = TestFusedNormOneChip._operands(one_chip)
    return _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), x, x, p, p)


class TestKernelNames:
    """Every Pallas kernel of the train path carries a stable ``name=``, so
    the trace names it (an unnamed call takes the enclosing function's name:
    ``jvp__``, ``transpose_jvp___`` under a ``custom_vjp``) and the
    per-kernel roofline readers under ``benchmarks/metrics/`` find it."""

    @pytest.mark.parametrize(
        "build, names",
        [
            (_ce_grad, {"fused_ce_fwd", "fused_ce_bwd_dw"}),
            (
                _flash_grad,
                {"flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkdv"},
            ),
            (_norm_grad, {"fused_norm_fwd", "fused_norm_bwd"}),
        ],
        ids=["fused_ce", "flash_attention", "fused_norm"],
    )
    def test_gradient_program_names_its_kernels(self, one_chip, build, names):
        found = _custom_call_names(build(one_chip))
        assert found == names
        assert not any("jvp" in name for name in found)


class TestKernelsOnFourChipMesh:
    """The tests that would have caught ``attention: flash`` never having
    compiled on a TPU mesh: batch-sharded operands inside a GSPMD-jitted
    function, the kernels partitioned by their own ``shard_map``."""

    def test_flash_forward_backward_partitions(self, mesh4):
        batch = NamedSharding(mesh4, P(("data", "fsdp")))
        with mesh4:
            text = _compile(jax.grad(_flash_loss(), argnums=(0, 1, 2)), *_qkv(batch))
        assert text.count("tpu_custom_call") >= 3
        # Each chip's kernel sees its own batch shard only: B/4 rows.
        assert f"bf16[{B // 4},{T},{H},{D}]" in text

    def test_masked_flash_partitions(self, mesh4):
        batch = NamedSharding(mesh4, P(("data", "fsdp")))
        mask = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=batch)
        with mesh4:
            text = _compile(
                jax.grad(_flash_loss(), argnums=(0, 1, 2)), *_qkv(batch), mask
            )
        assert text.count("tpu_custom_call") >= 3

    def test_fused_ce_grad_partitions_and_sums_dw(self, mesh4):
        tokens = NamedSharding(mesh4, P(("data", "fsdp")))
        h = jax.ShapeDtypeStruct((B, T, D_MODEL), jnp.bfloat16, sharding=tokens)
        # The tied embedding's training layout: vocab→tensor, embed→fsdp.
        w = jax.ShapeDtypeStruct(
            (VOCAB, D_MODEL), jnp.bfloat16,
            sharding=NamedSharding(mesh4, P("tensor", "fsdp")),
        )
        lab = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=tokens)
        with mesh4:
            compiled = (
                jax.jit(jax.grad(TestFusedCEOneChip._loss, argnums=(0, 1)))
                .lower(h, w, lab)
                .compile()
            )
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 2
        # Per-chip token shard: (B/4)*T rows of d_model into the kernel.
        assert f"bf16[{B // 4 * T},{D_MODEL}]" in text
        # dW is summed over the token shards, the fsdp-sharded operand
        # gathered: both collectives must be in the program.
        assert "all-reduce" in text or "reduce-scatter" in text
        assert "all-gather" in text
        assert compiled.memory_analysis().temp_size_in_bytes > 0

    def test_fused_add_layer_norm_grad_partitions(self, mesh4):
        from llmtrain_tpu.ops.fused_norm import fused_add_layer_norm

        def loss(x, res, scale, bias):
            y, s = fused_add_layer_norm(x, res, scale, bias)
            return jnp.sum(y.astype(jnp.float32)) + jnp.sum(s.astype(jnp.float32))

        tokens = NamedSharding(mesh4, P(("data", "fsdp")))
        x = jax.ShapeDtypeStruct((B, T, D_MODEL), jnp.bfloat16, sharding=tokens)
        p = jax.ShapeDtypeStruct(
            (D_MODEL,), jnp.float32, sharding=NamedSharding(mesh4, P("fsdp"))
        )
        with mesh4:
            text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), x, x, p, p)
        assert text.count("tpu_custom_call") >= 2
        assert f"bf16[{B // 4 * T},{D_MODEL}]" in text


# --------------------------------------------------------------------------
# The paged KV pool: the engine's programs at the benchmark's pool shapes.

POOL_BLOCK_TOKENS, POOL_CONTEXT, POOL_LAYERS, POOL_VOCAB = 16, 1024, 2, 2048
POOL_SHAPES = {
    # gpt2-small.serve-batch: 96 slots x 64 blocks + the null block, rows of 768.
    "gpt2-small": dict(family="gpt", d_model=768, n_heads=12, slots=96, num_blocks=6145),
    # gpt2-xl.serve-chat: 24 slots, rows of 1,600 (12.5 lane tiles).
    "gpt2-xl": dict(family="gpt", d_model=1600, n_heads=25, slots=24, num_blocks=1537),
    # Llama family (RoPE), 4 KV heads of 64: rows of 256. Pools of the batch
    # cell's bytes: a pool of a few MB the compiler prefetches whole into
    # fast memory, which says nothing about one that fills a chip.
    "llama-gqa": dict(
        family="llama", d_model=768, n_heads=12, n_kv_heads=4, slots=96, num_blocks=18433
    ),
    # One KV head of 64, half a lane tile: two positions fold into a row.
    "llama-mqa": dict(
        family="llama", d_model=768, n_heads=12, n_kv_heads=1, slots=96, num_blocks=73729
    ),
}
POOL_PROGRAMS = ("prefill", "decode", "verify", "cow_copy")
# What may hold a whole pool leaf: the donated leaf itself, passed along,
# and the write into it (a fusion only when it wraps that write).
POOL_IN_PLACE = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "while",
    "scatter", "dynamic-update-slice",
}


def _pool_programs(name, one_chip):
    """``{program: (fn, shapes)}`` of the engine's four jitted programs for
    one pool shape, plus the cache leaf's shape. Shapes only: nothing is
    allocated (``PagedDecodeEngine`` itself would zero a pool on the CPU)."""
    import functools

    from llmtrain_tpu.serving import engine

    spec = dict(POOL_SHAPES[name])
    family, slots, num_blocks = (spec.pop(k) for k in ("family", "slots", "num_blocks"))
    common = dict(
        vocab_size=POOL_VOCAB, block_size=POOL_CONTEXT, n_layers=POOL_LAYERS,
        d_ff=2 * spec["d_model"], dropout=0.0, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, **spec,
    )
    if family == "llama":
        from llmtrain_tpu.models.llama import Llama as Model
    else:
        from llmtrain_tpu.models.gpt import GPT as Model
    mb = POOL_CONTEXT // POOL_BLOCK_TOKENS
    paged = Model(**common).for_paged_decoding(
        num_blocks=num_blocks, block_tokens=POOL_BLOCK_TOKENS
    )
    variables = jax.eval_shape(
        lambda: paged.init(
            jax.random.key(0), jnp.zeros((1, 1), jnp.int32), deterministic=True,
            positions=jnp.zeros((1,), jnp.int32),
            block_tables=jnp.zeros((1, mb), jnp.int32),
        )
    )

    def on_chip(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, cache = (
        jax.tree.map(lambda s: on_chip(*s.shape, dtype=s.dtype), variables[c])
        for c in ("params", "cache")
    )

    def sampling(rows):
        return (
            on_chip(rows, dtype=jnp.uint32), on_chip(rows, dtype=jnp.float32),
            on_chip(rows), on_chip(rows, dtype=jnp.float32),
        )

    seeds, *knobs = sampling(slots)
    programs = {
        "prefill": (
            functools.partial(engine._prefill_impl, paged),
            (params, cache, on_chip(1, 256), on_chip(1), on_chip(1), on_chip(1, mb),
             *sampling(1)),
        ),
        "decode": (
            functools.partial(engine._decode_impl, paged),
            (params, cache, on_chip(slots), on_chip(slots), on_chip(slots, mb),
             seeds, on_chip(slots), *knobs),
        ),
        "verify": (
            functools.partial(engine._verify_impl, paged),
            (params, cache, on_chip(slots, 4), on_chip(slots), on_chip(slots, mb)),
        ),
        "cow_copy": (
            lambda _params, cache, src, dst: engine._cow_impl(cache, src, dst),
            (params, cache, on_chip(1), on_chip(1)),
        ),
    }
    leaves = {leaf.shape for leaf in jax.tree.leaves(cache)}
    assert len(leaves) == 1
    return programs, leaves.pop()


def _elements(type_text: str) -> int:
    """Element count of the largest array in an HLO result type."""
    return max(
        (math.prod(int(d) for d in dims.split(",") if d)
         for dims in re.findall(r"\w+\[([\d,]*)\]", type_text)),
        default=0,
    )


def _hlo_instructions(text: str):
    """``(opcode, result type, called computation, line)`` of every
    instruction of every computation of an HLO module's text."""
    for line in text.splitlines():
        head, eq, rest = line.partition(" = ")
        if not eq or not head.lstrip().removeprefix("ROOT ").startswith("%"):
            continue
        # The opcode is the first `name(` outside a layout (`{...T(8,128)}`).
        bare = re.sub(r"\{[^{}]*\}", lambda m: " " * len(m.group()), rest)
        op = re.search(r"(?:^|\s)([a-z][\w\-]*)\(", bare)
        if op is None:
            continue
        called = re.search(r"calls=%([\w.\-]+)", rest)
        yield op.group(1), rest[: op.start()], called and called.group(1), line


def _entry_layout(text: str):
    """Parameter and result types (layout included) of the entry computation
    and ``{parameter number: output index}`` of ``input_output_alias``."""
    header = re.sub(r"/\*.*?\*/", "", text.split("\n", 1)[0])
    signature = header.split("entry_computation_layout={", 1)[1]
    signature = re.split(r"\}, [a-z_]+=", signature, maxsplit=1)[0]
    params, results = signature.split(")->", 1)

    def arrays(types: str) -> list[str]:
        return re.findall(r"\w+\[[\d,]*\]\{[^{}]*\}", types)

    aliased = {
        int(param): int(out)
        for out, param in re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
    }
    return arrays(params), arrays(results), aliased


def _computation(text: str, name: str) -> str:
    """The body of one named computation of an HLO module's text."""
    start = text.index(f"%{name} (")
    return text[start : text.index("\n}", start)]


def _unfused_computations(text: str):
    """``(name, body)`` of every computation that is not the body of a
    fusion: what a fused computation holds are values inside one kernel,
    not arrays in memory."""
    for match in re.finditer(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$", text, flags=re.M):
        if not match.group(1).startswith("fused_computation"):
            yield match.group(1), text[match.start() : text.index("\n}", match.start())]


class TestPagedPoolKeepsItsLayout:
    """No prefill, decode, verify or COW program copies, transposes or
    re-tiles a pool-sized array: the layout the compiler gives a pool leaf
    in HBM is the one its scatter, its block-table gather and the COW copy
    use, and the donated input aliases the output in it. With the leaf
    declared ``(num_blocks, block_tokens, kv_heads, 64)`` the compiler made
    ``num_blocks`` the minor dimension and every call transposed the whole
    pool three or four times (PERF.md section 6, PR 25)."""

    @pytest.mark.parametrize("program", POOL_PROGRAMS)
    @pytest.mark.parametrize("shape", list(POOL_SHAPES))
    def test_no_pool_sized_relayout(self, one_chip, shape, program):
        programs, leaf_shape = _pool_programs(shape, one_chip)
        fn, shapes = programs[program]
        text = jax.jit(fn, donate_argnums=(1,)).lower(*shapes).compile().as_text()
        leaf = math.prod(leaf_shape)

        params, results, aliased = _entry_layout(text)
        dims = "[" + ",".join(map(str, leaf_shape)) + "]"
        pool_params = [i for i, p in enumerate(params) if dims in p]
        assert len(pool_params) == 2 * POOL_LAYERS
        for i in pool_params:
            assert i in aliased, f"pool leaf (parameter {i}) is not donated in place"
            assert results[aliased[i]] == params[i], "the output's layout differs"

        for op, result, called, line in _hlo_instructions(text):
            if _elements(result) < leaf:
                continue
            in_place = op in POOL_IN_PLACE or (
                op == "fusion"
                and re.search(r" (scatter|dynamic-update-slice)\(", _computation(text, called))
            )
            assert in_place, f"pool-sized `{op}` in {shape}.{program}: {line.strip()[:300]}"


# -- recurrent-state rows beside the pool (models/falcon_h1.py, PR 26) --------

STATE_SLOTS = 96
STATE_LAYERS = 2


@pytest.fixture(scope="module")
def state_programs(one_chip):
    """Decode (96 rows) and prefill (one prompt of 640) of the Falcon-H1
    configuration the benchmark runs, at its published widths and whole
    vocabulary, 2 layers, as ``PagedDecodeEngine`` jits them; abstract
    shapes only."""
    import functools
    import json
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.reference import falcon_h1 as ref
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import initialize_registries
    from llmtrain_tpu.serving import engine

    cfg = json.loads((root / "benchmarks/configs/falcon-h1-34b.json").read_text())
    cfg["num_hidden_layers"] = STATE_LAYERS
    initialize_registries()
    run = RunConfig.model_validate({
        "schema_version": 1, "run": {"name": "aot", "seed": 1, "device": "cpu"}, "model": ref.program_model(cfg),
        "data": {"name": "dummy_text"}, "trainer": {"max_steps": 1, "micro_batch_size": 1, "warmup_steps": 0},
        "mlflow": {"enabled": False},
    })
    mb = POOL_CONTEXT // POOL_BLOCK_TOKENS
    paged = build_adapter(run).build_model(run).for_paged_decoding(
        num_blocks=1 + STATE_SLOTS * mb, block_tokens=POOL_BLOCK_TOKENS, state_rows=1 + STATE_SLOTS
    )
    variables = jax.eval_shape(
        lambda: paged.init(
            jax.random.key(0), jnp.zeros((1, 1), jnp.int32), deterministic=True,
            positions=jnp.zeros((1,), jnp.int32), block_tables=jnp.zeros((1, mb), jnp.int32),
        )
    )

    def on_chip(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: on_chip(*s.shape, dtype=jnp.bfloat16), variables["params"])
    cache = jax.tree.map(lambda s: on_chip(*s.shape, dtype=s.dtype), variables["cache"])

    def sampling(rows):
        return (on_chip(rows, dtype=jnp.uint32), on_chip(rows, dtype=jnp.float32),
                on_chip(rows), on_chip(rows, dtype=jnp.float32))

    seeds, *knobs = sampling(STATE_SLOTS)
    shapes = {
        "decode": (functools.partial(engine._decode_impl, paged),
                   (params, cache, on_chip(STATE_SLOTS), on_chip(STATE_SLOTS), on_chip(STATE_SLOTS, mb),
                    seeds, on_chip(STATE_SLOTS), *knobs, on_chip(STATE_SLOTS))),
        "prefill": (functools.partial(engine._prefill_impl, paged),
                    (params, cache, on_chip(1, 640), on_chip(1), on_chip(1), on_chip(1, mb),
                     *sampling(1), on_chip(1))),
    }
    state_shapes = {
        leaf.shape for path, leaf in jax.tree_util.tree_leaves_with_path(cache) if engine.is_state_leaf(path)
    }
    return shapes, state_shapes


class TestStateRowsUpdateInPlace:
    """PR 25's lesson applied to the state leaves before the first chip
    run: the donated ``state_ssm`` and ``state_conv`` leaves alias their
    outputs in one layout, and outside fused computations nothing as large
    as a ``state_ssm`` leaf exists but the leaf itself passed along and the
    ONE fusion a layer that writes it (decode: the elementwise update with
    ``y`` reduced in the same pass; prefill: an in-place
    ``dynamic-update-slice`` of the request's row). A gather of the batch's
    rows, the update and a scatter back would be three such passes."""

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_state_leaves_alias_and_nothing_else_is_their_size(self, state_programs, program):
        shapes, state_shapes = state_programs
        assert state_shapes == {(97, 3, 5120), (97, 32, 128, 256)}
        fn, args = shapes[program]
        text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
        params, results, aliased = _entry_layout(text)
        for shape in state_shapes:
            dims = "[" + ",".join(map(str, shape)) + "]"
            leaves = [i for i, p in enumerate(params) if dims in p]
            assert len(leaves) == STATE_LAYERS
            for i in leaves:
                assert i in aliased, f"state leaf (parameter {i}) is not donated in place"
                assert results[aliased[i]] == params[i], "the output's layout differs"

        writers = 0
        for name, body in _unfused_computations(text):
            for op, result, called, line in _hlo_instructions(body):
                if "[97,32,128,256]" not in result:
                    continue
                if op == "fusion":
                    writers += 1
                    fused = _computation(text, called)
                    assert re.search(r" (dynamic-update-slice|multiply|add)\(", fused), line[:300]
                    assert not re.search(r" (gather|scatter|copy|transpose)\(", fused), line[:300]
                else:
                    assert op in POOL_IN_PLACE, f"state-sized `{op}` in {program}: {line.strip()[:300]}"
        assert writers == STATE_LAYERS


# -- the form of the paged read (models/gpt.py paged_kv_form, PR 47) ---------

RELAYOUTS = {"reshape", "copy", "transpose"}
# Inside a fusion these move nothing by themselves.
_FREE = {"parameter", "bitcast", "constant", "tuple", "get-tuple-element"}


def _relayouts(text: str, elements: int) -> list[str]:
    """Every ``reshape``, ``copy`` or ``transpose`` instruction, and every
    fusion that is only one, whose result holds ``elements`` or more,
    outside fused computations: an array written again in another tiling."""
    found = []
    for _name, body in _unfused_computations(text):
        for op, result, called, line in _hlo_instructions(body):
            if _elements(result) < elements:
                continue
            if op == "fusion":
                inner = {o for o, *_ in _hlo_instructions(_computation(text, called))} - _FREE
                if not inner or not inner <= RELAYOUTS:
                    continue
            elif op not in RELAYOUTS:
                continue
            found.append(line.strip()[:200])
    return found


def _row_width(spec: dict) -> int:
    return (spec.get("n_kv_heads") or spec["n_heads"]) * (spec["d_model"] // spec["n_heads"])


class TestDecodeReadsGatheredBlocksAsRows:
    """A decode call contracts q against the gathered K/V blocks in the
    layout the gather leaves them in (models/gpt.py ``paged_kv_form``,
    ``"rows"``): nothing as large as one layer's gathered blocks (``slots x
    1,024 x width``) is reshaped, copied or transposed. The per-head form
    re-tiled each of a layer's two gathered leaves ``(slots, 1024,
    kv_heads, 64)``, 2.67 times its size with 12 heads of 64 on the two
    minor dimensions: 34% and 44% of the two GPT-2 serving cells' device
    time (PERF.md section 6, PR 47). A prefill call keeps the per-head form
    (one row's table; ``kv_heads`` times the FLOPs of a slab would cost
    more) and holds no more such instructions than it did."""

    # Shapes the rule sends the new way; ``llama-mqa`` folds two positions
    # into a pool row and keeps the per-head form.
    SHAPES = ("gpt2-small", "gpt2-xl", "llama-gqa")
    # Instructions of one row's gathered table (``1,024 x width``) or more
    # in the prefill program of 256 positions, at PR 46: the re-tile of K
    # and of V in each of the two layers; at ``gpt2-xl`` also the copies of
    # the two embedding tables (1,024 and 2,048 rows of 1,600) into fast
    # memory.
    PREFILL_RELAYOUTS = {"gpt2-small": 4, "gpt2-xl": 6, "llama-gqa": 4, "llama-mqa": 4}

    @pytest.mark.parametrize("shape", SHAPES)
    def test_decode_program_holds_no_relayout_of_a_gathered_leaf(self, one_chip, shape):
        from llmtrain_tpu.models.gpt import paged_kv_form

        spec = POOL_SHAPES[shape]
        kv_heads = spec.get("n_kv_heads") or spec["n_heads"]
        assert paged_kv_form(
            t=1, n_heads=spec["n_heads"], kv_heads=kv_heads, head_dim=spec["d_model"] // spec["n_heads"],
            block_tokens=POOL_BLOCK_TOKENS,
        ) == "rows"
        programs, _leaf = _pool_programs(shape, one_chip)
        fn, shapes = programs["decode"]
        text = jax.jit(fn, donate_argnums=(1,)).lower(*shapes).compile().as_text()
        gathered = spec["slots"] * POOL_CONTEXT * _row_width(spec)
        assert _relayouts(text, gathered) == []
        # The gathered blocks are there, as the pool's rows.
        assert f"bf16[{spec['slots']},{POOL_CONTEXT},{_row_width(spec)}]" in text

    @pytest.mark.parametrize("shape", list(POOL_SHAPES))
    def test_prefill_program_holds_no_more_relayouts_than_it_did(self, one_chip, shape):
        programs, _leaf = _pool_programs(shape, one_chip)
        fn, shapes = programs["prefill"]
        text = jax.jit(fn, donate_argnums=(1,)).lower(*shapes).compile().as_text()
        found = _relayouts(text, POOL_CONTEXT * _row_width(POOL_SHAPES[shape]))
        assert len(found) <= self.PREFILL_RELAYOUTS[shape], found

    def test_falcon_h1_decode_program_holds_no_relayout_of_a_gathered_leaf(self, state_programs):
        """20 query heads over 4 K/V heads of 128, rows of 512: the same
        function, the same rule (``falcon-h1-34b.serve-batch``)."""
        shapes, _state = state_programs
        fn, args = shapes["decode"]
        text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
        assert _relayouts(text, STATE_SLOTS * POOL_CONTEXT * 512) == []
        assert f"bf16[{STATE_SLOTS},{POOL_CONTEXT},512]" in text


# -- the latent pool and the held experts (models/latent_moe.py, PR 31) -------

LATENT_SLOTS, LATENT_CONTEXT, LATENT_LAYERS = 96, 4096, 2


def _cell_programs(one_chip, family: str, config: str, *, layers: int, slots: int, context: int, bucket: int,
                   overrides: dict | None = None, ring: int = 0, temporaries: dict | None = None):
    """``({"decode": text, "prefill": text}, [cache leaf shapes])``: the decode
    (``slots`` rows) and prefill (one prompt in ``bucket``) programs of a
    benchmark configuration at its published widths, its share of the experts
    and its slice of the vocabulary, ``slots`` x ``context`` positions,
    ``layers`` of its layers, as ``PagedDecodeEngine`` jits them; abstract
    shapes only. ``overrides`` replaces keys of the configuration; ``ring`` > 0
    gives the model a window pool of ``slots`` rings and the programs the ring
    tables as the engine's keyword; ``temporaries`` is filled with each
    program's temporary bytes. The flash dispatch sees platform tpu while
    this traces (a module's fixture is built before the test's own
    ``_as_on_chip``), and nothing compiled here goes to the persistent cache."""
    import functools
    import importlib
    import json
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    ref = importlib.import_module(f"benchmarks.reference.{family}")
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import initialize_registries
    from llmtrain_tpu.serving import engine

    cfg = json.loads((root / "benchmarks/configs" / config).read_text())
    cfg["num_hidden_layers"] = layers
    cfg.update(overrides or {})
    initialize_registries()
    run = RunConfig.model_validate({
        "schema_version": 1, "run": {"name": "aot", "seed": 1, "device": "cpu"}, "model": ref.program_model(cfg),
        "data": {"name": "dummy_text"}, "trainer": {"max_steps": 1, "micro_batch_size": 1, "warmup_steps": 0},
        "mlflow": {"enabled": False},
    })
    mb = context // POOL_BLOCK_TOKENS
    paged = build_adapter(run).build_model(run).for_paged_decoding(
        num_blocks=1 + slots * mb, block_tokens=POOL_BLOCK_TOKENS,
        **({"window_num_blocks": 1 + slots * ring} if ring else {}),
    )

    def on_chip(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def rings(rows):
        return {"window_tables": on_chip(rows, ring)} if ring else {}

    variables = jax.eval_shape(
        lambda: paged.init(
            jax.random.key(0), jnp.zeros((1, 1), jnp.int32), deterministic=True,
            positions=jnp.zeros((1,), jnp.int32), block_tables=jnp.zeros((1, mb), jnp.int32),
            **{k: jnp.zeros(v.shape, v.dtype) for k, v in rings(1).items()},
        )
    )

    # What the server holds: bf16 but the router, which the program declares float32.
    params = jax.tree.map(
        lambda s: on_chip(*s.shape, dtype=s.dtype if s.dtype == jnp.float32 else jnp.bfloat16),
        variables["params"],
    )
    cache = jax.tree.map(lambda s: on_chip(*s.shape, dtype=s.dtype), variables["cache"])

    def sampling(rows):
        return (on_chip(rows, dtype=jnp.uint32), on_chip(rows, dtype=jnp.float32),
                on_chip(rows), on_chip(rows, dtype=jnp.float32))

    seeds, *knobs = sampling(slots)
    shapes = {
        "decode": (functools.partial(engine._decode_impl, paged),
                   (params, cache, on_chip(slots), on_chip(slots), on_chip(slots, mb), seeds, on_chip(slots), *knobs),
                   rings(slots)),
        "prefill": (functools.partial(engine._prefill_impl, paged),
                    (params, cache, on_chip(1, bucket), on_chip(1), on_chip(1), on_chip(1, mb), *sampling(1)),
                    rings(1)),
    }
    texts = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            for name, (fn, args, named) in shapes.items():
                compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args, **named).compile()
                texts[name] = compiled.as_text()
                if temporaries is not None:
                    temporaries[name] = compiled.memory_analysis().temp_size_in_bytes
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
    return texts, sorted(leaf.shape for leaf in jax.tree.leaves(cache))


@pytest.fixture(scope="module")
def latent_programs(one_chip):
    """Decode (96 rows) and prefill (one prompt in the 1,024 bucket) of the
    A.X-K1 configuration the benchmark runs, the cell's 96 slots x 4,096
    positions, 2 layers (the dense one and ONE expert layer)."""
    texts, leaves = _cell_programs(one_chip, "axk1", "ax-k1.json", layers=LATENT_LAYERS, slots=LATENT_SLOTS,
                                   context=LATENT_CONTEXT, bucket=1024)
    return texts, set(leaves)


class TestLatentPoolKeepsItsLayout:
    """PR 25's lesson read before the first chip run of the latent cache: a
    pool row of 576 values (4.5 lane tiles) the compiler kept ``num_blocks``
    minor and copied whole, twice a layer, in every program; padded to 640
    lanes the leaf is row-major, the donated input aliases the output in
    that layout, and nothing as large as the leaf exists but the leaf itself
    passed along and the ONE fusion a layer that scatters the call's rows
    into it. And the expert layer regroups its tokens: no array pairs the
    tokens with all 192 experts beyond the router's own (tokens, experts)
    scores, and the held experts run as grouped products."""

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_latent_leaf_is_row_major_and_updated_in_place(self, latent_programs, program):
        texts, leaves = latent_programs
        assert leaves == {(1 + 96 * 256, 16, 640)}
        text = texts[program]
        params, results, aliased = _entry_layout(text)
        pool = [i for i, p in enumerate(params) if "[24577,16,640]" in p]
        assert len(pool) == LATENT_LAYERS
        for i in pool:
            assert "{2,1,0:" in params[i], f"the latent leaf is not row-major: {params[i]}"
            assert i in aliased and results[aliased[i]] == params[i], "not donated in place in one layout"
        leaf = 24577 * 16 * 640  # (the prefill's float32 scores, 64 x 1,024 x 4,096, are larger: not a pool)
        for op, result, called, line in _hlo_instructions(text):
            if _elements(result) != leaf:
                continue
            in_place = op in POOL_IN_PLACE or (
                op == "fusion" and re.search(r" (scatter|dynamic-update-slice)\(", _computation(text, called))
            )
            assert in_place, f"pool-sized `{op}` in {program}: {line.strip()[:300]}"

    def test_decode_regroups_its_tokens_and_holds_no_one_hot_over_the_experts(self, latent_programs):
        text = latent_programs[0]["decode"]
        assert "ragged-dot" in text  # the held experts: grouped products over the sorted pairs
        routed = [(result, line) for _op, result, _called, line in _hlo_instructions(text) if "/moe/" in line]
        assert len(routed) > 20  # the expert layer's instructions carry its scope
        for result, line in routed:
            for dims in re.findall(r"\w+\[([\d,]+)\]", result):
                sizes = [int(d) for d in dims.split(",")]
                # (tokens, experts) is the router's scores and (tokens, groups, experts a group)
                # their grouping; anything over tokens or pairs AND all 192 experts (or the
                # 12 held) AND a third axis would be a dispatch one-hot.
                over_tokens = any(n in (96, 96 * 8) for n in sizes)
                over_experts = 192 in sizes or (12 in sizes and 7168 not in sizes and 2048 not in sizes)
                assert not (over_tokens and over_experts and len(sizes) > 2), line.strip()[:300]


# -- three pool leaves a layer and a gather of chosen rows (models/indexed_moe.py, PR 35) --

INDEXED_SLOTS, INDEXED_CONTEXT, INDEXED_LAYERS, INDEXED_BUCKET = 64, 6656, 2, 6144


@pytest.fixture(scope="module")
def indexed_programs(one_chip):
    """Decode (64 rows) and prefill (one prompt in the 6,144 bucket) of the
    Keye-VL-2.0 configuration the benchmark runs, the cell's 64 slots x 6,656
    positions, 2 of its 8 layers (all alike)."""
    return _cell_programs(one_chip, "keye_vl2", "keye-vl2-30b-a3b.json", layers=INDEXED_LAYERS, slots=INDEXED_SLOTS,
                          context=INDEXED_CONTEXT, bucket=INDEXED_BUCKET)


class TestIndexedPoolKeepsItsLayout:
    """Read before the first chip run of index-selected attention: K, V and
    the index key are three leaves a layer, each row-major with a lane-dense
    minor dimension (the 64-wide index key is zero-padded to a lane tile),
    each donated in place in one layout, and nothing as large as a leaf
    exists but the leaf passed along and the scatter of the call's rows into
    it. In particular the decode program's gather of the CHOSEN rows reads the
    K/V leaf through its ``(positions, width)`` view, which is the leaf
    itself: seen as ``(positions, heads, head_dim)`` the compiler re-tiled
    the whole 436 MB leaf, twice a layer (PERF.md section 6, PR 35)."""

    NUM_BLOCKS = 1 + INDEXED_SLOTS * (INDEXED_CONTEXT // POOL_BLOCK_TOKENS)  # 26,625

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_three_leaves_a_layer_are_row_major_and_updated_in_place(self, indexed_programs, program):
        texts, leaves = indexed_programs
        n = self.NUM_BLOCKS
        assert leaves == sorted([(n, 16, 128)] * INDEXED_LAYERS + [(n, 16, 512)] * 2 * INDEXED_LAYERS)
        text = texts[program]
        params, results, aliased = _entry_layout(text)
        pool = [i for i, p in enumerate(params) if f"[{n}," in p]
        assert len(pool) == 3 * INDEXED_LAYERS
        for i in pool:
            assert "{2,1,0:" in params[i], f"a pool leaf is not row-major: {params[i]}"
            assert i in aliased and results[aliased[i]] == params[i], "not donated in place in one layout"
        sizes = {n * 16 * 128, n * 16 * 512}
        for op, result, called, line in _hlo_instructions(text):
            if _elements(result) not in sizes:
                continue
            in_place = op in POOL_IN_PLACE or (
                op == "fusion" and re.search(r" (scatter|dynamic-update-slice)\(", _computation(text, called))
            )
            assert in_place, f"pool-sized `{op}` in {program}: {line.strip()[:300]}"

    def test_decode_gathers_the_chosen_rows_and_prefill_holds_no_score_matrix(self, indexed_programs):
        texts, _ = indexed_programs
        decode, prefill = texts["decode"], texts["prefill"]
        # 64 rows x 2,048 chosen positions x 512 lanes, K and V of each layer: gathered, never the whole table
        gathered = [r for op, r, _c, _l in _hlo_instructions(decode) if "bf16[64,2048,512]" in r or "bf16[131072,512]" in r]
        assert gathered
        assert "bf16[64,6656,512]" not in decode and "bf16[64,416,16,512]" not in decode
        assert "ragged-dot" in decode  # the held experts: grouped products over the sorted pairs
        # prefill: scores exist for a chunk of 512 queries and a block of 512 keys, index dots for a chunk,
        # the indexer's 16 heads and the table (the largest thing the attention makes); nothing of it pairs
        # all 6,144 queries with all 6,656 positions
        scoped = [(result, line) for _op, result, _called, line in _hlo_instructions(prefill) if "/attn/" in line]
        assert len(scoped) > 50  # the attention's instructions carry its scope
        for result, line in scoped:
            for dims in re.findall(r"\w+\[([\d,]+)\]", result):
                sizes = [int(d) for d in dims.split(",")]
                assert not (INDEXED_BUCKET in sizes and INDEXED_CONTEXT in sizes), line.strip()[:300]
                assert math.prod(sizes) <= 512 * 16 * INDEXED_CONTEXT or self.NUM_BLOCKS in sizes, line.strip()[:300]


# -- window layers' ring beside the global pool (models/windowed_moe.py, PR 45) --

WINDOWED_SLOTS, WINDOWED_CONTEXT, WINDOWED_BUCKET, WINDOW = 32, 8192, 6144, 4096
WINDOWED_RING = WINDOW // POOL_BLOCK_TOKENS + 1  # 257
WINDOWED_LAYERS = ("sliding_attention", "full_attention")


@pytest.fixture(scope="module")
def windowed_programs(one_chip):
    """Decode (32 rows) and prefill (one prompt in the 6,144 bucket) of the
    Command A+ configuration the benchmark runs, the cell's 32 slots x 8,192
    positions, 2 layers (ONE window layer, ONE global layer):
    ``({program: (text, temporary bytes)}, [cache leaf shapes])``."""
    temporaries: dict = {}
    texts, leaves = _cell_programs(
        one_chip, "cohere2_moe", "command-a-plus.json", layers=len(WINDOWED_LAYERS), slots=WINDOWED_SLOTS,
        context=WINDOWED_CONTEXT, bucket=WINDOWED_BUCKET, overrides={"layer_types": list(WINDOWED_LAYERS)},
        ring=WINDOWED_RING, temporaries=temporaries,
    )
    return {name: (text, temporaries[name]) for name, text in texts.items()}, leaves


class TestWindowRingKeepsItsLayout:
    """Read before the first chip run of the window ring: the window layer's
    K and V are leaves of the WINDOW pool's block count (32 x 257 + 1), not
    the global pool's (32 x 512 + 1); all four leaves are row-major, donated
    in place in one layout, and nothing as large as a leaf exists but the
    leaf passed along and the scatter of the call's rows into it; the window
    layer's decode gathers a ring's worth of positions a row (257 x 16 =
    4,112), never the global table's 8,192; the prefill runs the Pallas flash
    forward in both layers and holds no (queries, keys) score matrix; and the
    prefill's temporaries stay under 3 GB beside 12.2 GB resident."""

    GLOBAL_BLOCKS = 1 + WINDOWED_SLOTS * (WINDOWED_CONTEXT // POOL_BLOCK_TOKENS)  # 16,385
    WINDOW_BLOCKS = 1 + WINDOWED_SLOTS * WINDOWED_RING  # 8,225

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_both_kinds_of_leaf_are_row_major_and_updated_in_place(self, windowed_programs, program):
        texts, leaves = windowed_programs
        assert leaves == sorted([(self.WINDOW_BLOCKS, 16, 1024)] * 2 + [(self.GLOBAL_BLOCKS, 16, 1024)] * 2)
        text = texts[program][0]
        params, results, aliased = _entry_layout(text)
        for blocks in (self.WINDOW_BLOCKS, self.GLOBAL_BLOCKS):
            pool = [i for i, p in enumerate(params) if f"[{blocks},16,1024]" in p]
            assert len(pool) == 2  # K and V of the one layer of that kind
            for i in pool:
                assert "{2,1,0:" in params[i], f"a pool leaf is not row-major: {params[i]}"
                assert i in aliased and results[aliased[i]] == params[i], "not donated in place in one layout"
        sizes = {self.WINDOW_BLOCKS * 16 * 1024, self.GLOBAL_BLOCKS * 16 * 1024}
        for op, result, called, line in _hlo_instructions(text):
            if _elements(result) not in sizes:
                continue
            in_place = op in POOL_IN_PLACE or (
                op == "fusion" and re.search(r" (scatter|dynamic-update-slice)\(", _computation(text, called))
            )
            assert in_place, f"pool-sized `{op}` in {program}: {line.strip()[:300]}"

    def test_the_window_layers_gather_is_bounded_by_the_ring(self, windowed_programs):
        decode = windowed_programs[0]["decode"][0]
        ring_positions, table_positions = WINDOWED_RING * 16, WINDOWED_CONTEXT
        by_scope = {"window_attention": set(), "global_attention": set()}
        for _op, result, _called, line in _hlo_instructions(decode):
            for scope, seen in by_scope.items():
                if f"/{scope}/" in line:
                    seen.update(int(d) for dims in re.findall(r"\w+\[([\d,]+)\]", result) for d in dims.split(","))
        # the window layer reads 32 rows x 4,112 gathered positions and nothing of the table's length
        assert ring_positions in by_scope["window_attention"] or WINDOWED_RING in by_scope["window_attention"]
        assert table_positions not in by_scope["window_attention"]
        assert table_positions in by_scope["global_attention"]  # the global layer gathers its whole table
        assert "ragged-dot" in decode  # the held experts: grouped products over the sorted pairs

    def test_prefill_attends_by_blocks_and_its_temporaries_fit(self, windowed_programs):
        prefill, temporaries = windowed_programs[0]["prefill"]
        # the Pallas flash forward once a layer, on the projections' own rows (grouped K/V read in place);
        # the other custom calls are the held experts' grouped products
        assert _custom_call_names(prefill) == {"flash_attention_fwd", "ragged-dot-metadata", "ragged-dot-none"}
        assert len(re.findall(
            r"%flash_attention_fwd[\w.]* = [^\n]*operand_layout_constraints=\{bf16\[1,6144,16384\]\{2,1,0\}, "
            r"bf16\[1,6144,1024\]\{2,1,0\}, bf16\[1,6144,1024\]", prefill)) == len(WINDOWED_LAYERS)
        for _op, result, _called, line in _hlo_instructions(prefill):
            if "_attention/" not in line:
                continue
            for dims in re.findall(r"\w+\[([\d,]+)\]", result):
                sizes = [int(d) for d in dims.split(",")]
                assert sizes.count(WINDOWED_BUCKET) < 2, line.strip()[:300]  # no (queries, keys) scores
        assert f"[1,{WINDOWED_BUCKET},32768]" not in prefill  # the head at the last true position alone
        assert temporaries < 3e9, f"prefill temporaries {temporaries / 1e9:.2f} GB"
        assert windowed_programs[0]["decode"][1] < 3e9

"""Compiled-Pallas correctness on real TPU hardware.

Interpret-mode tests (tests/test_ops.py) validate kernel math on CPU and
tests/test_tpu_aot_compile.py proves the kernels lower for the chip; only a
run on the chip shows the lowered kernel computes the right numbers. These
tests run the compiled kernels against the dense reference at bf16
tolerance, sweeping the VMEM-relevant block shapes. They skip everywhere
except on a TPU backend; run them on the machine with the chip:

    LLMTRAIN_TEST_TPU=1 python -m pytest tests/test_tpu_compiled.py -q
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _require_tpu():
    """Skip unless this process runs on a TPU — decided when a test of
    this file starts, never while the module is imported (xdist workers
    must all collect the same tests)."""
    if jax.default_backend() != "tpu":
        pytest.skip("requires a TPU backend")


def _qkv(b=2, t=512, h=4, d=64, dtype=jnp.bfloat16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype=dtype) for k in keys)


def _dense_ref(q, k, v):
    from llmtrain_tpu.models.gpt import dense_attention

    return dense_attention(q, k, v, attention_mask=None)


class TestCompiledForward:
    def test_matches_dense_bf16(self):
        from llmtrain_tpu.ops.pallas_attention import pallas_flash_attention

        q, k, v = _qkv()
        out = jax.device_get(pallas_flash_attention(q, k, v))
        ref = jax.device_get(_dense_ref(q, k, v))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
        )

    @pytest.mark.parametrize(
        "block_q,block_k",
        [
            (128, 128), (128, 256), (256, 128), (256, 256), (512, 512),
            # resident rows wider than the streamed keys: what _auto_block picks
            (512, 256), (512, 128), (256, 512),
        ],
    )
    def test_block_shape_sweep(self, block_q, block_k):
        """VMEM-relevant tilings: every (block_q, block_k) must lower and
        agree with the dense reference."""
        from llmtrain_tpu.ops.pallas_attention import pallas_flash_attention

        q, k, v = _qkv(t=512, seed=1)
        out = jax.device_get(
            pallas_flash_attention(q, k, v, block_q=block_q, block_k=block_k)
        )
        ref = jax.device_get(_dense_ref(q, k, v))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
        )

    def test_f32_tight_tolerance(self):
        """With MXU passes forced to full f32 (the TPU default is bf16
        multiplies even for f32 inputs), kernel and dense agree tightly."""
        from llmtrain_tpu.ops.pallas_attention import pallas_flash_attention

        q, k, v = _qkv(t=256, dtype=jnp.float32, seed=2)
        with jax.default_matmul_precision("highest"):
            out = jax.device_get(pallas_flash_attention(q, k, v))
            ref = jax.device_get(_dense_ref(q, k, v))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


class TestCompiledMaskedAndGQA:
    """Round-3 kernel capabilities lowered for real: in-kernel padding
    masks and native grouped-query K/V (tests/test_ops.py has the
    interpret-mode equivalents)."""

    def test_masked_forward_matches_dense(self):
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.pallas_attention import pallas_flash_attention

        q, k, v = _qkv(t=512, seed=11)
        lens = np.asarray([512, 300, 512, 77])[: q.shape[0]]
        mask = jnp.asarray((np.arange(512)[None, :] < lens[:, None]).astype(np.int32))
        out = jax.device_get(pallas_flash_attention(q, k, v, mask))
        ref = jax.device_get(dense_attention(q, k, v, attention_mask=mask))
        m = np.asarray(mask)[:, :, None, None]
        np.testing.assert_allclose(
            np.asarray(out, np.float32) * m, np.asarray(ref, np.float32) * m, atol=2e-2
        )

    def test_masked_backward_matches_dense_grads(self):
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, k, v = _qkv(t=256, dtype=jnp.float32, seed=12)
        lens = np.asarray([256, 100])[: q.shape[0]]
        mask = jnp.asarray((np.arange(256)[None, :] < lens[:, None]).astype(np.int32))
        g = jax.random.normal(jax.random.key(13), q.shape, jnp.float32)
        g = g * mask[:, :, None, None].astype(jnp.float32)

        def loss(q, k, v):
            return jnp.sum(dense_attention(q, k, v, attention_mask=mask) * g)

        with jax.default_matmul_precision("highest"):
            out, lse = pallas_flash_attention_fwd(q, k, v, mask)
            dq, dk, dv = pallas_flash_attention_bwd(q, k, v, out, lse, g, mask)
            rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for got, want in ((dq, rq), (dk, rk), (dv, rv)):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(got)), np.asarray(jax.device_get(want)),
                atol=1e-3,
            )

    @pytest.mark.parametrize("hkv", [1, 2], ids=["mqa", "gqa2"])
    def test_gqa_forward_and_backward(self, hkv):
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        b, t, h, d = 2, 256, 4, 64
        ks = jax.random.split(jax.random.key(14), 3)
        q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
        kn = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
        vn = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
        reps = h // hkv
        g = jax.random.normal(jax.random.key(15), q.shape, jnp.float32)

        def loss(q, kn, vn):
            kw = jnp.repeat(kn, reps, axis=2)
            vw = jnp.repeat(vn, reps, axis=2)
            return jnp.sum(dense_attention(q, kw, vw, attention_mask=None) * g)

        with jax.default_matmul_precision("highest"):
            out, lse = pallas_flash_attention_fwd(q, kn, vn)
            dq, dk, dv = pallas_flash_attention_bwd(q, kn, vn, out, lse, g)
            rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, kn, vn)
            # Reference must run INSIDE the precision context: on TPU the
            # default is bf16 MXU passes even for f32 inputs, and a
            # default-precision dense ref vs highest-precision kernel
            # differs by ~1e-3 relative (r4 chip run caught exactly that).
            ref = dense_attention(
                q, jnp.repeat(kn, reps, axis=2), jnp.repeat(vn, reps, axis=2),
                attention_mask=None,
            )
        assert dk.shape == kn.shape and dv.shape == vn.shape
        np.testing.assert_allclose(
            np.asarray(jax.device_get(out)), np.asarray(jax.device_get(ref)),
            atol=1e-4,
        )
        for got, want in ((dq, rq), (dk, rk), (dv, rv)):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(got)), np.asarray(jax.device_get(want)),
                atol=1e-3,
            )


class TestCompiledSegments:
    """Round-4 segment masking (packed cross-document) lowered for real
    (tests/test_packing.py has the interpret-mode equivalents)."""

    def test_segment_forward_matches_dense(self):
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.pallas_attention import pallas_flash_attention

        q, k, v = _qkv(t=512, seed=61)
        seg = np.ones((q.shape[0], 512), np.int32)
        seg[:, 200:420] = 2
        seg[:, 420:] = 3
        seg = jnp.asarray(seg)
        out = jax.device_get(pallas_flash_attention(q, k, v, seg))
        ref = jax.device_get(dense_attention(q, k, v, attention_mask=seg))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
        )

    def test_segment_backward_matches_dense_grads(self):
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, k, v = _qkv(t=256, dtype=jnp.float32, seed=62)
        seg = np.ones((q.shape[0], 256), np.int32)
        seg[:, 100:] = 2
        seg = jnp.asarray(seg)
        g = jax.random.normal(jax.random.key(63), q.shape, jnp.float32)

        def loss(q, k, v):
            return jnp.sum(dense_attention(q, k, v, attention_mask=seg) * g)

        with jax.default_matmul_precision("highest"):
            out, lse = pallas_flash_attention_fwd(q, k, v, seg)
            dq, dk, dv = pallas_flash_attention_bwd(q, k, v, out, lse, g, seg)
            rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for got, want in ((dq, rq), (dk, rk), (dv, rv)):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(got)), np.asarray(jax.device_get(want)),
                atol=1e-3,
            )


class TestCompiledSlidingWindow:
    """Round-4 sliding-window kernels lowered for real (tests/test_ops.py
    TestSlidingWindow has the interpret-mode equivalents)."""

    def test_windowed_forward_matches_dense(self):
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.pallas_attention import pallas_flash_attention

        q, k, v = _qkv(t=512, seed=51)
        out = jax.device_get(pallas_flash_attention(q, k, v, window=300))
        ref = jax.device_get(dense_attention(q, k, v, attention_mask=None, window=300))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
        )

    def test_windowed_backward_matches_dense_grads(self):
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, k, v = _qkv(t=256, dtype=jnp.float32, seed=52)
        g = jax.random.normal(jax.random.key(53), q.shape, jnp.float32)

        def loss(q, k, v):
            return jnp.sum(
                dense_attention(q, k, v, attention_mask=None, window=100) * g
            )

        with jax.default_matmul_precision("highest"):
            out, lse = pallas_flash_attention_fwd(q, k, v, window=100)
            dq, dk, dv = pallas_flash_attention_bwd(
                q, k, v, out, lse, g, window=100
            )
            rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for got, want in ((dq, rq), (dk, rk), (dv, rv)):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(got)), np.asarray(jax.device_get(want)),
                atol=1e-3,
            )


class TestCompiledBackward:
    @pytest.mark.parametrize(
        "block_q,block_k,dkdv_q,dkdv_k",
        [
            (128, 128, None, None),
            (256, 256, None, None),
            # dq: rows resident, keys streamed; dk/dv: keys resident, queries
            # streamed — the unequal pairs _auto_block can pick, and their mirror.
            (512, 256, 256, 512),
            (512, 128, 128, 512),
            (128, 256, 256, 128),
        ],
    )
    def test_fused_bwd_matches_dense_grads(self, block_q, block_k, dkdv_q, dkdv_k):
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, k, v = _qkv(t=512, dtype=jnp.float32, seed=3)
        g = jax.random.normal(jax.random.key(7), q.shape, jnp.float32)

        def loss(q, k, v):
            return jnp.sum(_dense_ref(q, k, v) * g)

        # Force full-f32 MXU passes in both paths: the TPU default is bf16
        # multiplies even for f32 inputs, which dominates a 1e-3 tolerance.
        with jax.default_matmul_precision("highest"):
            out, lse = pallas_flash_attention_fwd(
                q, k, v, block_q=block_q, block_k=block_k
            )
            dq, dk, dv = pallas_flash_attention_bwd(
                q, k, v, out, lse, g, block_q=block_q, block_k=block_k,
                dkdv_block_q=dkdv_q, dkdv_block_k=dkdv_k,
            )
            rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(dq)), np.asarray(jax.device_get(rq)), atol=1e-3
        )
        np.testing.assert_allclose(
            np.asarray(jax.device_get(dk)), np.asarray(jax.device_get(rk)), atol=1e-3
        )
        np.testing.assert_allclose(
            np.asarray(jax.device_get(dv)), np.asarray(jax.device_get(rv)), atol=1e-3
        )

    def test_fused_bwd_bf16_mha(self):
        """The default training dtype: bf16 MHA backward must lower (the
        group==1 output refs keep the narrow dtype — a f32 store into a
        bf16 ref is a Mosaic error) and agree loosely with dense grads."""
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, k, v = _qkv(t=256, dtype=jnp.bfloat16, seed=8)
        g = jax.random.normal(jax.random.key(9), q.shape, jnp.bfloat16)
        out, lse = pallas_flash_attention_fwd(q, k, v)
        dq, dk, dv = pallas_flash_attention_bwd(q, k, v, out, lse, g)
        assert dk.dtype == jnp.bfloat16 and dv.dtype == jnp.bfloat16
        qf, kf, vf, gf = (x.astype(jnp.float32) for x in (q, k, v, g))

        def loss(q, k, v):
            return jnp.sum(dense_attention(q, k, v, attention_mask=None) * gf)

        rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(qf, kf, vf)
        for got, want in ((dq, rq), (dk, rk), (dv, rv)):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(got), np.float32),
                np.asarray(jax.device_get(want)),
                atol=0.1, rtol=0.1,
            )

    @pytest.mark.parametrize("d,hkv", [(64, 4), (128, 2)], ids=["head64", "head128-gqa"])
    def test_bf16_grads_with_the_picked_tiles_match_dense(self, d, hkv):
        """The train cell's dtype through the dispatch, so with the tiles
        ``_auto_block`` picks at T 1,024: bf16 operands straight to the MXU,
        p and dS rounded to bf16 once, against the dense gradient on the SAME
        bf16 inputs."""
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.flash_attention import flash_attention

        b, t, h = 2, 1024, 4
        ks = jax.random.split(jax.random.key(21), 4)
        q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.bfloat16)
        g = jax.random.normal(ks[3], (b, t, h, d), jnp.bfloat16)

        def dense(q, k, v):
            wide = lambda x: jnp.repeat(x, h // hkv, axis=2)  # noqa: E731
            return dense_attention(q, wide(k), wide(v), attention_mask=None)

        out, vjp = jax.vjp(flash_attention, q, k, v)
        ref, ref_vjp = jax.vjp(dense, q, k, v)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(out), np.float32),
            np.asarray(jax.device_get(ref), np.float32), atol=2e-2,
        )
        for got, want in zip(vjp(g), ref_vjp(g)):
            assert got.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(jax.device_get(got), np.float32),
                np.asarray(jax.device_get(want), np.float32),
                atol=0.1, rtol=0.1,
            )

    def test_custom_vjp_dispatch_uses_pallas_bwd(self):
        """flash_attention's grad on TPU goes through the fused kernels and
        agrees with the gradient of the XLA blockwise twin."""
        from llmtrain_tpu.ops.blockwise_attention import blockwise_attention
        from llmtrain_tpu.ops.flash_attention import flash_attention

        q, k, v = _qkv(t=256, dtype=jnp.float32, seed=4)

        def loss(q):
            return flash_attention(q, k, v).sum()

        def loss_blockwise(q):
            return blockwise_attention(q, k, v, causal=True).sum()

        with jax.default_matmul_precision("highest"):
            g_fused = jax.device_get(jax.grad(loss)(q))
            g_recompute = jax.device_get(jax.grad(loss_blockwise)(q))
        np.testing.assert_allclose(
            np.asarray(g_fused), np.asarray(g_recompute), atol=1e-3
        )


class TestCompiledHandOffs:
    """The three hand-offs between the projections and the kernels, compiled
    and run through the dispatch (so with the tiles ``_auto_block`` picks):
    ``(B, T, H*D)`` arrays indexed in place, two 64-wide heads a lane block
    or one head of 128; the fused qkv projection output read as three column
    ranges with ONE gradient array; and the folded ``(B*H, T, D)`` arrays a
    64-wide head under GQA keeps. Output and gradients against the dense
    reference on the same inputs, two runs bit for bit the same, and the
    fused array's numbers bit for bit those of its slices."""

    T = 512
    CASES = {
        # name: (h, hkv, d, window, mask: None / "pad" / "segments", heads a lane block)
        "d64-mha": (4, 4, 64, 0, None, 2),
        "d128-gqa": (4, 2, 128, 0, None, 1),
        "d64-mask": (2, 2, 64, 0, "pad", 2),
        "d64-segments": (2, 2, 64, 0, "segments", 2),
        "d64-window": (2, 2, 64, 200, None, 2),
        "d128-mqa-window-segments": (2, 1, 128, 200, "segments", 1),
        "d64-gqa-folded": (4, 2, 64, 0, None, None),
    }

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("case", list(CASES))
    def test_matches_dense_and_repeats_bit_for_bit(self, case, dtype):
        from llmtrain_tpu.models.gpt import dense_attention
        from llmtrain_tpu.ops.flash_attention import flash_attention, flash_attention_qkv
        from llmtrain_tpu.ops.pallas_attention import lane_block_heads

        h, hkv, d, window, masking, heads_a_block = self.CASES[case]
        assert lane_block_heads(h, hkv, d) == heads_a_block
        dtype = jnp.dtype(dtype)
        b, t = 2, self.T
        ks = jax.random.split(jax.random.key(31), 4)
        q = jax.random.normal(ks[0], (b, t, h, d), dtype)
        k = jax.random.normal(ks[1], (b, t, hkv, d), dtype)
        v = jax.random.normal(ks[2], (b, t, hkv, d), dtype)
        g = jax.random.normal(ks[3], (b, t, h, d), dtype)
        mask = None
        if masking == "pad":
            mask = jnp.asarray(np.arange(t)[None, :] < np.array([[t], [t - 150]]), jnp.int32)
        elif masking == "segments":
            seg = np.zeros((b, t), np.int32)
            seg[:, :170], seg[:, 170:t - 70] = 1, 2
            seg[0, :] = 1
            mask = jnp.asarray(seg)
        if mask is not None:
            g = g * (mask != 0)[:, :, None, None].astype(dtype)

        def flash(q, k, v):
            return flash_attention(q, k, v, attention_mask=mask, window=window)

        def dense(q, k, v):
            wide = lambda x: jnp.repeat(x, h // hkv, axis=2)  # noqa: E731
            return dense_attention(q, wide(k), wide(v), attention_mask=mask, window=window)

        f32 = lambda x: np.asarray(jax.device_get(x), np.float32)  # noqa: E731
        # Full float32 MXU passes for float32 operands on both sides (the
        # chip's default multiplies in bf16); bf16 operands go as they are.
        precision = "highest" if dtype == jnp.float32 else None
        with jax.default_matmul_precision(precision):
            out, vjp = jax.vjp(flash, q, k, v)
            grads = vjp(g)
            again, vjp2 = jax.vjp(flash, q, k, v)
            grads2 = vjp2(g)
            ref, ref_vjp = jax.vjp(dense, q, k, v)
            want = ref_vjp(g)
        live = 1.0 if mask is None else f32(mask != 0)[:, :, None, None]
        if dtype == jnp.float32:
            fwd_tol, grad_tol = dict(atol=1e-4), dict(atol=1e-3)
        else:
            fwd_tol, grad_tol = dict(atol=2e-2), dict(atol=0.1, rtol=0.1)
        np.testing.assert_allclose(f32(out) * live, f32(ref) * live, **fwd_tol)
        np.testing.assert_array_equal(f32(out), f32(again))
        for got, twice, ref_g in zip(grads, grads2, want):
            assert got.dtype == dtype
            np.testing.assert_allclose(f32(got), f32(ref_g), **grad_tol)
            np.testing.assert_array_equal(f32(got), f32(twice))
        if h != hkv:
            return
        qkv = jnp.stack([q, k, v], axis=2)
        with jax.default_matmul_precision(precision):
            fused, fused_vjp = jax.vjp(
                lambda x: flash_attention_qkv(x, attention_mask=mask, window=window), qkv
            )
            (dqkv,) = fused_vjp(g)
        assert dqkv.shape == qkv.shape and dqkv.dtype == dtype
        np.testing.assert_array_equal(f32(fused), f32(out))
        for i, apart in enumerate(grads):
            np.testing.assert_array_equal(f32(dqkv[:, :, i]), f32(apart))


class TestCompiledTrainStep:
    def test_gpt_flash_train_step_runs(self):
        """One real optimizer step of the flagship GPT with attention=flash,
        compiled on the chip."""
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.models.gpt import GPTAdapter
        from llmtrain_tpu.training.optimizer import build_optimizer
        from llmtrain_tpu.training.train_step import create_train_state, make_train_step

        cfg = RunConfig.model_validate(
            {
                "run": {"name": "tpu-smoke", "device": "tpu"},
                "model": {
                    "name": "gpt",
                    "block_size": 256,
                    "d_model": 128,
                    "n_layers": 2,
                    "n_heads": 4,
                    "d_ff": 512,
                    "dropout": 0.0,
                    "vocab_size": 1024,
                    "dtype": "bfloat16",
                    "attention": "flash",
                },
                "data": {"name": "dummy_text"},
                "trainer": {"micro_batch_size": 4, "grad_accum_steps": 1, "warmup_steps": 0},
            }
        )
        adapter = GPTAdapter()
        model = adapter.build_model(cfg)
        tx = build_optimizer(cfg.trainer)
        rng = jax.random.key(0)
        params = adapter.init_params(model, cfg, rng)
        state = create_train_state(params, tx)
        step_fn = jax.jit(
            make_train_step(adapter, model, tx, grad_accum_steps=1, use_dropout=False)
        )
        tokens = np.random.default_rng(0).integers(0, 1024, size=(1, 4, 256), dtype=np.int32)
        batch = {
            "input_ids": jnp.asarray(tokens),
            "labels": jnp.asarray(tokens),
            "attention_mask": jnp.ones_like(jnp.asarray(tokens)),
        }
        state, metrics = step_fn(state, batch, rng)
        loss = float(jax.device_get(metrics["loss"]))
        assert np.isfinite(loss) and loss > 0


class TestCompiledChunkedCE:
    """ops/chunked_ce.py lowered for real: the scan + custom_vjp must
    compile on the chip and agree with the dense CE at bf16 tolerance."""

    def test_value_and_grads_match_dense(self):
        from llmtrain_tpu.ops.chunked_ce import chunked_ce_components

        b, t, d, v = 4, 256, 128, 50257
        k1, k2 = jax.random.split(jax.random.key(5))
        hidden = jax.random.normal(k1, (b, t, d), jnp.bfloat16)
        w = (jax.random.normal(k2, (v, d), jnp.float32) * 0.02).astype(jnp.float32)
        labels = jax.random.randint(jax.random.key(6), (b, t), 0, v)
        mask = jnp.ones((b, t), jnp.float32)

        def loss_chunked(h, w_):
            s, tok = chunked_ce_components(h, w_, labels, mask, chunk=8192)
            return jnp.sum(s) / jnp.sum(tok)

        def loss_dense(h, w_):
            logits = jnp.einsum("btd,vd->btv", h, w_.astype(h.dtype))
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            per = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            return jnp.mean(per)

        lc, (gch, gcw) = jax.jit(jax.value_and_grad(loss_chunked, argnums=(0, 1)))(
            hidden, w
        )
        ld, (gdh, gdw) = jax.jit(jax.value_and_grad(loss_dense, argnums=(0, 1)))(
            hidden, w
        )
        assert abs(float(lc) - float(ld)) < 5e-2
        np.testing.assert_allclose(
            np.asarray(jax.device_get(gch), np.float32),
            np.asarray(jax.device_get(gdh), np.float32),
            atol=5e-2,
        )
        np.testing.assert_allclose(
            np.asarray(jax.device_get(gcw)),
            np.asarray(jax.device_get(gdw)),
            atol=5e-2,
        )

    def test_train_step_with_chunked_ce(self):
        """One compiled optimizer step of GPT with loss_impl=chunked_ce at
        the real GPT-2 vocab."""
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.models.gpt import GPTAdapter
        from llmtrain_tpu.training.optimizer import build_optimizer
        from llmtrain_tpu.training.train_step import create_train_state, make_train_step

        cfg = RunConfig.model_validate(
            {
                "run": {"name": "tpu-cce", "device": "tpu"},
                "model": {
                    "name": "gpt",
                    "block_size": 256,
                    "d_model": 128,
                    "n_layers": 2,
                    "n_heads": 4,
                    "d_ff": 512,
                    "dropout": 0.0,
                    "vocab_size": 50257,
                    "dtype": "bfloat16",
                    "attention": "flash",
                    "extra": {"loss_impl": "chunked_ce"},
                },
                "data": {"name": "dummy_text"},
                "trainer": {"micro_batch_size": 4, "grad_accum_steps": 1, "warmup_steps": 0},
            }
        )
        adapter = GPTAdapter()
        model = adapter.build_model(cfg)
        tx = build_optimizer(cfg.trainer)
        rng = jax.random.key(0)
        params = adapter.init_params(model, cfg, rng)
        state = create_train_state(params, tx)
        step_fn = jax.jit(
            make_train_step(adapter, model, tx, grad_accum_steps=1, use_dropout=False)
        )
        tokens = np.random.default_rng(0).integers(
            0, 50257, size=(1, 4, 256), dtype=np.int32
        )
        batch = {
            "input_ids": jnp.asarray(tokens),
            "labels": jnp.asarray(tokens),
            "attention_mask": jnp.ones_like(jnp.asarray(tokens)),
        }
        state, metrics = step_fn(state, batch, rng)
        loss = float(jax.device_get(metrics["loss"]))
        assert np.isfinite(loss) and loss > 0


class TestCompiledFusedCE:
    """ops/fused_ce.py on the chip, against a float32 dense loss: the
    backward accumulates dW in HBM through an aliased input and output, and
    only the chip prefetches a step's inputs while earlier outputs are
    still being written (the interpreter runs grid steps in turn)."""

    @pytest.mark.parametrize(
        "b, vocab, block_t, block_v",
        [
            (4, 50257, None, None),  # the parity shape of PERF.md (PR 41): 2 x 99 blocks
            (1, 50257, None, None),  # ONE token block: every dW block visited once
            (4, 300, 512, None),  # ONE vocabulary block: dW a revisited accumulator
            (4, 2000, 512, 512),  # 8 x 4: the fewest blocks between two visits
        ],
        ids=["4x1024x50257", "one-token-block", "one-vocab-block", "8x4-blocks"],
    )
    def test_loss_and_grads_match_float32_dense_and_repeat_bit_equal(
        self, b, vocab, block_t, block_v
    ):
        from llmtrain_tpu.ops.fused_ce import fused_ce_per_token

        t, d = 1024, 768
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        h = jax.random.normal(ks[0], (b, t, d), jnp.float32).astype(jnp.bfloat16)
        w = (jax.random.normal(ks[1], (vocab, d), jnp.float32) * 0.05).astype(jnp.bfloat16)
        labels = jax.random.randint(ks[2], (b, t), 0, vocab)
        g = jax.random.uniform(ks[3], (b, t), jnp.float32, 0.5, 1.5) / (b * t)

        def dense(h, w):
            logits = jnp.einsum("btd,vd->btv", h, w, precision="highest")
            lse = jax.nn.logsumexp(logits, axis=-1)
            return lse - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]

        def grads(per_token):
            def run(h, w):
                loss, vjp = jax.vjp(per_token, h, w)
                return loss, vjp(g)

            return jax.jit(run)

        fused = grads(lambda h, w: fused_ce_per_token(h, w, labels, block_t, block_v))
        loss, (dh, dw) = fused(h, w)
        again = fused(h, w)
        ref_loss, (ref_dh, ref_dw) = grads(dense)(
            h.astype(jnp.float32), w.astype(jnp.float32)
        )

        def gap(a, ref):
            return float(jnp.linalg.norm(a.astype(jnp.float32) - ref) / jnp.linalg.norm(ref))

        gaps = {"loss": gap(loss, ref_loss), "dh": gap(dh, ref_dh), "dw": gap(dw, ref_dw)}
        print(f"fused_ce gaps against float32 dense, b={b} V={vocab}: {gaps}")
        for out in (loss, dh, dw):
            assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
        for a, b_ in zip(jax.tree.leaves((loss, (dh, dw))), jax.tree.leaves(again)):
            assert bool((a == b_).all()), "two runs of one program differ"
        # The two-kernel backward before PR 41 read 1.3e-7 / 2.37e-3 / 2.37e-3
        # at the first shape (bf16 operands against float32: the operands'
        # own rounding); a dW block read back stale is off by a whole
        # token block's share.
        assert gaps["loss"] < 2e-7
        assert gaps["dh"] < 2.5e-3 and gaps["dw"] < 2.5e-3


class TestCompiledRound5Serving:
    """Round-5 serving features lowered for real: int8 weights via the
    __jax_array__ dequant, the int8 KV cache, and the qwen2/gemma family
    deltas — all CPU-validated (tests/test_quant.py, test_qwen2.py,
    test_gemma.py); these pin the on-chip compiles."""

    def _tiny(self, name="gpt", **extra):
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.models.lora import build_adapter
        from llmtrain_tpu.registry import initialize_registries

        initialize_registries()

        cfg = RunConfig.model_validate(
            {
                "run": {"name": f"tpu-{name}", "device": "tpu"},
                "model": {
                    "name": name,
                    "block_size": 128,
                    "d_model": 128,
                    "n_layers": 2,
                    "n_heads": 4,
                    "d_ff": 256,
                    "dropout": 0.0,
                    "vocab_size": 1024,
                    "dtype": "bfloat16",
                    "extra": {"tokenizer": "byte", **extra},
                },
                "data": {"name": "dummy_text"},
                "trainer": {"micro_batch_size": 2, "grad_accum_steps": 1,
                            "warmup_steps": 0},
            }
        )
        adapter = build_adapter(cfg)
        model = adapter.build_model(cfg)
        params = adapter.init_params(model, cfg, jax.random.key(0))
        from flax.core import meta as nn_meta

        return model, nn_meta.unbox(params)

    def test_int8_weights_compile_and_track_full(self):
        from llmtrain_tpu.ops.quant import quantize_tree

        model, params = self._tiny()
        ids = jnp.asarray(
            np.random.default_rng(1).integers(0, 1024, (2, 64), np.int32)
        )
        f = jax.jit(lambda p, i: model.apply({"params": p}, i, deterministic=True))
        full = jax.device_get(f(params, ids))
        quant = jax.device_get(f(quantize_tree(params), ids))
        a = np.asarray(full, np.float64).reshape(-1)
        b = np.asarray(quant, np.float64).reshape(-1)
        cos = (a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cos > 0.99

    def test_int8_kv_cache_decode_compiles(self):
        from llmtrain_tpu.generation import generate

        model, params = self._tiny(kv_cache_dtype="int8")
        out = generate(
            model, params, np.asarray([[1, 2, 3]], np.int32),
            max_new_tokens=8, temperature=0.0, use_cache=True,
        )
        arr = np.asarray(out)
        assert arr.shape == (1, 11) and ((arr >= 0) & (arr < 1024)).all()

    @pytest.mark.parametrize("family", ["qwen2", "gemma"])
    def test_new_family_forward_compiles(self, family):
        model, params = self._tiny(name=family, n_kv_heads=2)
        ids = jnp.asarray(
            np.random.default_rng(2).integers(0, 1024, (2, 64), np.int32)
        )
        logits = jax.device_get(
            jax.jit(
                lambda p, i: model.apply({"params": p}, i, deterministic=True)
            )(params, ids)
        )
        assert np.isfinite(np.asarray(logits, np.float32)).all()


class TestCompiledWindowRing:
    """Decode through the window layers' ring at the geometry a served cell
    runs (a window of 4,096 over blocks of 16: a ring of 257 entries a row,
    128-wide heads), on the chip: the benchmark's own ``correct`` cannot see
    it (PERF.md section 6, PR 45: greedy tokens of random weights repeat with
    a wide margin, and a rotated ring table passes its limits). Narrow widths,
    float32 with full-precision products, queries and keys scaled up so that
    attention is peaked and WHICH keys a row reads matters. Dense masked
    scores on both sides: the Pallas forward's window is held by
    TestCompiledSlidingWindow, and in float32 at 512 lanes a row its tiles
    pass the scoped VMEM limit (17.59 MB of 16) at a slab of 4,096."""

    WINDOW, BLOCK, STEPS = 4096, 16, 40
    DEPTHS = ((100, 1536), (4090, 4096), (6000, 6144))  # (true prompt, its bucket): under, crossing, past the window

    def _model(self, attention):
        from llmtrain_tpu.models.windowed_moe import WindowedMoE

        return WindowedMoE(
            vocab_size=512, block_size=8192, d_model=256, n_layers=2, n_heads=4, num_key_value_heads=2, head_dim=128,
            intermediate_size=128, num_experts=4, num_experts_per_tok=2, num_shared_experts=1,
            sliding_window=self.WINDOW, layer_types=("sliding_attention", "full_attention"), rope_theta=50000.0,
            attention=attention,
        )

    def test_decode_at_three_depths_matches_the_full_forward_and_a_rotated_ring_does_not(self):
        from flax.core import meta as nn_meta

        from llmtrain_tpu.serving.paged_kv import window_ring_blocks

        dense = served = self._model("dense")
        total = self.DEPTHS[-1][1]
        ids = np.random.default_rng(45).integers(0, 512, (3, total)).astype(np.int32)
        params = nn_meta.unbox(dense.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
        for block in ("block_0", "block_1"):  # scores of a few units: a softmax that picks keys
            for name in ("q_proj", "k_proj"):
                params[block]["attn"][name]["kernel"] = params[block]["attn"][name]["kernel"] * 5.5
        bt, mb, ring = self.BLOCK, 8192 // self.BLOCK, window_ring_blocks(self.WINDOW, self.BLOCK)
        assert ring == 257
        paged = served.for_paged_decoding(num_blocks=1 + 3 * mb, block_tokens=bt, window_num_blocks=1 + 3 * ring)
        tables = jnp.asarray(1 + np.arange(3 * mb).reshape(3, mb), jnp.int32)
        rings = jnp.asarray(1 + np.arange(3 * ring).reshape(3, ring), jnp.int32)
        with jax.default_matmul_precision("highest"):
            full = jax.jit(lambda i: dense.apply({"params": params}, i, deterministic=True))
            want = np.stack([np.asarray(full(jnp.asarray(ids[r : r + 1])))[0] for r in range(3)])  # (3, total, vocab)
            tol = 2e-4 * float(np.abs(want).max())
            call = jax.jit(lambda c, tok, pos, table, ring_, n: paged.apply(
                {"params": params, "cache": c}, tok, positions=pos, block_tables=table, window_tables=ring_,
                true_len=n, mutable=["cache"]))
            shapes = jax.eval_shape(lambda: paged.init(
                jax.random.key(0), jnp.zeros((1, 1), jnp.int32), positions=jnp.zeros((1,), jnp.int32),
                block_tables=tables[:1], window_tables=rings[:1]))["cache"]
            cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
            assert cache["block_0"]["attn"]["window_key"].shape[0] == 1 + 3 * ring
            for r, (n, bucket) in enumerate(self.DEPTHS):  # each prompt alone, padded to its bucket
                slab = np.zeros((1, bucket), np.int32)
                slab[0, :n] = ids[r, :n]
                logits, mutated = call(cache, jnp.asarray(slab), jnp.zeros((1,), jnp.int32), tables[r : r + 1],
                                       rings[r : r + 1], jnp.asarray([n], jnp.int32))
                cache = mutated["cache"]
                assert np.abs(np.asarray(logits)[0, 0] - want[r, n - 1]).max() <= tol, (r, "prefill")

            def decode(cache, rings_):
                worst = 0.0
                for step in range(self.STEPS):  # row 1 leaves the window at 4,096 and wraps its ring at 4,112
                    pos = jnp.asarray([n + step for n, _ in self.DEPTHS], jnp.int32)
                    tok = jnp.asarray([[ids[r, n + step]] for r, (n, _) in enumerate(self.DEPTHS)], jnp.int32)
                    logits, mutated = call(cache, tok, pos, tables, rings_, None)
                    cache = mutated["cache"]
                    got = np.asarray(logits)[:, 0]
                    worst = max(worst, max(
                        float(np.abs(got[r] - want[r, n + step]).max()) for r, (n, _) in enumerate(self.DEPTHS)))
                return worst

            sound, rotated = decode(cache, rings), decode(cache, jnp.roll(rings, 1, axis=1))
        print(f"window ring on {jax.default_backend()}: sound {sound:.3g}, rotated {rotated:.3g}, tolerance {tol:.3g}")
        assert sound <= tol, (sound, tol)
        assert rotated > 20 * tol, (rotated, tol)  # the comparison sees a ring read one entry off

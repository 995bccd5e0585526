"""Attention ops: blockwise + pallas (interpret mode) vs dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmtrain_tpu.models.gpt import GPT, dense_attention
from llmtrain_tpu.ops.blockwise_attention import blockwise_attention
from llmtrain_tpu.ops.flash_attention import flash_attention
from llmtrain_tpu.ops.pallas_attention import pallas_flash_attention


def _qkv(b=2, t=32, h=2, d=8, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype=jnp.float32) for k in keys)


def _dense_ref(q, k, v, causal=True):
    return dense_attention(q, k, v, attention_mask=None)


class TestBlockwise:
    def test_matches_dense(self):
        q, k, v = _qkv()
        out = blockwise_attention(q, k, v, causal=True, q_chunk=8, kv_chunk=8)
        ref = _dense_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_single_chunk_matches(self):
        q, k, v = _qkv(t=16)
        out = blockwise_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
        ref = _dense_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_non_causal(self):
        q, k, v = _qkv(t=16)
        out = blockwise_attention(q, k, v, causal=False, q_chunk=4, kv_chunk=4)
        import math

        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        probs = jax.nn.softmax(scores, axis=-1)
        ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_gradients_match_dense(self):
        q, k, v = _qkv(t=16)

        def loss_block(q, k, v):
            return blockwise_attention(q, k, v, causal=True, q_chunk=4, kv_chunk=4).sum()

        def loss_dense(q, k, v):
            return _dense_ref(q, k, v).sum()

        g_block = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for gb, gd in zip(g_block, g_dense):
            np.testing.assert_allclose(np.asarray(gb), np.asarray(gd), atol=1e-4)

    def test_kv_offset_for_ring(self):
        """Chunked causal mask with offsets == global causal attention."""
        q, k, v = _qkv(t=16)
        full = blockwise_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
        # Query block [8:16] attending to keys [0:16] with the right offsets.
        out = blockwise_attention(
            q[:, 8:], k, v, causal=True, q_chunk=8, kv_chunk=8, q_offset=8, kv_offset=0
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, 8:]), atol=1e-5)


class TestPallasInterpret:
    def test_matches_dense(self):
        q, k, v = _qkv(t=32)
        out = pallas_flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
        ref = _dense_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_bf16(self):
        q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(t=16))
        out = pallas_flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = _dense_ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32), atol=2e-2
        )

    def test_ragged_seq_raises(self):
        q, k, v = _qkv(t=24)
        with pytest.raises(ValueError, match="divisible"):
            pallas_flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)

    def test_lse_matches_reference(self):
        """Forward's logsumexp residual == logsumexp of scaled masked logits."""
        import math

        from llmtrain_tpu.ops.pallas_attention import pallas_flash_attention_fwd

        q, k, v = _qkv(b=1, t=16, h=1, d=8)
        _, lse = pallas_flash_attention_fwd(q, k, v, block_q=8, block_k=8, interpret=True)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.tril(jnp.ones((16, 16), bool))
        s = jnp.where(mask, s, -jnp.inf)
        ref = jax.scipy.special.logsumexp(s, axis=-1).reshape(1, 16)  # b*h=1
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize(
        "block_q,block_k",
        [
            pytest.param(8, 8, marks=pytest.mark.slow),
            (8, 16),
            pytest.param(16, 8, marks=pytest.mark.slow),
            (32, 32),
        ],
    )
    def test_fused_backward_matches_dense_grads(self, block_q, block_k):
        """The Pallas dq/dk/dv kernels against jax.grad of the dense
        reference, over a block-shape sweep (VERDICT r1 #4)."""
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, k, v = _qkv(b=2, t=32, h=2, d=8, seed=3)
        g = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)

        out, lse = pallas_flash_attention_fwd(
            q, k, v, block_q=block_q, block_k=block_k, interpret=True
        )
        dq, dk, dv = pallas_flash_attention_bwd(
            q, k, v, out, lse, g, block_q=block_q, block_k=block_k, interpret=True
        )

        def loss(q, k, v):
            return jnp.sum(_dense_ref(q, k, v) * g)

        rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=1e-4)

    def test_fused_backward_bf16_mha(self):
        """bf16 MHA backward — the default training dtype on TPU. Guards
        the group==1 narrow-dtype output store (a float32 value stored
        into a bfloat16 ref raises in Pallas); grads are checked at bf16
        tolerance against the dense reference."""
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(b=2, t=32, h=2, d=8, seed=4))
        g = jax.random.normal(jax.random.key(10), q.shape, jnp.bfloat16)

        out, lse = pallas_flash_attention_fwd(q, k, v, block_q=8, block_k=8, interpret=True)
        dq, dk, dv = pallas_flash_attention_bwd(
            q, k, v, out, lse, g, block_q=8, block_k=8, interpret=True
        )
        assert dk.dtype == jnp.bfloat16 and dv.dtype == jnp.bfloat16

        qf, kf, vf, gf = (x.astype(jnp.float32) for x in (q, k, v, g))

        def loss(q, k, v):
            return jnp.sum(_dense_ref(q, k, v) * gf)

        rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(qf, kf, vf)
        for got, want in ((dq, rq), (dk, rk), (dv, rv)):
            np.testing.assert_allclose(
                np.asarray(got, dtype=np.float32), np.asarray(want), atol=0.1, rtol=0.1
            )


class TestTiledKernels:
    """The rewritten loops: unequal tiles in each of the three kernels, a
    sequence with interior, diagonal and strip-skipped blocks all present
    (T = 4 x the tile, the diagonal walked in strips a quarter of it), bf16
    operands handed to the MXU as they are, and GQA / window / segment mask
    across block and strip edges, all against ``dense_attention`` on the
    SAME inputs (output and all three gradients)."""

    T = 128

    @pytest.fixture
    def strip8(self, monkeypatch):
        """Walk the diagonal in 8-wide strips (the chip's are 128 wide), so
        a 32-wide tile has dead sub-tiles to skip. The wrappers are jitted:
        the module constant is read at trace time."""
        from llmtrain_tpu.ops import pallas_attention

        monkeypatch.setattr(pallas_attention, "_FWD_STRIP", 8)
        monkeypatch.setattr(pallas_attention, "_BWD_STRIP", 8)
        jax.clear_caches()
        yield
        jax.clear_caches()

    @staticmethod
    def _segments(b, t):
        """Two documents meeting inside a block (and a strip), then padding."""
        seg = np.zeros((b, t), np.int32)
        seg[:, :45] = 1
        seg[:, 45:t - 19] = 2
        seg[0, :] = 1  # one fully packed row
        return jnp.asarray(seg)

    CASES = {
        # name: (dtype, d, h, hkv, window, masked, fwd, dq, dkdv) tiles (block_q, block_k)
        "f32-square": ("float32", 8, 2, 2, 0, False, (32, 32), (32, 32), (32, 32)),
        "f32-fwd-q64-k16": ("float32", 8, 2, 2, 0, False, (64, 16), (32, 32), (32, 32)),
        "f32-fwd-q16-k64": ("float32", 8, 2, 2, 0, False, (16, 64), (32, 32), (32, 32)),
        "f32-dq-q64-k16": ("float32", 8, 2, 2, 0, False, (32, 32), (64, 16), (32, 32)),
        "f32-dq-q16-k32": ("float32", 8, 2, 2, 0, False, (32, 32), (16, 32), (32, 32)),
        "f32-dkdv-q16-k64": ("float32", 8, 2, 2, 0, False, (32, 32), (32, 32), (16, 64)),
        "f32-dkdv-q32-k16": ("float32", 8, 2, 2, 0, False, (32, 32), (32, 32), (32, 16)),
        "bf16-scale-on-scores": ("bfloat16", 8, 2, 2, 0, False, (32, 32), (32, 16), (16, 32)),
        "bf16-scale-folded": ("bfloat16", 16, 2, 2, 0, False, (32, 32), (32, 16), (16, 32)),
        "f32-gqa": ("float32", 8, 4, 2, 0, False, (32, 16), (32, 16), (16, 32)),
        "f32-mqa-window": ("float32", 8, 2, 1, 40, False, (32, 16), (32, 16), (16, 32)),
        "f32-window-in-strip": ("float32", 8, 2, 2, 5, False, (32, 32), (32, 32), (32, 32)),
        "f32-window-unequal": ("float32", 8, 2, 2, 27, False, (16, 32), (16, 32), (32, 16)),
        "f32-segments": ("float32", 8, 2, 2, 0, True, (32, 16), (32, 16), (16, 32)),
        "bf16-gqa-window-segments": ("bfloat16", 16, 4, 2, 40, True, (32, 16), (32, 16), (16, 32)),
        # Shapes with whole lane blocks: the kernels index (B, T, H*D) in
        # place, two 64-wide heads a block or one head of a multiple of 128.
        "f32-rows-d64-mha": ("float32", 64, 4, 4, 0, False, (32, 16), (32, 16), (16, 32)),
        "bf16-rows-d64-mha": ("bfloat16", 64, 2, 2, 0, False, (32, 32), (32, 16), (16, 32)),
        "f32-rows-d128-gqa": ("float32", 128, 4, 2, 0, False, (32, 16), (32, 16), (16, 32)),
        "f32-rows-d64-mask": ("float32", 64, 2, 2, 0, "pad", (32, 16), (32, 16), (16, 32)),
        "f32-rows-d64-segments": ("float32", 64, 2, 2, 0, True, (32, 16), (16, 32), (32, 16)),
        "f32-rows-d64-window": ("float32", 64, 2, 2, 27, False, (16, 32), (16, 32), (32, 16)),
        "bf16-rows-d128-mqa-window-segments": (
            "bfloat16", 128, 2, 1, 40, True, (32, 16), (32, 16), (16, 32)),
        # ...and 64-wide shapes without them, which keep the folded arrays.
        "f32-folded-d64-gqa": ("float32", 64, 4, 2, 0, False, (32, 16), (32, 16), (16, 32)),
        "f32-folded-d64-odd-heads": ("float32", 64, 3, 3, 0, True, (32, 16), (32, 16), (16, 32)),
    }
    # Heads a lane block holds in the cases that have lane blocks.
    ROWS = {
        "f32-rows-d64-mha": 2, "bf16-rows-d64-mha": 2, "f32-rows-d128-gqa": 1,
        "f32-rows-d64-mask": 2, "f32-rows-d64-segments": 2, "f32-rows-d64-window": 2,
        "bf16-rows-d128-mqa-window-segments": 1,
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_matches_dense(self, strip8, case):
        from llmtrain_tpu.ops.pallas_attention import (
            lane_block_heads,
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
            pallas_flash_attention_qkv_bwd,
            pallas_flash_attention_qkv_fwd,
        )

        dtype, d, h, hkv, window, masked, fwd, dq_t, dkdv = self.CASES[case]
        assert lane_block_heads(h, hkv, d) == self.ROWS.get(case)
        dtype = jnp.dtype(dtype)
        b, t = 2, self.T
        keys = jax.random.split(jax.random.key(17), 4)
        q = jax.random.normal(keys[0], (b, t, h, d), dtype)
        k = jax.random.normal(keys[1], (b, t, hkv, d), dtype)
        v = jax.random.normal(keys[2], (b, t, hkv, d), dtype)
        g = jax.random.normal(keys[3], (b, t, h, d), dtype)
        mask = self._segments(b, t) if masked else None
        if masked == "pad":  # a plain key-padding mask: one document, then padding
            mask = jnp.asarray(np.arange(t)[None, :] < np.array([[t], [t - 37]]), jnp.int32)
        if masked:  # the model zeroes padded rows' output, so their cotangent
            g = g * (mask != 0)[:, :, None, None].astype(dtype)

        out, lse = pallas_flash_attention_fwd(
            q, k, v, mask, block_q=fwd[0], block_k=fwd[1], window=window,
            interpret=True,
        )
        dq, dk, dv = pallas_flash_attention_bwd(
            q, k, v, out, lse, g, mask, block_q=dq_t[0], block_k=dq_t[1],
            dkdv_block_q=dkdv[0], dkdv_block_k=dkdv[1], window=window,
            interpret=True,
        )
        assert out.dtype == dq.dtype == dtype and dk.dtype == dv.dtype == dtype
        assert lse.shape == (b * h, t) and lse.dtype == jnp.float32

        def dense(q, k, v):
            wide = lambda x: jnp.repeat(x, h // hkv, axis=2)  # noqa: E731
            return dense_attention(
                q, wide(k), wide(v), attention_mask=mask, window=window
            )

        ref, vjp = jax.vjp(dense, q, k, v)
        rq, rk, rv = vjp(g)
        f32 = lambda x: np.asarray(x, dtype=np.float32)  # noqa: E731
        if dtype == jnp.float32:  # today's tolerances
            fwd_tol, grad_tol = dict(atol=1e-5), dict(atol=1e-4)
        else:  # both sides round p (and here dS) to bf16 once
            fwd_tol, grad_tol = dict(atol=2e-2), dict(atol=0.1, rtol=0.1)
        live = np.ones((b, t, 1, 1), np.float32)
        if masked:
            live = f32(mask != 0)[:, :, None, None]
        np.testing.assert_allclose(f32(out) * live, f32(ref) * live, **fwd_tol)
        np.testing.assert_allclose(f32(dq), f32(rq), **grad_tol)
        np.testing.assert_allclose(f32(dk), f32(rk), **grad_tol)
        np.testing.assert_allclose(f32(dv), f32(rv), **grad_tol)
        if case not in self.ROWS or h != hkv:
            return
        # The same kernels reading q, k and v out of the ONE projection
        # output and writing ONE gradient: the same numbers, bit for bit.
        qkv = jnp.stack([q, k, v], axis=2)
        out2, lse2 = pallas_flash_attention_qkv_fwd(
            qkv, mask, block_q=fwd[0], block_k=fwd[1], window=window, interpret=True,
        )
        dqkv = pallas_flash_attention_qkv_bwd(
            qkv, out2, lse2, g, mask, block_q=dq_t[0], block_k=dq_t[1],
            dkdv_block_q=dkdv[0], dkdv_block_k=dkdv[1], window=window,
            interpret=True,
        )
        assert dqkv.shape == qkv.shape and dqkv.dtype == dtype
        np.testing.assert_array_equal(f32(out2), f32(out))
        np.testing.assert_array_equal(np.asarray(lse2), np.asarray(lse))
        for i, want in enumerate((dq, dk, dv)):
            np.testing.assert_array_equal(f32(dqkv[:, :, i]), f32(want))

    def test_a_fused_array_without_lane_blocks_is_sliced_and_its_gradients_stacked(self):
        from llmtrain_tpu.ops.pallas_attention import (
            lane_block_heads,
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
            pallas_flash_attention_qkv_bwd,
            pallas_flash_attention_qkv_fwd,
        )

        assert lane_block_heads(3, 3, 8) is None
        qkv = jnp.stack(_qkv(b=1, t=16, h=3, d=8, seed=29), axis=2)
        g = jax.random.normal(jax.random.key(30), (1, 16, 3, 8), jnp.float32)
        tiles = dict(block_q=8, block_k=8, interpret=True)
        out, lse = pallas_flash_attention_qkv_fwd(qkv, **tiles)
        dqkv = pallas_flash_attention_qkv_bwd(qkv, out, lse, g, **tiles)
        q, k, v = (qkv[:, :, i] for i in range(3))
        want, want_lse = pallas_flash_attention_fwd(q, k, v, **tiles)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        grads = pallas_flash_attention_bwd(q, k, v, want, want_lse, g, **tiles)
        np.testing.assert_array_equal(np.asarray(dqkv), np.asarray(jnp.stack(grads, axis=2)))

    def test_auto_block_tiles_are_legal_for_the_schedules(self):
        """Whatever ``_auto_block`` picks divides T, and the statistics keep
        a whole lane tile: every width a schedule walks is a multiple of 128."""
        from llmtrain_tpu.ops.flash_attention import _auto_block

        for t in (128, 256, 384, 512, 768, 1024, 1536, 2048, 4096, 8192):
            resident, streamed = _auto_block(t)
            assert t % resident == 0 and resident % streamed == 0, (t, resident, streamed)
            assert streamed % 128 == 0, (t, resident, streamed)
        assert _auto_block(200) is None


class TestFlashDispatch:
    def test_cpu_dispatch_and_grads(self):
        q, k, v = _qkv(t=16)
        out = flash_attention(q, k, v)
        ref = _dense_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        g = jax.grad(lambda q: flash_attention(q, k, v).sum())(q)
        g_ref = jax.grad(lambda q: _dense_ref(q, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)

    @pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
    @pytest.mark.parametrize("window", [0, 5], ids=["full", "window"])
    def test_the_fused_projection_output_gives_what_its_slices_give(self, masked, window):
        """``flash_attention_qkv`` on the (B, T, 3, H, D) array against
        ``flash_attention`` on its three slices: output and the gradient of
        the whole array (off the chip both are the blockwise twin)."""
        from llmtrain_tpu.ops.flash_attention import flash_attention_qkv

        qkv = jnp.stack(_qkv(t=16, seed=21), axis=2)
        mask = jnp.asarray([[1] * 16, [1] * 11 + [0] * 5], jnp.int32) if masked else None
        live = 1.0 if mask is None else mask[:, :, None, None].astype(jnp.float32)

        def fused(qkv):
            return flash_attention_qkv(qkv, attention_mask=mask, window=window) * live

        def apart(qkv):
            return flash_attention(
                qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], attention_mask=mask, window=window
            ) * live

        np.testing.assert_allclose(np.asarray(fused(qkv)), np.asarray(apart(qkv)), atol=1e-6)
        got = jax.grad(lambda x: jnp.sum(fused(x) ** 2))(qkv)
        want = jax.grad(lambda x: jnp.sum(apart(x) ** 2))(qkv)
        assert got.shape == qkv.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    @pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
    def test_on_a_mesh_each_device_runs_its_own_batch_rows_and_heads(self, masked):
        """Under ``{data: 2, tensor: 2}`` the call wraps itself in
        ``shard_map`` (batch rows over data, heads over tensor, for q, k, v
        apart and for the fused array's head axis alike) and gives what the
        unsharded call gives, gradients included."""
        from llmtrain_tpu.config.schemas import MeshConfig
        from llmtrain_tpu.distributed import build_mesh
        from llmtrain_tpu.ops.flash_attention import flash_attention_qkv

        mesh = build_mesh(MeshConfig(data=2, tensor=2), jax.devices()[:4])
        qkv = jnp.stack(_qkv(b=4, t=16, h=4, seed=23), axis=2)
        mask = None
        if masked:
            mask = jnp.asarray(np.arange(16)[None, :] < np.array([16, 9, 16, 12])[:, None], jnp.int32)
        live = 1.0 if mask is None else mask[:, :, None, None].astype(jnp.float32)

        def fused(x):
            return jnp.sum((flash_attention_qkv(x, attention_mask=mask) * live) ** 2)

        def apart(x):
            out = flash_attention(x[:, :, 0], x[:, :, 1], x[:, :, 2], attention_mask=mask)
            return jnp.sum((out * live) ** 2)

        want, want_grad = jax.value_and_grad(fused)(qkv)
        for loss in (fused, apart):
            with mesh:
                got, got_grad = jax.jit(jax.value_and_grad(loss))(qkv)
            np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
            np.testing.assert_allclose(np.asarray(got_grad), np.asarray(want_grad), atol=1e-5)

    def test_all_ones_mask_matches_unmasked(self):
        q, k, v = _qkv(t=16)
        out = flash_attention(q, k, v, attention_mask=jnp.ones((2, 16), jnp.int32))
        ref = flash_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def _suffix_mask(b, t, seed=1):
    """Per-row valid prefix lengths in [1, t] — reference padding shape."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, t + 1, size=(b,))
    lens[0] = t  # keep one fully-packed row in the mix
    return jnp.asarray((np.arange(t)[None, :] < lens[:, None]).astype(np.int32))


def _valid(x, mask):
    """Zero padded query rows: comparisons follow the model contract,
    which multiplies attention output by the mask (models/gpt.py)."""
    return np.asarray(x) * np.asarray(mask)[:, :, None, None].astype(np.float32)


class TestMaskedFlash:
    """Key-padding masks applied INSIDE attention (reference gpt.py:60-64),
    on every flash path: Pallas kernels, blockwise fallback, dispatch."""

    def test_pallas_fwd_matches_masked_dense(self):
        q, k, v = _qkv(b=3, t=32, h=2, d=8, seed=5)
        mask = _suffix_mask(3, 32)
        out = pallas_flash_attention(q, k, v, mask, block_q=8, block_k=8, interpret=True)
        ref = dense_attention(q, k, v, attention_mask=mask)
        np.testing.assert_allclose(_valid(out, mask), _valid(ref, mask), atol=1e-5)

    def test_pallas_bwd_matches_masked_dense_grads(self):
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, k, v = _qkv(b=3, t=32, h=2, d=8, seed=7)
        mask = _suffix_mask(3, 32, seed=2)
        # Cotangent zeroed on padded rows — exactly what the model's
        # output-mask multiply feeds back into attention.
        g = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
        g = g * mask[:, :, None, None].astype(jnp.float32)

        out, lse = pallas_flash_attention_fwd(
            q, k, v, mask, block_q=8, block_k=8, interpret=True
        )
        dq, dk, dv = pallas_flash_attention_bwd(
            q, k, v, out, lse, g, mask, block_q=8, block_k=8, interpret=True
        )

        def loss(q, k, v):
            return jnp.sum(dense_attention(q, k, v, attention_mask=mask) * g)

        rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=1e-4)

    def test_blockwise_key_mask_matches_masked_dense(self):
        q, k, v = _qkv(b=3, t=16, h=2, d=8, seed=11)
        mask = _suffix_mask(3, 16, seed=3)
        out = blockwise_attention(q, k, v, causal=True, q_chunk=4, kv_chunk=4, key_mask=mask)
        ref = dense_attention(q, k, v, attention_mask=mask)
        np.testing.assert_allclose(_valid(out, mask), _valid(ref, mask), atol=1e-5)

    def test_dispatch_masked_fwd_and_grads(self):
        """flash_attention(attention_mask=...) on the CPU fallback path."""
        q, k, v = _qkv(b=2, t=16, h=2, d=8, seed=13)
        mask = _suffix_mask(2, 16, seed=4)
        gmask = mask[:, :, None, None].astype(jnp.float32)
        out = flash_attention(q, k, v, attention_mask=mask)
        ref = dense_attention(q, k, v, attention_mask=mask)
        np.testing.assert_allclose(_valid(out, mask), _valid(ref, mask), atol=1e-5)

        g = jax.grad(
            lambda q: (flash_attention(q, k, v, attention_mask=mask) * gmask).sum()
        )(q)
        g_ref = jax.grad(
            lambda q: (dense_attention(q, k, v, attention_mask=mask) * gmask).sum()
        )(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)


class TestSequenceParallelMasks:
    """Padding masks inside ring/Ulysses attention: the mask shard rotates
    with its K/V shard (ring) or is all-gathered after the head exchange
    (ulysses); both equal masked dense on valid rows."""

    def _mesh(self):
        from llmtrain_tpu.config.schemas import MeshConfig
        from llmtrain_tpu.distributed import build_mesh

        return build_mesh(
            MeshConfig(data=2, fsdp=1, tensor=2, sequence=2), jax.devices()[:8]
        )

    @pytest.mark.parametrize("scheme", ["ring", "ulysses"])
    def test_sharded_masked_matches_dense(self, scheme):
        if scheme == "ring":
            from llmtrain_tpu.ops.ring_attention import ring_attention_sharded as fn
        else:
            from llmtrain_tpu.ops.ulysses_attention import (
                ulysses_attention_sharded as fn,
            )

        q, k, v = _qkv(b=4, t=16, h=4, d=8, seed=41)
        mask = _suffix_mask(4, 16, seed=7)
        ref = dense_attention(q, k, v, attention_mask=mask)
        mesh = self._mesh()
        out = jax.jit(
            lambda q, k, v, m: fn(q, k, v, mesh, key_mask=m)
        )(q, k, v, mask)
        np.testing.assert_allclose(_valid(out, mask), _valid(ref, mask), atol=1e-5)

    @pytest.mark.parametrize("scheme", ["ring", "ulysses"])
    def test_sharded_masked_grads_match_dense(self, scheme):
        if scheme == "ring":
            from llmtrain_tpu.ops.ring_attention import ring_attention_sharded as fn
        else:
            from llmtrain_tpu.ops.ulysses_attention import (
                ulysses_attention_sharded as fn,
            )

        q, k, v = _qkv(b=4, t=16, h=4, d=8, seed=43)
        mask = _suffix_mask(4, 16, seed=8)
        gmask = mask[:, :, None, None].astype(jnp.float32)
        mesh = self._mesh()

        g_sp = jax.jit(
            jax.grad(lambda q: (fn(q, k, v, mesh, key_mask=mask) * gmask).sum())
        )(q)
        g_ref = jax.grad(
            lambda q: (dense_attention(q, k, v, attention_mask=mask) * gmask).sum()
        )(q)
        np.testing.assert_allclose(np.asarray(g_sp), np.asarray(g_ref), atol=1e-4)

    @pytest.mark.parametrize("scheme", ["ring", "ulysses"])
    def test_sharded_segment_mask_matches_dense(self, scheme):
        """SEGMENT masks (packed cross-document) ride the SP schemes: the
        query-side segments come from the unrotated local shard (ring) or
        the full replicated mask (ulysses)."""
        if scheme == "ring":
            from llmtrain_tpu.ops.ring_attention import ring_attention_sharded as fn
        else:
            from llmtrain_tpu.ops.ulysses_attention import (
                ulysses_attention_sharded as fn,
            )

        q, k, v = _qkv(b=4, t=16, h=4, d=8, seed=51)
        seg = np.ones((4, 16), np.int32)
        seg[:, 6:13] = 2  # doc boundary NOT on the shard boundary (t/2=8)
        seg[:, 13:] = 0
        seg = jnp.asarray(seg)
        ref = dense_attention(q, k, v, attention_mask=seg)
        mesh = self._mesh()
        out = jax.jit(
            lambda q, k, v, m: fn(q, k, v, mesh, key_mask=m)
        )(q, k, v, seg)
        np.testing.assert_allclose(_valid(out, seg), _valid(ref, seg), atol=1e-5)

    @pytest.mark.parametrize("scheme", ["ring", "ulysses"])
    def test_sharded_segment_grads_match_dense(self, scheme):
        if scheme == "ring":
            from llmtrain_tpu.ops.ring_attention import ring_attention_sharded as fn
        else:
            from llmtrain_tpu.ops.ulysses_attention import (
                ulysses_attention_sharded as fn,
            )

        q, k, v = _qkv(b=4, t=16, h=4, d=8, seed=53)
        seg = np.ones((4, 16), np.int32)
        seg[:, 5:11] = 2
        seg[:, 11:] = 3
        seg = jnp.asarray(seg)
        gmask = (seg != 0)[:, :, None, None].astype(jnp.float32)
        mesh = self._mesh()
        g_sp = jax.jit(
            jax.grad(lambda q: (fn(q, k, v, mesh, key_mask=seg) * gmask).sum())
        )(q)
        g_ref = jax.grad(
            lambda q: (dense_attention(q, k, v, attention_mask=seg) * gmask).sum()
        )(q)
        np.testing.assert_allclose(np.asarray(g_sp), np.asarray(g_ref), atol=1e-4)

    def test_fallback_keeps_segment_semantics(self):
        """No mesh → blockwise fallback: a split_documents segment mask
        must STILL block cross-document attention (degrading to key-only
        padding here silently re-opened the leak the feature closes)."""
        from llmtrain_tpu.ops.ring_attention import ring_or_blockwise
        from llmtrain_tpu.ops.ulysses_attention import ulysses_or_blockwise

        q, k, v = _qkv(b=2, t=16, h=2, d=8, seed=55)
        seg = np.ones((2, 16), np.int32)
        seg[:, 7:] = 2
        seg = jnp.asarray(seg)
        ref = dense_attention(q, k, v, attention_mask=seg)
        for fn in (ring_or_blockwise, ulysses_or_blockwise):
            out = fn(q, k, v, key_mask=seg)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=1e-5
            )

    def test_fallback_masked_matches_dense(self):
        """No mesh: the route-or-fallback path passes the mask to
        blockwise."""
        from llmtrain_tpu.ops.ring_attention import ring_or_blockwise
        from llmtrain_tpu.ops.ulysses_attention import ulysses_or_blockwise

        q, k, v = _qkv(b=2, t=16, h=2, d=8, seed=47)
        mask = _suffix_mask(2, 16, seed=9)
        ref = dense_attention(q, k, v, attention_mask=mask)
        for fn in (ring_or_blockwise, ulysses_or_blockwise):
            out = fn(q, k, v, key_mask=mask)
            np.testing.assert_allclose(
                _valid(out, mask), _valid(ref, mask), atol=1e-5
            )


class TestSlidingWindow:
    """Mistral-style sliding-window masking across the stack: dense
    (full-matrix reference), blockwise (mask-only), Pallas interpret
    (skip-block), and the flash dispatch fallback — all must agree."""

    def _naive_window_ref(self, q, k, v, window):
        import math

        scale = 1.0 / math.sqrt(q.shape[-1])
        t = q.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        pos = jnp.arange(t)
        live = (pos[:, None] >= pos[None, :]) & (
            pos[:, None] - pos[None, :] < window
        )
        s = jnp.where(live[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def test_dense_matches_naive(self):
        q, k, v = _qkv(t=32)
        out = dense_attention(q, k, v, attention_mask=None, window=5)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._naive_window_ref(q, k, v, 5)),
            atol=1e-5,
        )

    @pytest.mark.parametrize(
        "window",
        [
            1,
            pytest.param(7, marks=pytest.mark.slow),
            8,
            pytest.param(13, marks=pytest.mark.slow),
            pytest.param(32, marks=pytest.mark.slow),
            100,
        ],
    )
    def test_blockwise_matches_dense(self, window):
        """Window edges off/on chunk boundaries, window == 1 (self only),
        window >= T (== full causal)."""
        q, k, v = _qkv(t=32, seed=41)
        out = blockwise_attention(
            q, k, v, causal=True, q_chunk=8, kv_chunk=8, window=window
        )
        ref = dense_attention(q, k, v, attention_mask=None, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize(
        "window",
        [
            1,
            pytest.param(7, marks=pytest.mark.slow),
            8,
            pytest.param(13, marks=pytest.mark.slow),
            pytest.param(32, marks=pytest.mark.slow),
            100,
        ],
    )
    def test_pallas_fwd_matches_dense(self, window):
        q, k, v = _qkv(t=32, seed=42)
        out = pallas_flash_attention(
            q, k, v, block_q=8, block_k=8, interpret=True, window=window
        )
        ref = dense_attention(q, k, v, attention_mask=None, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize("window", [7, 16])
    def test_pallas_bwd_matches_autodiff(self, window):
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, k, v = _qkv(t=32, seed=43)
        g = jax.random.normal(jax.random.key(44), q.shape, jnp.float32)
        out, lse = pallas_flash_attention_fwd(
            q, k, v, block_q=8, block_k=8, interpret=True, window=window
        )
        dq, dk, dv = pallas_flash_attention_bwd(
            q, k, v, out, lse, g, block_q=8, block_k=8, interpret=True,
            window=window,
        )

        def loss(q, k, v):
            return jnp.sum(
                dense_attention(q, k, v, attention_mask=None, window=window) * g
            )

        rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=1e-4)

    def test_window_with_padding_mask(self):
        """Sliding window and key-padding combine in one kernel."""
        q, k, v = _qkv(b=3, t=32, seed=45)
        mask = _suffix_mask(3, 32, seed=46)
        out = pallas_flash_attention(
            q, k, v, mask, block_q=8, block_k=8, interpret=True, window=9
        )
        ref = dense_attention(q, k, v, attention_mask=mask, window=9)
        np.testing.assert_allclose(_valid(out, mask), _valid(ref, mask), atol=1e-5)

    def test_window_with_gqa(self):
        """Sliding window over narrow grouped-query K/V."""
        ks = jax.random.split(jax.random.key(47), 3)
        q = jax.random.normal(ks[0], (2, 32, 4, 8), jnp.float32)
        kn = jax.random.normal(ks[1], (2, 32, 2, 8), jnp.float32)
        vn = jax.random.normal(ks[2], (2, 32, 2, 8), jnp.float32)
        out = pallas_flash_attention(
            q, kn, vn, block_q=8, block_k=8, interpret=True, window=11
        )
        kw, vw = jnp.repeat(kn, 2, axis=2), jnp.repeat(vn, 2, axis=2)
        ref = dense_attention(q, kw, vw, attention_mask=None, window=11)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_dispatch_fallback_grads(self):
        """flash_attention(window=...) differentiates through the
        blockwise fallback and matches dense-window autodiff."""
        q, k, v = _qkv(t=16, seed=48)

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, window=6).sum()

        def loss_dense(q, k, v):
            return dense_attention(q, k, v, attention_mask=None, window=6).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    def test_window_requires_causal(self):
        q, k, v = _qkv(t=16)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=4)

    def test_negative_window_rejected(self):
        """A negative window would silently mask EVERY key (uniform-
        average garbage) — the ops layer rejects it."""
        q, k, v = _qkv(t=16)
        with pytest.raises(ValueError, match=">= 0"):
            flash_attention(q, k, v, window=-1)
        with pytest.raises(ValueError, match=">= 0"):
            blockwise_attention(q, k, v, causal=True, window=-1)
        with pytest.raises(ValueError, match=">= 0"):
            pallas_flash_attention(q, k, v, interpret=True, window=-1)


class TestGQAKernels:
    """Native grouped-query attention: narrow (B, T, Hkv, D) K/V through
    the Pallas kernels with in-kernel group mapping — no jnp.repeat."""

    def _gqa_qkv(self, b=2, t=32, h=4, hkv=2, d=8, seed=21):
        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("hkv", [1, 2, 4], ids=["mqa", "gqa2", "mha"])
    def test_fwd_matches_widened_dense(self, hkv):
        q, kn, vn = self._gqa_qkv(hkv=hkv)
        reps = q.shape[2] // hkv
        kw, vw = jnp.repeat(kn, reps, axis=2), jnp.repeat(vn, reps, axis=2)
        out = pallas_flash_attention(q, kn, vn, block_q=8, block_k=8, interpret=True)
        ref = dense_attention(q, kw, vw, attention_mask=None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize("hkv", [1, 2], ids=["mqa", "gqa2"])
    def test_bwd_matches_widened_autodiff(self, hkv):
        """dk/dv come back at the NARROW width, equal to autodiff through
        widen-then-dense (which group-sums the cotangents)."""
        from llmtrain_tpu.ops.pallas_attention import (
            pallas_flash_attention_bwd,
            pallas_flash_attention_fwd,
        )

        q, kn, vn = self._gqa_qkv(hkv=hkv, seed=23)
        reps = q.shape[2] // hkv
        g = jax.random.normal(jax.random.key(29), q.shape, jnp.float32)

        out, lse = pallas_flash_attention_fwd(
            q, kn, vn, block_q=8, block_k=8, interpret=True
        )
        dq, dk, dv = pallas_flash_attention_bwd(
            q, kn, vn, out, lse, g, block_q=8, block_k=8, interpret=True
        )
        assert dk.shape == kn.shape and dv.shape == vn.shape

        def loss(q, kn, vn):
            kw = jnp.repeat(kn, reps, axis=2)
            vw = jnp.repeat(vn, reps, axis=2)
            return jnp.sum(dense_attention(q, kw, vw, attention_mask=None) * g)

        rq, rk, rv = jax.grad(loss, argnums=(0, 1, 2))(q, kn, vn)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=1e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=1e-4)

    def test_gqa_with_mask(self):
        """GQA and key-padding combine in one kernel invocation."""
        q, kn, vn = self._gqa_qkv(b=3, hkv=2, seed=31)
        mask = _suffix_mask(3, 32, seed=6)
        reps = q.shape[2] // 2
        kw, vw = jnp.repeat(kn, reps, axis=2), jnp.repeat(vn, reps, axis=2)
        out = pallas_flash_attention(q, kn, vn, mask, block_q=8, block_k=8, interpret=True)
        ref = dense_attention(q, kw, vw, attention_mask=mask)
        np.testing.assert_allclose(_valid(out, mask), _valid(ref, mask), atol=1e-5)

    def test_dispatch_gqa_fallback(self):
        """flash_attention with narrow K/V on the CPU fallback path."""
        q, kn, vn = self._gqa_qkv(t=16, hkv=2, seed=37)
        reps = q.shape[2] // 2
        kw, vw = jnp.repeat(kn, reps, axis=2), jnp.repeat(vn, reps, axis=2)
        out = flash_attention(q, kn, vn)
        ref = dense_attention(q, kw, vw, attention_mask=None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize("hkv", [1, 2], ids=["mqa", "gqa2"])
    def test_blockwise_narrow_kv_fwd_and_grads(self, hkv):
        """Blockwise consumes narrow K/V natively (grouped queries) —
        forward and grads equal the widened dense reference."""
        q, kn, vn = self._gqa_qkv(t=16, hkv=hkv, seed=41)
        reps = q.shape[2] // hkv
        g = jax.random.normal(jax.random.key(43), q.shape, jnp.float32)

        def loss_narrow(q, kn, vn):
            return jnp.sum(
                blockwise_attention(q, kn, vn, causal=True, q_chunk=4, kv_chunk=4) * g
            )

        def loss_wide(q, kn, vn):
            kw = jnp.repeat(kn, reps, axis=2)
            vw = jnp.repeat(vn, reps, axis=2)
            return jnp.sum(dense_attention(q, kw, vw, attention_mask=None) * g)

        np.testing.assert_allclose(
            float(loss_narrow(q, kn, vn)), float(loss_wide(q, kn, vn)), rtol=1e-5
        )
        gn = jax.grad(loss_narrow, argnums=(0, 1, 2))(q, kn, vn)
        gw = jax.grad(loss_wide, argnums=(0, 1, 2))(q, kn, vn)
        for a, b in zip(gn, gw):
            assert a.shape == b.shape  # dk/dv born narrow
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    def test_ring_mqa_widens_minimally_instead_of_losing_sp(self):
        """MQA (hkv=1) with tensor=2 head shards: the router widens K/V
        just enough (1 -> 2 heads) and KEEPS the ring path — previously
        this would silently fall back to single-device blockwise."""
        from llmtrain_tpu.config.schemas import MeshConfig
        from llmtrain_tpu.distributed import build_mesh
        from llmtrain_tpu.ops.ring_attention import ring_or_blockwise

        q, kn, vn = self._gqa_qkv(b=4, t=16, h=4, hkv=1, seed=53)
        kw, vw = jnp.repeat(kn, 4, axis=2), jnp.repeat(vn, 4, axis=2)
        ref = dense_attention(q, kw, vw, attention_mask=None)
        mesh = build_mesh(
            MeshConfig(data=2, fsdp=1, tensor=2, sequence=2), jax.devices()[:8]
        )
        with mesh:
            out = jax.jit(lambda q, k, v: ring_or_blockwise(q, k, v))(q, kn, vn)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize("hkv", [1, 2], ids=["mqa", "gqa2"])
    def test_ulysses_narrow_kv_matches_widened_dense(self, hkv):
        """Ulysses exchanges narrow K/V (separate q and kv all-to-alls,
        minimal widening when Hkv doesn't split the axis) and matches the
        widened dense reference, masks included."""
        from llmtrain_tpu.config.schemas import MeshConfig
        from llmtrain_tpu.distributed import build_mesh
        from llmtrain_tpu.ops.ulysses_attention import ulysses_attention_sharded

        q, kn, vn = self._gqa_qkv(b=4, t=16, h=8, hkv=hkv, seed=59)
        reps = 8 // hkv
        mask = _suffix_mask(4, 16, seed=13)
        kw, vw = jnp.repeat(kn, reps, axis=2), jnp.repeat(vn, reps, axis=2)
        ref = dense_attention(q, kw, vw, attention_mask=mask)
        mesh = build_mesh(
            MeshConfig(data=2, fsdp=1, tensor=2, sequence=2), jax.devices()[:8]
        )
        out = jax.jit(
            lambda q, k, v, m: ulysses_attention_sharded(q, k, v, mesh, key_mask=m)
        )(q, kn, vn, mask)
        np.testing.assert_allclose(_valid(out, mask), _valid(ref, mask), atol=1e-5)

    def test_ring_rotates_narrow_kv(self):
        """Ring attention with grouped-query K/V: narrow shards rotate
        (G x less ICI traffic) and results match the widened dense
        reference, masks included."""
        from llmtrain_tpu.config.schemas import MeshConfig
        from llmtrain_tpu.distributed import build_mesh
        from llmtrain_tpu.ops.ring_attention import ring_attention_sharded

        q, kn, vn = self._gqa_qkv(b=4, t=16, h=4, hkv=2, seed=47)
        reps = 2
        mask = _suffix_mask(4, 16, seed=11)
        kw, vw = jnp.repeat(kn, reps, axis=2), jnp.repeat(vn, reps, axis=2)
        ref = dense_attention(q, kw, vw, attention_mask=mask)
        mesh = build_mesh(
            MeshConfig(data=2, fsdp=1, tensor=2, sequence=2), jax.devices()[:8]
        )
        out = jax.jit(
            lambda q, k, v, m: ring_attention_sharded(q, k, v, mesh, key_mask=m)
        )(q, kn, vn, mask)
        np.testing.assert_allclose(_valid(out, mask), _valid(ref, mask), atol=1e-5)


class TestRingAttention:
    def _mesh(self, sequence=2, data=2, tensor=2):
        from llmtrain_tpu.config.schemas import MeshConfig
        from llmtrain_tpu.distributed import build_mesh

        return build_mesh(
            MeshConfig(data=data, fsdp=1, tensor=tensor, sequence=sequence),
            jax.devices()[: data * tensor * sequence],
        )

    def test_matches_dense_on_sequence_mesh(self):
        from llmtrain_tpu.ops.ring_attention import ring_attention_sharded

        q, k, v = _qkv(b=4, t=16, h=2, d=8)
        ref = _dense_ref(q, k, v)
        mesh = self._mesh()
        out = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_gradients_match_dense(self):
        from llmtrain_tpu.ops.ring_attention import ring_attention_sharded

        q, k, v = _qkv(b=4, t=16, h=2, d=8)
        mesh = self._mesh()

        g_ring = jax.jit(
            jax.grad(lambda q: ring_attention_sharded(q, k, v, mesh).sum())
        )(q)
        g_ref = jax.grad(lambda q: _dense_ref(q, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref), atol=1e-4)

    def test_fallback_without_mesh(self):
        from llmtrain_tpu.ops.ring_attention import ring_or_blockwise

        q, k, v = _qkv(t=16)
        out = ring_or_blockwise(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(_dense_ref(q, k, v)), atol=1e-5)

    def test_external_mesh_with_only_sequence_axis(self):
        """An externally built mesh carrying a sequence axis but none of
        data/fsdp/tensor must still route through ring attention (missing
        axes count as unsharded), not KeyError at trace time (ADVICE r1)."""
        from llmtrain_tpu.ops.ring_attention import ring_or_blockwise

        q, k, v = _qkv(b=4, t=16, h=2, d=8)
        ref = _dense_ref(q, k, v)
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("sequence",))
        with mesh:
            out = jax.jit(ring_or_blockwise)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_ring_gpt_matches_dense_gpt_under_mesh(self):
        kwargs = dict(
            vocab_size=64,
            block_size=16,
            d_model=32,
            n_layers=1,
            n_heads=4,
            d_ff=64,
            dropout=0.0,
        )
        dense = GPT(**kwargs, attention="dense")
        ring = GPT(**kwargs, attention="ring")
        tokens = jax.random.randint(jax.random.key(0), (4, 16), 0, 64)
        params = dense.init({"params": jax.random.key(1)}, tokens, deterministic=True)["params"]
        out_d = dense.apply({"params": params}, tokens, deterministic=True)
        mesh = self._mesh()
        with mesh:
            out_r = jax.jit(
                lambda p, t: ring.apply({"params": p}, t, deterministic=True)
            )(params, tokens)
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_r), atol=1e-5)


class TestGPTIntegration:
    def test_flash_gpt_matches_dense_gpt(self):
        kwargs = dict(
            vocab_size=64,
            block_size=16,
            d_model=32,
            n_layers=1,
            n_heads=4,
            d_ff=64,
            dropout=0.0,
        )
        dense = GPT(**kwargs, attention="dense")
        flash = GPT(**kwargs, attention="flash")
        tokens = jax.random.randint(jax.random.key(0), (2, 16), 0, 64)
        params = dense.init({"params": jax.random.key(1)}, tokens, deterministic=True)["params"]
        out_d = dense.apply({"params": params}, tokens, deterministic=True)
        out_f = flash.apply({"params": params}, tokens, deterministic=True)
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_f), atol=1e-5)

    def test_remat_with_dropout_trains(self):
        """Regression: remat + dropout>0 must trace (static deterministic)."""
        model = GPT(
            vocab_size=32,
            block_size=8,
            d_model=16,
            n_layers=1,
            n_heads=2,
            d_ff=32,
            dropout=0.1,
            remat=True,
        )
        tokens = jnp.zeros((2, 8), jnp.int32)
        params = model.init({"params": jax.random.key(0)}, tokens, deterministic=True)["params"]
        out = model.apply(
            {"params": params},
            tokens,
            deterministic=False,
            rngs={"dropout": jax.random.key(1)},
        )
        assert np.isfinite(np.asarray(out)).all()


class TestUlyssesAttention:
    """All-to-all sequence parallelism (ops/ulysses_attention.py) — the
    ring alternative; exact attention, so it must match dense."""

    def _mesh(self, sequence=2, data=2, tensor=2):
        from llmtrain_tpu.config.schemas import MeshConfig
        from llmtrain_tpu.distributed import build_mesh

        return build_mesh(
            MeshConfig(data=data, fsdp=1, tensor=tensor, sequence=sequence),
            jax.devices()[: data * tensor * sequence],
        )

    def test_matches_dense_on_sequence_mesh(self):
        from llmtrain_tpu.ops.ulysses_attention import ulysses_attention_sharded

        # tensor=2 leaves 2 local heads per shard; sequence=2 divides them.
        q, k, v = _qkv(b=4, t=16, h=4, d=8)
        ref = _dense_ref(q, k, v)
        mesh = self._mesh()
        out = jax.jit(lambda q, k, v: ulysses_attention_sharded(q, k, v, mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_matches_ring(self):
        """Both SP schemes compute the same exact attention."""
        from llmtrain_tpu.ops.ring_attention import ring_attention_sharded
        from llmtrain_tpu.ops.ulysses_attention import ulysses_attention_sharded

        q, k, v = _qkv(b=4, t=16, h=4, d=8, seed=9)
        mesh = self._mesh()
        a = jax.jit(lambda q, k, v: ulysses_attention_sharded(q, k, v, mesh))(q, k, v)
        b = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_gradients_match_dense(self):
        from llmtrain_tpu.ops.ulysses_attention import ulysses_attention_sharded

        q, k, v = _qkv(b=4, t=16, h=4, d=8)
        mesh = self._mesh()
        g_uly = jax.jit(
            jax.grad(lambda q: ulysses_attention_sharded(q, k, v, mesh).sum())
        )(q)
        g_ref = jax.grad(lambda q: _dense_ref(q, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g_uly), np.asarray(g_ref), atol=1e-4)

    def test_fallback_without_mesh(self):
        from llmtrain_tpu.ops.ulysses_attention import ulysses_or_blockwise

        q, k, v = _qkv(t=16)
        out = ulysses_or_blockwise(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_dense_ref(q, k, v)), atol=1e-5
        )

    def test_fallback_when_heads_not_divisible(self):
        """sequence=4 but only 2 local heads: falls back to blockwise (with
        a warning) instead of crashing inside shard_map."""
        from llmtrain_tpu.ops.ulysses_attention import ulysses_or_blockwise

        q, k, v = _qkv(b=4, t=16, h=2, d=8)
        mesh = self._mesh(sequence=4, data=2, tensor=1)
        with mesh:
            out = ulysses_or_blockwise(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_dense_ref(q, k, v)), atol=1e-5
        )

    def test_gpt_model_route(self):
        """attention='ulysses' through the real GPT forward on a sequence
        mesh matches the dense model's logits."""
        from flax.linen import meta as nn_meta

        from llmtrain_tpu.models.gpt import GPT
        from llmtrain_tpu.parallel.sharding import DEFAULT_LOGICAL_AXIS_RULES

        def build(attention):
            return GPT(
                vocab_size=64, block_size=16, d_model=32, n_layers=2,
                n_heads=4, d_ff=64, dropout=0.0, attention=attention,
            )

        ids = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (4, 16)), jnp.int32
        )
        dense = build("dense")
        params = nn_meta.unbox(
            dense.init(jax.random.key(0), ids, deterministic=True)
        )["params"]
        ref = dense.apply({"params": params}, ids, deterministic=True)

        import flax.linen as nn

        mesh = self._mesh(sequence=2, data=2, tensor=2)
        with mesh, nn.logical_axis_rules(DEFAULT_LOGICAL_AXIS_RULES):
            out = build("ulysses").apply({"params": params}, ids, deterministic=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

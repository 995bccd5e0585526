"""Fused lm-head + CE Pallas kernel (ops/fused_ce.py) and the fused
residual-add + LayerNorm kernel (ops/fused_norm.py), docs/perf.md
"Fused lm-head + CE".

Tier-1 keeps to pure units and interpret-mode kernels — forward/grad
parity vs the dense reference and chunked_ce's custom_vjp (tied/untied,
z_loss on/off, shapes not multiples of the blocks, padded tokens), the
fused-norm parity vs nn.LayerNorm with an identical param tree, the
loss_impl/fused_norm resolution rules, and the planner's logits-buffer
accounting. Everything that runs full fits (5-step loss parity, the
checkpoint resume with loss_impl flipped across the boundary, the
attribution pin) is ``@pytest.mark.slow`` under ``make verify-fusedce``.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmtrain_tpu.config.schemas import RunConfig
from llmtrain_tpu.models.gpt import GPTAdapter
from llmtrain_tpu.ops import fused_ce as fused_ce_mod
from llmtrain_tpu.ops import fused_norm as fused_norm_mod
from llmtrain_tpu.ops.chunked_ce import chunked_ce_components, chunked_ce_per_token
from llmtrain_tpu.ops.fused_ce import (
    LOSS_IMPLS,
    fused_ce_components,
    fused_ce_per_token,
    resolve_loss_impl,
)
from llmtrain_tpu.ops.fused_norm import (
    fused_add_layer_norm,
    fused_layer_norm,
    resolve_fused_norm,
)
from llmtrain_tpu.registry import initialize_registries

# Interpret-mode blocks chosen to NOT divide the test shapes below, so
# every padding path (token rows and vocab columns) is exercised.
BT, BV = 16, 64
# Adapter-level wiring tests use coarser blocks: the interpreter pays
# python-loop overhead per grid step, and the padding paths are already
# covered by the kernel tests above at (BT, BV).
WBT, WBV = 64, 128
# What ``_choose_tiles`` gives gpt2-small.train-64k (PERF.md section 6, PR 41).
CELL_FWD_TILES = (4096, 256)
CELL_BWD_TILES = (2048, 512)


@pytest.fixture(scope="module", autouse=True)
def _registries():
    initialize_registries()


def _gpt_cfg(extra: dict, *, vocab: int = 256, seq: int = 16, tie: bool = True,
             root=None, **trainer_kw):
    doc = {
        "run": {"name": "fusedce-test", "seed": 7, "device": "cpu"},
        "model": {
            "name": "gpt",
            "block_size": seq,
            "d_model": 32,
            "n_layers": 2,
            "n_heads": 2,
            "d_ff": 64,
            "dropout": 0.0,
            "vocab_size": vocab,
            "tie_embeddings": tie,
            "extra": extra,
        },
        "data": {"name": "dummy_text"},
        "trainer": {
            "micro_batch_size": 4,
            "grad_accum_steps": 1,
            "lr": 3e-3,
            "warmup_steps": 0,
            **trainer_kw,
        },
        "mlflow": {"enabled": False},
    }
    if root is not None:
        doc["output"] = {"root_dir": str(root)}
    return RunConfig.model_validate(doc)


def _dense_ce_ref(h, w, labels, z_loss=0.0):
    logits = jnp.einsum("btd,vd->btv", h, w)
    lse = jax.nn.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    per = lse - label_logit
    if z_loss:
        per = per + z_loss * jnp.square(lse)
    return per


def _rand_problem(b=2, t=13, d=32, v=117, seed=0):
    """Shapes deliberately NOT multiples of (BT, BV): B*T=26 pads to 32
    token rows (2 blocks), V=117 pads to 128 vocab rows (2 blocks)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(ks[0], (b, t, d), jnp.float32)
    w = jax.random.normal(ks[1], (v, d), jnp.float32) * 0.2
    labels = jax.random.randint(ks[2], (b, t), 0, v)
    return h, w, labels


# --------------------------------------------------------------------------
# kernel parity (interpret mode): forward + custom_vjp grads
# --------------------------------------------------------------------------


# Kernel-level grids, tiles passed by hand: (b, t, v, block_t, block_v).
# Every N and V is ragged against its tile. "3x3" and "3x5" visit each
# HBM-accumulated dW block three times (once a token block); "one-token-block"
# and "one-vocab-block" are the two degenerate axes of the backward's grid
# (the second is the revisited-accumulator path of ``_bwd_kernel``);
# "chosen" leaves both tiles to ``_choose_tiles``.
GRIDS = {
    "2x2": (2, 13, 117, BT, BV),
    "3x3": (2, 21, 150, 16, 64),
    "3x5": (3, 15, 290, 16, 64),
    "one-token-block": (2, 13, 117, 64, 32),
    "one-vocab-block": (2, 13, 117, 8, 128),
    "chosen": (2, 13, 117, None, None),
}


class TestFusedCEKernel:
    @pytest.mark.parametrize("grid", GRIDS)
    @pytest.mark.parametrize("z_loss", [0.0, 1e-3])
    def test_forward_matches_dense_and_chunked(self, z_loss, grid):
        b, t, v, bt, bv = GRIDS[grid]
        h, w, labels = _rand_problem(b, t, v=v)
        fused = fused_ce_per_token(h, w, labels, bt, bv, None, z_loss, True)
        dense = _dense_ce_ref(h, w, labels, z_loss)
        chunked = chunked_ce_per_token(h, w, labels, BV, None, z_loss)
        np.testing.assert_allclose(fused, dense, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(fused, chunked, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("grid", GRIDS)
    @pytest.mark.parametrize("z_loss", [0.0, 1e-3])
    def test_grads_match_chunked_vjp(self, z_loss, grid):
        b, t, v, bt, bv = GRIDS[grid]
        h, w, labels = _rand_problem(b, t, v=v, seed=1)
        # Non-uniform cotangent: a mean-loss-only check would hide
        # per-token cotangent bugs (every g identical). The last three
        # positions of every row are masked: a zero cotangent.
        g = jax.random.normal(jax.random.PRNGKey(9), labels.shape)
        g = g * (jnp.arange(t) < t - 3)

        def fused_loss(h, w):
            return jnp.sum(fused_ce_per_token(h, w, labels, bt, bv, None, z_loss, True) * g)

        def chunked_loss(h, w):
            return jnp.sum(chunked_ce_per_token(h, w, labels, BV, None, z_loss) * g)

        def dense_loss(h, w):
            return jnp.sum(_dense_ce_ref(h, w, labels, z_loss) * g)

        dh_f, dw_f = jax.grad(fused_loss, argnums=(0, 1))(h, w)
        dh_c, dw_c = jax.grad(chunked_loss, argnums=(0, 1))(h, w)
        dh_d, dw_d = jax.grad(dense_loss, argnums=(0, 1))(h, w)
        np.testing.assert_allclose(dh_f, dh_c, atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(dw_f, dw_c, atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(dh_f, dh_d, atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(dw_f, dw_d, atol=1e-5, rtol=1e-4)
        assert bool(jnp.all(dh_f[:, t - 3:] == 0.0)), "masked rows leaked gradient"

    def test_block_sizes_larger_than_problem(self):
        # One grid cell total: blocks exceeding N and V must still pad
        # and mask correctly.
        h, w, labels = _rand_problem(seed=2)
        fused = fused_ce_per_token(h, w, labels, 512, 512, None, 0.0, True)
        np.testing.assert_allclose(
            fused, _dense_ce_ref(h, w, labels), atol=1e-5, rtol=1e-5
        )

    def test_components_mask_semantics_match_chunked(self):
        # Padded tokens (mask 0) drop out; packed segment ids > 1 count
        # as boolean 1, not as loss weights.
        h, w, labels = _rand_problem(seed=3)
        mask = jnp.array([[1] * 9 + [0] * 4, [2] * 6 + [1] * 3 + [0] * 4])
        ls_f, n_f = fused_ce_components(
            h, w, labels, mask, block_t=BT, block_v=BV, z_loss=1e-3, interpret=True
        )
        ls_c, n_c = chunked_ce_components(
            h, w, labels, mask, chunk=BV, z_loss=1e-3
        )
        np.testing.assert_allclose(ls_f, ls_c, atol=1e-4, rtol=1e-5)
        np.testing.assert_array_equal(n_f, n_c)
        assert n_f.tolist() == [9.0, 9.0]

    def test_masked_grads_zero_for_padded_tokens(self):
        h, w, labels = _rand_problem(seed=4)
        mask = jnp.concatenate(
            [jnp.ones((2, 7), jnp.int32), jnp.zeros((2, 6), jnp.int32)], axis=1
        )

        def loss(h):
            ls, n = fused_ce_components(
                h, w, labels, mask, block_t=BT, block_v=BV, interpret=True
            )
            return jnp.sum(ls) / jnp.sum(n)

        dh = jax.grad(loss)(h)
        assert bool(jnp.all(dh[:, 7:] == 0.0)), "padded tokens leaked gradient"
        assert bool(jnp.any(dh[:, :7] != 0.0))


# --------------------------------------------------------------------------
# the tile chooser, and the contract with the benchmark's readers
# --------------------------------------------------------------------------


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


class TestTileChooser:
    @pytest.mark.parametrize("kind", ["fwd", "bwd"])
    @pytest.mark.parametrize("vocab", [1000, 50257, 128256])
    @pytest.mark.parametrize("width", [2, 4])
    @pytest.mark.parametrize("d", [768, 1600, 4096, 8192])
    def test_tiles_are_legal_and_fit_the_vmem_limit(self, d, width, vocab, kind):
        n = 8192
        block_t, block_v = fused_ce_mod._choose_tiles(kind, n, vocab, d, width)
        # Tokens lie on the lanes of the logits tile and of the per-token
        # rows, and on the sublanes of the hidden block; the vocabulary on
        # the sublanes of the weight and dW blocks and, as the contraction
        # of dh's product, on lanes. 128 is a multiple of every dtype's
        # sublane count (8 x 4 / width).
        assert block_t % 128 == 0 and block_v % 128 == 0
        limit = fused_ce_mod._compiler_params("arbitrary", "arbitrary").vmem_limit_bytes
        assert limit == fused_ce_mod._VMEM_LIMIT_BYTES
        assert fused_ce_mod._vmem_bytes(kind, block_t, block_v, d, width) <= limit
        # dW's read-modify-write never revisits a block 2 or 3 steps later.
        n_vb = -(-vocab // block_v)
        assert kind == "fwd" or n_vb == 1 or n_vb >= fused_ce_mod._MIN_RMW_BLOCKS
        # The token tile pads N by at most 1/32.
        assert (-(-n // block_t) * block_t - n) * 32 <= n

    def test_the_train_cell_gets_the_tiles_measured_fastest(self):
        # gpt2-small.train-64k: 32 x 1,024 tokens, d 768, V 50,257, bf16
        # (PERF.md section 6, PR 41: the sweep on the chip).
        assert fused_ce_mod._choose_tiles("fwd", 32768, 50257, 768, 2) == CELL_FWD_TILES
        assert fused_ce_mod._choose_tiles("bwd", 32768, 50257, 768, 2) == CELL_BWD_TILES

    @pytest.mark.parametrize(
        "n, vocab, want_t, want_vb",
        [(26, 117, 128, 1), (1000, 300, 1024, 1), (10240, 600, 2048, 5), (4096 + 128, 2000, 256, 4)],
    )
    def test_small_and_ragged_shapes(self, n, vocab, want_t, want_vb):
        block_t, block_v = fused_ce_mod._choose_tiles("bwd", n, vocab, 768, 2)
        assert block_t == want_t
        if want_vb is not None:
            assert -(-vocab // block_v) == want_vb

    def test_an_override_that_risks_stale_dw_sums_is_refused_on_the_chip(self, monkeypatch):
        # Two vocabulary blocks under several token blocks: the interpreter
        # runs it (grid steps one after another), the chip must not.
        h, w, labels = _rand_problem(seed=5)
        loss = lambda interpret: jax.grad(  # noqa: E731
            lambda h: jnp.sum(fused_ce_per_token(h, w, labels, BT, BV, None, 0.0, interpret))
        )
        assert np.all(np.isfinite(loss(True)(h)))
        with pytest.raises(ValueError, match="needs 1 block or at least 4"):
            jax.eval_shape(loss(False), h)

    def test_chosen_tiles_are_logged_once(self, caplog):
        fused_ce_mod._TILES_LOGGED.clear()
        with caplog.at_level(logging.INFO, logger="llmtrain_tpu.ops.fused_ce"):
            for _ in range(2):
                fused_ce_mod._tiles("bwd", 32768, 50257, 768, 2, None, None)
        lines = [r.getMessage() for r in caplog.records if "fused_ce bwd tiles" in r.getMessage()]
        assert len(lines) == 1
        assert "chosen from shapes" in lines[0] and "MiB of VMEM" in lines[0]


class TestBenchmarkContract:
    def test_one_forward_and_one_backward_kernel_under_names_the_readers_know(self):
        """``benchmarks/lib/kernel_costs.py`` finds the kernels by name in the
        trace and divides by ONE call's cost: a second backward kernel, or a
        name it does not list, and ``kernel_roofline_share.fused_ce_*`` reads
        nothing."""
        import sys
        from pathlib import Path

        root = str(Path(__file__).resolve().parents[1])
        if root not in sys.path:
            sys.path.insert(0, root)
        from benchmarks.lib.kernel_costs import KERNELS

        h, w, labels = _rand_problem()
        jaxpr = jax.make_jaxpr(
            jax.grad(
                lambda h, w: jnp.sum(fused_ce_per_token(h, w, labels, BT, BV, None, 0.0, True)),
                argnums=(0, 1),
            )
        )(h, w)
        names = [eqn.params["name"] for eqn in _pallas_calls(jaxpr.jaxpr)]
        fwd_names, _ = KERNELS["fused_ce_fwd"]
        bwd_names, _ = KERNELS["fused_ce_bwd"]
        assert len(names) == 2
        assert sum(name in fwd_names for name in names) == 1
        assert sum(name in bwd_names for name in names) == 1


# --------------------------------------------------------------------------
# fused residual-add + LayerNorm kernel
# --------------------------------------------------------------------------


class TestFusedNormKernel:
    def _ref_ln(self, x, scale, bias, eps=1e-6):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias

    def _operands(self, seed=0, d=48):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        x = jax.random.normal(ks[0], (2, 13, d))
        r = jax.random.normal(ks[1], (2, 13, d))
        scale = 1.0 + 0.1 * jax.random.normal(ks[2], (d,))
        bias = 0.1 * jax.random.normal(ks[3], (d,))
        return x, r, scale, bias

    def test_plain_norm_matches_reference(self):
        x, _, scale, bias = self._operands()
        y = fused_layer_norm(x, scale, bias, 1e-6, BT, True)
        np.testing.assert_allclose(
            y, self._ref_ln(x, scale, bias), atol=1e-5, rtol=1e-5
        )

    def test_plain_norm_grads(self):
        x, _, scale, bias = self._operands(seed=1)
        g = jax.random.normal(jax.random.PRNGKey(8), x.shape)

        def fused(x, s, b):
            return jnp.sum(fused_layer_norm(x, s, b, 1e-6, BT, True) * g)

        def ref(x, s, b):
            return jnp.sum(self._ref_ln(x, s, b) * g)

        got = jax.grad(fused, argnums=(0, 1, 2))(x, scale, bias)
        want = jax.grad(ref, argnums=(0, 1, 2))(x, scale, bias)
        for a, b_ in zip(got, want):
            np.testing.assert_allclose(a, b_, atol=2e-5, rtol=1e-4)

    def test_add_norm_returns_sum_and_matches_reference(self):
        x, r, scale, bias = self._operands(seed=2)
        y, s = fused_add_layer_norm(x, r, scale, bias, 1e-6, BT, True)
        np.testing.assert_allclose(s, x + r, atol=0, rtol=0)
        np.testing.assert_allclose(
            y, self._ref_ln(x + r, scale, bias), atol=1e-5, rtol=1e-5
        )

    def test_add_norm_grads_through_both_outputs(self):
        # Both outputs carry cotangents in the real block wiring: the
        # normed copy feeds the MLP, the sum continues the residual stream.
        x, r, scale, bias = self._operands(seed=3)
        gy = jax.random.normal(jax.random.PRNGKey(5), x.shape)
        gs = jax.random.normal(jax.random.PRNGKey(6), x.shape)

        def fused(x, r, s, b):
            y, summed = fused_add_layer_norm(x, r, s, b, 1e-6, BT, True)
            return jnp.sum(y * gy) + jnp.sum(summed * gs)

        def ref(x, r, s, b):
            return jnp.sum(self._ref_ln(x + r, s, b) * gy) + jnp.sum((x + r) * gs)

        got = jax.grad(fused, argnums=(0, 1, 2, 3))(x, r, scale, bias)
        want = jax.grad(ref, argnums=(0, 1, 2, 3))(x, r, scale, bias)
        for a, b_ in zip(got, want):
            np.testing.assert_allclose(a, b_, atol=2e-5, rtol=1e-4)


# --------------------------------------------------------------------------
# model wiring (adapter loss dispatch, fused_norm blocks, decode clones)
# -- the full parity fits are @slow: tier-1 keeps to pure units +
# interpret kernels (make verify-fusedce runs everything)
# --------------------------------------------------------------------------


class TestModelWiring:
    def _batch(self, vocab=256, seq=16):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        ids = jax.random.randint(ks[0], (4, seq), 0, vocab)
        labels = jax.random.randint(ks[1], (4, seq), 0, vocab)
        return {
            "input_ids": ids,
            "labels": labels,
            "attention_mask": jnp.ones((4, seq), jnp.int32),
        }

    @pytest.mark.slow
    @pytest.mark.parametrize("tie", [True, False])
    def test_loss_components_parity_across_impls(self, tie):
        adapter = GPTAdapter()
        batch = self._batch()
        results = {}
        params = None
        for impl in LOSS_IMPLS:
            extra = {
                "loss_impl": impl,
                "fused_ce_block_t": WBT,
                "fused_ce_block_v": WBV,
                "pallas_interpret": True,
            }
            model = adapter.build_model(_gpt_cfg(extra, tie=tie))
            assert model.loss_impl == impl
            if params is None:
                params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
            (_, ls), grads = jax.value_and_grad(
                lambda p: (
                    lambda c: (jnp.sum(c[0]), c[0])
                )(adapter.compute_loss_components(model, p, batch)),
                has_aux=True,
            )(params)
            results[impl] = (np.asarray(ls), jax.tree.leaves(jax.tree.map(np.asarray, grads)))
        for impl in ("chunked_ce", "fused_ce"):
            np.testing.assert_allclose(
                results[impl][0], results["dense"][0], atol=1e-4, rtol=1e-5
            )
            for a, b in zip(results[impl][1], results["dense"][1]):
                np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)

    @pytest.mark.slow
    def test_fused_norm_param_tree_and_parity(self):
        adapter = GPTAdapter()
        batch = self._batch()
        plain = adapter.build_model(_gpt_cfg({}))
        fused = adapter.build_model(_gpt_cfg({"fused_norm": True, "pallas_interpret": True}))
        assert fused.fused_norm is True
        params = plain.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
        fused_params = fused.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
        # Checkpoint compatibility: identical tree (ln_1/ln_2 scale+bias).
        assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
            fused_params
        )
        out_p = plain.apply({"params": params}, batch["input_ids"])
        out_f = fused.apply({"params": params}, batch["input_ids"])
        np.testing.assert_allclose(out_f, out_p, atol=1e-4, rtol=1e-4)
        g_p = jax.grad(
            lambda p: jnp.sum(plain.apply({"params": p}, batch["input_ids"]) ** 2)
        )(params)
        g_f = jax.grad(
            lambda p: jnp.sum(fused.apply({"params": p}, batch["input_ids"]) ** 2)
        )(params)
        for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_f)):
            np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)

    def test_decode_clones_clear_fused_norm(self):
        model = GPTAdapter().build_model(
            _gpt_cfg({"fused_norm": True, "pallas_interpret": True})
        )
        assert model.for_decoding(8).fused_norm is False
        assert (
            model.for_paged_decoding(num_blocks=2, block_tokens=4).fused_norm is False
        )

    @pytest.mark.slow
    def test_moe_adapter_routes_fused_ce_through_hidden(self):
        from llmtrain_tpu.registry import get_model_adapter

        adapter = get_model_adapter("gpt_moe")()
        cfg = _gpt_cfg(
            {
                "loss_impl": "fused_ce",
                "pallas_interpret": True,
                "fused_ce_block_t": WBT,
                "fused_ce_block_v": WBV,
                "n_experts": 2,
            }
        )
        model = adapter.build_model(cfg)
        assert model.loss_impl == "fused_ce"
        batch = self._batch()
        params = adapter.init_params(model, cfg, jax.random.PRNGKey(0))
        ls, n = adapter.compute_loss_components(model, params, batch)
        assert np.all(np.isfinite(np.asarray(ls)))


# --------------------------------------------------------------------------
# config validation + capability fallbacks (resolution rules)
# --------------------------------------------------------------------------


class TestConfigResolution:
    def test_unknown_loss_impl_raises(self):
        with pytest.raises(ValueError, match="loss_impl 'typo' unknown"):
            GPTAdapter().build_model(_gpt_cfg({"loss_impl": "typo"}))

    def test_fused_ce_without_pallas_falls_back_warn_once(self, caplog):
        # CPU backend, no interpret: the fp8_supported() contract — degrade
        # to chunked_ce, warn ONCE per process.
        fused_ce_mod._FALLBACK_WARNED.discard("fused_ce")
        with caplog.at_level(logging.WARNING, logger="llmtrain_tpu.ops.fused_ce"):
            m1 = GPTAdapter().build_model(_gpt_cfg({"loss_impl": "fused_ce"}))
            m2 = GPTAdapter().build_model(_gpt_cfg({"loss_impl": "fused_ce"}))
        assert m1.loss_impl == "chunked_ce" and m2.loss_impl == "chunked_ce"
        warnings = [r for r in caplog.records if "falling back to chunked_ce" in r.message]
        assert len(warnings) == 1, "fallback must warn exactly once per process"

    def test_fused_norm_without_pallas_falls_back_warn_once(self, caplog):
        fused_norm_mod._FALLBACK_WARNED.discard("fused_norm")
        with caplog.at_level(logging.WARNING, logger="llmtrain_tpu.ops.fused_norm"):
            m1 = GPTAdapter().build_model(_gpt_cfg({"fused_norm": True}))
            m2 = GPTAdapter().build_model(_gpt_cfg({"fused_norm": True}))
        assert m1.fused_norm is False and m2.fused_norm is False
        warnings = [r for r in caplog.records if "unfused LayerNorm path" in r.message]
        assert len(warnings) == 1

    def test_interpret_knob_forces_fused_paths_on_cpu(self):
        m = GPTAdapter().build_model(
            _gpt_cfg({"loss_impl": "fused_ce", "fused_norm": True, "pallas_interpret": True})
        )
        assert m.loss_impl == "fused_ce" and m.fused_norm is True

    def test_auto_select_prefers_fused_only_with_pallas(self):
        # vocab >= ce_auto_vocab, loss_impl unset: chunked on a plain CPU
        # backend, fused when the interpret path is forced on.
        assert resolve_loss_impl(None, vocab_size=256, ce_auto_vocab=128) == "chunked_ce"
        assert (
            resolve_loss_impl(None, vocab_size=256, ce_auto_vocab=128, interpret=True)
            == "fused_ce"
        )
        assert resolve_loss_impl(None, vocab_size=64, ce_auto_vocab=128) == "dense"
        m = GPTAdapter().build_model(_gpt_cfg({"ce_auto_vocab": 128}))
        assert m.loss_impl == "chunked_ce"

    def test_resolve_fused_norm_passthrough(self):
        assert resolve_fused_norm(False) is False
        assert resolve_fused_norm(True, interpret=True) is True

    @pytest.mark.parametrize("key", ["fused_ce_block_t", "fused_ce_block_v"])
    def test_block_knobs_must_be_positive(self, key):
        with pytest.raises(ValueError, match=key):
            GPTAdapter().build_model(_gpt_cfg({key: 0}))

    def test_pipeline_adapter_rejects_fused_ce(self):
        from llmtrain_tpu.registry import get_model_adapter

        adapter = get_model_adapter("gpt_pipeline")()
        cfg = _gpt_cfg({"loss_impl": "fused_ce"})
        cfg = cfg.model_copy(
            update={"model": cfg.model.model_copy(update={"name": "gpt_pipeline"})}
        )
        with pytest.raises(ValueError, match="not supported with.*pipeline"):
            adapter.build_model(cfg)

    def test_llama_adapter_rejects_fused_norm(self):
        from llmtrain_tpu.registry import get_model_adapter

        adapter = get_model_adapter("llama")()
        cfg = _gpt_cfg({"fused_norm": True, "pallas_interpret": True})
        cfg = cfg.model_copy(
            update={"model": cfg.model.model_copy(update={"name": "llama"})}
        )
        with pytest.raises(ValueError, match="RMSNorm"):
            adapter.build_model(cfg)

    def test_llama_adapter_accepts_fused_ce(self):
        from llmtrain_tpu.registry import get_model_adapter

        adapter = get_model_adapter("llama")()
        cfg = _gpt_cfg(
            {
                "loss_impl": "fused_ce",
                "pallas_interpret": True,
                "fused_ce_block_t": WBT,
                "fused_ce_block_v": WBV,
            }
        )
        cfg = cfg.model_copy(
            update={"model": cfg.model.model_copy(update={"name": "llama"})}
        )
        model = adapter.build_model(cfg)
        assert model.loss_impl == "fused_ce"
        # The compute path itself is shared with the GPT adapter
        # (chunked_components_from_hidden); a loss evaluation here would
        # only re-pay the interpret cost, so tier-1 stops at the build.


# --------------------------------------------------------------------------
# fits + attribution pin (@slow, make verify-fusedce)
# --------------------------------------------------------------------------


def _fit_losses(extra: dict, steps: int = 5, vocab: int = 256):
    from llmtrain_tpu.training.optimizer import build_optimizer
    from llmtrain_tpu.training.train_step import create_train_state, make_train_step

    cfg = _gpt_cfg(extra, vocab=vocab)
    adapter = GPTAdapter()
    model = adapter.build_model(cfg)
    tx = build_optimizer(cfg.trainer)
    params = adapter.init_params(model, cfg, jax.random.key(0))
    state = create_train_state(params, tx)
    step_fn = jax.jit(
        make_train_step(adapter, model, tx, grad_accum_steps=1, use_dropout=False)
    )
    tokens = np.random.default_rng(0).integers(0, vocab, size=(1, 4, 16), dtype=np.int32)
    batch = {
        "input_ids": jnp.asarray(tokens),
        "labels": jnp.asarray(tokens),
        "attention_mask": jnp.ones_like(jnp.asarray(tokens)),
    }
    rng = jax.random.key(0)
    losses = []
    for _ in range(steps):
        state, metrics = step_fn(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
    return losses


@pytest.mark.slow
class TestFusedFits:
    # The CE-parity band, 5e-4 (docs/perf.md; chip_smoke.py holds the chip
    # to the same at step 1): identical math, fp reduction-order noise
    # amplified over the 5-step trajectory.
    CE_RTOL = 5e-4

    def test_fit_loss_parity_vs_dense(self):
        ref = _fit_losses({"loss_impl": "dense"})
        got = _fit_losses(
            {
                "loss_impl": "fused_ce",
                "pallas_interpret": True,
                "fused_ce_block_t": WBT,
                "fused_ce_block_v": WBV,
            }
        )
        max_rel = max(abs(q - f) / max(abs(f), 1e-6) for q, f in zip(got, ref))
        assert max_rel < self.CE_RTOL, f"fused_ce drifted {max_rel:.6f}"

    def test_checkpoint_resume_flips_loss_impl(self, tmp_path):
        """loss_impl is resume-mutable: a dense checkpoint trains on under
        fused_ce (and back) — the param tree is impl-independent."""
        from llmtrain_tpu.tracking import NullTracker
        from llmtrain_tpu.training import Trainer

        def fit(run_dir, extra, resume_from=None):
            run_dir.mkdir(parents=True, exist_ok=True)
            cfg = _gpt_cfg(
                extra,
                root=tmp_path,
                max_steps=6,
                log_every_steps=1,
                eval_every_steps=100,
                save_every_steps=3,
            )
            return Trainer(cfg, run_dir, NullTracker(), None).fit(
                resume_from=resume_from
            )

        fused_extra = {
            "loss_impl": "fused_ce",
            "pallas_interpret": True,
            "fused_ce_block_t": WBT,
            "fused_ce_block_v": WBV,
        }
        full = fit(tmp_path / "full", fused_extra)
        ckpt = tmp_path / "full" / "checkpoints" / "step_000003.ckpt"
        assert ckpt.exists()
        resumed = fit(tmp_path / "resume_fused", fused_extra, resume_from=str(ckpt))
        assert resumed.resumed_from_step == 3
        np.testing.assert_allclose(
            resumed.final_loss, full.final_loss, rtol=self.CE_RTOL
        )
        flipped = fit(
            tmp_path / "resume_dense", {"loss_impl": "dense"}, resume_from=str(ckpt)
        )
        assert flipped.resumed_from_step == 3
        # Same math across the boundary, so the flipped trajectory stays
        # inside the CE parity band of the unflipped one.
        np.testing.assert_allclose(
            flipped.final_loss, full.final_loss, rtol=self.CE_RTOL
        )

    def test_attribution_pin_no_logits_dot_under_fused(self):
        """Satellite pin: under fused_ce the aggregate ``dot``-class op
        bytes stay BELOW the [B,T,V] logits size (the tile dots live in
        the kernel's grid loop, counted once) — while dense CE provably
        materializes the full logits dot. Mirror of the chunked-CE pin in
        test_quant_train.py."""
        from llmtrain_tpu.telemetry import profiling
        from llmtrain_tpu.training.optimizer import build_optimizer
        from llmtrain_tpu.training.train_step import create_train_state, make_train_step

        B, T, V = 4, 64, 16384

        def dot_bytes(extra):
            cfg = _gpt_cfg(extra, vocab=V, seq=T)
            adapter = GPTAdapter()
            model = adapter.build_model(cfg)
            tx = build_optimizer(cfg.trainer)
            params = adapter.init_params(model, cfg, jax.random.key(0))
            state = create_train_state(params, tx)
            step_fn = jax.jit(
                make_train_step(adapter, model, tx, grad_accum_steps=1, use_dropout=False)
            )
            tokens = np.zeros((1, B, T), np.int32)
            batch = {
                "input_ids": jnp.asarray(tokens),
                "labels": jnp.asarray(tokens),
                "attention_mask": jnp.ones_like(jnp.asarray(tokens)),
            }
            prof = profiling.aot_profile(
                step_fn,
                (state, batch, jax.random.key(0)),
                name="fused_pin",
                peaks=profiling.resolve_peaks(),
            )
            assert prof is not None
            rows = {r["op"]: r for r in prof["top_ops"]}
            return model.loss_impl, rows.get("dot", {"bytes_accessed": 0.0})[
                "bytes_accessed"
            ]

        logits_bytes = B * T * V * 4
        impl_dense, dense_bytes = dot_bytes({"loss_impl": "dense"})
        impl_fused, fused_bytes = dot_bytes(
            {"loss_impl": "fused_ce", "pallas_interpret": True}
        )
        assert impl_dense == "dense" and impl_fused == "fused_ce"
        assert dense_bytes >= logits_bytes, "dense CE must materialize the logits dot"
        assert fused_bytes < logits_bytes, "fused CE leaked a full-vocab logits dot"

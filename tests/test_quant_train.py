"""Quantized training path (ops/quant.py training section, docs/perf.md
"Quantized training").

Tier-1 keeps to pure units — per-channel scale/STE-vjp behavior, the
quant_dot_general modes against plain ``lax.dot_general``, QuantDense's
drop-in contract, knob validation + the fp8 capability fallback, and the
chunked-CE auto-select rule. Everything that runs train steps or compiles
a full program (the int8-vs-f32 loss-parity fit, the non-finite-guard fit,
the checkpoint/elastic-resume round-trip, the attribution pin) is
``@pytest.mark.slow`` under ``make verify-quant``.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from llmtrain_tpu.config.schemas import RunConfig
from llmtrain_tpu.models.gpt import GPTAdapter
from llmtrain_tpu.ops import quant
from llmtrain_tpu.ops.quant import (
    MATMUL_PRECISIONS,
    QuantDense,
    fake_quant,
    fp8_supported,
    quant_dot_general,
    quantize_array,
    resolve_matmul_precision,
)
from llmtrain_tpu.registry import initialize_registries

# docs/perf.md "Parity band": the documented N-step loss-trajectory rtols.
PARITY_RTOL = {"int8": 0.05, "int8_act": 0.05, "fp8": 0.10}

_DN = (((1,), (0,)), ((), ()))  # plain (M,K)x(K,N) contraction


@pytest.fixture(scope="module", autouse=True)
def _registries():
    initialize_registries()


def _gpt_cfg(extra: dict, *, vocab: int = 256, seq: int = 16, root=None, **trainer_kw):
    doc = {
        "run": {"name": "quant-test", "seed": 7, "device": "cpu"},
        "model": {
            "name": "gpt",
            "block_size": seq,
            "d_model": 32,
            "n_layers": 2,
            "n_heads": 2,
            "d_ff": 64,
            "dropout": 0.0,
            "vocab_size": vocab,
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


# --------------------------------------------------------------------------
# per-channel scales + straight-through fake_quant
# --------------------------------------------------------------------------


class TestScalesAndSTE:
    def test_per_channel_scales_and_zero_channel_guard(self):
        w = np.random.default_rng(0).normal(size=(8, 6)).astype(np.float32)
        w[:, 2] = 0.0  # all-zero output channel
        qa = quantize_array(jnp.asarray(w), reduce_axes=(0,))
        scale = np.asarray(qa.scale)
        assert scale.shape == (1, 6)  # keepdims: one scale per output unit
        # amax/127 per channel; the zero channel gets the 1.0 guard so the
        # round-trip is exact and gradients stay finite.
        expect = np.abs(w).max(axis=0) / 127.0
        np.testing.assert_allclose(scale[0, [0, 1, 3, 4, 5]], expect[[0, 1, 3, 4, 5]], rtol=1e-6)
        assert scale[0, 2] == 1.0
        deq = np.asarray(qa.dequantize())
        np.testing.assert_array_equal(deq[:, 2], 0.0)
        # symmetric int8: error bounded by half a step per channel
        assert np.all(np.abs(deq - w) <= scale / 2 + 1e-7)

    def test_fake_quant_straight_through_gradient(self):
        w = jnp.asarray(np.random.default_rng(1).normal(size=(5, 4)).astype(np.float32))
        grads = jax.grad(lambda x: jnp.sum(fake_quant(x, (0,))))(w)
        # STE: backward is the exact identity onto the f32 master weights.
        np.testing.assert_array_equal(np.asarray(grads), np.ones_like(np.asarray(w)))


# --------------------------------------------------------------------------
# quant_dot_general modes
# --------------------------------------------------------------------------


class TestQuantDotGeneral:
    def setup_method(self):
        rng = np.random.default_rng(2)
        self.lhs = jnp.asarray(rng.normal(size=(6, 16)).astype(np.float32))
        self.rhs = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
        self.ref = lax.dot_general(self.lhs, self.rhs, _DN)

    def test_f32_mode_is_stock_path(self):
        # None -> flax uses its default lax.dot_general: bit-identical
        # builds for everyone who never sets the knob.
        assert quant_dot_general("f32") is None

    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError, match="int4"):
            quant_dot_general("int4")

    @pytest.mark.parametrize("mode", ["int8", "int8_act"])
    def test_int8_modes_close_with_finite_grads(self, mode):
        dg = quant_dot_general(mode)
        out = dg(self.lhs, self.rhs, _DN)
        rel = float(jnp.max(jnp.abs(out - self.ref)) / jnp.max(jnp.abs(self.ref)))
        assert rel < 0.05, f"{mode} dot drifted {rel:.4f} from f32"
        gl, gr = jax.grad(lambda a, b: jnp.sum(dg(a, b, _DN) ** 2), argnums=(0, 1))(
            self.lhs, self.rhs
        )
        assert bool(jnp.all(jnp.isfinite(gl))) and bool(jnp.all(jnp.isfinite(gr)))

    @pytest.mark.skipif(not fp8_supported(), reason="backend has no fp8 dot")
    def test_fp8_forward_close_backward_exact_f32(self):
        dg = quant_dot_general("fp8")
        out = dg(self.lhs, self.rhs, _DN)
        rel = float(jnp.max(jnp.abs(out - self.ref)) / jnp.max(jnp.abs(self.ref)))
        assert rel < 0.10
        # The backward replays an exact f32 dot_general VJP on the saved
        # operands — gradients must MATCH the plain dot's, not just be
        # finite (an fp8 transpose would be neither).
        loss_q = lambda a, b: jnp.sum(dg(a, b, _DN) * 0.5)  # noqa: E731
        loss_f = lambda a, b: jnp.sum(lax.dot_general(a, b, _DN) * 0.5)  # noqa: E731
        gq = jax.grad(loss_q, argnums=(0, 1))(self.lhs, self.rhs)
        gf = jax.grad(loss_f, argnums=(0, 1))(self.lhs, self.rhs)
        for q, f in zip(gq, gf):
            np.testing.assert_array_equal(np.asarray(q), np.asarray(f))

    def test_jit_matches_eager(self):
        dg = quant_dot_general("int8")
        eager = dg(self.lhs, self.rhs, _DN)
        jitted = jax.jit(lambda a, b: dg(a, b, _DN))(self.lhs, self.rhs)
        np.testing.assert_allclose(np.asarray(eager), np.asarray(jitted), rtol=1e-6)


# --------------------------------------------------------------------------
# QuantDense drop-in contract
# --------------------------------------------------------------------------


class TestQuantDense:
    def test_same_param_tree_and_close_outputs(self):
        from flax import linen as nn

        x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 16)).astype(np.float32))
        dense = nn.Dense(8)
        qdense = QuantDense(8, matmul_precision="int8")
        pd = dense.init(jax.random.key(0), x)
        pq = qdense.init(jax.random.key(0), x)
        # Checkpoint compatibility both ways: identical tree AND identical
        # f32 master values (init never sees the quantizer).
        assert jax.tree.structure(pd) == jax.tree.structure(pq)
        for a, b in zip(jax.tree.leaves(pd), jax.tree.leaves(pq)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        out_d = dense.apply(pd, x)
        out_q = qdense.apply(pd, x)  # Dense params applied through QuantDense
        rel = float(jnp.max(jnp.abs(out_d - out_q)) / jnp.max(jnp.abs(out_d)))
        assert 0.0 < rel < 0.05  # quantized (so not bitwise) but close

    def test_f32_mode_bitwise_equals_dense(self):
        from flax import linen as nn

        x = jnp.asarray(np.random.default_rng(4).normal(size=(4, 16)).astype(np.float32))
        dense = nn.Dense(8)
        params = dense.init(jax.random.key(0), x)
        out_f32 = QuantDense(8, matmul_precision="f32").apply(params, x)
        np.testing.assert_array_equal(np.asarray(dense.apply(params, x)), np.asarray(out_f32))


# --------------------------------------------------------------------------
# knob validation + fp8 capability fallback
# --------------------------------------------------------------------------


class TestKnobValidation:
    def test_resolve_rejects_unknown_mode(self):
        with pytest.raises(ValueError, match="int4"):
            resolve_matmul_precision("int4")

    def test_adapter_rejects_unknown_mode(self):
        cfg = _gpt_cfg({"matmul_precision": "bf8"})
        with pytest.raises(ValueError, match="bf8"):
            GPTAdapter().build_model(cfg)

    @pytest.mark.parametrize("mode", MATMUL_PRECISIONS)
    def test_all_documented_modes_build(self, mode):
        model = GPTAdapter().build_model(_gpt_cfg({"matmul_precision": mode}))
        assert model.matmul_precision in MATMUL_PRECISIONS

    def test_fp8_falls_back_to_f32_with_one_warning(self, monkeypatch, caplog):
        monkeypatch.setattr(quant, "fp8_supported", lambda: False)
        monkeypatch.setattr(quant, "_FALLBACK_WARNED", set())
        with caplog.at_level(logging.WARNING, logger="llmtrain_tpu.ops.quant"):
            assert resolve_matmul_precision("fp8") == "f32"
            assert resolve_matmul_precision("fp8") == "f32"
        warnings = [r for r in caplog.records if "fp8" in r.getMessage()]
        assert len(warnings) == 1  # once per process, not per matmul


# --------------------------------------------------------------------------
# chunked-CE auto-select (model.extra.ce_auto_vocab)
# --------------------------------------------------------------------------


class TestChunkedCEAutoSelect:
    def test_large_vocab_auto_selects_chunked(self):
        model = GPTAdapter().build_model(_gpt_cfg({}, vocab=40000))
        assert model.loss_impl == "chunked_ce"

    def test_small_vocab_stays_dense(self):
        model = GPTAdapter().build_model(_gpt_cfg({}, vocab=256))
        assert model.loss_impl == "dense"

    def test_explicit_dense_wins_at_large_vocab(self):
        model = GPTAdapter().build_model(_gpt_cfg({"loss_impl": "dense"}, vocab=40000))
        assert model.loss_impl == "dense"

    def test_ce_auto_vocab_override(self):
        model = GPTAdapter().build_model(_gpt_cfg({"ce_auto_vocab": 128}, vocab=256))
        assert model.loss_impl == "chunked_ce"


# --------------------------------------------------------------------------
# fits: loss parity, guard, checkpoint/elastic resume (@slow)
# --------------------------------------------------------------------------


def _fit_losses(extra: dict, steps: int = 5, *, nonfinite_guard: bool = False):
    """N train steps on the tiny GPT straight through make_train_step;
    returns (per-step losses, final params, final metrics)."""
    from llmtrain_tpu.training.optimizer import build_optimizer
    from llmtrain_tpu.training.train_step import create_train_state, make_train_step

    cfg = _gpt_cfg(extra)
    adapter = GPTAdapter()
    model = adapter.build_model(cfg)
    tx = build_optimizer(cfg.trainer)
    rng = jax.random.key(0)
    params = adapter.init_params(model, cfg, rng)
    state = create_train_state(params, tx)
    step_fn = jax.jit(
        make_train_step(
            adapter, model, tx, grad_accum_steps=1, use_dropout=False,
            nonfinite_guard=nonfinite_guard,
        )
    )
    tokens = np.random.default_rng(0).integers(0, 256, size=(1, 4, 16), dtype=np.int32)
    batch = {
        "input_ids": jnp.asarray(tokens),
        "labels": jnp.asarray(tokens),
        "attention_mask": jnp.ones_like(jnp.asarray(tokens)),
    }
    losses = []
    metrics = {}
    for _ in range(steps):
        state, metrics = step_fn(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
    return losses, state.params, metrics


@pytest.mark.slow
class TestQuantFits:
    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    def test_loss_parity_vs_f32_within_band(self, mode):
        """The bench matrix's parity gate, as a unit: N quantized steps
        track the f32 trajectory within the documented rtol."""
        if mode == "fp8" and not fp8_supported():
            pytest.skip("backend has no fp8 dot; clean f32 fallback covered elsewhere")
        ref, _, _ = _fit_losses({"matmul_precision": "f32"})
        got, _, _ = _fit_losses({"matmul_precision": mode})
        max_rel = max(abs(q - f) / max(abs(f), 1e-6) for q, f in zip(got, ref))
        assert max_rel < PARITY_RTOL[mode], f"{mode} drifted {max_rel:.4f}"
        # and the f32 knob itself is bitwise the no-knob baseline
        base, _, _ = _fit_losses({})
        assert ref == base

    def test_grads_finite_under_nonfinite_guard(self):
        losses, params, metrics = _fit_losses(
            {"matmul_precision": "int8"}, nonfinite_guard=True
        )
        assert all(np.isfinite(losses))
        # guard never tripped: quantized grads are finite, no step skipped
        assert int(jax.device_get(metrics["nonfinite_count"])) == 0
        assert all(bool(jnp.all(jnp.isfinite(p))) for p in jax.tree.leaves(params))

    def test_checkpoint_elastic_resume_roundtrip_int8(self, tmp_path):
        """A checkpoint written under int8 training resumes bitwise — with
        the same knob AND with the knob flipped (f32 master weights mean
        matmul_precision is resume-mutable, like loss_impl)."""
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
            return Trainer(cfg, run_dir, NullTracker(), None).fit(resume_from=resume_from)

        full = fit(tmp_path / "full", {"matmul_precision": "int8"})
        ckpt = tmp_path / "full" / "checkpoints" / "step_000003.ckpt"
        assert ckpt.exists()
        resumed = fit(
            tmp_path / "resume_int8", {"matmul_precision": "int8"}, resume_from=str(ckpt)
        )
        assert resumed.resumed_from_step == 3
        assert resumed.final_loss == full.final_loss  # bitwise trajectory
        # knob change across resume: int8 checkpoint trains on at f32
        flipped = fit(
            tmp_path / "resume_f32", {"matmul_precision": "f32"}, resume_from=str(ckpt)
        )
        assert flipped.resumed_from_step == 3
        assert np.isfinite(flipped.final_loss)

    def test_attribution_pin_logits_absent_under_auto_chunked(self):
        """Satellite pin for the auto-select: under auto-selected
        chunked_ce no dot materializes the [B,T,V] logits — the dense
        run's aggregate ``dot`` bytes include the full logits tensor, the
        chunked run's stay below it (attribution-based, via the same
        aot_profile the `llmtrain profile` CLI uses)."""
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
                step_fn, (state, batch, jax.random.key(0)),
                name="pin", peaks=profiling.resolve_peaks(),
            )
            assert prof is not None
            rows = {r["op"]: r for r in prof["top_ops"]}
            return model.loss_impl, rows.get("dot", {"bytes_accessed": 0.0})["bytes_accessed"]

        logits_bytes = B * T * V * 4
        impl_dense, dense_bytes = dot_bytes({"loss_impl": "dense"})
        impl_auto, chunked_bytes = dot_bytes({"ce_auto_vocab": 1024})
        assert impl_dense == "dense" and impl_auto == "chunked_ce"
        assert dense_bytes >= logits_bytes, "dense CE must materialize the logits dot"
        assert chunked_bytes < logits_bytes, "chunked CE leaked a full-vocab logits dot"

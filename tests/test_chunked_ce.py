"""Chunked cross-entropy (ops/chunked_ce.py) vs the dense loss path.

The op must be numerically the dense masked CE (models/base.py) in both
value and gradient — it only changes WHERE the compute happens (streamed
vocab chunks + recompute-in-backward), never the math.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.linen import meta as nn_meta

from llmtrain_tpu.models.base import masked_ce_components
from llmtrain_tpu.models.gpt import GPT, GPTAdapter
from llmtrain_tpu.ops.chunked_ce import chunked_ce_components, chunked_ce_per_token

B, T, D, V = 2, 8, 16, 203  # V deliberately not a chunk multiple


def _data(seed=0, v=V):
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(v, D)) * 0.05, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=(B, T)), jnp.int32)
    return hidden, w, labels


def _dense_per_token(hidden, w, labels):
    logits = jnp.einsum("btd,vd->btv", hidden, w)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


class TestValue:
    @pytest.mark.parametrize("chunk", [64, 128, 203, 512])
    def test_matches_dense_any_chunking(self, chunk):
        hidden, w, labels = _data()
        got = chunked_ce_per_token(hidden, w, labels, chunk)
        want = _dense_per_token(hidden, w, labels)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)

    def test_components_match_masked_dense(self):
        hidden, w, labels = _data(3)
        mask = jnp.asarray(np.random.default_rng(4).integers(0, 2, (B, T)), jnp.int32)
        logits = jnp.einsum("btd,vd->btv", hidden, w)
        want_sum, want_tok = masked_ce_components(logits, labels, mask)
        got_sum, got_tok = chunked_ce_components(hidden, w, labels, mask, chunk=64)
        np.testing.assert_allclose(np.asarray(got_sum), np.asarray(want_sum), atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(got_tok), np.asarray(want_tok))

    def test_jit_and_single_chunk(self):
        hidden, w, labels = _data(5)
        f = jax.jit(lambda h, w, l: chunked_ce_per_token(h, w, l, 1024))
        np.testing.assert_allclose(
            np.asarray(f(hidden, w, labels)),
            np.asarray(_dense_per_token(hidden, w, labels)),
            atol=1e-5,
            rtol=1e-5,
        )


class TestGrad:
    @pytest.mark.parametrize("chunk", [64, 203])
    def test_grads_match_dense_autodiff(self, chunk):
        hidden, w, labels = _data(7)
        mask = jnp.ones((B, T), jnp.float32)

        def loss_chunked(h, w_):
            s, t = chunked_ce_components(h, w_, labels, mask, chunk=chunk)
            return jnp.sum(s) / jnp.sum(t)

        def loss_dense(h, w_):
            per = _dense_per_token(h, w_, labels)
            return jnp.mean(per)

        gc_h, gc_w = jax.grad(loss_chunked, argnums=(0, 1))(hidden, w)
        gd_h, gd_w = jax.grad(loss_dense, argnums=(0, 1))(hidden, w)
        np.testing.assert_allclose(np.asarray(gc_h), np.asarray(gd_h), atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(gc_w), np.asarray(gd_w), atol=1e-5, rtol=1e-4)

    def test_masked_grads(self):
        """Masked positions contribute nothing to either gradient."""
        hidden, w, labels = _data(9)
        mask = jnp.ones((B, T), jnp.float32).at[0, T // 2 :].set(0.0)

        def loss(h, w_):
            s, t = chunked_ce_components(h, w_, labels, mask, chunk=64)
            return jnp.sum(s) / jnp.sum(t)

        g_h = jax.grad(loss)(hidden, w)
        assert np.allclose(np.asarray(g_h)[0, T // 2 :], 0.0, atol=1e-7)


def _gpt(tie: bool, loss_impl: str):
    model = GPT(
        vocab_size=V,
        block_size=T,
        d_model=D,
        n_layers=2,
        n_heads=4,
        d_ff=32,
        dropout=0.0,
        tie_embeddings=tie,
        loss_impl=loss_impl,
        ce_chunk=64,
    )
    ids = jnp.zeros((1, T), jnp.int32)
    params = nn_meta.unbox(model.init(jax.random.key(0), ids, deterministic=True))[
        "params"
    ]
    return model, params


class TestAdapterIntegration:
    @pytest.mark.parametrize(
        "tie",
        [
            pytest.param(True, id="tied"),
            # budget: untied rides test-all; the tied run keeps the
            # adapter-parity contract tier-1
            pytest.param(False, id="untied", marks=pytest.mark.slow),
        ],
    )
    def test_same_loss_and_grads_as_dense_path(self, tie):
        rng = np.random.default_rng(11)
        batch = {
            "input_ids": jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
            "attention_mask": jnp.ones((B, T), jnp.int32),
        }
        adapter = GPTAdapter()
        dense_model, params = _gpt(tie, "dense")
        chunk_model, _ = _gpt(tie, "chunked_ce")

        def loss_with(model):
            def f(p):
                s, t = adapter.compute_loss_components(model, p, batch)
                return jnp.sum(s) / jnp.sum(t)

            return f

        ld, gd = jax.value_and_grad(loss_with(dense_model))(params)
        lc, gc = jax.value_and_grad(loss_with(chunk_model))(params)
        np.testing.assert_allclose(float(lc), float(ld), atol=1e-5, rtol=1e-5)
        for (pd, vd), (pc, vc) in zip(
            jax.tree_util.tree_leaves_with_path(gd),
            jax.tree_util.tree_leaves_with_path(gc),
            strict=True,
        ):
            assert pd == pc
            np.testing.assert_allclose(
                np.asarray(vd), np.asarray(vc), atol=2e-5, rtol=1e-3,
                err_msg=jax.tree_util.keystr(pd),
            )

    def test_trains_end_to_end(self):
        """Few train steps through the real train_step with chunked CE."""
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.tracking.base import NullTracker
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()
        cfg = RunConfig.model_validate(
            {
                "run": {"name": "chunked-ce", "seed": 0, "device": "cpu"},
                "model": {
                    "name": "gpt",
                    "block_size": 8,
                    "d_model": 16,
                    "n_layers": 1,
                    "n_heads": 4,
                    "d_ff": 32,
                    "dropout": 0.0,
                    "vocab_size": 64,
                    "extra": {"tokenizer": "byte", "loss_impl": "chunked_ce", "ce_chunk": 32},
                },
                "data": {"name": "dummy_text"},
                "trainer": {
                    "max_steps": 10,
                    "micro_batch_size": 2,
                    "grad_accum_steps": 1,
                    "warmup_steps": 2,
                    "log_every_steps": 5,
                    "eval_every_steps": 10,
                    "save_every_steps": 10,
                },
                "mlflow": {"enabled": False},
            }
        )
        trainer = Trainer(cfg, run_dir=None, tracker=NullTracker())
        result = trainer.fit()
        assert result.final_step == 10
        assert result.final_loss < result.first_step_loss


class TestKnobValidation:
    """Review findings: unknown loss_impl values and unsupported model
    families must fail loudly, not silently run dense."""

    def _cfg(self, model_name, extra):
        from llmtrain_tpu.config.schemas import RunConfig

        return RunConfig.model_validate(
            {
                "run": {"name": "x", "device": "cpu"},
                "model": {
                    "name": model_name,
                    "block_size": 8,
                    "d_model": 16,
                    "n_layers": 1,
                    "n_heads": 4,
                    "d_ff": 32,
                    "dropout": 0.0,
                    "vocab_size": 64,
                    "extra": {"tokenizer": "byte", **extra},
                },
                "data": {"name": "dummy_text"},
                "trainer": {"max_steps": 1, "micro_batch_size": 2, "warmup_steps": 0},
                "mlflow": {"enabled": False},
            }
        )

    @pytest.mark.parametrize("value", ["chunked", "typo"])
    def test_unknown_loss_impl_rejected(self, value):
        with pytest.raises(ValueError, match="loss_impl"):
            GPTAdapter().build_model(self._cfg("gpt", {"loss_impl": value}))

    def test_gpt_moe_chunked_matches_dense(self):
        """MoE composes with chunked CE: same CE + router-aux loss and
        gradients as the dense path."""
        from llmtrain_tpu.models.gpt_moe import GPTMoEAdapter

        adapter = GPTMoEAdapter()
        rng = np.random.default_rng(23)
        batch = {
            "input_ids": jnp.asarray(rng.integers(0, 64, (2, 8)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, 64, (2, 8)), jnp.int32),
            "attention_mask": jnp.ones((2, 8), jnp.int32),
        }

        def build(loss_impl):
            cfg = self._cfg(
                "gpt_moe",
                {"n_experts": 4, "capacity_factor": 2.0, "loss_impl": loss_impl,
                 "ce_chunk": 32},
            )
            model = adapter.build_model(cfg)
            params = nn_meta.unbox(
                model.init(jax.random.key(0), batch["input_ids"], deterministic=True)
            )["params"]
            return model, params

        dense_model, params = build("dense")
        chunk_model, _ = build("chunked_ce")

        def loss_with(model):
            def f(p):
                s, t = adapter.compute_loss_components(model, p, batch)
                return jnp.sum(s) / jnp.sum(t)

            return f

        ld, gd = jax.value_and_grad(loss_with(dense_model))(params)
        lc, gc = jax.value_and_grad(loss_with(chunk_model))(params)
        np.testing.assert_allclose(float(lc), float(ld), atol=1e-5, rtol=1e-5)
        for (pd, vd), (pc, vc) in zip(
            jax.tree_util.tree_leaves_with_path(gd),
            jax.tree_util.tree_leaves_with_path(gc),
            strict=True,
        ):
            assert pd == pc
            np.testing.assert_allclose(
                np.asarray(vd), np.asarray(vc), atol=2e-5, rtol=1e-3,
                err_msg=jax.tree_util.keystr(pd),
            )


class TestShardedMesh:
    """chunked_ce composes with tensor/fsdp/sequence sharding: the vocab
    reshape inside the scan must not change results under a sharded mesh
    (verified bit-identical to the dense path on the virtual 8-device
    mesh)."""

    @pytest.mark.parametrize(
        "mesh",
        [
            {"tensor": 2, "data": 4},
            {"tensor": 2, "fsdp": 2, "sequence": 2, "data": 1},
        ],
        ids=["tp-dp", "tp-fsdp-sp"],
    )
    def test_matches_dense_on_mesh(self, mesh):
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.tracking.base import NullTracker
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()

        def run(loss_impl):
            cfg = RunConfig.model_validate(
                {
                    "run": {"name": "cce-mesh", "seed": 0, "device": "cpu"},
                    "model": {
                        "name": "gpt",
                        "block_size": 8,
                        "d_model": 32,
                        "n_layers": 2,
                        "n_heads": 4,
                        "d_ff": 64,
                        "dropout": 0.0,
                        "vocab_size": 64,
                        "extra": {
                            "tokenizer": "byte",
                            "loss_impl": loss_impl,
                            "ce_chunk": 32,
                        },
                    },
                    "data": {"name": "dummy_text"},
                    "trainer": {
                        "max_steps": 3,
                        "micro_batch_size": 2,
                        "grad_accum_steps": 2,
                        "warmup_steps": 0,
                        "log_every_steps": 1,
                        "eval_every_steps": 3,
                        "save_every_steps": 3,
                    },
                    "distributed": {"mesh": mesh},
                    "mlflow": {"enabled": False},
                }
            )
            trainer = Trainer(cfg, run_dir=None, tracker=NullTracker())
            result = trainer.fit()
            return result.final_loss, result.final_val_loss

        dense = run("dense")
        chunked = run("chunked_ce")
        assert abs(dense[0] - chunked[0]) < 1e-5
        assert abs(dense[1] - chunked[1]) < 1e-5


def test_gpt_pipeline_rejects_unknown_loss_impl():
    """Unknown values fail loudly, not silently run dense."""
    from llmtrain_tpu.models.gpt_pipeline import PipelineGPTAdapter

    cfg = TestKnobValidation()._cfg("gpt_pipeline", {"loss_impl": "chunked"})
    with pytest.raises(ValueError, match="loss_impl"):
        PipelineGPTAdapter().build_model(cfg)


class TestPipelineChunked:
    """gpt_pipeline composes with chunked CE: the lm_head applies outside
    the stage shard_map, so the streamed loss drops in like for gpt."""

    def _run(self, loss_impl, mesh):
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.tracking.base import NullTracker
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()
        cfg = RunConfig.model_validate(
            {
                "run": {"name": "pipe-cce", "seed": 0, "device": "cpu"},
                "model": {
                    "name": "gpt_pipeline",
                    "block_size": 8,
                    "d_model": 32,
                    "n_layers": 4,
                    "n_heads": 4,
                    "d_ff": 64,
                    "dropout": 0.0,
                    "vocab_size": 64,
                    "extra": {
                        "tokenizer": "byte",
                        "pipeline_microbatches": 2,
                        "loss_impl": loss_impl,
                        "ce_chunk": 32,
                    },
                },
                "data": {"name": "dummy_text"},
                "trainer": {
                    "max_steps": 3,
                    "micro_batch_size": 4,
                    "grad_accum_steps": 1,
                    "warmup_steps": 0,
                    "log_every_steps": 1,
                    "eval_every_steps": 3,
                    "save_every_steps": 3,
                },
                "distributed": {"mesh": mesh},
                "mlflow": {"enabled": False},
            }
        )
        trainer = Trainer(cfg, run_dir=None, tracker=NullTracker())
        result = trainer.fit()
        return result.final_loss

    def test_matches_dense_data_parallel_mesh(self):
        mesh = {"data": -1}  # all 8 virtual devices, no pipeline
        assert abs(self._run("dense", mesh) - self._run("chunked_ce", mesh)) < 1e-5

    @pytest.mark.slow  # budget: tier-1 sibling test_matches_dense_data_parallel_mesh; pipeline mesh rides test-all
    def test_matches_dense_on_pipeline_mesh(self):
        mesh = {"pipeline": 2, "data": -1}  # 2 stages x 4 data shards
        assert abs(self._run("dense", mesh) - self._run("chunked_ce", mesh)) < 1e-5


def test_ce_chunk_must_be_positive():
    tk = TestKnobValidation()
    with pytest.raises(ValueError, match="ce_chunk"):
        GPTAdapter().build_model(
            tk._cfg("gpt", {"loss_impl": "chunked_ce", "ce_chunk": 0})
        )
    from llmtrain_tpu.models.gpt_pipeline import PipelineGPTAdapter

    with pytest.raises(ValueError, match="ce_chunk"):
        PipelineGPTAdapter().build_model(
            tk._cfg("gpt_pipeline", {"loss_impl": "chunked_ce", "ce_chunk": -8})
        )


class TestZLoss:
    """PaLM z-loss (z * log(Z)^2 per token) in both loss paths."""

    def test_analytic_value(self):
        """For a hand-checkable 1-token case the z-loss term is exactly
        z * logsumexp(logits)^2."""
        hidden = jnp.ones((1, 2, 2), jnp.float32)
        w = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [0.5, 0.5]], jnp.float32)
        labels = jnp.zeros((1, 2), jnp.int32)
        logits = np.asarray(hidden @ w.T)
        lse = np.log(np.exp(logits).sum(-1))
        base = np.asarray(chunked_ce_per_token(hidden, w, labels, 2, None, 0.0))
        with_z = np.asarray(chunked_ce_per_token(hidden, w, labels, 2, None, 0.1))
        np.testing.assert_allclose(with_z - base, 0.1 * lse**2, atol=1e-6)

    def test_chunked_matches_dense_value_and_grads(self):
        hidden, w, labels = _data(31)
        mask = jnp.ones((B, T), jnp.float32)
        z = 1e-2

        def loss_chunked(h, w_):
            s, t = chunked_ce_components(h, w_, labels, mask, chunk=64, z_loss=z)
            return jnp.sum(s) / jnp.sum(t)

        def loss_dense(h, w_):
            logits = jnp.einsum("btd,vd->btv", h, w_)
            s, t = masked_ce_components(logits, labels, mask, z_loss=z)
            return jnp.sum(s) / jnp.sum(t)

        lc, (gch, gcw) = jax.value_and_grad(loss_chunked, argnums=(0, 1))(hidden, w)
        ld, (gdh, gdw) = jax.value_and_grad(loss_dense, argnums=(0, 1))(hidden, w)
        np.testing.assert_allclose(float(lc), float(ld), atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(gch), np.asarray(gdh), atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(gcw), np.asarray(gdw), atol=1e-5, rtol=1e-4)

    def test_adapter_paths_agree_with_z(self):
        """gpt with z_loss: dense and chunked loss paths still match."""
        rng = np.random.default_rng(37)
        batch = {
            "input_ids": jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32),
            "attention_mask": jnp.ones((B, T), jnp.int32),
        }
        adapter = GPTAdapter()

        def build(loss_impl):
            model = GPT(
                vocab_size=V, block_size=T, d_model=D, n_layers=1, n_heads=4,
                d_ff=32, dropout=0.0, loss_impl=loss_impl, ce_chunk=64,
                z_loss=1e-3,
            )
            ids = jnp.zeros((1, T), jnp.int32)
            params = nn_meta.unbox(
                model.init(jax.random.key(0), ids, deterministic=True)
            )["params"]
            return model, params

        dense_model, params = build("dense")
        chunk_model, _ = build("chunked_ce")
        sd, td = adapter.compute_loss_components(dense_model, params, batch)
        sc, tc = adapter.compute_loss_components(chunk_model, params, batch)
        np.testing.assert_allclose(np.asarray(sc), np.asarray(sd), atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(tc), np.asarray(td))

    def test_negative_z_rejected(self):
        from llmtrain_tpu.models.gpt_pipeline import PipelineGPTAdapter

        tk = TestKnobValidation()
        with pytest.raises(ValueError, match="z_loss"):
            GPTAdapter().build_model(tk._cfg("gpt", {"z_loss": -0.1}))
        with pytest.raises(ValueError, match="z_loss"):
            PipelineGPTAdapter().build_model(tk._cfg("gpt_pipeline", {"z_loss": -0.1}))

    def test_z_zero_is_reference_behavior(self):
        """Default z=0 leaves the loss bit-identical to plain CE."""
        hidden, w, labels = _data(41)
        a = chunked_ce_per_token(hidden, w, labels, 64, None, 0.0)
        b = chunked_ce_per_token(hidden, w, labels, 64)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

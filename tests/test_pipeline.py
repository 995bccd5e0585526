"""Pipeline parallelism: GPipe executor + gpt_pipeline model.

New capability beyond the reference (SURVEY §2.3: PP absent there). The
technique mirrors the rest of the suite: a real 8-virtual-device CPU mesh
(conftest) exercises the actual shard_map/ppermute schedule in one
process, with equivalence against the sequential application of the same
stacked params as the correctness oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from llmtrain_tpu.config import RunConfig
from llmtrain_tpu.parallel.pipeline import gpipe_apply, pipeline_degree
from llmtrain_tpu.registry import initialize_registries
from llmtrain_tpu.tracking.base import NullTracker
from llmtrain_tpu.training.trainer import Trainer


def _mesh(pipeline=4, data=2):
    devs = np.array(jax.devices()[: pipeline * data]).reshape(pipeline, data)
    return Mesh(devs, ("pipeline", "data"))


def _stage_fn(p, h):
    def layer(h, lp):
        return jnp.tanh(h @ lp[0] + lp[1]), None

    h, _ = jax.lax.scan(layer, h, (p["w"], p["b"]))
    return h


def _stack_params(L=8, D=16, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {
        "w": jax.random.normal(k1, (L, D, D)) * 0.1,
        "b": jax.random.normal(k2, (L, D)) * 0.1,
    }


class TestGPipeExecutor:
    def test_forward_matches_sequential(self):
        params = _stack_params()
        x = jax.random.normal(jax.random.key(2), (8, 4, 16))
        ref = _stage_fn(params, x)
        mesh = _mesh()
        with mesh:
            y = jax.jit(
                lambda p, x: gpipe_apply(_stage_fn, p, x, mesh, n_microbatches=4)
            )(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)

    @pytest.mark.parametrize("n_microbatches", [1, 2, 8])
    def test_microbatch_counts(self, n_microbatches):
        params = _stack_params(seed=3)
        x = jax.random.normal(jax.random.key(4), (16, 4, 16))
        ref = _stage_fn(params, x)
        mesh = _mesh()
        with mesh:
            y = jax.jit(
                lambda p, x: gpipe_apply(
                    _stage_fn, p, x, mesh, n_microbatches=n_microbatches
                )
            )(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)

    def test_gradients_match_sequential(self):
        params = _stack_params(seed=5)
        x = jax.random.normal(jax.random.key(6), (8, 4, 16))
        mesh = _mesh()

        def loss_pipe(p):
            return (gpipe_apply(_stage_fn, p, x, mesh, n_microbatches=4) ** 2).sum()

        def loss_ref(p):
            return (_stage_fn(p, x) ** 2).sum()

        with mesh:
            g_pipe = jax.jit(jax.grad(loss_pipe))(params)
        g_ref = jax.grad(loss_ref)(params)
        for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_degree_one_is_sequential(self):
        params = _stack_params(seed=7)
        x = jax.random.normal(jax.random.key(8), (4, 4, 16))
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("pipeline", "data"))
        with mesh:
            y = gpipe_apply(_stage_fn, params, x, mesh, n_microbatches=4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(_stage_fn(params, x)), atol=1e-6)

    def test_pipeline_degree_helper(self):
        assert pipeline_degree(None) == 1
        assert pipeline_degree(_mesh()) == 4


def _pp_cfg(**overrides):
    model = {
        "name": "gpt_pipeline",
        "block_size": 16,
        "d_model": 32,
        "n_layers": 4,
        "n_heads": 4,
        "d_ff": 64,
        "dropout": 0.0,
        "vocab_size": 32,
        "extra": {"tokenizer": "byte", "pipeline_microbatches": 2},
    }
    model.update(overrides.pop("model", {}))
    raw = {
        "run": {"name": "pp", "seed": 0, "device": "cpu"},
        "model": model,
        "data": {"name": "dummy_text"},
        "trainer": {
            "max_steps": 20,
            "micro_batch_size": 8,
            "grad_accum_steps": 2,
            "warmup_steps": 5,
            "log_every_steps": 10,
            "eval_every_steps": 10,
            "save_every_steps": 100,
        },
        "distributed": {"enabled": False, "mesh": {"pipeline": 4, "data": 2}},
    }
    raw.update(overrides)
    return RunConfig.model_validate(raw)


class TestPipelineGPT:
    def setup_method(self):
        initialize_registries()

    def _build(self, cfg):
        from llmtrain_tpu.models.gpt_pipeline import PipelineGPTAdapter

        adapter = PipelineGPTAdapter()
        model = adapter.build_model(cfg)
        params = adapter.init_params(model, cfg, jax.random.key(0))
        return adapter, model, params

    def test_pipelined_forward_matches_sequential(self):
        cfg = _pp_cfg()
        _, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 32)
        ref = model.apply({"params": params}, tokens)  # no mesh -> sequential
        mesh = _mesh()
        with mesh:
            out = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_pipelined_grads_match_sequential(self):
        cfg = _pp_cfg()
        adapter, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(2), (8, 16), 0, 32)
        batch = {
            "input_ids": tokens,
            "labels": tokens,
            "attention_mask": jnp.ones_like(tokens),
        }

        def loss(p):
            ls, tk = adapter.compute_loss_components(model, p, batch)
            return jnp.sum(ls) / jnp.sum(tk)

        g_ref = jax.grad(loss)(params)
        with _mesh():
            g_pp = jax.jit(jax.grad(loss))(params)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_indivisible_real_batch_raises_on_pipeline_mesh(self):
        """A real batch that cannot engage the pipeline is an ERROR on a
        multi-stage mesh — 'running without pipeline parallelism' would
        materialize every stage's layers on every device (an OOM at real
        sizes, previously reached via a warning; VERDICT r2 weak #5)."""
        cfg = _pp_cfg()
        _, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(3), (6, 16), 0, 32)
        with _mesh():
            with pytest.raises(ValueError, match="not divisible"):
                model.apply({"params": params}, tokens)

    def test_batch_one_probe_still_falls_back(self):
        """The batch-1 param-init probe (models/base.py) must keep tracing
        sequentially on a pipeline mesh."""
        cfg = _pp_cfg()
        _, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(3), (1, 16), 0, 32)
        ref = model.apply({"params": params}, tokens)
        with _mesh():
            out = model.apply({"params": params}, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    @pytest.mark.parametrize("attention", ["dense", "flash"])
    def test_masked_pipelined_matches_sequential(self, attention):
        """Padding masks inside pipelined attention: the executor hands
        each stage tick its microbatch's mask slice, so pipelined and
        sequential execution agree on padded batches."""
        cfg = _pp_cfg(model={"attention": attention})
        _, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(5), (8, 16), 0, 32)
        lens = np.asarray([16, 9, 16, 3, 12, 16, 7, 16])
        mask = jnp.asarray(
            (np.arange(16)[None, :] < lens[:, None]).astype(np.int32)
        )
        ref = model.apply({"params": params}, tokens, mask)
        mesh = _mesh()
        with mesh:
            out = jax.jit(
                lambda p, t, m: model.apply({"params": p}, t, m)
            )(params, tokens, mask)
        # Compare valid rows (padded rows' logits are zeroed-garbage by
        # contract; the loss masks them).
        valid = np.asarray(mask)[:, :, None]
        np.testing.assert_allclose(
            np.asarray(out) * valid, np.asarray(ref) * valid, atol=1e-5
        )

    @pytest.mark.parametrize("attention", ["dense", "flash"])
    def test_masked_pipelined_grads_match_sequential(self, attention):
        cfg = _pp_cfg(model={"attention": attention})
        adapter, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(6), (8, 16), 0, 32)
        lens = np.asarray([16, 9, 16, 3, 12, 16, 7, 14])
        mask = jnp.asarray(
            (np.arange(16)[None, :] < lens[:, None]).astype(np.int32)
        )
        batch = {"input_ids": tokens, "labels": tokens, "attention_mask": mask}

        def loss(p):
            ls, tk = adapter.compute_loss_components(model, p, batch)
            return jnp.sum(ls) / jnp.sum(tk)

        g_ref = jax.grad(loss)(params)
        with _mesh():
            g_pp = jax.jit(jax.grad(loss))(params)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    @pytest.mark.parametrize("attention", ["dense", "flash"])
    def test_windowed_pipelined_matches_sequential(self, attention):
        """sliding_window flows into every stage's attention: the
        pipelined result equals the single-device stack, and the window
        actually binds (differs from full causal)."""
        cfg = _pp_cfg(
            model={
                "attention": attention,
                "extra": {
                    "tokenizer": "byte",
                    "pipeline_microbatches": 2,
                    "sliding_window": 5,
                },
            }
        )
        _, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(7), (8, 16), 0, 32)
        ref = model.apply({"params": params}, tokens)
        with _mesh():
            out = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        full = model.clone(sliding_window=0).apply({"params": params}, tokens)
        assert np.abs(np.asarray(full) - np.asarray(ref)).max() > 1e-4

    def test_assume_packed_drops_mask(self):
        """assume_packed ignores the mask operand entirely — identical
        output with and without one (all-ones equivalence is the packed
        contract)."""
        cfg = _pp_cfg(model={"extra": {"tokenizer": "byte",
                                       "pipeline_microbatches": 2,
                                       "assume_packed": True}})
        _, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(7), (4, 16), 0, 32)
        half = jnp.asarray(
            (np.arange(16)[None, :] < 8).astype(np.int32)
        ) * jnp.ones((4, 1), jnp.int32)
        a = model.apply({"params": params}, tokens)
        b = model.apply({"params": params}, tokens, half)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("attention", ["dense", "flash"])
    def test_gqa_pipelined_matches_sequential(self, attention):
        """Grouped-query attention (split stacked q/kv kernels) under the
        pipeline schedule equals sequential execution; flash consumes the
        narrow K/V natively."""
        cfg = _pp_cfg(
            model={
                "attention": attention,
                "extra": {"tokenizer": "byte", "pipeline_microbatches": 2,
                          "n_kv_heads": 2},
            }
        )
        _, model, params = self._build(cfg)
        assert "q_kernel" in params and "qkv_kernel" not in params
        tokens = jax.random.randint(jax.random.key(9), (8, 16), 0, 32)
        ref = model.apply({"params": params}, tokens)
        with _mesh():
            out = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    @pytest.mark.slow  # budget: tier-1 sibling test_pp_tp_compose_matches_sequential; GQA compose rides test-all
    def test_gqa_pp_tp_compose_matches_sequential(self):
        """GQA under pipeline x tensor: the split q/kv sharding specs
        shard K/V heads over the tensor axis; forward equals sequential
        execution of the same params."""
        cfg = _pp_cfg(
            model={
                "extra": {"tokenizer": "byte", "pipeline_microbatches": 2,
                          "n_kv_heads": 2},
            },
            distributed={"enabled": False,
                         "mesh": {"pipeline": 2, "tensor": 2, "data": 2}},
        )
        _, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(11), (8, 16), 0, 32)
        ref = model.apply({"params": params}, tokens)
        devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
        mesh = Mesh(devs, ("pipeline", "tensor", "data"))
        with mesh:
            out = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_gqa_pp_tp_kv_heads_must_divide(self):
        """A tensor axis bigger than n_kv_heads fails at startup with a
        clear message (validate_mesh), not an opaque sharding error."""
        cfg = _pp_cfg(
            model={"extra": {"tokenizer": "byte", "pipeline_microbatches": 2,
                             "n_kv_heads": 1}},
            distributed={"enabled": False,
                         "mesh": {"pipeline": 2, "tensor": 2, "data": 2}},
        )
        with pytest.raises(ValueError, match="n_kv_heads"):
            Trainer(cfg, None, NullTracker())

    def test_batch_divisor_hook(self):
        from llmtrain_tpu.models.gpt_pipeline import PipelineGPTAdapter

        cfg = _pp_cfg()
        adapter = PipelineGPTAdapter()
        # {pipeline: 4, data: 2} x microbatches 2 -> rows must divide 4.
        assert adapter.batch_divisor(cfg, _mesh()) == 4
        assert adapter.batch_divisor(cfg, None) == 1

    def test_validate_mesh_rejects_indivisible_training_batch(self):
        trainer_cfg = {
            "max_steps": 2,
            "micro_batch_size": 3,  # not divisible by microbatches (2)
            "grad_accum_steps": 1,
            "warmup_steps": 0,
        }
        cfg = _pp_cfg(trainer=trainer_cfg)
        with pytest.raises(ValueError, match="pipeline_microbatches"):
            Trainer(cfg, None, NullTracker())

    def test_eval_pads_to_divisor_and_matches_sequential(self):
        """Eval batches are padded up to data_shards × microbatches
        (zero-masked rows are exact under token-weighted aggregation), so
        the eval pass runs the pipeline schedule — the dummy val set (25
        examples) is NOT divisible by 4, and an unpadded batch would now
        raise (see test_indivisible_real_batch_raises_on_pipeline_mesh).
        The padded pipelined val loss equals sequential eval of the same
        (untrained, same-seed) params."""
        pp = Trainer(_pp_cfg(), None, NullTracker())
        seq = Trainer(
            _pp_cfg(distributed={"enabled": False, "mesh": {"data": 8}}),
            None,
            NullTracker(),
        )
        m_pp = pp._evaluate(step=0, max_steps=1)
        m_seq = seq._evaluate(step=0, max_steps=1)
        assert m_pp is not None and m_seq is not None
        assert abs(m_pp["val/loss"] - m_seq["val/loss"]) < 1e-5

    def test_trainer_loss_decreases_on_pipeline_mesh(self):
        trainer = Trainer(_pp_cfg(), None, NullTracker())
        result = trainer.fit()
        assert result.first_step_loss is not None
        assert result.final_loss < result.first_step_loss
        assert result.final_val_loss is not None

    def test_layer_params_sharded_over_pipeline(self):
        """Stacked block params must actually shard their leading dim."""
        trainer = Trainer(_pp_cfg(), None, NullTracker())
        from flax.core import meta as nn_meta

        params = nn_meta.unbox(trainer.state.params)
        qkv = params["qkv_kernel"]
        spec = qkv.sharding.spec
        assert spec and spec[0] == "pipeline", spec

    def test_plain_gpt_rejects_pipeline_mesh(self):
        cfg = _pp_cfg(model={"name": "gpt", "extra": {"tokenizer": "byte"}})
        with pytest.raises(ValueError, match="does not stack its layers"):
            Trainer(cfg, None, NullTracker())

    def test_layers_must_divide_stages(self):
        cfg = _pp_cfg(model={"n_layers": 3})
        with pytest.raises(ValueError, match="pipeline stages"):
            Trainer(cfg, None, NullTracker())

    def test_rejects_dropout(self):
        from llmtrain_tpu.models.gpt_pipeline import PipelineGPTAdapter

        cfg = _pp_cfg(model={"dropout": 0.1})
        with pytest.raises(ValueError, match="dropout"):
            PipelineGPTAdapter().build_model(cfg)

    def test_rejects_fsdp_sharding(self):
        cfg = _pp_cfg(
            distributed={"enabled": False, "mesh": {"pipeline": 4, "fsdp": 2}}
        )
        with pytest.raises(ValueError, match="fsdp"):
            Trainer(cfg, None, NullTracker()).fit()

    def test_pp_tp_compose_matches_sequential(self):
        """DP x PP x TP: {pipeline: 2, tensor: 2, data: 2} — stage params
        shard whole heads / mlp width over tensor, with explicit Megatron
        row-parallel psums inside the stage. Forward and grads must match
        sequential execution of the same params."""
        cfg = _pp_cfg(
            distributed={
                "enabled": False,
                "mesh": {"pipeline": 2, "tensor": 2, "data": 2},
            }
        )
        adapter, model, params = self._build(cfg)
        tokens = jax.random.randint(jax.random.key(9), (8, 16), 0, 32)
        batch = {
            "input_ids": tokens,
            "labels": tokens,
            "attention_mask": jnp.ones_like(tokens),
        }
        ref = model.apply({"params": params}, tokens)
        mesh = Mesh(
            np.array(jax.devices()[:8]).reshape(2, 2, 2),
            ("pipeline", "tensor", "data"),
        )
        with mesh:
            out = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

        def loss(p):
            ls, tk = adapter.compute_loss_components(model, p, batch)
            return jnp.sum(ls) / jnp.sum(tk)

        g_ref = jax.grad(loss)(params)
        with mesh:
            g_pp = jax.jit(jax.grad(loss))(params)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_pp_tp_trainer_loss_decreases(self):
        cfg = _pp_cfg(
            distributed={
                "enabled": False,
                "mesh": {"pipeline": 2, "tensor": 2, "data": 2},
            }
        )
        result = Trainer(cfg, None, NullTracker()).fit()
        assert result.final_loss < result.first_step_loss


class TestStageMLPActivationEvaluatedOnce:
    """A stage's MLP goes through ``models/gpt.py:gelu_once`` like the
    ``gpt`` block's: under a gradient the value and the derivative come
    from one float32 erf. Against the same model with the plain
    ``nn.gelu(m, approximate=False)`` the stage was written with: on one
    device (the rematerialised ``scan``), through the ``shard_map`` ring,
    and with the tensor-parallel shards inside a stage."""

    MESHES = {
        "scan": None,
        "ring": {"pipeline": 4, "data": 2},
        "ring_tp": {"pipeline": 2, "tensor": 2, "data": 2},
    }

    def setup_method(self):
        initialize_registries()

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("layout", list(MESHES))
    def test_value_and_gradients_match_the_plain_stage(self, monkeypatch, layout, dtype):
        import contextlib

        import flax.linen as nn

        from llmtrain_tpu.models import gpt_pipeline

        axes = self.MESHES[layout] or {"pipeline": 4, "data": 2}
        cfg = _pp_cfg(
            model={"dtype": dtype, "param_dtype": "float32"},
            distributed={"enabled": False, "mesh": axes},
        )
        adapter = gpt_pipeline.PipelineGPTAdapter()
        model = adapter.build_model(cfg)
        params = adapter.init_params(model, cfg, jax.random.key(0))
        # Pre-activations that reach both tails, as a trained model's do.
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: 40.0 * leaf if "fc_kernel" in jax.tree_util.keystr(path) else leaf, params
        )
        tokens = jax.random.randint(jax.random.key(2), (8, 16), 0, 32)
        batch = {"input_ids": tokens, "labels": tokens, "attention_mask": jnp.ones_like(tokens)}

        def loss(p):
            ls, tk = adapter.compute_loss_components(model, p, batch)
            return jnp.sum(ls) / jnp.sum(tk)

        def run():
            mesh = contextlib.nullcontext()
            if self.MESHES[layout]:
                mesh = Mesh(np.array(jax.devices()[:8]).reshape(*axes.values()), tuple(axes))
            with mesh:
                return jax.jit(jax.value_and_grad(loss))(params), jax.jit(loss)(params)

        (value, grads), inference = run()
        # The gradient's program holds the forward rule's barrier; the plain one none.
        assert "optimization_barrier" in str(jax.make_jaxpr(jax.grad(loss))(params))
        monkeypatch.setattr(gpt_pipeline, "gelu_once", lambda h: nn.gelu(h, approximate=False))
        (ref_value, ref_grads), ref_inference = run()
        assert "optimization_barrier" not in str(jax.make_jaxpr(jax.grad(loss))(params))

        f32 = dtype == "float32"
        tol = 1e-5 if f32 else 2e-2
        # No gradient taken: the plain stage, bit for bit.
        assert float(inference) == float(ref_inference)
        np.testing.assert_allclose(float(value), float(ref_value), rtol=tol)
        np.testing.assert_allclose(float(value), float(inference), rtol=1e-6 if f32 else 2.0**-7)
        for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads), strict=True
        ):
            got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
            assert np.isfinite(got).all(), path
            gap = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
            assert gap <= tol, (jax.tree_util.keystr(path), gap)


class TestInterleavedSchedule:
    """virtual_chunks > 1: the Megatron-style interleaved schedule, where
    each stage holds strided layer chunks and microbatches loop the ring
    v times. Correctness oracle: sequential application of the same
    stacked params (global layer order must be preserved through the
    shard permutation and per-round chunk selection)."""

    @pytest.mark.parametrize("v,n_micro,L", [(2, 4, 8), (2, 8, 8), (4, 4, 16)])
    def test_forward_matches_sequential(self, v, n_micro, L):
        params = _stack_params(L=L, seed=11)
        x = jax.random.normal(jax.random.key(12), (16, 4, 16))
        ref = _stage_fn(params, x)
        mesh = _mesh()
        with mesh:
            y = jax.jit(
                lambda p, x: gpipe_apply(
                    _stage_fn, p, x, mesh, n_microbatches=n_micro, virtual_chunks=v
                )
            )(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)

    def test_gradients_match_sequential(self):
        params = _stack_params(L=8, seed=13)
        x = jax.random.normal(jax.random.key(14), (8, 4, 16))
        mesh = _mesh()

        def loss_pipe(p):
            return (
                gpipe_apply(
                    _stage_fn, p, x, mesh, n_microbatches=4, virtual_chunks=2
                )
                ** 2
            ).sum()

        def loss_ref(p):
            return (_stage_fn(p, x) ** 2).sum()

        with mesh:
            g_pipe = jax.jit(jax.grad(loss_pipe))(params)
        g_ref = jax.grad(loss_ref)(params)
        for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_too_few_microbatches_raises(self):
        params = _stack_params(L=8, seed=15)
        x = jax.random.normal(jax.random.key(16), (8, 4, 16))
        mesh = _mesh()
        with mesh, pytest.raises(ValueError, match="n_microbatches"):
            gpipe_apply(_stage_fn, params, x, mesh, n_microbatches=2, virtual_chunks=2)

    def test_layers_must_divide_stages_times_chunks(self):
        params = _stack_params(L=8, seed=17)
        x = jax.random.normal(jax.random.key(18), (8, 4, 16))
        mesh = _mesh()
        with mesh, pytest.raises(ValueError, match="divide"):
            gpipe_apply(_stage_fn, params, x, mesh, n_microbatches=4, virtual_chunks=3)

    def test_model_interleaved_matches_sequential(self):
        cfg = _pp_cfg(
            model={
                "n_layers": 8,
                "extra": {
                    "tokenizer": "byte",
                    "pipeline_microbatches": 4,
                    "pipeline_virtual_chunks": 2,
                },
            }
        )
        from llmtrain_tpu.models.gpt_pipeline import PipelineGPTAdapter

        adapter = PipelineGPTAdapter()
        model = adapter.build_model(cfg)
        params = adapter.init_params(model, cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(19), (8, 16), 0, 32)
        ref = model.apply({"params": params}, tokens)
        with _mesh():
            out = jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_trainer_interleaved_loss_decreases(self):
        cfg = _pp_cfg(
            model={
                "n_layers": 8,
                "extra": {
                    "tokenizer": "byte",
                    "pipeline_microbatches": 4,
                    "pipeline_virtual_chunks": 2,
                },
            }
        )
        trainer = Trainer(cfg, None, NullTracker())
        result = trainer.fit()
        assert result.final_loss < result.first_step_loss

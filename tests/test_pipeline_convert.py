"""gpt_pipeline ↔ gpt parameter conversion (interop/pipeline_convert.py).

Pipeline-trained checkpoints unlock the rest of the toolchain through
this conversion: reference-format torch export, KV-cache generation via
the gpt tree, and import back into a pipeline config. The math oracle is
logits equality — the two modules implement the same architecture (LN
eps 1e-6 aligned), so conversion must be numerically exact.
"""

from __future__ import annotations

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml
from flax.linen import meta as nn_meta

from llmtrain_tpu.interop import (
    gpt_params_to_pipeline,
    is_pipeline_tree,
    pipeline_params_to_gpt,
)
from llmtrain_tpu.models.gpt import GPT
from llmtrain_tpu.models.gpt_pipeline import PipelineGPT

DIMS = dict(vocab_size=64, block_size=16, d_model=32, n_layers=4, n_heads=4, d_ff=64)


def _pipeline_params(tie=True):
    model = PipelineGPT(tie_embeddings=tie, **DIMS)
    params = nn_meta.unbox(
        model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))
    )["params"]
    return model, params


class TestConversion:
    @pytest.mark.parametrize("tie", [True, False], ids=["tied", "untied"])
    def test_roundtrip_identity(self, tie):
        _, params = _pipeline_params(tie)
        back = gpt_params_to_pipeline(pipeline_params_to_gpt(params))
        for (pa, va), (pb, vb) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(back),
            strict=True,
        ):
            assert pa == pb
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))

    @pytest.mark.parametrize("tie", [True, False], ids=["tied", "untied"])
    def test_converted_params_drive_gpt_to_same_logits(self, tie):
        pipe, params = _pipeline_params(tie)
        gpt = GPT(dropout=0.0, tie_embeddings=tie, **DIMS)
        converted = pipeline_params_to_gpt(params)
        ids = jnp.asarray(
            np.random.default_rng(3).integers(0, 64, (2, 16)), jnp.int32
        )
        a = pipe.apply({"params": params}, ids)
        b = gpt.apply({"params": converted}, ids, deterministic=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=lambda d: jnp.dtype(d).name)
    def test_converted_params_train_gpt_with_the_same_gradients(self, dtype):
        """The two modules share one activation (``gelu_once``), so the
        forward a gradient runs and the gradients agree as the inference
        logits do: a pipeline checkpoint goes on training as ``gpt``."""
        pipe, params = _pipeline_params()
        pipe = pipe.clone(dtype=dtype, remat=False)
        gpt = GPT(dropout=0.0, dtype=dtype, **DIMS)
        ids = jnp.asarray(np.random.default_rng(5).integers(0, 64, (2, 16)), jnp.int32)

        def loss(logits):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(logp, ids[..., None], axis=-1))

        value, grads = jax.value_and_grad(lambda p: loss(pipe.apply({"params": p}, ids)))(params)
        ref_value, ref_grads = jax.value_and_grad(
            lambda p: loss(gpt.apply({"params": p}, ids, deterministic=False))
        )(pipeline_params_to_gpt(params))
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(float(value), float(ref_value), rtol=tol)
        for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(pipeline_params_to_gpt(grads)),
            jax.tree.leaves(ref_grads),
            strict=True,
        ):
            got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
            gap = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
            assert gap <= tol, (jax.tree_util.keystr(path), gap)

    def test_is_pipeline_tree(self):
        _, params = _pipeline_params()
        assert is_pipeline_tree(params)
        assert not is_pipeline_tree(pipeline_params_to_gpt(params))

    def test_abstract_template_conversion(self):
        """ShapeDtypeStruct trees convert too — the import-checkpoint path
        maps torch weights through a gpt-shaped abstract template."""
        _, params = _pipeline_params()
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), params
        )
        gpt_tpl = pipeline_params_to_gpt(abstract)
        assert gpt_tpl["block_0"]["attn"]["qkv_proj"]["kernel"].shape == (32, 3, 4, 8)
        assert isinstance(
            gpt_tpl["block_0"]["attn"]["qkv_proj"]["kernel"], jax.ShapeDtypeStruct
        )

    def test_cached_decode_via_conversion_matches_pipeline_reforward(self):
        """Greedy KV-cache decoding through the converted GPT equals the
        pipeline model's own re-forward decoding — the generate CLI's
        conversion path is exact."""
        from llmtrain_tpu.generation import generate

        pipe, params = _pipeline_params(True)
        gpt = GPT(dropout=0.0, tie_embeddings=True, **DIMS)
        converted = pipeline_params_to_gpt(params)
        prompt = np.asarray([[3, 1, 4, 1, 5]], np.int32)
        cached = generate(
            gpt, converted, prompt, max_new_tokens=8, temperature=0.0,
            use_cache=True,
        )
        windowed = generate(
            pipe, params, prompt, max_new_tokens=8, temperature=0.0,
            use_cache=False,
        )
        np.testing.assert_array_equal(cached, windowed)

    def test_gqa_roundtrip_and_logits(self):
        """The split q/kv (GQA) layout converts both ways and drives the
        GQA GPT to the pipeline model's exact logits."""
        pipe = PipelineGPT(tie_embeddings=True, n_kv_heads=2, **DIMS)
        params = nn_meta.unbox(
            pipe.init(jax.random.key(1), jnp.zeros((1, 16), jnp.int32))
        )["params"]
        assert "q_kernel" in params and "qkv_kernel" not in params
        assert is_pipeline_tree(params)

        converted = pipeline_params_to_gpt(params)
        assert "q_proj" in converted["block_0"]["attn"]
        back = gpt_params_to_pipeline(converted)
        for (pa, va), (pb, vb) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(back),
            strict=True,
        ):
            assert pa == pb
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))

        gpt = GPT(dropout=0.0, tie_embeddings=True, n_kv_heads=2, **DIMS)
        ids = jnp.asarray(
            np.random.default_rng(9).integers(0, 64, (2, 16)), jnp.int32
        )
        a = pipe.apply({"params": params}, ids)
        b = gpt.apply({"params": converted}, ids, deterministic=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow
class TestPipelineExportCLI:
    def test_pipeline_train_export_reference_load_import_eval(self, tmp_path):
        """Full loop for a pipeline-trained run: train -> export (auto
        conversion) -> strict-load into the REAL reference torch GPT where
        available -> import back into the pipeline config -> eval matches
        the source checkpoint exactly."""
        cfg = {
            "run": {"name": "ppconv", "seed": 0, "device": "cpu"},
            "model": {
                "name": "gpt_pipeline",
                "block_size": 16,
                "d_model": 32,
                "n_layers": 4,
                "n_heads": 4,
                "d_ff": 64,
                "dropout": 0.0,
                "vocab_size": 64,
                "extra": {"tokenizer": "byte", "pipeline_microbatches": 2},
            },
            "data": {"name": "dummy_text"},
            "trainer": {
                "max_steps": 2,
                "micro_batch_size": 2,
                "grad_accum_steps": 1,
                "warmup_steps": 0,
                "log_every_steps": 1,
                "eval_every_steps": 2,
                "save_every_steps": 2,
            },
            "mlflow": {"enabled": False},
            "output": {"root_dir": str(tmp_path / "runs")},
        }
        cfg_path = tmp_path / "cfg.yaml"
        cfg_path.write_text(yaml.safe_dump(cfg, sort_keys=False))

        def run(argv):
            return subprocess.run(
                [sys.executable, "-m", "llmtrain_tpu", *argv],
                capture_output=True, text=True, timeout=300,
            )

        train = run(["train", "--config", str(cfg_path), "--run-id", "src", "--json"])
        assert train.returncode == 0, train.stderr

        pt = tmp_path / "model.pt"
        exp = run(["export-checkpoint", "--config", str(cfg_path), "--from", "src",
                   "--output", str(pt), "--json"])
        assert exp.returncode == 0, exp.stderr

        torch = pytest.importorskip("torch")
        sd = torch.load(pt, weights_only=True)
        assert "blocks.0.attn.qkv_proj.weight" in sd  # per-layer, not stacked

        import os
        ref_src = os.environ.get("LLMTRAIN_REFERENCE_SRC", "/root/reference/src")
        if os.path.isdir(ref_src):
            sys.path.insert(0, ref_src)
            try:
                from llmtrain.models.gpt import GPT as RefGPT  # type: ignore

                ref = RefGPT(vocab_size=64, block_size=16, d_model=32,
                             n_layers=4, n_heads=4, d_ff=64, dropout=0.0,
                             tie_embeddings=True)
                missing, unexpected = ref.load_state_dict(sd, strict=True)
                assert not missing and not unexpected
            finally:
                sys.path.remove(ref_src)

        imported = tmp_path / "imported"
        imp = run(["import-checkpoint", "--config", str(cfg_path), "--input", str(pt),
                   "--output", str(imported), "--json"])
        assert imp.returncode == 0, imp.stderr

        gen = run(["generate", "--config", str(cfg_path), "--from", "src",
                   "--prompt-ids", "1,2,3", "--max-new-tokens", "4",
                   "--temperature", "0", "--json"])
        assert gen.returncode == 0, gen.stderr
        assert len(json.loads(gen.stdout)["output_ids"]) == 7
        assert "converted to the gpt tree" in gen.stderr

        ev_src = run(["eval", "--config", str(cfg_path), "--from", "src", "--json"])
        ev_imp = run(["eval", "--config", str(cfg_path), "--from", str(imported), "--json"])
        assert ev_src.returncode == 0 and ev_imp.returncode == 0, ev_imp.stderr
        src_loss = json.loads(ev_src.stdout)["metrics"]["val/loss"]
        imp_loss = json.loads(ev_imp.stdout)["metrics"]["val/loss"]
        assert abs(src_loss - imp_loss) < 1e-6

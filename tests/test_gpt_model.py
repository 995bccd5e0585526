"""GPT model correctness tests (parity with reference tests/test_gpt_model.py).

Includes the flagship causality-invariance test: perturbing tokens after
position t must leave logits at positions <= t unchanged (reference
test_gpt_model.py:144-175).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmtrain_tpu.models.gpt import GPT

VOCAB = 97
BLOCK = 16


def _tiny_gpt(**overrides):
    kwargs = dict(
        vocab_size=VOCAB,
        block_size=BLOCK,
        d_model=32,
        n_layers=2,
        n_heads=4,
        d_ff=64,
        dropout=0.0,
        tie_embeddings=True,
    )
    kwargs.update(overrides)
    return GPT(**kwargs)


def _init(model, batch=2, seqlen=BLOCK, seed=0):
    tokens = jnp.zeros((batch, seqlen), dtype=jnp.int32)
    return model.init({"params": jax.random.key(seed)}, tokens, deterministic=True)["params"]


def test_forward_shape():
    model = _tiny_gpt()
    params = _init(model)
    tokens = jax.random.randint(jax.random.key(1), (3, 10), 0, VOCAB)
    logits = model.apply({"params": params}, tokens, deterministic=True)
    assert logits.shape == (3, 10, VOCAB)


def test_block_size_overflow_raises():
    model = _tiny_gpt()
    params = _init(model)
    tokens = jnp.zeros((1, BLOCK + 1), dtype=jnp.int32)
    with pytest.raises(ValueError, match="exceeds block size"):
        model.apply({"params": params}, tokens, deterministic=True)


def test_weight_tying_removes_lm_head():
    tied = _tiny_gpt(tie_embeddings=True)
    untied = _tiny_gpt(tie_embeddings=False)
    tied_params = _init(tied)
    untied_params = _init(untied)
    assert "lm_head" not in tied_params
    assert "lm_head" in untied_params
    tied_count = sum(x.size for x in jax.tree.leaves(tied_params))
    untied_count = sum(x.size for x in jax.tree.leaves(untied_params))
    assert untied_count == tied_count + 32 * VOCAB


def test_causality_invariance():
    """Perturb tokens after position t; logits up to t must be unchanged."""
    model = _tiny_gpt()
    params = _init(model)
    key = jax.random.key(7)
    tokens = jax.random.randint(key, (2, BLOCK), 0, VOCAB)
    t = 9
    perturbed = tokens.at[:, t + 1 :].set((tokens[:, t + 1 :] + 13) % VOCAB)

    logits_a = model.apply({"params": params}, tokens, deterministic=True)
    logits_b = model.apply({"params": params}, perturbed, deterministic=True)

    np.testing.assert_allclose(
        np.asarray(logits_a[:, : t + 1]), np.asarray(logits_b[:, : t + 1]), atol=1e-6
    )
    assert not np.allclose(np.asarray(logits_a[:, t + 1 :]), np.asarray(logits_b[:, t + 1 :]))


def test_padding_mask_zeroes_padded_rows_and_blocks_keys():
    model = _tiny_gpt()
    params = _init(model)
    tokens = jax.random.randint(jax.random.key(3), (1, 8), 0, VOCAB)
    mask = jnp.array([[1, 1, 1, 1, 1, 0, 0, 0]], dtype=jnp.int32)

    logits_masked = model.apply({"params": params}, tokens, attention_mask=mask)
    # Changing tokens in the padded region must not change unpadded logits.
    perturbed = tokens.at[:, 5:].set((tokens[:, 5:] + 1) % VOCAB)
    logits_masked2 = model.apply({"params": params}, perturbed, attention_mask=mask)
    np.testing.assert_allclose(
        np.asarray(logits_masked[:, :5]), np.asarray(logits_masked2[:, :5]), atol=1e-6
    )


def test_gradient_flow():
    model = _tiny_gpt()
    params = _init(model)
    tokens = jax.random.randint(jax.random.key(5), (2, BLOCK), 0, VOCAB)

    def loss_fn(p):
        logits = model.apply({"params": p}, tokens, deterministic=True)
        return jnp.mean(logits**2)

    grads = jax.grad(loss_fn)(params)
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert sum(norms) > 0.0


def test_dropout_rng_changes_output():
    model = _tiny_gpt(dropout=0.5)
    params = _init(model)
    tokens = jax.random.randint(jax.random.key(2), (2, 8), 0, VOCAB)
    out1 = model.apply(
        {"params": params}, tokens, deterministic=False, rngs={"dropout": jax.random.key(1)}
    )
    out2 = model.apply(
        {"params": params}, tokens, deterministic=False, rngs={"dropout": jax.random.key(2)}
    )
    assert not np.allclose(np.asarray(out1), np.asarray(out2))


def test_bfloat16_compute_dtype():
    model = _tiny_gpt(dtype=jnp.bfloat16)
    params = _init(model)
    # Master params stay f32; activations/logits come out bf16.
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(params))
    tokens = jnp.zeros((1, 4), dtype=jnp.int32)
    logits = model.apply({"params": params}, tokens, deterministic=True)
    assert logits.dtype == jnp.bfloat16


def test_remat_matches_no_remat():
    base = _tiny_gpt(remat=False)
    rem = _tiny_gpt(remat=True)
    params = _init(base)
    tokens = jax.random.randint(jax.random.key(11), (2, BLOCK), 0, VOCAB)
    out_a = base.apply({"params": params}, tokens, deterministic=True)
    out_b = rem.apply({"params": params}, tokens, deterministic=True)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), atol=1e-6)


class TestRematPolicy:
    """model.extra.remat_policy: value/grad equality across policies (the
    policy only changes what gets RECOMPUTED, never the math)."""

    def _model(self, policy):
        return GPT(
            vocab_size=64, block_size=16, d_model=32, n_layers=2, n_heads=4,
            d_ff=64, dropout=0.0, remat=True, remat_policy=policy,
        )

    @pytest.mark.parametrize("policy", ["dots", "dots_no_batch"])
    def test_matches_default_policy(self, policy):
        from flax.linen import meta as nn_meta

        base = self._model("nothing")
        ids = jnp.zeros((1, 16), jnp.int32)
        params = nn_meta.unbox(
            base.init(jax.random.key(0), ids, deterministic=True)
        )["params"]
        toks = jnp.asarray(
            np.random.default_rng(3).integers(0, 64, (2, 16)), jnp.int32
        )

        def loss(model, p):
            logits = model.apply({"params": p}, toks, deterministic=True)
            return jnp.mean(logits.astype(jnp.float32) ** 2)

        v0, g0 = jax.value_and_grad(lambda p: loss(base, p))(params)
        v1, g1 = jax.value_and_grad(lambda p: loss(self._model(policy), p))(params)
        assert abs(float(v0) - float(v1)) < 1e-6
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_unknown_policy_raises(self):
        ids = jnp.zeros((1, 16), jnp.int32)
        with pytest.raises(ValueError, match="remat_policy"):
            self._model("everything").init(
                jax.random.key(0), ids, deterministic=True
            )

    def test_adapter_validates_policy_even_without_remat(self):
        """A typo'd policy fails at config time, not silently ignored
        until someone later flips remat: true."""
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.models.gpt import GPTAdapter

        cfg = RunConfig.model_validate(
            {
                "run": {"name": "x", "device": "cpu"},
                "model": {
                    "name": "gpt", "block_size": 8, "d_model": 16,
                    "n_layers": 1, "n_heads": 4, "d_ff": 32,
                    "vocab_size": 64, "remat": False,
                    "extra": {"tokenizer": "byte", "remat_policy": "dotz"},
                },
                "data": {"name": "dummy_text"},
                "trainer": {"max_steps": 1, "micro_batch_size": 2,
                            "warmup_steps": 0},
            }
        )
        with pytest.raises(ValueError, match="remat_policy"):
            GPTAdapter().build_model(cfg)


class TestGroupedQueryAttention:
    """GQA (model.extra.n_kv_heads): narrow K/V heads shared across query
    groups; the decode cache stores only n_kv_heads."""

    def _model(self, n_kv_heads, **kw):
        return GPT(
            vocab_size=64, block_size=16, d_model=32, n_layers=2, n_heads=4,
            d_ff=64, dropout=0.0, n_kv_heads=n_kv_heads, **kw,
        )

    def _params(self, model):
        from flax.linen import meta as nn_meta

        ids = jnp.zeros((1, 16), jnp.int32)
        return nn_meta.unbox(model.init(jax.random.key(0), ids, deterministic=True))[
            "params"
        ]

    def test_mha_param_tree_unchanged(self):
        """n_kv_heads=0 (and ==n_heads) keeps the fused qkv_proj tree so
        existing checkpoints still load."""
        for kvh in (0, 4):
            params = self._params(self._model(kvh))
            attn = params["block_0"]["attn"]
            assert "qkv_proj" in attn and "q_proj" not in attn

    def test_gqa_param_tree_and_shapes(self):
        params = self._params(self._model(2))
        attn = params["block_0"]["attn"]
        assert "qkv_proj" not in attn
        assert attn["q_proj"]["kernel"].shape == (32, 4, 8)
        assert attn["kv_proj"]["kernel"].shape == (32, 2, 2, 8)

    @pytest.mark.parametrize("kvh", [1, 2], ids=["mqa", "gqa2"])
    def test_causality_invariance(self, kvh):
        """Perturbing tokens after position t leaves logits <= t unchanged
        (the reference's flagship invariant, test_gpt_model.py:144-175)."""
        model = self._model(kvh)
        params = self._params(model)
        rng = np.random.default_rng(5)
        ids = rng.integers(0, 64, (2, 16))
        t = 7
        pert = ids.copy()
        pert[:, t + 1 :] = rng.integers(0, 64, (2, 16 - t - 1))
        a = model.apply({"params": params}, jnp.asarray(ids, jnp.int32), deterministic=True)
        b = model.apply({"params": params}, jnp.asarray(pert, jnp.int32), deterministic=True)
        np.testing.assert_allclose(
            np.asarray(a[:, : t + 1]), np.asarray(b[:, : t + 1]), atol=1e-6
        )

    @pytest.mark.parametrize("kvh", [1, 2, 0], ids=["mqa", "gqa2", "mha"])
    def test_flash_route_matches_dense(self, kvh):
        """The flash path consumes narrow K/V natively (no jnp.repeat in
        the model), and under MHA the fused projection's output whole;
        logits and parameter gradients equal the dense-attention route."""
        dense = self._model(kvh)
        params = self._params(dense)
        flash = self._model(kvh, attention="flash")
        ids = jnp.asarray(np.random.default_rng(7).integers(0, 64, (2, 16)), jnp.int32)
        a = dense.apply({"params": params}, ids, deterministic=True)
        b = flash.apply({"params": params}, ids, deterministic=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

        def loss(model):
            return lambda p: jnp.sum(model.apply({"params": p}, ids, deterministic=True) ** 2)

        ga, gb = jax.grad(loss(dense))(params), jax.grad(loss(flash))(params)
        for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(ga), jax.tree.leaves(gb)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), atol=2e-3, rtol=1e-4, err_msg=str(path)
            )

    @pytest.mark.parametrize("kvh", [0, 2], ids=["mha", "gqa2"])
    def test_flash_route_keeps_the_parameter_tree_value_for_value(self, kvh):
        """The flash branch forms its projections as one matrix product
        each (``RowsDenseGeneral``); what it initialises is what
        ``nn.DenseGeneral`` initialises on every other branch: the same
        names, shapes, partitioning and values, so a checkpoint of one
        loads into the other."""
        import flax.linen as nn

        ids = jnp.zeros((1, 16), jnp.int32)
        stock = self._model(kvh).init(jax.random.key(3), ids, deterministic=True)
        rows = self._model(kvh, attention="flash").init(
            jax.random.key(3), ids, deterministic=True
        )
        assert nn.get_partition_spec(stock) == nn.get_partition_spec(rows)
        a, b = nn.meta.unbox(stock), nn.meta.unbox(rows)
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)):
            assert x.shape == y.shape and x.dtype == y.dtype, path
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=str(path))

    @pytest.mark.parametrize("kvh", [2, 0], ids=["gqa2", "mha"])
    def test_flash_route_applies_padding_mask(self, kvh):
        """Padded batches through attention='flash' now match dense — the
        padding mask is applied INSIDE attention on every path (closes the
        r2 'flash ignores masks' gap; reference gpt.py:60-64)."""
        dense = self._model(kvh)
        params = self._params(dense)
        flash = self._model(kvh, attention="flash")
        ids = jnp.asarray(np.random.default_rng(8).integers(0, 64, (2, 16)), jnp.int32)
        mask = jnp.asarray(
            (np.arange(16)[None, :] < np.asarray([16, 9])[:, None]).astype(np.int32)
        )
        a = dense.apply(
            {"params": params}, ids, attention_mask=mask, deterministic=True
        )
        b = flash.apply(
            {"params": params}, ids, attention_mask=mask, deterministic=True
        )
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        # assume_packed drops the mask — valid rows must then differ from
        # the masked result only on rows that actually carry padding.
        packed = self._model(kvh, attention="flash", assume_packed=True)
        c = packed.apply(
            {"params": params}, ids, attention_mask=mask, deterministic=True
        )
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(c[0]), atol=1e-5)

    def test_decode_cache_stores_narrow_kv(self):
        model = self._model(1).for_decoding(cache_len=8)
        variables = model.init(
            jax.random.key(0), jnp.zeros((2, 1), jnp.int32), deterministic=True
        )
        cache_shape = variables["cache"]["block_0"]["attn"]["cached_key"].shape
        assert cache_shape == (2, 8, 1, 8)  # n_kv_heads=1, not n_heads=4

    @pytest.mark.parametrize("kvh", [1, 2], ids=["mqa", "gqa2"])
    def test_cached_decode_matches_windowed(self, kvh):
        """The narrow-cache decode path equals the full re-forward path —
        the GQA twin of the MHA equivalence test (test_generation.py)."""
        from llmtrain_tpu.generation import generate

        model = self._model(kvh)
        params = self._params(model)
        prompt = np.asarray([[3, 1, 4, 1, 5]], np.int32)
        cached = generate(
            model, params, prompt, max_new_tokens=8, temperature=0.0, use_cache=True
        )
        windowed = generate(
            model, params, prompt, max_new_tokens=8, temperature=0.0, use_cache=False
        )
        np.testing.assert_array_equal(cached, windowed)

    def test_training_loss_decreases(self):
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.tracking.base import NullTracker
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()
        cfg = RunConfig.model_validate(
            {
                "run": {"name": "gqa", "seed": 0, "device": "cpu"},
                "model": {
                    "name": "gpt",
                    "block_size": 8,
                    "d_model": 16,
                    "n_layers": 1,
                    "n_heads": 4,
                    "d_ff": 32,
                    "dropout": 0.0,
                    "vocab_size": 64,
                    "extra": {"tokenizer": "byte", "n_kv_heads": 2},
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
        assert result.final_loss < result.first_step_loss

    def test_invalid_n_kv_heads_rejected(self):
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.models.gpt import GPTAdapter

        def cfg(kvh):
            return RunConfig.model_validate(
                {
                    "run": {"name": "x", "device": "cpu"},
                    "model": {
                        "name": "gpt", "block_size": 8, "d_model": 16,
                        "n_layers": 1, "n_heads": 4, "d_ff": 32,
                        "vocab_size": 64,
                        "extra": {"tokenizer": "byte", "n_kv_heads": kvh},
                    },
                    "data": {"name": "dummy_text"},
                    "trainer": {"max_steps": 1, "micro_batch_size": 2, "warmup_steps": 0},
                    "mlflow": {"enabled": False},
                }
            )

        with pytest.raises(ValueError, match="n_kv_heads"):
            GPTAdapter().build_model(cfg(3))  # 4 % 3 != 0
        with pytest.raises(ValueError, match="n_kv_heads"):
            GPTAdapter().build_model(cfg(-1))

    def test_tp_mesh_incompatible_kv_heads_rejected_loudly(self):
        """MQA (n_kv_heads=1) on a tensor=2 mesh must fail with a clear
        message at Trainer construction, not an opaque pjit sharding error
        at compile time; kv_heads >= tp shards fine."""
        from llmtrain_tpu.config.schemas import RunConfig
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.tracking.base import NullTracker
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()

        def cfg(kvh):
            return RunConfig.model_validate(
                {
                    "run": {"name": "gqa-tp", "seed": 0, "device": "cpu"},
                    "model": {
                        "name": "gpt", "block_size": 8, "d_model": 32,
                        "n_layers": 1, "n_heads": 4, "d_ff": 64,
                        "dropout": 0.0, "vocab_size": 64,
                        "extra": {"tokenizer": "byte", "n_kv_heads": kvh},
                    },
                    "data": {"name": "dummy_text"},
                    "trainer": {
                        "max_steps": 1, "micro_batch_size": 2,
                        "grad_accum_steps": 1, "warmup_steps": 0,
                        "log_every_steps": 1, "eval_every_steps": 1,
                        "save_every_steps": 1,
                    },
                    "distributed": {"mesh": {"tensor": 2, "data": 4}},
                    "mlflow": {"enabled": False},
                }
            )

        with pytest.raises(ValueError, match="divisible by the mesh tensor axis"):
            Trainer(cfg(1), run_dir=None, tracker=NullTracker())
        result = Trainer(cfg(2), run_dir=None, tracker=NullTracker()).fit(
            max_steps_override=1
        )
        assert result.final_step == 1


class TestMLPActivationEvaluatedOnce:
    """Under a gradient the dense MLP's erf GELU goes through
    ``gelu_once``: one float32 evaluation leaves the value and the
    derivative, each rounded once to the compute dtype, and the backward
    multiplies. Against ``jax.grad`` of the plain ``nn.gelu(h,
    approximate=False)`` composition, which is what the block was."""

    D, FF, B, T = 32, 128, 2, 16

    @classmethod
    def _block(cls, dtype, **module):
        from llmtrain_tpu.models.gpt import TransformerBlock

        return TransformerBlock(
            d_model=cls.D, n_heads=4, d_ff=cls.FF, n_layers=2, dropout=0.0,
            dtype=dtype, param_dtype=jnp.float32, **module,
        )

    @classmethod
    def _operands(cls, block, dtype):
        import flax.linen as nn

        keys = jax.random.split(jax.random.key(3), 4)
        # Inputs and weights wide enough that pre-activations reach both tails.
        x = (2.0 * jax.random.normal(keys[0], (cls.B, cls.T, cls.D))).astype(dtype)
        weight = jax.random.normal(keys[1], (cls.B, cls.T, cls.D)).astype(dtype)
        params = nn.meta.unbox(block.init(keys[2], x))
        params = jax.tree.map(
            lambda leaf: 8.0 * leaf if leaf.ndim == 2 else leaf + 0.1, params
        )
        return params, x, weight, keys[3]

    @pytest.mark.parametrize(
        "dtype,case",
        [
            (dtype, case)
            for dtype in (jnp.float32, jnp.bfloat16)
            for case in ("plain", "remat", "lora", "int8", "int8_act", "fp8")
        ],
        ids=lambda v: v if isinstance(v, str) else jnp.dtype(v).name,
    )
    def test_value_and_gradients_match_the_plain_composition(self, monkeypatch, dtype, case):
        import flax.linen as nn

        from llmtrain_tpu.models import gpt
        from llmtrain_tpu.models.lora import LoraSpec, init_lora, merge_lora

        module = {"matmul_precision": case} if case in ("int8", "int8_act", "fp8") else {}
        block = self._block(dtype, **module)
        params, x, weight, rng = self._operands(block, dtype)
        spec = LoraSpec(rank=4, alpha=8.0, targets=("mlp_fc", "mlp_proj"))
        if case == "lora":
            factors = init_lora(params, spec, rng)
            # A zero ``b`` would make every factor gradient but b's vanish.
            factors = jax.tree.map(lambda leaf: leaf + 0.05, factors)
            trained = factors
        else:
            trained = params["params"]

        def apply(p, x):
            return block.apply(p, x, deterministic=False)

        if case == "remat":
            apply = jax.checkpoint(apply)

        def loss(trained, x):
            p = merge_lora(params, trained, spec, freeze_base=True) if case == "lora" else {"params": trained}
            y = apply(p, x).astype(jnp.float32)
            return jnp.sum(y * y * weight.astype(jnp.float32)) / x.size, y

        def run():
            """Loss, the forward the gradient ran, gradients, the inference forward."""
            (value, out), grads = jax.jit(
                jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
            )(trained, x)
            return value, out, grads, jax.jit(lambda trained, x: loss(trained, x)[1])(trained, x)

        value, out, grads, inference = run()
        monkeypatch.setattr(gpt, "gelu_once", lambda h: nn.gelu(h, approximate=False))
        ref_value, _, ref_grads, ref_inference = run()

        f32 = dtype == jnp.float32
        # float32: the two differ by erf against erfc, a few units in the last
        # place. bf16: by the plain form's roundings of erfc's argument and
        # result and of the derivative's parts, each to 8 bits, where the new
        # form rounds `a` and `g` once.
        tol = 1e-6 if f32 else 2e-2
        np.testing.assert_allclose(float(value), float(ref_value), rtol=tol)
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        ref_flat = jax.tree.leaves(ref_grads)
        assert len(flat) == len(ref_flat)
        mlp_leaves = 0
        for (path, got), want in zip(flat, ref_flat):
            got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
            assert np.isfinite(got).all(), path
            gap = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
            assert gap <= tol, (jax.tree_util.keystr(path), gap)
            mlp_leaves += "mlp_" in jax.tree_util.keystr(path) and np.linalg.norm(want) > 0
        assert mlp_leaves >= 2  # mlp_fc and mlp_proj: kernels (and biases), or their factors
        # Not under a gradient the block is the plain composition, bit for
        # bit; the forward the gradient ran agrees with it up to erf against
        # erfc in float32 and up to the roundings above in bf16.
        out, inference = np.asarray(out, np.float32), np.asarray(inference, np.float32)
        np.testing.assert_array_equal(inference, np.asarray(ref_inference, np.float32))
        scale = np.abs(inference).max()
        assert np.abs(out - inference).max() <= (1e-6 if f32 else 2.0**-7) * scale

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=lambda d: jnp.dtype(d).name)
    def test_one_rounding_of_value_and_derivative(self, dtype):
        """``a`` and ``g`` are the float64 GELU and its derivative rounded
        once: within half a unit in the last place of the compute dtype (a
        whole one allows for erf's own last place), over both tails."""
        import math

        from llmtrain_tpu.models.gpt import gelu_once

        h = jnp.linspace(-9.0, 9.0, 4001).astype(dtype)
        a, vjp = jax.vjp(gelu_once, h)
        (g,) = vjp(jnp.ones_like(h))
        h64 = np.asarray(h, np.float64)
        cdf = 0.5 * np.vectorize(math.erfc)(-h64 / math.sqrt(2.0))
        want_a = h64 * cdf
        want_g = cdf + h64 * np.exp(-0.5 * h64 * h64) / math.sqrt(2.0 * math.pi)
        eps = float(jnp.finfo(dtype).eps)
        for got, want in ((a, want_a), (g, want_g)):
            assert got.dtype == dtype
            # 1 + erf loses the far lower tail's last places: an absolute
            # term of one float32 unit at 1 beside the relative one.
            err = np.abs(np.asarray(got, np.float64) - want)
            assert (err <= eps * np.abs(want) + 2.0**-23 * np.maximum(1.0, np.abs(h64))).all()
        # Not under a gradient it is nn.gelu itself.
        np.testing.assert_array_equal(
            np.asarray(gelu_once(h), np.float32),
            np.asarray(jax.nn.gelu(h, approximate=False), np.float32),
        )

    def test_forward_mode_is_refused(self):
        """A ``custom_vjp`` has no forward-mode rule: ``jvp`` (and what is
        built on it: ``jacfwd``, ``hessian``, ``linearize``) raises, as the
        docstring says, and does not silently differentiate something else."""
        from llmtrain_tpu.models.gpt import gelu_once

        h = jnp.linspace(-2.0, 2.0, 8)
        with pytest.raises(TypeError, match="custom_vjp"):
            jax.jvp(gelu_once, (h,), (jnp.ones_like(h),))


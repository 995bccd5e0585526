"""Latent attention over routed and shared experts (models/latent_moe.py,
models/moe.py:DroplessMoE, YaRN in ops/rope.py), CPU, tiny sizes.

The routing by hand; the dropless dispatch against a loop over tokens; the
shares of the experts adding up to the uncut layer; the whole model against
the benchmark's plain reference (``benchmarks/reference/axk1.py``, which
shares no code with the program) on seeded random weights: the full forward,
prefill then decode through the paged latent cache (the absorbed path
against the materialised one), rows at different depths in one decode call;
the adapter's validation and the refusals the family makes by name; the
tiny preset through ``llmtrain train`` and ``serve-bench``.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import axk1 as ref  # noqa: E402
from llmtrain_tpu.models.moe import DroplessMoE, MoEMLP, group_limited_top_k  # noqa: E402
from llmtrain_tpu.ops.rope import yarn_inv_freq, yarn_mscale  # noqa: E402

CONFIG = json.loads((ROOT / "benchmarks/configs/ax-k1.json").read_text())
# The configuration's own rehearsal size: 1 dense + 2 expert layers, 4 heads of
# 16 + 8 / 16, ranks 24 / 16, 16 experts in 4 groups of which 2 stay, 4 a
# token, experts 0-3 held, the PUBLISHED YaRN keys and scaling factor.
TINY = {**CONFIG, **CONFIG["rehearsal"]}
UNCUT = {**TINY, "n_routed_experts": 16, "experts_held": [0, 16]}


def run_config(model: dict, **sections) -> dict:
    return {
        "schema_version": 1, "run": {"name": "latent_moe_test", "seed": 1, "device": "cpu"}, "model": model,
        "data": {"name": "dummy_text"}, "trainer": {"max_steps": 1, "micro_batch_size": 1, "warmup_steps": 0},
        "mlflow": {"enabled": False}, **sections,
    }


def build_model(cfg: dict = TINY, dtype: str = "float32", **extra):
    """The program's model for ``cfg``, built as the CLI builds it."""
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import initialize_registries

    initialize_registries()
    model = ref.program_model(cfg)
    model.update(dtype=dtype, param_dtype="float32")
    model["extra"].update(extra)
    run = RunConfig.model_validate(run_config(model))
    adapter = build_adapter(run)
    return adapter, adapter.build_model(run)


def tiny_params(cfg: dict = TINY, seed: int = 1234):
    return jax.jit(lambda k: ref.program_tree(ref.make_weights(cfg, k), cfg))(ref.seed_key(seed, 1))


def reference_logits(cfg: dict, ids, seed: int = 1234):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda i: ref.logits_fn(ref.init_weights(cfg, seed), i, cfg))(jnp.asarray(ids)))


def tolerance(logits: np.ndarray, cfg: dict = TINY) -> float:
    """float32 on both sides: 50x float32's epsilon at the logits' scale,
    times the square root of the contraction's length (reduction order)."""
    return 50 * 2.0**-23 * float(np.abs(logits).max()) * math.sqrt(cfg["hidden_size"])


# ------------------------------------------------------------------ YaRN


def test_yarn_frequencies_and_softmax_scale_by_hand():
    rs = CONFIG["rope_scaling"]
    got = np.asarray(yarn_inv_freq(64, theta=10000.0, factor=32.0, original_max_position_embeddings=4096,
                                   beta_fast=32.0, beta_slow=1.0))
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # 4,096 positions make 32 turns at pair 10.5 and 1 turn at pair 22.5: the
    # ramp runs from pair 10 (floored) to pair 23 (ceiled).
    turns = 4096 * base / (2 * np.pi)
    assert turns[10] > 32 > turns[11] and turns[22] > 1 > turns[23]
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(got, base * (1 - ramp) + base / 32 * ramp, rtol=1e-6)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)  # fast pairs keep their frequency
    np.testing.assert_allclose(got[23:], base[23:] / 32, rtol=1e-6)  # slow pairs are interpolated
    assert yarn_mscale(32.0, rs["mscale_all_dim"]) == pytest.approx(1.34657, abs=1e-5)
    assert yarn_mscale(1.0, 1.0) == 1.0
    freq, scale = ref.yarn(CONFIG)  # the reference wrote the same numbers down on its own
    np.testing.assert_allclose(np.asarray(freq), got, rtol=1e-6)
    assert scale == pytest.approx(192**-0.5 * 1.34657**2, rel=1e-5) and scale == pytest.approx(0.13086, rel=1e-4)


# --------------------------------------------------------------- routing


def test_group_limited_selection_by_hand():
    # 8 experts in 4 groups of 2, 2 groups stay, 3 a token. Group scores (sum
    # of the two highest = both): 0.9+0.1, 0.6+0.5, 0.8+0.0, 0.3+0.2 -> groups
    # 1 (1.1) and 0 (1.0) stay; expert 4 (0.8), the third highest of all, sits
    # in a masked group and is NOT chosen.
    scores = jnp.asarray([[0.9, 0.1, 0.6, 0.5, 0.8, 0.0, 0.3, 0.2]], jnp.float32)
    picked, weights = group_limited_top_k(scores, top_k=3, n_group=4, topk_group=2)
    assert sorted(np.asarray(picked)[0].tolist()) == [0, 2, 3]
    np.testing.assert_allclose(sorted(np.asarray(weights)[0]), [0.5, 0.6, 0.9])
    plain, _ = group_limited_top_k(scores, top_k=3, n_group=1, topk_group=1)
    assert sorted(np.asarray(plain)[0].tolist()) == [0, 2, 4]
    # the reference's own routing makes the same choice, weights 2.5 * s / sum
    small = {**TINY, "published": {**TINY["published"], "n_routed_experts": 8}, "n_routed_experts": 8,
             "experts_held": [0, 8], "n_group": 4, "topk_group": 2, "num_experts_per_tok": 3}
    want = np.zeros(8)
    want[[0, 2, 3]] = 2.5 * np.asarray([0.9, 0.6, 0.5]) / 2.0
    np.testing.assert_allclose(np.asarray(ref.routing(scores, small))[0], want, rtol=1e-6)


def _layer(**kw):
    base = dict(d_model=16, d_ff=24, n_experts=16, top_k=4, n_layers=2, n_group=4, topk_group=2, scale=2.5)
    return DroplessMoE(**{**base, **kw})


def _layer_params(layer, x, seed=1):
    return nn.unbox(layer.init(jax.random.key(seed), x))["params"]


def test_dropless_layer_is_the_loop_over_tokens_and_weights_sum_to_the_scale():
    layer = _layer()
    x = jax.random.normal(jax.random.key(0), (2, 7, 16))
    p = _layer_params(layer, x)
    got, stats = layer.apply({"params": p}, x, mutable=["moe_stats"])
    tokens = x.reshape(-1, 16)
    scores = jax.nn.sigmoid(tokens @ p["router"]["kernel"])
    picked, w = group_limited_top_k(scores, top_k=4, n_group=4, topk_group=2)
    w = 2.5 * w / w.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-6)
    want = np.zeros_like(tokens)
    for i in range(tokens.shape[0]):
        for j in range(4):
            e = int(picked[i, j])
            want[i] += w[i, j] * ((jax.nn.silu(tokens[i] @ p["wg"][e]) * (tokens[i] @ p["wu"][e])) @ p["wo"][e])
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 16), want, atol=1e-6)
    assert int(stats["moe_stats"]["counts"][0]) == 14 * 4  # every pair is held by the uncut layer
    assert int(stats["moe_stats"]["counts"][1]) == len(set(np.asarray(picked).ravel().tolist()))


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    """512 tokens, one router row that sends them all to the same four
    experts: a capacity of ceil(1.25 * 4 * 512 / 16) = 160 would drop 352 of
    each expert's 512; here every token gets all four."""
    layer = _layer()
    x = jnp.ones((1, 512, 16)) + 0.01 * jax.random.normal(jax.random.key(3), (1, 512, 16))
    p = _layer_params(layer, x)
    kernel = jnp.zeros((16, 16)).at[:, jnp.asarray([0, 1, 2, 3])].set(1.0)  # experts 0-3 score highest
    p = {**p, "router": {"kernel": kernel}}
    got, stats = layer.apply({"params": p}, x, mutable=["moe_stats"])
    assert int(stats["moe_stats"]["counts"][0]) == 512 * 4 and int(stats["moe_stats"]["counts"][1]) == 4
    one = layer.apply({"params": p}, x[:, :1])  # a token alone gets what it got in the crowd
    np.testing.assert_allclose(np.asarray(got[:, :1]), np.asarray(one), atol=1e-6)
    assert float(jnp.abs(got).min(axis=-1).max()) > 0 and np.all(np.abs(np.asarray(got)).sum(-1) > 0)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 holders of one expert each: their routed parts sum to what the
    layer that holds all 16 gives (the shared expert is another module,
    counted once by whoever adds it: test_reference_axk1.py does that for
    the reference's whole block)."""
    layer = _layer()
    x = jax.random.normal(jax.random.key(5), (3, 9, 16))
    p = _layer_params(layer, x)
    whole = layer.apply({"params": p}, x)
    share = lambda first, count: layer.clone(experts_held=(first, count)).apply(  # noqa: E731
        {"params": {"router": p["router"], **{k: p[k][first : first + count] for k in ("wg", "wu", "wo")}}},
        x, mutable=["moe_stats"])
    parts, stats = zip(*(share(e, 1) for e in range(16)))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=2e-6)
    assert sum(int(s["moe_stats"]["counts"][0]) for s in stats) == 27 * 4
    np.testing.assert_array_equal(np.asarray(share(0, 16)[0]), np.asarray(whole))  # held = all IS the uncut layer
    two = share(4, 2)[0] + share(6, 2)[0]
    np.testing.assert_allclose(np.asarray(two), np.asarray(share(4, 4)[0]), atol=2e-6)


def test_a_call_in_which_no_token_picks_a_held_expert_adds_nothing():
    layer = _layer(experts_held=(12, 4))
    x = jnp.ones((1, 6, 16))
    p = _layer_params(layer, x)
    kernel = jnp.zeros((16, 16)).at[:, :8].set(1.0)  # groups 0 and 1 win: experts 12-15 are never chosen
    got, stats = layer.apply({"params": {**p, "router": {"kernel": kernel}}}, x, mutable=["moe_stats"])
    np.testing.assert_array_equal(np.asarray(got), 0.0)  # the block then adds the shared expert alone
    assert int(stats["moe_stats"]["counts"][0]) == 0 and int(stats["moe_stats"]["counts"][1]) == 0


def test_layer_validates_its_sizes_and_the_capacity_path_names_the_dropless_one():
    x = jnp.ones((1, 2, 16))
    for kw, match in ((dict(top_k=0), "top_k"), (dict(n_group=3), "n_group"), (dict(top_k=9), "do not fit"),
                      (dict(experts_held=(14, 4)), "experts_held")):
        with pytest.raises(ValueError, match=match):
            _layer(**kw).init(jax.random.key(0), x)
    old = MoEMLP(d_model=16, d_ff=16, n_experts=4, n_layers=1, router_top_k=3)
    with pytest.raises(ValueError, match="capacity-and-drop path.*DroplessMoE"):
        old.init(jax.random.key(0), x)


# ------------------------------------------------- the model and the reference


def test_full_forward_against_the_plain_reference():
    _, model = build_model()
    ids = np.random.default_rng(0).integers(0, 512, (3, 48)).astype(np.int32)
    want = reference_logits(TINY, ids)
    got = np.asarray(jax.jit(model.apply)({"params": tiny_params()}, jnp.asarray(ids)))
    assert np.abs(got - want).max() <= tolerance(want)
    # the parameter tree the program declares is the one the reference's weights fill
    declared = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.asarray(ids))))["params"]
    assert jax.tree.map(lambda s: s.shape, declared) == jax.tree.map(lambda a: a.shape, tiny_params())
    # a share that holds every expert is the uncut model, and differs from the share of four
    _, uncut = build_model(UNCUT)
    whole = np.asarray(uncut.apply({"params": tiny_params(UNCUT)}, jnp.asarray(ids)))
    assert np.abs(whole - reference_logits(UNCUT, ids)).max() <= tolerance(want)
    assert np.abs(whole - got).max() > 100 * tolerance(want)


def _paged(model, slots=3, block_tokens=8):
    mb = model.block_size // block_tokens
    paged = model.for_paged_decoding(num_blocks=1 + slots * mb, block_tokens=block_tokens)
    shapes = jax.eval_shape(lambda: paged.init(
        jax.random.key(0), jnp.zeros((1, 1), jnp.int32), positions=jnp.zeros((1,), jnp.int32),
        block_tables=jnp.zeros((1, mb), jnp.int32)))["cache"]
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    tables = jnp.asarray(1 + np.arange(slots * mb).reshape(slots, mb), jnp.int32)
    return paged, cache, tables


def test_prefill_then_decode_through_the_latent_cache_against_the_reference():
    """The materialised path (prefill of 20 tokens) and the absorbed path
    (decode, token by token) both land on the reference's full forward,
    which has neither a cache nor an absorbed product: LOGITS are compared,
    at every position."""
    _, model = build_model()
    params = tiny_params()
    ids = np.random.default_rng(1).integers(0, 512, (3, 40)).astype(np.int32)
    want = reference_logits(TINY, ids)
    paged, cache, tables = _paged(model)
    leaf = cache["block_0"]["attn"]["paged_latent"]
    assert leaf.shape == (25, 1, 8 * 24)  # a 24-wide row: the 8 positions of a block fold into one row
    step = jax.jit(lambda c, tok, pos: paged.apply(
        {"params": params, "cache": c}, tok, positions=pos, block_tables=tables, mutable=["cache"]))
    logits, mutated = step(cache, jnp.asarray(ids[:, :20]), jnp.zeros((3,), jnp.int32))
    assert np.abs(np.asarray(logits) - want[:, :20]).max() <= tolerance(want)
    cache = mutated["cache"]
    for p in range(20, 40):
        logits, mutated = step(cache, jnp.asarray(ids[:, p : p + 1]), jnp.full((3,), p, jnp.int32))
        cache = mutated["cache"]
        assert np.abs(np.asarray(logits)[:, 0] - want[:, p]).max() <= tolerance(want), p


def test_rows_at_different_depths_in_one_decode_call():
    _, model = build_model()
    params = tiny_params()
    ids = np.random.default_rng(2).integers(0, 512, (3, 30)).astype(np.int32)
    want = reference_logits(TINY, ids)
    paged, cache, tables = _paged(model)
    depth = [5, 17, 26]  # each row's prompt is prefilled alone, to its own depth
    call = jax.jit(lambda c, tok, pos, table: paged.apply(
        {"params": params, "cache": c}, tok, positions=pos, block_tables=table, mutable=["cache", "moe_stats"]))
    for r, n in enumerate(depth):
        _, mutated = call(cache, jnp.asarray(ids[r : r + 1, :n]), jnp.zeros((1,), jnp.int32), tables[r : r + 1])
        cache = mutated["cache"]
    for step in range(4):
        pos = jnp.asarray([n + step for n in depth], jnp.int32)
        tok = jnp.asarray([[ids[r, n + step]] for r, n in enumerate(depth)], jnp.int32)
        logits, mutated = call(cache, tok, pos, tables)
        cache = mutated["cache"]
        for r, n in enumerate(depth):
            assert np.abs(np.asarray(logits)[r, 0] - want[r, n + step]).max() <= tolerance(want)
        counted = jax.tree.leaves(mutated["moe_stats"])  # [expert_pairs, experts_hit] of each expert layer
        assert len(counted) == model.expert_layers and all(0 <= int(c.max()) <= 3 * 4 for c in counted)


def test_bf16_program_lands_near_the_reference_and_a_chunk_of_a_prompt_attends_the_cached_rest():
    _, model = build_model(dtype="bfloat16")
    params = jax.tree.map(lambda a: a if a.shape[-1] == 16 and a.ndim == 2 else a.astype(jnp.bfloat16), tiny_params())
    ids = np.random.default_rng(3).integers(0, 512, (2, 32)).astype(np.int32)
    want = reference_logits(TINY, ids)
    got = np.asarray(jax.jit(model.apply)({"params": params}, jnp.asarray(ids)), np.float32)
    assert np.abs(got - want).max() < 0.06 * np.abs(want).max()  # bf16 products, and a choice of expert may flip
    # chunked prefill (offset > 0): the second slab's materialised keys come from the pool
    _, exact = build_model()
    paged, cache, tables = _paged(exact, slots=2)
    full = tiny_params()
    chunk = jax.jit(lambda c, tok, pos: paged.apply(
        {"params": full, "cache": c}, tok, positions=pos, block_tables=tables, mutable=["cache"]))
    for start, stop in ((0, 16), (16, 32)):
        logits, mutated = chunk(cache, jnp.asarray(ids[:, start:stop]), jnp.full((2,), start, jnp.int32))
        cache = mutated["cache"]
        assert np.abs(np.asarray(logits) - want[:, start:stop]).max() <= tolerance(want)


# ------------------------------------------------------- adapter and refusals


def test_adapter_validates_and_refuses_by_name():
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.generation import generate
    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import available_model_adapters, initialize_registries

    initialize_registries()
    assert "latent_moe" in available_model_adapters()

    def build(**changes):
        model = ref.program_model(TINY)
        model.update(dtype="float32", param_dtype="float32")
        for key, value in changes.items():
            if key in model:
                model[key] = value
            elif value is None:
                del model["extra"][key]
            else:
                model["extra"][key] = value
        cfg = RunConfig.model_validate(run_config(model))
        return build_adapter(cfg).build_model(cfg)

    model = build()
    assert model.expert_layers == 2 and model.experts_held == (0, 4) and model.n_routed_experts == 16
    assert dict(model.rope_scaling)["factor"] == 32.0 and not model.tie_embeddings
    for changes, match in (
        (dict(kv_lora_rank=None), "kv_lora_rank"), (dict(capacity_factor=1.0), "not latent_moe settings"),
        (dict(attention="flash"), "computes its attention itself"), (dict(scoring_func="softmax"), "sigmoid"),
        (dict(qk_rope_head_dim=7), "even"), (dict(rope_scaling={"type": "linear", "factor": 2}), "yarn"),
        (dict(rope_scaling={**CONFIG["rope_scaling"], "mscale": 0.7}), "mscale"), (dict(remat=True), "remat"),
    ):
        with pytest.raises(ValueError, match=match):
            build(**changes)
    assert build(rope_scaling=None).rope_scaling == ()  # plain RoPE without the key
    with pytest.raises(ValueError, match="no linear decode cache"):
        model.for_decoding()
    with pytest.raises(ValueError, match="serving.mode: continuous"):
        generate(model, tiny_params(), jnp.zeros((1, 4), jnp.int32), max_new_tokens=2, temperature=0.0)


# ------------------------------------------------------------------ the preset


def test_preset_trains_and_serves_on_the_cpu(tmp_path, capsys):
    from llmtrain_tpu.cli import main

    preset = ROOT / "configs/presets/latent_moe_smoke.yaml"
    config = tmp_path / "latent_moe_smoke.yaml"
    config.write_text(preset.read_text().replace('root_dir: "runs"', f'root_dir: "{tmp_path}"'))
    assert main(["train", "--config", str(config), "--run-id", "lm1", "--json"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["train_result"]
    assert math.isfinite(result["final_loss"]) and result["final_loss"] < result["first_step_loss"]
    out_dir = tmp_path / "bench"
    assert main([
        "serve-bench", "--config", str(config), "--from", "lm1", "--requests", "6", "--rate-rps", "50",
        "--max-new-tokens", "6", "--out", str(out_dir),
    ]) == 0
    serving = json.loads((out_dir / "report.json").read_text())["serving"]
    assert serving["requests"]["completed"] == 6 and serving["requests"]["failed"] == 0
    assert serving["compile"]["within_budget"] is True and "state_leaves" not in serving["compile"]

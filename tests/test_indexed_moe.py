"""Index-selected attention over an expert layer in every block
(models/indexed_moe.py, models/moe.py:DroplessMoE with softmax scores), CPU,
tiny sizes.

The selection's mask by hand (ties included); softmax routing against a
hand-written top-k; the shares of the experts adding up to the uncut layer;
the whole model against the benchmark's plain reference
(``benchmarks/reference/keye_vl2.py``, which shares no code with the
program) on seeded random weights, ALWAYS with the selection live (``topk``
8 of 40-48 positions): the full forward (queries in chunks), prefill then
decode through the three paged leaves (the mask path against the gather
path), a context that crosses ``topk`` mid-decode, rows at different depths
in one decode call; an fp8 control that fails the tolerance; the adapter's
validation and the refusals the family makes by name; the tiny preset
through ``llmtrain train`` and ``serve-bench``.
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

from benchmarks.reference import keye_vl2 as ref  # noqa: E402
from llmtrain_tpu.models.indexed_moe import top_k_mask  # noqa: E402
from llmtrain_tpu.models.moe import DroplessMoE  # noqa: E402

CONFIG = json.loads((ROOT / "benchmarks/configs/keye-vl2-30b-a3b.json").read_text())
# The configuration's own rehearsal size: 2 layers, 4 query / 2 K/V heads of
# 16, 4 index heads of 8, topk 8 in chunks of 8 queries, 16 experts of which
# 4 a token, experts 0-3 held, 64 positions.
TINY = {**CONFIG, **CONFIG["rehearsal"]}
UNCUT = {**TINY, "num_experts": 16, "experts_held": [0, 16]}


def run_config(model: dict, **sections) -> dict:
    return {
        "schema_version": 1, "run": {"name": "indexed_moe_test", "seed": 1, "device": "cpu"}, "model": model,
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


def reference_logits(cfg: dict, ids, seed: int = 1234, precision: str = "f32"):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda i: ref.logits_fn(ref.init_weights(cfg, seed), i, cfg, precision))(jnp.asarray(ids)))


def tolerance(logits: np.ndarray, cfg: dict = TINY) -> float:
    """float32 on both sides, the same choices of positions and experts: 50x
    float32's epsilon at the logits' scale, times the square root of the
    contraction's length (reduction order). A position or an expert chosen
    otherwise would miss it by orders of magnitude (the control below does)."""
    return 50 * 2.0**-23 * float(np.abs(logits).max()) * math.sqrt(cfg["hidden_size"])


# ------------------------------------------------------------- the selection


def test_top_k_mask_is_the_set_top_k_returns_ties_to_the_lower_position():
    inf = -np.inf
    scores = jnp.asarray([
        [0.5, 0.9, 0.5, 0.1, 0.5, 0.7, inf, inf],   # k = 3: 0.9, 0.7, then the FIRST of the three 0.5s
        [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],   # all equal: the first three
        [0.3, 0.2, inf, inf, inf, inf, inf, inf],   # two seeable positions: both, and nothing at -inf
        [-1.0, 2.0, 2.0, 2.0, 2.0, -3.0, 0.0, inf],  # four equal at the top: the first three of them
    ], jnp.float32)
    want = np.asarray([
        [1, 1, 0, 0, 0, 1, 0, 0],
        [1, 1, 1, 0, 0, 0, 0, 0],
        [1, 1, 0, 0, 0, 0, 0, 0],
        [0, 1, 1, 1, 0, 0, 0, 0],
    ], bool)
    np.testing.assert_array_equal(np.asarray(top_k_mask(scores, 3)), want)
    np.testing.assert_array_equal(np.asarray(ref.top_k_mask(scores, 3)), want)  # the reference's own way there
    # against the indices themselves, on scores with many exact ties (a ReLU's zeros)
    noisy = jnp.round(jax.random.normal(jax.random.key(0), (64, 40)) * 2) / 2
    noisy = jnp.where(noisy == 0, 0.0, noisy)  # no -0.0: top_k orders it below +0.0 (the model rids its scores of it)
    noisy = jnp.where(jnp.arange(40)[None, :] <= jnp.arange(64)[:, None] % 40, noisy, -jnp.inf)
    _, idx = jax.lax.top_k(noisy, 8)
    by_index = np.zeros((64, 40), bool)
    by_index[np.arange(64)[:, None], np.asarray(idx)] = True
    by_index &= np.asarray(noisy) > -np.inf
    np.testing.assert_array_equal(np.asarray(top_k_mask(noisy, 8)), by_index)
    np.testing.assert_array_equal(np.asarray(ref.top_k_mask(noisy, 8)), by_index)


# --------------------------------------------------------------- routing


def _layer(**kw):
    base = dict(d_model=16, d_ff=24, n_experts=16, top_k=4, n_layers=2, scoring="softmax")
    return DroplessMoE(**{**base, **kw})


def _layer_params(layer, x, seed=1):
    return nn.unbox(layer.init(jax.random.key(seed), x))["params"]


def test_softmax_routing_is_the_hand_written_top_k_and_weights_sum_to_one():
    layer = _layer()
    x = jax.random.normal(jax.random.key(0), (2, 7, 16))
    p = _layer_params(layer, x)
    got, stats = layer.apply({"params": p}, x, mutable=["moe_stats"])
    tokens = np.asarray(x.reshape(-1, 16), np.float64)
    logits = tokens @ np.asarray(p["router"]["kernel"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)  # over ALL 16 experts
    want = np.zeros_like(tokens)
    for i in range(tokens.shape[0]):
        chosen = sorted(range(16), key=lambda e: (-probs[i, e], e))[:4]  # the 4 largest, ties to the lower index
        for e in chosen:
            w = probs[i, e] / sum(probs[i, c] for c in chosen)  # renormalised over the chosen
            t = jnp.asarray(tokens[i], jnp.float32)
            want[i] += w * np.asarray((jax.nn.silu(t @ p["wg"][e]) * (t @ p["wu"][e])) @ p["wo"][e])
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 16), want, atol=1e-4 * scale)  # float32 against a float64 hand
    assert int(stats["moe_stats"]["counts"][0]) == 14 * 4  # every pair is held by the uncut layer
    # the reference's own routing makes the same choice with the same weights
    small = {**UNCUT, "num_experts_per_tok": 4}
    weights = np.asarray(ref.routing(jnp.asarray(probs, jnp.float32), small))
    assert ((weights > 0).sum(-1) == 4).all()
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    # without the renormalisation the weights are the probabilities themselves (about 1/16 each, where a
    # sigmoid's are about 1/2: the two scorings are told apart there)
    raw = np.asarray(ref.routing(jnp.asarray(probs, jnp.float32), {**small, "norm_topk_prob": False}))
    np.testing.assert_allclose(raw[raw > 0], probs[raw > 0], rtol=1e-5)
    plain = np.asarray(layer.clone(normalize=False).apply({"params": p}, x))
    np.testing.assert_allclose(plain.reshape(-1, 16), want * np.asarray(raw.sum(-1))[:, None], atol=1e-4 * scale)
    sigmoid = np.asarray(layer.clone(normalize=False, scoring="sigmoid").apply({"params": p}, x))
    assert np.abs(sigmoid).max() > 4 * np.abs(plain).max()
    with pytest.raises(ValueError, match="scoring"):
        _layer(scoring="tanh").init(jax.random.key(0), x)


def test_eight_holders_of_two_experts_add_up_to_the_uncut_layer():
    layer = _layer()
    x = jax.random.normal(jax.random.key(5), (3, 9, 16))
    p = _layer_params(layer, x)
    whole = layer.apply({"params": p}, x)
    share = lambda first, count: layer.clone(experts_held=(first, count)).apply(  # noqa: E731
        {"params": {"router": p["router"], **{k: p[k][first : first + count] for k in ("wg", "wu", "wo")}}},
        x, mutable=["moe_stats"])
    parts, stats = zip(*(share(2 * holder, 2) for holder in range(8)))
    scale = float(np.abs(np.asarray(whole)).max())
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=1e-5 * scale)  # float32 sums reordered
    assert sum(int(s["moe_stats"]["counts"][0]) for s in stats) == 27 * 4
    assert np.abs(np.asarray(parts[0]) - np.asarray(whole)).max() > 0.1 * scale  # one share is not the layer


# ------------------------------------------------- the model and the reference


def test_full_forward_against_the_plain_reference_with_the_selection_live():
    _, model = build_model()
    assert model.selects_positions == 8 and model.expert_layers == 2 and model.q_chunk_size == 8
    ids = np.random.default_rng(0).integers(0, 512, (3, 48)).astype(np.int32)
    want = reference_logits(TINY, ids)
    got = np.asarray(jax.jit(model.apply)({"params": tiny_params()}, jnp.asarray(ids)))
    assert np.abs(got - want).max() <= tolerance(want)
    # the parameter tree the program declares is the one the reference's weights fill
    declared = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.asarray(ids))))["params"]
    assert jax.tree.map(lambda s: s.shape, declared) == jax.tree.map(lambda a: a.shape, tiny_params())
    # the selection does something: attending all 48 positions is another function, equal only while t < topk
    dense = {**TINY, "sa_config": {**TINY["sa_config"], "topk": 64}}
    everything = reference_logits(dense, ids)
    assert np.abs(everything[:, :8] - want[:, :8]).max() <= tolerance(want)
    assert np.abs(everything[:, 8:] - want[:, 8:]).max() > 100 * tolerance(want)
    _, dense_model = build_model(dense)
    assert np.abs(np.asarray(dense_model.apply({"params": tiny_params()}, jnp.asarray(ids))) - everything).max() \
        <= tolerance(want)
    # a share that holds every expert is the uncut model, and differs from the share of four
    _, uncut = build_model(UNCUT)
    whole = np.asarray(uncut.apply({"params": tiny_params(UNCUT)}, jnp.asarray(ids)))
    assert np.abs(whole - reference_logits(UNCUT, ids)).max() <= tolerance(want)
    assert np.abs(whole - got).max() > 100 * tolerance(want)
    # packed segments: a token sees (and selects among) its own segment only
    seg = np.ones((3, 48), np.int32)
    seg[:, 20:] = 2
    packed = np.asarray(model.apply({"params": tiny_params()}, jnp.asarray(ids), jnp.asarray(seg)))
    alone = reference_logits(TINY, ids[:, 20:])
    assert np.abs(packed[:, :20] - want[:, :20]).max() <= tolerance(want)
    assert np.abs(packed[:, 20:] - alone).max() <= tolerance(want)  # RoPE is relative: the segment alone, shifted
    assert np.abs(packed[:, 20:] - want[:, 20:]).max() > 100 * tolerance(want)


def _paged(model, slots=3, block_tokens=8):
    mb = model.block_size // block_tokens
    paged = model.for_paged_decoding(num_blocks=1 + slots * mb, block_tokens=block_tokens)
    shapes = jax.eval_shape(lambda: paged.init(
        jax.random.key(0), jnp.zeros((1, 1), jnp.int32), positions=jnp.zeros((1,), jnp.int32),
        block_tables=jnp.zeros((1, mb), jnp.int32)))["cache"]
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    tables = jnp.asarray(1 + np.arange(slots * mb).reshape(slots, mb), jnp.int32)
    return paged, cache, tables


@pytest.mark.parametrize("prompt", [20, 5])
def test_prefill_then_decode_through_the_three_leaves_against_the_reference(prompt):
    """The mask path (prefill, queries in chunks of 8) and the gather path
    (decode, token by token: top-k, positions through the block table, the
    chosen K/V rows) both land on the reference's full forward, which has
    neither a cache nor a gather: LOGITS are compared, at every position.
    A prompt of 20 selects from its ninth token on; a prompt of 5 attends
    everything and its context crosses ``topk`` = 8 three decode steps in."""
    _, model = build_model()
    params = tiny_params()
    ids = np.random.default_rng(1).integers(0, 512, (3, 40)).astype(np.int32)
    want = reference_logits(TINY, ids)
    paged, cache, tables = _paged(model)
    attn = cache["block_0"]["attn"]
    assert sorted(attn) == ["paged_index", "paged_key", "paged_value"]
    assert attn["paged_key"].shape == attn["paged_value"].shape == (25, 2, 4 * 32)  # a 32-wide row folds 4 positions
    assert attn["paged_index"].shape == (25, 8, 128)  # an 8-wide index key padded to a lane tile: a row a position
    step = jax.jit(lambda c, tok, pos: paged.apply(
        {"params": params, "cache": c}, tok, positions=pos, block_tables=tables, mutable=["cache"]))
    logits, mutated = step(cache, jnp.asarray(ids[:, :prompt]), jnp.zeros((3,), jnp.int32))
    assert np.abs(np.asarray(logits) - want[:, :prompt]).max() <= tolerance(want)
    cache = mutated["cache"]
    for p in range(prompt, 40):
        logits, mutated = step(cache, jnp.asarray(ids[:, p : p + 1]), jnp.full((3,), p, jnp.int32))
        cache = mutated["cache"]
        assert np.abs(np.asarray(logits)[:, 0] - want[:, p]).max() <= tolerance(want), p


def test_rows_at_different_depths_in_one_decode_call():
    """One row under ``topk`` (attends all it has), two past it, in one call."""
    _, model = build_model()
    params = tiny_params()
    ids = np.random.default_rng(2).integers(0, 512, (3, 30)).astype(np.int32)
    want = reference_logits(TINY, ids)
    paged, cache, tables = _paged(model)
    depth = [3, 17, 26]  # each row's prompt is prefilled alone, to its own depth
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


def test_a_chunk_of_a_prompt_selects_among_the_cached_rest_and_bf16_lands_near():
    _, exact = build_model()
    ids = np.random.default_rng(3).integers(0, 512, (2, 36)).astype(np.int32)
    want = reference_logits(TINY, ids)
    paged, cache, tables = _paged(exact, slots=2)
    full = tiny_params()
    chunk = jax.jit(lambda c, tok, pos: paged.apply(
        {"params": full, "cache": c}, tok, positions=pos, block_tables=tables, mutable=["cache"]))
    for start, stop in ((0, 12), (12, 36)):  # chunked prefill (offset > 0); 24 queries = 3 chunks of 8
        logits, mutated = chunk(cache, jnp.asarray(ids[:, start:stop]), jnp.full((2,), start, jnp.int32))
        cache = mutated["cache"]
        assert np.abs(np.asarray(logits) - want[:, start:stop]).max() <= tolerance(want)
    _, model = build_model(dtype="bfloat16")
    params = jax.tree.map(
        lambda a: a if a.shape == (64, 16) else a.astype(jnp.bfloat16), tiny_params())  # the router stays float32
    got = np.asarray(jax.jit(model.apply)({"params": params}, jnp.asarray(ids)), np.float32)
    # bf16 products: 1% of the largest logit while a query attends everything it sees (t < topk) and in the
    # median; past that a rounding may flip one of only 8 chosen positions (or an expert), and such a
    # logit is simply another one (at topk 2,048 a flipped position is a 2,048th of the attention).
    scale = np.abs(want).max()
    assert np.abs(got - want)[:, :8].max() < 0.02 * scale and np.median(np.abs(got - want)) < 0.01 * scale


def test_an_fp8_control_fails_the_tolerance_the_program_meets():
    ids = np.random.default_rng(4).integers(0, 512, (2, 40)).astype(np.int32)
    want = reference_logits(TINY, ids)
    control = reference_logits(TINY, ids, precision="fp8")
    assert np.abs(control - want).max() > 1000 * tolerance(want)
    assert np.abs(reference_logits(TINY, ids, precision="bf16") - want).max() > 100 * tolerance(want)


# ------------------------------------------------------- adapter and refusals


def test_adapter_validates_and_refuses_by_name():
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.generation import generate
    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import available_model_adapters, initialize_registries

    initialize_registries()
    assert "indexed_moe" in available_model_adapters()

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
    assert model.experts_held == (0, 4) and model.num_experts == 16 and model.num_key_value_heads == 2
    assert (model.topk, model.indexer_num_heads, model.indexer_head_dim) == (8, 4, 8) and not model.tie_embeddings
    sa = TINY["sa_config"]
    for changes, match in (
        (dict(head_dim=None), "head_dim"), (dict(capacity_factor=1.0), "not indexed_moe settings"),
        (dict(sa_config={**sa, "window": 4}), "exactly the keys"), (dict(sa_config=None), "sa_config"),
        (dict(sa_config={**sa, "indexer_num_kv_heads": 2}), "ONE index key"),
        (dict(sa_config={**sa, "indexer_head_dim": 7}), "even"), (dict(sa_config={**sa, "topk": 0}), "topk"),
        (dict(attention="flash"), "computes its attention itself"), (dict(remat=True), "remat"),
        (dict(decoder_sparse_step=2), "every block"), (dict(mlp_only_layers=[0]), "every block"),
        (dict(rope_scaling={"rope_type": "yarn", "factor": 4}), "default rotary"),
        (dict(num_key_value_heads=3), "no multiple"),
    ):
        with pytest.raises(ValueError, match=match):
            build(**changes)
    assert build(rope_scaling=None).rope_theta == 10000000.0  # plain RoPE without the key
    with pytest.raises(ValueError, match="no linear decode cache"):
        model.for_decoding()
    with pytest.raises(ValueError, match="serving.mode: continuous"):
        generate(model, tiny_params(), jnp.zeros((1, 4), jnp.int32), max_new_tokens=2, temperature=0.0)


# ------------------------------------------------------------------ the preset


def test_preset_trains_and_serves_on_the_cpu(tmp_path, capsys):
    from llmtrain_tpu.cli import main

    preset = ROOT / "configs/presets/indexed_moe_smoke.yaml"
    config = tmp_path / "indexed_moe_smoke.yaml"
    config.write_text(preset.read_text().replace('root_dir: "runs"', f'root_dir: "{tmp_path}"'))
    assert main(["train", "--config", str(config), "--run-id", "im1", "--json"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["train_result"]
    assert math.isfinite(result["final_loss"]) and result["final_loss"] < result["first_step_loss"]
    out_dir = tmp_path / "bench"
    assert main([
        "serve-bench", "--config", str(config), "--from", "im1", "--requests", "6", "--rate-rps", "50",
        "--max-new-tokens", "6", "--out", str(out_dir),
    ]) == 0
    serving = json.loads((out_dir / "report.json").read_text())["serving"]
    assert serving["requests"]["completed"] == 6 and serving["requests"]["failed"] == 0
    assert serving["compile"]["within_budget"] is True and "state_leaves" not in serving["compile"]

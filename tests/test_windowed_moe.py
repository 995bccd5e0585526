"""Window and global attention by a layer pattern, in a parallel block over
routed and averaged shared experts (models/windowed_moe.py, ops/rope.py's
interleaved layout, models/moe.py:DroplessMoE with sigmoid scores), CPU, tiny
sizes.

Interleaved rotary against a hand-written pair rotation; the whole model
against the benchmark's plain reference (``benchmarks/reference/
cohere2_moe.py``, which shares no code with the program) on seeded random
weights, ALWAYS with a window (8) shorter than the sequence (40-48): the full
forward, dense and by blocks; prefill then decode at every position through
both kinds of cache leaf, with a prompt longer than the window and a context
that crosses it mid-decode; rows under, at and past the window in one decode
call; a global layer that does not notice shifted positions; the eight
shares' parts adding up to the uncut reference's layer with the shared
experts counted once; an fp8 control that fails the tolerance; the adapter's
validation and the refusals the family makes by name; the tiny preset through
``llmtrain train`` and ``serve-bench``.
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

from benchmarks.reference import cohere2_moe as ref  # noqa: E402
from llmtrain_tpu.serving.paged_kv import window_ring_blocks as window_ring  # noqa: E402
from llmtrain_tpu.ops.rope import apply_rope  # noqa: E402

CONFIG = json.loads((ROOT / "benchmarks/configs/command-a-plus.json").read_text())
# The configuration's own rehearsal size: 4 layers (three window layers of 8
# positions, one global), 4 query / 2 K/V heads of 16, 4 held experts of a
# published 16 of which 4 a token, 2 shared experts, 64 positions.
TINY = {**CONFIG, **CONFIG["rehearsal"]}
UNCUT = {**TINY, "num_experts": 16, "experts_held": [0, 16]}
WINDOW = TINY["sliding_window"]


def run_config(model: dict, **sections) -> dict:
    return {
        "schema_version": 1, "run": {"name": "windowed_moe_test", "seed": 1, "device": "cpu"}, "model": model,
        "data": {"name": "dummy_text"}, "trainer": {"max_steps": 1, "micro_batch_size": 1, "warmup_steps": 0},
        "mlflow": {"enabled": False}, **sections,
    }


def build_model(cfg: dict = TINY, dtype: str = "float32", attention: str = "dense", **extra):
    """The program's model for ``cfg``, built as the CLI builds it."""
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import initialize_registries

    initialize_registries()
    model = ref.program_model(cfg)
    model.update(dtype=dtype, param_dtype="float32", attention=attention)
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
    """float32 on both sides and the same choice of experts: 50x float32's
    epsilon at the logits' scale, times the square root of the contraction's
    length (reduction order). A key outside the window attended, a position
    on a global layer or an expert chosen otherwise misses it by orders of
    magnitude (the controls below do)."""
    return 50 * 2.0**-23 * float(np.abs(logits).max()) * math.sqrt(cfg["hidden_size"])


# --------------------------------------------------------------- the rotary


def test_interleaved_rotary_is_the_hand_written_pair_rotation():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(2, 5, 3, 8)).astype(np.float32)
    k = rng.normal(size=(2, 5, 1, 8)).astype(np.float32)
    pos = np.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 4095]])
    theta = 50000.0
    got_q, got_k = apply_rope(jnp.asarray(q), jnp.asarray(k), jnp.asarray(pos), theta=theta, interleaved=True)
    for x, got in ((q, got_q), (k, got_k)):
        want = np.zeros_like(x, np.float64)
        for b, t, h, i in np.ndindex(*x.shape[:3], 4):  # pair i is dimensions (2i, 2i + 1)
            angle = pos[b, t] * theta ** (-2.0 * i / 8)
            a, c = float(x[b, t, h, 2 * i]), float(x[b, t, h, 2 * i + 1])
            want[b, t, h, 2 * i] = a * math.cos(angle) - c * math.sin(angle)
            want[b, t, h, 2 * i + 1] = a * math.sin(angle) + c * math.cos(angle)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-3)  # float32 angles at position 4,095
        np.testing.assert_allclose(np.asarray(got)[0], want[0], atol=1e-6)
    # the reference's own rotation, written apart, is the same function
    by_ref = np.asarray(ref.rope_interleaved(jnp.asarray(q), jnp.asarray(pos), theta))
    np.testing.assert_allclose(by_ref[0], np.asarray(got_q)[0], atol=1e-5)
    np.testing.assert_allclose(by_ref, np.asarray(got_q), atol=2e-3)  # (float32 angles at 4,095, formed two ways)
    # the half-split layout pairs (i, i + d/2): another function of the same input, and the default
    half_q, _ = apply_rope(jnp.asarray(q), jnp.asarray(k), jnp.asarray(pos), theta=theta)
    assert np.abs(np.asarray(half_q) - np.asarray(got_q))[:, 1:].max() > 0.1
    # shared positions (T,) rotate every row alike
    flat_q, _ = apply_rope(jnp.asarray(q), jnp.asarray(k), jnp.arange(5), theta=theta, interleaved=True)
    np.testing.assert_allclose(np.asarray(flat_q)[0], np.asarray(got_q)[0], atol=1e-6)


# ------------------------------------------------- the model and the reference


def test_full_forward_against_the_plain_reference_with_a_window_shorter_than_the_sequence():
    _, model = build_model()
    assert model.paged_window == WINDOW == 8 and model.expert_layers == 4
    assert model.layer_types == ("sliding_attention",) * 3 + ("full_attention",) and model.tie_embeddings
    ids = np.random.default_rng(0).integers(0, 512, (3, 48)).astype(np.int32)
    want = reference_logits(TINY, ids)
    got = np.asarray(jax.jit(model.apply)({"params": tiny_params()}, jnp.asarray(ids)))
    assert np.abs(got - want).max() <= tolerance(want)
    # the parameter tree the program declares is the one the reference's weights fill
    declared = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.asarray(ids))))["params"]
    assert jax.tree.map(lambda s: s.shape, declared) == jax.tree.map(lambda a: a.shape, tiny_params())
    # attention by blocks (what a served slab runs on the chip; off it the XLA twin) is the same function
    _, blocked = build_model(attention="flash")
    by_blocks = np.asarray(jax.jit(blocked.apply)({"params": tiny_params()}, jnp.asarray(ids)))
    assert np.abs(by_blocks - want).max() <= tolerance(want)
    # the window does something: with a window of 64 the first 8 positions agree and the rest do not
    wide = {**TINY, "sliding_window": 64}
    everything = reference_logits(wide, ids)
    assert np.abs(everything[:, :8] - want[:, :8]).max() <= tolerance(want)
    assert np.abs(everything[:, 8:] - want[:, 8:]).max() > 100 * tolerance(want)
    # and its edge is i - j < 8: a window of 9 is yet another function
    assert np.abs(reference_logits({**TINY, "sliding_window": 9}, ids)[:, 8:] - want[:, 8:]).max() > 100 * tolerance(want)
    # the pattern matters: the same weights with the global layer first
    moved = {**TINY, "layer_types": ["full_attention"] + ["sliding_attention"] * 3}
    assert np.abs(reference_logits(moved, ids) - want).max() > 100 * tolerance(want)
    _, moved_model = build_model(moved)
    assert np.abs(np.asarray(moved_model.apply({"params": tiny_params()}, jnp.asarray(ids)))
                  - reference_logits(moved, ids)).max() <= tolerance(want)
    # a share that holds every expert is the uncut model, and differs from the share of four
    _, uncut = build_model(UNCUT)
    whole = np.asarray(uncut.apply({"params": tiny_params(UNCUT)}, jnp.asarray(ids)))
    assert np.abs(whole - reference_logits(UNCUT, ids)).max() <= tolerance(want)
    assert np.abs(whole - got).max() > 100 * tolerance(want)
    # packed segments: a token sees its own segment only; RoPE is relative and the global layer has no position
    seg = np.ones((3, 48), np.int32)
    seg[:, 20:] = 2
    packed = np.asarray(model.apply({"params": tiny_params()}, jnp.asarray(ids), jnp.asarray(seg)))
    alone = reference_logits(TINY, ids[:, 20:])
    assert np.abs(packed[:, :20] - want[:, :20]).max() <= tolerance(want)
    assert np.abs(packed[:, 20:] - alone).max() <= tolerance(want)
    assert np.abs(packed[:, 20:] - want[:, 20:]).max() > 100 * tolerance(want)


def test_a_global_layers_output_does_not_change_when_positions_are_shifted():
    """One layer of each kind alone, through the paged call (which takes
    positions): the global layer has no rotary, so a sequence written at
    positions 16.. attends exactly as at 0..; the window layer's output is the
    same too (RoPE is relative) but its CACHED keys are rotated by position."""
    from llmtrain_tpu.models.windowed_moe import PatternAttention

    x = jax.random.normal(jax.random.key(3), (1, 12, 64))
    tables = jnp.arange(1, 9, dtype=jnp.int32)[None]  # 8 blocks of 8 positions
    out = {}
    for window in (0, WINDOW):
        ring = window_ring(WINDOW, 8)
        attn = PatternAttention(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=4, window=window, rope_theta=50000.0,
            attention="dense", dtype=jnp.float32, param_dtype=jnp.float32, decode=True, paged_num_blocks=9,
            paged_block_tokens=8, window_num_blocks=1 + ring,
        )
        rings = jnp.arange(1, 1 + ring, dtype=jnp.int32)[None]
        call = lambda start: attn.apply(  # noqa: E731
            {"params": params, "cache": cache}, x, positions=jnp.asarray([start], jnp.int32), block_tables=tables,
            window_tables=rings, true_len=jnp.asarray([12], jnp.int32), mutable=["cache"])
        variables = attn.init(jax.random.key(0), x, positions=jnp.zeros((1,), jnp.int32), block_tables=tables,
                              window_tables=rings)
        params, cache = nn.unbox(variables["params"]), jax.tree.map(jnp.zeros_like, variables["cache"])
        (at_0, cache_0), (at_16, cache_16) = call(0), call(16)
        np.testing.assert_allclose(np.asarray(at_0), np.asarray(at_16), atol=1e-5)
        name = "window_key" if window else "paged_key"
        out[window] = (np.asarray(cache_0["cache"][name]), np.asarray(cache_16["cache"][name]))
    flat = lambda leaf: leaf[1:].reshape(-1, 32)  # noqa: E731  (positions in order, the null block dropped)
    glob_0, glob_16 = (flat(leaf) for leaf in out[0])
    np.testing.assert_array_equal(glob_0[:12], glob_16[16:28])  # the same keys, two blocks further on: no position in them
    win_0, win_16 = (flat(leaf) for leaf in out[WINDOW])
    assert np.abs(win_0[:12] - win_16[:12]).max() > 0.1  # ring entries 0 and 1 hold keys rotated by other angles


def _paged(model, slots=3, block_tokens=4):
    """The model's paged clone, zeroed caches, each row's block table and ring."""
    mb = model.block_size // block_tokens
    ring = window_ring(model.paged_window, block_tokens)
    paged = model.for_paged_decoding(
        num_blocks=1 + slots * mb, block_tokens=block_tokens, window_num_blocks=1 + slots * ring)
    shapes = jax.eval_shape(lambda: paged.init(
        jax.random.key(0), jnp.zeros((1, 1), jnp.int32), positions=jnp.zeros((1,), jnp.int32),
        block_tables=jnp.zeros((1, mb), jnp.int32), window_tables=jnp.zeros((1, ring), jnp.int32)))["cache"]
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    tables = jnp.asarray(1 + np.arange(slots * mb).reshape(slots, mb), jnp.int32)
    rings = jnp.asarray(1 + np.arange(slots * ring).reshape(slots, ring), jnp.int32)
    return paged, cache, tables, rings


@pytest.mark.parametrize("prompt", [21, 5])
def test_prefill_then_decode_through_both_kinds_of_leaf_against_the_reference(prompt):
    """The slab path (prefill: the slab's own keys, the last ring blocks
    written) and the ring path (decode, token by token: the ring gathered,
    positions recovered, the window masked) both land on the reference's full
    forward, which has neither a cache nor a ring: LOGITS are compared, at
    every position. A prompt of 21 is longer than the window of 8 (its first
    blocks are never written to the ring); a prompt of 5 lies under it and its
    context crosses the window three decode steps in, and the ring of 3
    blocks of 4 wraps at position 12, over and over up to 40."""
    _, model = build_model()
    params = tiny_params()
    ids = np.random.default_rng(1).integers(0, 512, (3, 40)).astype(np.int32)
    want = reference_logits(TINY, ids)
    paged, cache, tables, rings = _paged(model)
    assert sorted(cache["block_0"]["attn"]) == ["window_key", "window_value"]
    assert sorted(cache["block_3"]["attn"]) == ["paged_key", "paged_value"]
    # a 32-wide row folds 4 positions: a block of 4 is one row; the window leaves are sized by 3 x ring 3 + 1
    assert cache["block_0"]["attn"]["window_key"].shape == (10, 1, 128)
    assert cache["block_3"]["attn"]["paged_key"].shape == (49, 1, 128)
    step = jax.jit(lambda c, tok, pos, n: paged.apply(
        {"params": params, "cache": c}, tok, positions=pos, block_tables=tables, window_tables=rings,
        true_len=n, mutable=["cache"]))
    # a padded slab, as the engine sends it: 24 positions of which `prompt` are true
    slab = np.zeros((3, 24), np.int32)
    slab[:, :prompt] = ids[:, :prompt]
    logits, mutated = step(cache, jnp.asarray(slab), jnp.zeros((3,), jnp.int32), jnp.full((3,), prompt, jnp.int32))
    assert logits.shape == (3, 1, 512)  # the last true position's logits alone
    assert np.abs(np.asarray(logits)[:, 0] - want[:, prompt - 1]).max() <= tolerance(want)
    cache = mutated["cache"]
    for p in range(prompt, 40):
        logits, mutated = step(cache, jnp.asarray(ids[:, p : p + 1]), jnp.full((3,), p, jnp.int32), None)
        cache = mutated["cache"]
        assert np.abs(np.asarray(logits)[:, 0] - want[:, p]).max() <= tolerance(want), p
    # without `true_len` a slab returns every position's logits (no padding then: a whole bucket)
    logits, _ = step(jax.tree.map(jnp.zeros_like, cache), jnp.asarray(ids[:, :24]), jnp.zeros((3,), jnp.int32), None)
    assert np.abs(np.asarray(logits) - want[:, :24]).max() <= tolerance(want)


def test_rows_under_at_and_past_the_window_in_one_decode_call():
    """Depths 3 (under the window of 8), 8 (the first step at which a key has
    left it) and 26 (the ring has wrapped twice), in one call."""
    _, model = build_model()
    params = tiny_params()
    ids = np.random.default_rng(2).integers(0, 512, (3, 32)).astype(np.int32)
    want = reference_logits(TINY, ids)
    paged, cache, tables, rings = _paged(model)
    depth = [3, 8, 26]  # each row's prompt is prefilled alone, to its own depth
    call = jax.jit(lambda c, tok, pos, table, ring, n: paged.apply(
        {"params": params, "cache": c}, tok, positions=pos, block_tables=table, window_tables=ring, true_len=n,
        mutable=["cache", "moe_stats"]))
    for r, n in enumerate(depth):
        slab = np.zeros((1, 28), np.int32)
        slab[0, :n] = ids[r, :n]
        _, mutated = call(cache, jnp.asarray(slab), jnp.zeros((1,), jnp.int32), tables[r : r + 1], rings[r : r + 1],
                          jnp.asarray([n], jnp.int32))
        cache = mutated["cache"]
    for step in range(5):
        pos = jnp.asarray([n + step for n in depth], jnp.int32)
        tok = jnp.asarray([[ids[r, n + step]] for r, n in enumerate(depth)], jnp.int32)
        logits, mutated = call(cache, tok, pos, tables, rings, None)
        cache = mutated["cache"]
        for r, n in enumerate(depth):
            assert np.abs(np.asarray(logits)[r, 0] - want[r, n + step]).max() <= tolerance(want), (r, step)
        counted = jax.tree.leaves(mutated["moe_stats"])  # [expert_pairs, experts_hit] of each expert layer
        assert len(counted) == model.expert_layers and all(0 <= int(c.max()) <= 3 * 4 for c in counted)


def test_the_eight_shares_parts_add_up_to_the_uncut_references_layer():
    """Two experts a holder, eight holders: the routed parts of all the
    shares (program: DroplessMoE under ``experts_held``) plus the shared
    experts' part ONCE (program: ``SharedExperts``, which every holder
    computes alike) are the uncut reference's whole expert layer."""
    from llmtrain_tpu.models.moe import DroplessMoE
    from llmtrain_tpu.models.windowed_moe import SharedExperts

    key = ref.seed_key(7, 1)
    n = jax.random.normal(jax.random.key(5), (2, 9, 64))
    with jax.default_matmul_precision("highest"):
        whole = ref.make_layer(UNCUT, key, 1)
        routed_ref, shared_ref = ref.experts(whole, n, UNCUT)
        shared_by_hand = sum(
            (jax.nn.silu(n @ whole["shared.gate.w"][j]) * (n @ whole["shared.up.w"][j])) @ whole["shared.down.w"][j]
            for j in range(2)) / 2
    np.testing.assert_allclose(np.asarray(shared_ref), np.asarray(shared_by_hand), atol=1e-6)
    scale = float(np.abs(np.asarray(routed_ref + shared_ref)).max())
    parts = []
    for holder in range(8):
        cfg = {**TINY, "num_experts": 2, "experts_held": [2 * holder, 2]}
        tree = ref.program_tree({"embed": None, "final_norm.g": None, "layers": [ref.make_layer(cfg, key, 1)]}, cfg)["block_0"]
        layer = DroplessMoE(d_model=64, d_ff=64, n_experts=16, top_k=4, n_layers=4, experts_held=(2 * holder, 2))
        parts.append(layer.apply({"params": tree["moe"]}, n))
        # the reference's share of two is the same part
        np.testing.assert_allclose(
            np.asarray(ref.experts(ref.make_layer(cfg, key, 1), n, cfg)[0]), np.asarray(parts[-1]), atol=1e-5 * scale)
    shared = SharedExperts(d_model=64, d_ff=64, count=2, n_layers=4, dtype=jnp.float32, param_dtype=jnp.float32).apply(
        {"params": tree["shared_experts"]}, n)
    # float32 sums in another order
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(routed_ref + shared_ref), atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(shared), np.asarray(shared_ref), atol=1e-5 * scale)
    assert np.abs(np.asarray(parts[0] + shared) - np.asarray(routed_ref + shared_ref)).max() > 0.05 * scale  # one share is not the layer


def test_bf16_lands_near_and_an_fp8_control_fails_the_tolerance_the_program_meets():
    ids = np.random.default_rng(4).integers(0, 512, (2, 40)).astype(np.int32)
    want = reference_logits(TINY, ids)
    control = reference_logits(TINY, ids, precision="fp8")
    assert np.abs(control - want).max() > 500 * tolerance(want)  # (a tied head of 0.02-scale rows: logits of 0.5)
    assert np.abs(reference_logits(TINY, ids, precision="bf16") - want).max() > 100 * tolerance(want)
    _, model = build_model(dtype="bfloat16")
    params = jax.tree.map(
        lambda a: a if a.shape == (64, 16) else a.astype(jnp.bfloat16), tiny_params())  # the router stays float32
    got = np.asarray(jax.jit(model.apply)({"params": params}, jnp.asarray(ids)), np.float32)
    # bf16 products: 1% of the largest logit in the median; a rounding may flip one of a token's 4 experts,
    # and such a logit is simply another one
    scale = np.abs(want).max()
    assert np.median(np.abs(got - want)) < 0.01 * scale


# ------------------------------------------------------- adapter and refusals


def test_adapter_validates_and_refuses_by_name():
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.generation import generate
    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import available_model_adapters, initialize_registries

    initialize_registries()
    assert "windowed_moe" in available_model_adapters()

    def build(**changes):
        model = ref.program_model(TINY)
        model.update(dtype="float32", param_dtype="float32", attention="dense")
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
    assert (model.sliding_window, model.num_shared_experts, model.rope_theta) == (8, 2, 50000.0)
    kinds = TINY["layer_types"]
    for changes, match in (
        (dict(head_dim=None), "head_dim"), (dict(capacity_factor=1.0), "not windowed_moe settings"),
        (dict(layer_types=kinds[:3]), "names 3 layers"), (dict(layer_types=kinds[:3] + ["chunked_attention"]), "a layer is one of"),
        (dict(attention="ring"), "ring / Ulysses"), (dict(attention="ulysses"), "ring / Ulysses"), (dict(remat=True), "remat"),
        (dict(shared_expert_combination_strategy="sum"), "computes 'average' only"),
        (dict(position_embedding_type="rope"), "computes 'rope_gptj' only"), (dict(use_parallel_block=False), "computes True only"),
        (dict(use_qk_norm=True), "computes False only"), (dict(first_k_dense_replace=1), "computes 0 only"),
        (dict(num_key_value_heads=3), "no multiple"), (dict(sliding_window=0), "sliding_window must be >= 1"),
        (dict(dropout=0.1), "no dropout"),
    ):
        with pytest.raises(ValueError, match=match):
            build(**changes)
    assert build(layer_types=["full_attention"] * 4).paged_window == 0  # no window layer: one pool, as every family
    with pytest.raises(ValueError, match="window_num_blocks >= 2"):
        model.for_paged_decoding(num_blocks=9, block_tokens=8)
    with pytest.raises(ValueError, match="no linear decode cache"):
        model.for_decoding()
    with pytest.raises(ValueError, match="serving.mode: continuous"):
        generate(model, tiny_params(), jnp.zeros((1, 4), jnp.int32), max_new_tokens=2, temperature=0.0)


# ------------------------------------------------------------------ the preset


def test_preset_trains_and_serves_on_the_cpu(tmp_path, capsys):
    from llmtrain_tpu.cli import main

    preset = ROOT / "configs/presets/windowed_moe_smoke.yaml"
    config = tmp_path / "windowed_moe_smoke.yaml"
    config.write_text(preset.read_text().replace('root_dir: "runs"', f'root_dir: "{tmp_path}"'))
    assert main(["train", "--config", str(config), "--run-id", "wm1", "--json"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["train_result"]
    assert math.isfinite(result["final_loss"]) and result["final_loss"] < result["first_step_loss"]
    out_dir = tmp_path / "bench"
    assert main([
        "serve-bench", "--config", str(config), "--from", "wm1", "--requests", "6", "--rate-rps", "50",
        "--max-new-tokens", "12", "--out", str(out_dir),
    ]) == 0
    serving = json.loads((out_dir / "report.json").read_text())["serving"]
    assert serving["requests"]["completed"] == 6 and serving["requests"]["failed"] == 0
    assert serving["compile"]["within_budget"] is True and serving["compile"]["window_ring_blocks"] == 2
    assert serving["kv_pool"]["window_capacity_blocks"] == 4 * 2 and serving["kv_pool"]["window_allocated_blocks"] == 0

"""Tier-1 tests of the A.X-K1 family's plain reference
(``benchmarks/reference/axk1.py``) and of what the benchmark added with it
(CPU, tiny sizes): the configuration's numbers; weights as pure functions of
(key, leaf, layer, expert); the 16 shares of the experts adding up to the
uncut layer; the reference made layer by layer over PACKED rows and read in
slices of the vocabulary giving what the whole-model reference gives; the
program, served through the paged engine, landing on the reference's tokens;
the fp8 control not; and the new per-layer reader's arithmetic by hand.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import axk1 as ref  # noqa: E402

CONFIG = json.loads((ROOT / "benchmarks/configs/ax-k1.json").read_text())
# The configuration's own rehearsal size: 1 dense + 2 expert layers, 4 heads,
# 16 experts in 4 groups (2 stay, 4 a token), experts 0-3 held, 64 positions.
TINY = {**CONFIG, **CONFIG["rehearsal"]}


def _greedy(w, prompt, n):
    """The reference's own greedy continuation (whole-model path)."""
    import jax
    import jax.numpy as jnp

    logits_of = jax.jit(lambda ids: ref.logits_fn(w, ids, TINY))
    ids = list(prompt)
    for _ in range(n):
        padded = jnp.asarray([ids + [0] * (64 - len(ids))], jnp.int32)
        ids.append(int(np.asarray(logits_of(padded))[0, len(ids) - 1].argmax()))
    return np.asarray(ids[len(prompt):], np.int32)


def test_configuration_file_holds_every_published_number():
    """Every key of the catalog row at its published value but the four that
    are ``reduced``, whose published values the file keeps beside them."""
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert CONFIG["published"] == {"num_hidden_layers": 61, "n_routed_experts": 192, "vocab_size": 163840,
                                   "max_position_embeddings": 131072}
    assert [CONFIG[k] for k in CONFIG["reduced"]] == [5, 12, 20480, 4096] and CONFIG["experts_held"] == [0, 12]
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"]) == (7168, 18432, 2048)
    assert (CONFIG["num_attention_heads"], CONFIG["q_lora_rank"], CONFIG["kv_lora_rank"], CONFIG["qk_nope_head_dim"],
            CONFIG["qk_rope_head_dim"], CONFIG["v_head_dim"]) == (64, 1536, 512, 128, 64, 128)
    assert (CONFIG["num_experts_per_tok"], CONFIG["n_group"], CONFIG["topk_group"], CONFIG["n_shared_experts"],
            CONFIG["first_k_dense_replace"], CONFIG["routed_scaling_factor"]) == (8, 8, 4, 1, 1, 2.5)
    assert CONFIG["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
                                      "original_max_position_embeddings": 4096, "type": "yarn"}
    assert CONFIG["topk_method"] == "none" and "INFERENCE" in CONFIG["assumed"]["topk_method"]
    assert CONFIG["source"].endswith("skt/A.X-K1/blob/main/config.json")
    assert {"topk_method", "rope", "precision", "initializer"} <= set(CONFIG["assumed"])
    assert "16 chips share each layer" in CONFIG["deployment"] and "12 of 192" in CONFIG["deployment"]
    # ISSUE 31's arithmetic: 101.12M of attention, 44.04M an expert, 3,491.3M in all.
    assert ref.expert_params(CONFIG) == 3 * 7168 * 2048 == 44_040_192
    assert ref.layer_params(CONFIG, 0) == 497_500_160 and ref.layer_params(CONFIG, 1) == 146_554_880
    assert ref.total_params(CONFIG) == 3_491_257_344


def test_bytes_a_decode_call_must_move():
    assert ref.kv_bytes_per_position(CONFIG) == 5 * (512 + 64) * 2 == 5760
    assert ref.expert_bytes(CONFIG) == 88_080_384
    routers = 4 * 7168 * 192
    outside = 497_500_160 + 4 * 146_554_880 + 20480 * 7168 + 7168
    assert ref.weight_bytes(CONFIG) == (outside - routers) * 2 + routers * 4  # the routers stay float32
    call = ref.weight_bytes(CONFIG) + 48 * ref.expert_bytes(CONFIG)  # every held expert hit
    assert 6.69e9 < call < 6.72e9  # the issue's 6.69 GB a call, 8.2 ms at 819 GB/s
    with pytest.raises(NotImplementedError, match="train_flops_per_token.*no training cell"):
        ref.train_flops_per_token(CONFIG, 4096)
    model = ref.program_model(CONFIG)
    assert model["name"] == "latent_moe" and model["block_size"] == 4096 and model["vocab_size"] == 20480
    assert model["extra"]["n_routed_experts"] == 192 and model["extra"]["experts_held"] == [0, 12]


def test_weights_are_pure_functions_of_key_leaf_layer_and_expert():
    import jax
    import jax.numpy as jnp

    key = ref.seed_key(2147483700, 1)
    whole = jax.jit(lambda k: ref.make_weights(TINY, k))(key)
    rows = ref.vocab_slice_rows(TINY)
    close = dict(rtol=3e-7, atol=1e-9)  # another program may fuse the draw differently: one float32 ulp
    for i in (0, 3, ref.VOCAB_SLICES - 1):  # a vocabulary matrix is its slices, in order
        np.testing.assert_allclose(whole["embed"][i * rows : (i + 1) * rows], ref.embed_slice(TINY, key, i), **close)
        np.testing.assert_allclose(whole["head"][:, i * rows : (i + 1) * rows], ref.head_slice(TINY, key, i), **close)
    alone = jax.jit(lambda k: ref.make_layer(TINY, k, 2))(key)  # one layer made alone is that layer
    for name, leaf in alone.items():
        np.testing.assert_allclose(whole["layers"][2][name], leaf, **close)
    assert "gate.w" in whole["layers"][0] and "router.w" not in whole["layers"][0]  # layer 0 is dense
    assert whole["layers"][1]["router.w"].shape == (64, 16) and whole["layers"][1]["experts.gate.w"].shape == (4, 64, 32)
    assert not np.array_equal(whole["layers"][1]["q_a.w"], whole["layers"][2]["q_a.w"])
    # A share draws the SAME expert the whole layer would: experts 4-7 of the 16, alone or among all.
    everyone = jax.jit(lambda k: ref.make_layer(TINY, k, 1, held=(0, 16)))(key)
    share = jax.jit(lambda k: ref.make_layer(TINY, k, 1, held=(4, 4)))(key)
    for name in ("experts.gate.w", "experts.up.w", "experts.down.w"):
        np.testing.assert_allclose(everyone[name][4:8], share[name], **close)
        np.testing.assert_allclose(everyone[name][:4], whole["layers"][1][name], **close)
    one = jax.jit(lambda k: ref.make_expert(TINY, k, 1, 6))(key)
    np.testing.assert_allclose(one["experts.up.w"], share["experts.up.w"][2], **close)
    # What a server holds in bf16 is the rounding of what the reference holds; the router stays float32.
    low = jax.jit(lambda k: ref.make_weights(TINY, k, jnp.bfloat16))(key)
    np.testing.assert_array_equal(low["embed"], whole["embed"].astype(jnp.bfloat16))
    np.testing.assert_array_equal(low["layers"][1]["experts.down.w"],
                                  whole["layers"][1]["experts.down.w"].astype(jnp.bfloat16))
    assert low["layers"][1]["router.w"].dtype == jnp.float32 and low["layers"][1]["kv_b.w"].dtype == jnp.bfloat16
    assert ref.init_weights(TINY, 5).keys() == {"key"}  # the reference's own copy is a handle
    assert ref.seed_key(2**31 + 5, 1) is not None  # seeds pass 32 signed bits


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The guide's section 4 test, on the reference's whole expert block:
    what 16 holders of one expert each return, with the part every holder
    computes alike (residual, attention, shared expert) counted once, is
    what the holder of all 16 returns."""
    import jax
    import jax.numpy as jnp

    key = ref.seed_key(11, 1)
    lw = jax.jit(lambda k: ref.make_layer(TINY, k, 1, held=(0, 16)))(key)
    h = 0.5 * jax.random.normal(jax.random.key(1), (2, 24, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    seg = jnp.ones((2, 24), jnp.int32)

    def block(first, count):
        mine = {k: (v[first : first + count] if k.startswith("experts.") else v) for k, v in lw.items()}
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.layer_forward(mine, h, pos, seg, TINY, held=(first, count)), np.float64)

    uncut, alike = block(0, 16), block(0, 0)  # no expert held: residual + attention + shared expert
    shares = [block(e, 1) for e in range(16)]
    assert np.abs(uncut - alike).max() > 1e-3  # the routed part is there to be divided
    np.testing.assert_allclose(sum(s - alike for s in shares) + alike, uncut, atol=1e-6)
    np.testing.assert_allclose(block(0, 4) - alike, sum(s - alike for s in shares[:4]), atol=1e-6)
    # every token's weights sum to the scaling factor, over all 16 experts, held or not
    weights = np.asarray(ref.routing(jax.nn.sigmoid(jax.random.normal(jax.random.key(2), (50, 16))), TINY))
    assert ((weights > 0).sum(-1) == 4).all() and np.allclose(weights.sum(-1), 2.5, rtol=1e-6)


def test_layer_by_layer_packed_reference_reads_what_the_whole_model_reads():
    w = ref.init_weights(TINY, 99)
    rng = np.random.default_rng(1)
    seqs = []
    for n in (12, 20, 5, 30):
        prompt = rng.integers(0, 512, n).astype(np.int32)
        seqs.append((prompt, _greedy(w, prompt, 10)))
    assert ref.pack([22, 30, 15, 40], 64) == [[3, 0], [1, 2]]  # longest first, into the first row with room
    out = ref.served_token_gaps(w, TINY, seqs)  # its own greedy tokens, several sequences a packed row: gap 0
    assert out["tokens"] == 40 and out["widest_gap"] == 0.0 and out["first_mean_gap"] == 0.0
    alone = [ref.served_token_gaps(w, TINY, [s]) for s in seqs[:2]]  # a sequence packed with others reads as alone
    assert all(o["widest_gap"] == 0.0 for o in alone) and sum(o["tokens"] for o in alone) == 20
    exact = ref.served_token_gaps(w, TINY, seqs, precision="f32", pad_to=(32,))
    assert exact["control_widest_gap"] == 0.0  # the reference in its own place loses nothing
    altered = [(p, (s + 1) % 512) for p, s in seqs]  # a token altered where it is produced
    assert ref.served_token_gaps(w, TINY, altered)["widest_gap"] > 0.0
    with pytest.raises(ValueError, match="exceeds the context"):
        ref.pack([65], 64)


def test_served_token_gap_control_in_fp8_is_wider():
    w = ref.init_weights(TINY, 99)
    rng = np.random.default_rng(2)
    longer = [(rng.integers(0, 512, 12).astype(np.int32), rng.integers(0, 512, 40).astype(np.int32)) for _ in range(3)]
    control = ref.served_token_gaps(w, TINY, longer, precision="fp8")
    assert control["tokens"] == 120 and control["control_widest_gap"] > 0.0
    probes = [(rng.integers(0, 512, 20).astype(np.int32), np.zeros(1, np.int32)) for _ in range(64)]
    assert ref.served_token_gaps(w, TINY, probes, precision="fp8")["control_first_mean_gap"] > 0.0


def test_the_program_served_through_the_paged_engine_lands_on_the_reference():
    """The family's reference-against-program test in the form the cell
    uses: the program's model in float32 behind ``PagedDecodeEngine`` +
    ``ContinuousBatchingScheduler`` (materialised prefill in buckets,
    absorbed decode in compacted batches, latent blocks reused), its served
    tokens read by the layer-by-layer reference. float32 on both sides, so a
    served token lies under the reference's best only where two logits tie
    to reduction order: 50x float32's epsilon at the logits' scale. The
    control, the same reference with fp8 products in the program's place, is
    wider than that at the same positions."""
    import jax
    import jax.numpy as jnp

    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import initialize_registries
    from llmtrain_tpu.serving import ContinuousBatchingScheduler, PagedDecodeEngine, ServeRequest

    initialize_registries()
    seed = 4321
    w = ref.init_weights(TINY, seed)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (9, 21, 14, 30, 6, 17)]
    scale = float(np.abs(np.asarray(jax.jit(lambda i: ref.logits_fn(w, i, TINY))(
        jnp.asarray([list(prompts[3]) + [0] * 34], jnp.int32)))).max())
    tol = 50 * 2.0**-23 * scale * math.sqrt(TINY["hidden_size"])
    model_section = ref.program_model(TINY)
    model_section.update(dtype="float32", param_dtype="float32")
    cfg = RunConfig.model_validate({
        "schema_version": 1, "run": {"name": "t", "seed": 1, "device": "cpu"}, "model": model_section,
        "data": {"name": "dummy_text"}, "trainer": {"max_steps": 1, "micro_batch_size": 1, "warmup_steps": 0},
        "mlflow": {"enabled": False},
    })
    model = build_adapter(cfg).build_model(cfg)
    params = jax.jit(lambda k: ref.program_tree(ref.make_weights(TINY, k), TINY))(ref.seed_key(seed, 1))
    engine = PagedDecodeEngine(model, params, block_tokens=8, max_batch_slots=3,
                               prompt_buckets=[16, 32], batch_buckets=[3])
    scheduler = ContinuousBatchingScheduler(engine)
    reqs = [ServeRequest(prompt_ids=p, max_new_tokens=8, temperature=0.0, eos_token_id=None, seed=i)
            for i, p in enumerate(prompts)]
    for r in reqs:
        scheduler.submit(r)
    for _ in range(400):
        scheduler.step()
        if all(r.done.is_set() for r in reqs):
            break
    assert all(r.finish_reason == "length" for r in reqs), [r.error for r in reqs]
    served = [(r.prompt_ids, np.asarray(r.tokens, np.int32)) for r in reqs]
    gaps = ref.served_token_gaps(w, TINY, served, precision="fp8", pad_to=(32, 64))
    assert gaps["tokens"] == 48 and gaps["widest_gap"] <= tol < gaps["control_widest_gap"]


def _reader(name: str):
    spec = importlib.util.spec_from_file_location("reader_under_test", ROOT / "benchmarks/metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_decode_floor_share_by_hand():
    read = _reader("serve_decode_floor_share.k1reason")
    stage = lambda **a: ("serve/engine.stage", 0.0, 0.001, a)  # noqa: E731
    fetch = lambda **a: ("serve/engine.fetch", 0.0, 0.001, a)  # noqa: E731
    run = {
        "reference": ref, "config": CONFIG, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "records": {"span_args": [
            stage(call="decode", kv_live_tokens=90_000, kv_gathered_tokens=393_216),
            fetch(call="decode", expert_pairs=50, experts_hit=47),
            ("serve/decode", 0.0, 0.030, {"tick": 1}),
            stage(call="decode", kv_live_tokens=80_000, kv_gathered_tokens=393_216),
            fetch(call="decode", expert_pairs=44, experts_hit=40),
            ("serve/decode", 0.2, 0.240, {"tick": 2}),
            stage(call="prefill", prompt_tokens=100, bucket=256), fetch(call="prefill"),
        ]},
    }
    moved = 2 * ref.weight_bytes(CONFIG) + (47 + 40) * 88_080_384 + 170_000 * 5760
    assert read(run) == pytest.approx(100.0 * moved / 819e9 / 0.070)
    assert 20.0 < read(run) < 30.0
    # Nothing to read: no experts_hit counter (a model without an expert layer,
    # or the parent of the PR that added it), off the chip, another family.
    bare = {**run, "records": {"span_args": [
        stage(call="decode", kv_live_tokens=1, kv_gathered_tokens=2), fetch(call="decode"),
        ("serve/decode", 0.0, 0.1, {})]}}
    assert read(bare) is None
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    from benchmarks.reference import gpt2

    assert read({**run, "reference": gpt2}) is None


@pytest.mark.parametrize("name", ["serve_decode_step_ms.k1reason", "serve_prefill_share.k1reason",
                                  "serve_engine_host_ms.k1reason", "device_idle_share.k1reason",
                                  "serve_kv_read_useful_share.k1reason", "serve_decode_floor_share.k1reason"])
def test_the_six_readers_return_nothing_without_records(name):
    run = {"records": {"spans": [], "span_args": []}, "trace": None, "reference": ref, "config": CONFIG,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    assert _reader(name)(run) is None

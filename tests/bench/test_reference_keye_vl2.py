"""Tier-1 tests of the Keye-VL-2.0 family's plain reference
(``benchmarks/reference/keye_vl2.py``) and of what the benchmark added with
it (CPU, tiny sizes): the configuration's numbers; the top-k mask, the
softmax routing and the index score worked out by hand; the byte and
operation counts by hand; weights as pure functions of (key, leaf, layer,
expert); the eight shares of the experts adding up to the uncut layer; the
reference made group by group over PACKED rows giving what the whole-model
reference gives, in memory that does not depend on how many sequences there
are; the program, served through the paged engine, landing on the
reference's tokens; the fp8 control not; and the two new readers' arithmetic
by hand.
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

from benchmarks.reference import keye_vl2 as ref  # noqa: E402

CONFIG = json.loads((ROOT / "benchmarks/configs/keye-vl2-30b-a3b.json").read_text())
# The configuration's own rehearsal size: 2 layers, 4 query / 2 K/V heads of 16,
# 4 index heads of 8, topk 8, 16 experts (4 a token), experts 0-3 held, 64 positions.
TINY = {**CONFIG, **CONFIG["rehearsal"]}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


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
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"]
    assert CONFIG["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936,
                                   "max_position_embeddings": 262144}
    assert [CONFIG[k] for k in CONFIG["reduced"]] == [8, 16, 18992, 6656] and CONFIG["experts_held"] == [0, 16]
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"]) == (2048, 6144, 768)
    assert (CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"], CONFIG["head_dim"]) == (32, 4, 128)
    assert (CONFIG["num_experts_per_tok"], CONFIG["norm_topk_prob"], CONFIG["decoder_sparse_step"],
            CONFIG["mlp_only_layers"], CONFIG["rope_theta"]) == (8, True, 1, [], 10000000)
    assert CONFIG["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                                   "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
    assert CONFIG["rope_scaling"] == {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"}
    assert CONFIG["source"].endswith("Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assumed = CONFIG["assumed"]
    assert {"a_qk_norm", "b_indexer", "c_chunks", "d_ties", "e_precision", "f_no_vision_tower"} <= set(assumed)
    assert all("INFERENCE" in assumed[k] for k in ("a_qk_norm", "b_indexer", "c_chunks", "d_ties"))
    assert "8 chips share each layer" in CONFIG["deployment"] and "16 of 128" in CONFIG["deployment"]
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]  # an eighth: the guide's floor
    assert TINY["sa_config"]["topk"] < TINY["max_position_embeddings"] // 2  # the rehearsal selects
    if CATALOG.is_file():  # the catalog's own row: every number at the top level, nested groups whole
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert CONFIG["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if CONFIG.get(k, "absent") != v]
        assert sorted(differs) == sorted(CONFIG["reduced"])
    # ISSUE 35's arithmetic: 18.87M of attention, 2.26M of indexer, 4.72M an expert, 853M in all.
    shapes = {n: math.prod(s) for n, (s, _) in ref.layer_shapes(CONFIG).items()}
    assert sum(shapes[n] for n in ("q.w", "k.w", "v.w", "o.w")) == 18_874_368
    assert sum(shapes[n] for n in ("index_q.w", "index_k.w", "index_w.w")) == 2_260_992
    assert shapes["router.w"] == 262_144 and ref.expert_params(CONFIG) == 3 * 2048 * 768 == 4_718_592
    assert ref.layer_params(CONFIG) == 21_401_984  # + 4,096 + 256 + 128 of norms
    assert ref.total_params(CONFIG) == 8 * (21_401_984 + 16 * 4_718_592) + 2 * 18992 * 2048 + 2048 == 852_988_928


def test_bytes_and_operations_by_hand():
    assert ref.kv_bytes_per_position(CONFIG) == 8 * 2 * 4 * 128 * 2 == 16_384
    assert ref.index_bytes_per_position(CONFIG) == 8 * 64 * 2 == 1_024
    assert ref.expert_bytes(CONFIG) == 9_437_184
    routers = 8 * 2048 * 128
    outside = 8 * 21_401_984 + 18992 * 2048 + 2048
    assert ref.weight_bytes(CONFIG) == (outside - routers) * 2 + routers * 4  # the routers stay float32
    assert 0.42e9 < ref.weight_bytes(CONFIG) < 0.43e9  # the issue's 0.42 GB outside the experts
    # the issue's decode call: 64 rows at 3,400 live positions, every held expert hit
    call = (ref.weight_bytes(CONFIG) + 8 * 16 * ref.expert_bytes(CONFIG) + 64 * 3400 * ref.index_bytes_per_position(CONFIG)
            + 64 * 2048 * ref.kv_bytes_per_position(CONFIG))
    assert 3.9e9 < call < 4.05e9 and 64 * 2048 * 16_384 / call > 0.5  # the selection's K/V is over half of it
    # a prompt of 3 tokens, by hand: 3 tokens through 21,397,504 matrix parameters and (8 * 16 / 128 = 1)
    # held pair each, 1 + 2 + 3 index pairs of 16 x 64, as many attended pairs of 32 x 128 twice, one row of the head
    matrices = 21_401_984 - (2048 + 128 + 128 + 64 + 64 + 2048)
    want = 8 * (3 * 2 * (matrices + 4_718_592) + 2 * 16 * 64 * 6 + 4 * 32 * 128 * 6) + 2 * 2048 * 18992
    assert ref.prefill_flops(CONFIG, 3, 6, 6) == pytest.approx(want, rel=1e-12)
    # a whole 6,144-token prompt: past position 2,047 a query attends 2,048 positions, not all it sees
    seen = np.arange(1, 6145)
    full = ref.prefill_flops(CONFIG, 6144, int(seen.sum()), int(np.minimum(seen, 2048).sum()))
    dense = ref.prefill_flops(CONFIG, 6144, int(seen.sum()), int(seen.sum()))
    assert 3.0e12 < full < dense and (dense - full) / dense > 0.2
    with pytest.raises(NotImplementedError, match="train_flops_per_token.*no training cell"):
        ref.train_flops_per_token(CONFIG, 4096)


def test_top_k_mask_routing_and_index_score_by_hand():
    import jax
    import jax.numpy as jnp

    inf = -np.inf
    scores = jnp.asarray([
        [0.5, 0.9, 0.5, 0.1, 0.5, 0.7, inf, inf],   # k = 3: 0.9, 0.7, then the FIRST of the three 0.5s
        [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],   # all equal: the first three
        [0.3, 0.2, inf, inf, inf, inf, inf, inf],   # two seeable positions: both, nothing at -inf
        [-1.0, 2.0, 2.0, 2.0, 2.0, -3.0, 0.0, inf],  # four equal at the top: the first three of them
    ], jnp.float32)
    want = np.asarray([[1, 1, 0, 0, 0, 1, 0, 0], [1, 1, 1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0, 0, 0],
                       [0, 1, 1, 1, 0, 0, 0, 0]], bool)
    np.testing.assert_array_equal(np.asarray(ref.top_k_mask(scores, 3)), want)
    np.testing.assert_array_equal(np.asarray(ref.top_k_mask(scores, 8)), np.asarray(scores) > inf)  # k >= width: all seen
    # softmax routing: 2 of 4 experts, ties to the lower index, weights renormalised over the two
    four = {**TINY, "num_experts_per_tok": 2}
    probs = jnp.asarray([[0.1, 0.4, 0.4, 0.1], [0.7, 0.1, 0.1, 0.1]], jnp.float32)
    np.testing.assert_allclose(np.asarray(ref.routing(probs, four)), [[0, 0.5, 0.5, 0], [0.875, 0.125, 0, 0]], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.routing(probs, {**four, "norm_topk_prob": False})),
                               [[0, 0.4, 0.4, 0], [0.7, 0.1, 0, 0]], rtol=1e-6)
    # the index score and the selection inside a layer: identity projections make it readable. One sequence of 4
    # tokens, topk 2, one index head of width 2 with weight 1 at every query: I[t, s] = relu(qI_t . kI_s).
    one = {**TINY, "hidden_size": 2, "num_attention_heads": 1, "num_key_value_heads": 1, "head_dim": 2,
           "moe_intermediate_size": 2, "rms_norm_eps": 0.0,
           "sa_config": {**TINY["sa_config"], "indexer_head_dim": 2, "indexer_num_heads": 1, "topk": 2}}
    eye = jnp.eye(2, dtype=jnp.float32)
    lw = {"attn_norm.g": jnp.ones(2), "q.w": eye, "k.w": eye, "v.w": eye, "q_norm.g": jnp.ones(2), "k_norm.g": jnp.ones(2),
          "o.w": eye, "index_q.w": eye, "index_k.w": eye, "index_k_norm.g": jnp.ones(2), "index_k_norm.b": jnp.zeros(2),
          "index_w.w": jnp.ones((2, 1)) / 2, "mlp_norm.g": jnp.ones(2), "router.w": jnp.zeros((2, 16)),
          **{f"experts.{k}.w": jnp.zeros((4, 2, 2)) for k in ("gate", "up", "down")}}
    # RMSNorm to unit mean square, then the index LayerNorm maps (a, b) to +-(1, -1): kI is (1, -1) for tokens 0, 2, 3
    # and (-1, 1) for token 1; qI_t = RMSNorm(x_t); w_t = mean of RMSNorm(x_t).
    x = jnp.asarray([[[2.0, 0.0], [0.0, 2.0], [3.0, 1.0], [3.0, 1.0]]])
    pos, seg = jnp.zeros((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)  # every token at position 0: no rotation
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.layer_forward(lw, x, pos, seg, one))[0]
    # Query 3 = RMSNorm(3, 1) = (1.342, 0.447): scores 0.894 at s = 0, 2, 3 (kI (1, -1)) and relu(-0.894) = 0 at
    # s = 1, each times w_3 = 0.894. topk 2 of the three equal scores: s = 0 and s = 2, NOT itself. Keys and values
    # are the normed tokens: k_0 = v_0 = (1.414, 0), k_2 = v_2 = (1.342, 0.447) = q_3.
    q3 = np.asarray([3.0, 1.0]) / math.sqrt(5.0)
    keys = {0: np.asarray([math.sqrt(2.0), 0.0]), 2: q3}
    logit = {s: float(q3 @ k) / math.sqrt(2.0) for s, k in keys.items()}
    weight = {s: math.exp(v) / sum(math.exp(u) for u in logit.values()) for s, v in logit.items()}
    attended = sum(weight[s] * keys[s] for s in keys)  # v_s = RMSNorm(x_s) = k_s here
    np.testing.assert_allclose(got[3], np.asarray([3.0, 1.0]) + attended, rtol=1e-5)  # the experts add nothing
    np.testing.assert_allclose(got[0], [2.0 + math.sqrt(2.0), 0.0], rtol=1e-5)  # query 0 sees itself alone


def test_weights_are_pure_functions_of_key_leaf_layer_and_expert():
    import jax
    import jax.numpy as jnp

    key = ref.seed_key(2147483700, 1)
    whole = jax.jit(lambda k: ref.make_weights(TINY, k))(key)
    close = dict(rtol=3e-7, atol=1e-9)  # another program may fuse the draw differently: one float32 ulp
    alone = jax.jit(lambda k: ref.make_layer(TINY, k, 1))(key)  # one layer made alone is that layer
    for name, leaf in alone.items():
        np.testing.assert_allclose(whole["layers"][1][name], leaf, **close)
    assert whole["layers"][0]["router.w"].shape == (64, 16) and whole["layers"][0]["experts.gate.w"].shape == (4, 64, 32)
    assert whole["layers"][0]["index_q.w"].shape == (64, 32) and whole["layers"][0]["index_k_norm.b"].shape == (8,)
    assert not np.array_equal(whole["layers"][0]["q.w"], whole["layers"][1]["q.w"])
    assert whole["embed"].shape == (512, 64) and whole["head"].shape == (64, 512)
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
    assert low["layers"][1]["router.w"].dtype == jnp.float32 and low["layers"][1]["index_k.w"].dtype == jnp.bfloat16
    assert ref.init_weights(TINY, 5).keys() == {"key"}  # the reference's own copy is a handle
    assert ref.seed_key(2**31 + 5, 1) is not None  # seeds pass 32 signed bits
    with pytest.raises(ValueError, match="experts_held"):
        ref.dims({**TINY, "experts_held": [14, 4]})


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's section 4 test, on the reference's whole block: what 8
    holders of two experts each return, with the part every holder computes
    alike (residual, selected attention) counted once, is what the holder
    of all 16 returns."""
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

    uncut, alike = block(0, 16), block(0, 0)  # no expert held: residual + attention
    shares = [block(2 * holder, 2) for holder in range(8)]
    routed = np.abs(uncut - alike).max()
    assert routed > 1e-5  # the routed part is there to be divided
    np.testing.assert_allclose(sum(s - alike for s in shares) + alike, uncut, atol=1e-4 * routed)
    np.testing.assert_allclose(block(0, 4) - alike, sum(s - alike for s in shares[:2]), atol=1e-4 * routed)
    # every token's weights sum to one, over all 16 experts, held or not
    weights = np.asarray(ref.routing(jax.nn.softmax(jax.random.normal(jax.random.key(2), (50, 16))), TINY))
    assert ((weights > 0).sum(-1) == 4).all() and np.allclose(weights.sum(-1), 1.0, rtol=1e-6)


def test_group_by_group_packed_reference_reads_what_the_whole_model_reads(monkeypatch):
    w = ref.init_weights(TINY, 99)
    rng = np.random.default_rng(1)
    seqs = []
    for n in (12, 20, 5, 30):
        prompt = rng.integers(0, 512, n).astype(np.int32)
        seqs.append((prompt, _greedy(w, prompt, 10)))
    assert ref.pack([22, 30, 15, 40], 64) == [[3, 0], [1, 2]]  # longest first, into the first row with room
    monkeypatch.setattr(ref, "GROUP_POSITIONS", 64)  # one packed row a group: two groups here
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


def test_the_reference_holds_the_same_arrays_whatever_the_window_finished(monkeypatch, capsys):
    """Three times the sequences: three times the groups, each of the SAME
    shapes (one compiled program a stage, whatever the count), and one line
    a group says where the run is."""
    import jax

    w = ref.init_weights(TINY, 7)
    rng = np.random.default_rng(5)
    make = lambda n: [(rng.integers(0, 512, 30).astype(np.int32), rng.integers(0, 512, 20).astype(np.int32))  # noqa: E731
                      for _ in range(n)]
    monkeypatch.setattr(ref, "GROUP_POSITIONS", 128)  # two packed rows of 64 a group
    shapes = []
    real_jit = jax.jit

    def counting_jit(fn, *a, **kw):
        jitted = real_jit(fn, *a, **kw)

        def call(*args, **kwargs):
            shapes.append(tuple(x.shape for x in jax.tree.leaves(args) if hasattr(x, "shape")))
            return jitted(*args, **kwargs)

        return call

    monkeypatch.setattr(jax, "jit", counting_jit)
    small = ref.served_token_gaps(w, TINY, make(4))
    seen_small, n_small = set(shapes), len(shapes)
    shapes.clear()
    large = ref.served_token_gaps(w, TINY, make(12))
    assert small["tokens"] == 80 and large["tokens"] == 240
    assert set(shapes) == seen_small and len(shapes) > 2 * n_small  # more calls, no new shape: the same memory
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("[reference keye_vl2] rows")]
    assert len(lines) == 2 + 6 and "rows 10-11 of 12" in lines[-1]  # 4 and 12 packed rows, two a group


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
    ``ContinuousBatchingScheduler`` (prefill under the selection's mask in
    buckets, decode by top-k and a gather of the chosen rows in compacted
    batches, contexts of 2-5x ``topk``), its served tokens read by the
    group-by-group reference. float32 on both sides, so a served token lies
    under the reference's best only where two logits tie to reduction order:
    50x float32's epsilon at the logits' scale. The control, the same
    reference with fp8 products in the program's place, is wider than that
    at the same positions."""
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


def _span(name, t0, t1, **args):
    return (name, t0, t1, args)


def test_decode_floor_share_by_hand():
    read = _reader("serve_decode_floor_share.k2video")
    run = {
        "reference": ref, "config": CONFIG, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "records": {"span_args": [
            _span("serve/engine.stage", 0.0, 0.001, call="decode", kv_live_tokens=217_600, kv_gathered_tokens=425_984,
                  kv_selected_tokens=129_000, rows_past_topk=60),
            _span("serve/engine.fetch", 0.0, 0.001, call="decode", expert_pairs=60, experts_hit=120),
            _span("serve/decode", 0.0, 0.040, tick=1),
            _span("serve/engine.stage", 0.2, 0.201, call="decode", kv_live_tokens=200_000, kv_gathered_tokens=425_984,
                  kv_selected_tokens=125_000, rows_past_topk=58),
            _span("serve/engine.fetch", 0.2, 0.201, call="decode", expert_pairs=70, experts_hit=128),
            _span("serve/decode", 0.2, 0.245, tick=2),
            _span("serve/engine.stage", 0.3, 0.301, call="prefill", prompt_tokens=100, bucket=2048, index_pairs=5050,
                  selected_pairs=5050),
            _span("serve/engine.fetch", 0.3, 0.301, call="prefill"),
        ]},
    }
    moved = 2 * ref.weight_bytes(CONFIG) + 248 * 9_437_184 + 417_600 * 1_024 + 254_000 * 16_384
    assert read(run) == pytest.approx(100.0 * moved / 819e9 / 0.085)
    assert 5.0 < read(run) < 20.0
    # Nothing to read: no kv_selected_tokens counter (a model that selects nothing, or the parent of the PR
    # that added it), off the chip, another family.
    bare = {**run, "records": {"span_args": [
        _span("serve/engine.stage", 0.0, 0.001, call="decode", kv_live_tokens=1, kv_gathered_tokens=2),
        _span("serve/engine.fetch", 0.0, 0.001, call="decode", expert_pairs=1, experts_hit=1),
        _span("serve/decode", 0.0, 0.1)]}}
    assert read(bare) is None
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    from benchmarks.reference import axk1

    assert read({**run, "reference": axk1}) is None


def test_prefill_floor_share_by_hand():
    read = _reader("serve_prefill_floor_share.k2video")
    seen = np.arange(1, 6001)
    big = dict(prompt_tokens=6000, bucket=6144, index_pairs=int(seen.sum()), selected_pairs=int(np.minimum(seen, 2048).sum()))
    run = {
        "reference": ref, "config": CONFIG, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "records": {"span_args": [
            _span("serve/engine.stage", 0.0, 0.001, call="prefill", **big),
            _span("serve/prefill", 0.0, 0.300, tick=1),
            _span("serve/engine.stage", 0.4, 0.401, call="prefill", prompt_tokens=8, bucket=2048, index_pairs=36, selected_pairs=36),
            _span("serve/prefill", 0.4, 0.450, tick=2),
            _span("serve/engine.stage", 0.5, 0.501, call="decode", kv_live_tokens=5, kv_gathered_tokens=9, kv_selected_tokens=5,
                  rows_past_topk=0),
        ]},
    }
    # the long prompt is bound by its operations, the short one by the weights a call streams (all 128 held experts)
    ops_s = ref.prefill_flops(CONFIG, 6000, big["index_pairs"], big["selected_pairs"]) / 197e12
    weights_s = (ref.weight_bytes(CONFIG) + 128 * 9_437_184) / 819e9
    assert ops_s > weights_s > ref.prefill_flops(CONFIG, 8, 36, 36) / 197e12
    assert read(run) == pytest.approx(100.0 * (ops_s + weights_s) / 0.350)
    assert 0.0 < read(run) < 100.0
    bare = {**run, "records": {"span_args": [
        _span("serve/engine.stage", 0.0, 0.001, call="prefill", prompt_tokens=8, bucket=16),
        _span("serve/prefill", 0.0, 0.1)]}}
    assert read(bare) is None  # no index_pairs counter
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    from benchmarks.reference import axk1

    assert read({**run, "reference": axk1}) is None


@pytest.mark.parametrize("name", ["serve_decode_step_ms.k2video", "serve_prefill_share.k2video",
                                  "serve_engine_host_ms.k2video", "serve_prefill_pad_share.k2video",
                                  "device_idle_share.k2video", "serve_decode_floor_share.k2video",
                                  "serve_prefill_floor_share.k2video"])
def test_the_seven_readers_return_nothing_without_records(name):
    run = {"records": {"spans": [], "span_args": []}, "trace": None, "reference": ref, "config": CONFIG,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    assert _reader(name)(run) is None

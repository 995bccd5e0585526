"""Tier-1 tests of the ``cohere2_moe`` family's plain reference
(``benchmarks/reference/cohere2_moe.py``) and of what the benchmark added
with it (CPU, tiny sizes): the configuration's numbers against the catalog's
row and the issue's arithmetic; the window and causal masks, the sigmoid
routing and the averaged shared experts worked out by hand; a held expert
run over its choosers giving what it gives over all tokens; attention by
query blocks giving what one dense mask gives; the byte and operation counts
by hand; weights as pure functions of (key, leaf, layer, expert); the
reference made layer by layer over PACKED rows, hidden states on the host,
giving what the whole-model reference gives, on a device footprint that does
not depend on how many sequences there are; the program, served through the
paged engine with contexts past the window, landing on the reference's
tokens; the fp8 control not; and the new readers' arithmetic by hand.
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

from benchmarks.reference import cohere2_moe as ref  # noqa: E402

CONFIG = json.loads((ROOT / "benchmarks/configs/command-a-plus.json").read_text())
# The configuration's own rehearsal size: 4 layers (three window layers of 8 positions, one global), 4 query /
# 2 K/V heads of 16, 4 held experts of a published 16 (4 a token), 2 shared experts, 64 positions.
TINY = {**CONFIG, **CONFIG["rehearsal"]}
UNCUT = {**TINY, "num_experts": 16, "experts_held": [0, 16]}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CELL = "command-a-plus.serve-rag"
READERS = ["serve_decode_step_ms.caprag", "serve_prefill_share.caprag", "serve_engine_host_ms.caprag",
           "serve_prefill_pad_share.caprag", "device_idle_share.caprag", "serve_decode_floor_share.caprag",
           "serve_prefill_floor_share.caprag", "serve_window_blocks_share.caprag",
           "flash_attention_fwd_roofline.caprag", "serve_scheduler_self_ms.caprag", "serve_occupancy.caprag",
           "serve_device_wait_share.caprag", "serve_kv_gather_useful_share.caprag"]


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
    are ``reduced``, whose published values the file keeps beside them;
    ``layer_types`` whole, its first ``num_hidden_layers`` entries run."""
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"]
    assert CONFIG["published"] == {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144,
                                   "max_position_embeddings": 200000}
    assert [CONFIG[k] for k in CONFIG["reduced"]] == [4, 16, 32768, 8192] and CONFIG["experts_held"] == [0, 16]
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"], CONFIG["sliding_window"]) == (4096, 4096, 4096)
    assert (CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"], CONFIG["head_dim"]) == (128, 8, 128)
    assert (CONFIG["num_experts_per_tok"], CONFIG["num_shared_experts"], CONFIG["norm_topk_prob"],
            CONFIG["expert_selection_fn"], CONFIG["rope_theta"], CONFIG["layer_norm_eps"]) == (8, 4, True, "sigmoid", 50000, 1e-5)
    assert len(CONFIG["layer_types"]) == 32 and ref.dims(CONFIG)["kinds"] == ("sliding_attention",) * 3 + ("full_attention",)
    assert ref.layer_counts(CONFIG) == (3, 1) and CONFIG["family"] == "cohere2_moe"
    assert CONFIG["source"].endswith("CohereLabs/command-a-plus-05-2026/blob/main/config.json")
    assumed = CONFIG["assumed"]
    assert {"a_shared_average", "b_expert_width", "c_window_edge", "d_ties", "f_precision", "h_no_vision_tower"} <= set(assumed)
    assert all("INFERENCE" in assumed[k] for k in ("a_shared_average", "b_expert_width", "c_window_edge", "d_ties"))
    assert "8 chips share each layer" in CONFIG["deployment"] and "16 of 128" in CONFIG["deployment"]
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]  # an eighth: the guide's floor
    assert CONFIG["num_hidden_layers"] % CONFIG["layer_switch"] == 0  # whole periods of the pattern
    assert CONFIG["program"]["model"] == {"name": "windowed_moe", "attention": "flash", "dtype": "bfloat16",
                                          "param_dtype": "bfloat16", "dropout": 0.0, "extra": {"loss_impl": "dense"}}
    # the rehearsal's window is SHORTER than its contexts, and its period is whole
    assert TINY["sliding_window"] == 8 < TINY["max_position_embeddings"] // 4 and ref.layer_counts(TINY) == (3, 1)
    if CATALOG.is_file():  # the catalog's own row: every key, nested groups and lists whole
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "command-a-plus-05-2026")
        assert CONFIG["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if CONFIG.get(k, "absent") != v]
        assert sorted(differs) == sorted(CONFIG["reduced"])
    # ISSUE 45's arithmetic: 142.6M of attention, 344.46M a layer outside the routed experts, 50.33M an expert,
    # 4,733.3M held here, and the published whole: 218.3B, 25B active.
    shapes = {n: math.prod(s) for n, (s, _) in ref.layer_shapes(CONFIG).items()}
    assert sum(shapes[n] for n in ("q.w", "k.w", "v.w", "o.w")) == 142_606_336
    assert ref.layer_params(CONFIG) == 344_461_312 and ref.expert_params(CONFIG) == 50_331_648
    assert ref.total_params(CONFIG) == 4 * (344_461_312 + 16 * 50_331_648) + 32768 * 4096 + 4096 == 4_733_292_544
    published = 32 * (344_461_312 + 128 * 50_331_648) + 262144 * 4096 + 4096
    active = 32 * (344_461_312 + 8 * 50_331_648) + 262144 * 4096 + 4096
    assert 218.2e9 < published < 218.4e9 and 24.9e9 < active < 25.1e9  # "218B-A25B"


def test_bytes_and_operations_by_hand():
    assert ref.kv_bytes_per_position(CONFIG) == 2 * 8 * 128 * 2 == 4_096  # ONE layer's
    assert ref.expert_bytes(CONFIG) == 100_663_296
    routers = 4 * 4096 * 128
    outside = 4 * 344_461_312 + 32768 * 4096 + 4096
    assert ref.weight_bytes(CONFIG) == (outside - routers) * 2 + routers * 4  # the routers stay float32
    assert 3.02e9 < ref.weight_bytes(CONFIG) < 3.04e9  # the issue's 2.76 GB outside the experts + 0.27 GB of head
    assert 32768 * 4096 * 2 / (ref.total_params(CONFIG) * 2) == pytest.approx(0.028, abs=0.001)  # the head: 2.8% of weight bytes
    # the issue's decode call: 32 rows at 3,000 live positions of which a window layer reads at most 4,096, 56 experts hit
    call = ref.weight_bytes(CONFIG) + 56 * ref.expert_bytes(CONFIG) + 32 * 3000 * (3 + 1) * 4_096
    assert 10.0e9 < call < 10.5e9
    # a slot of 8,192 positions: 33.6 MB in the global layer, 16.8 MB in each window layer's ring; x 32 = 2.69 GB
    slot = 8192 * 4096 + 3 * (4096 + 16) * 4096
    assert slot == 84_082_688 and 2.68e9 < 32 * slot < 2.70e9
    # a prompt of 3 tokens, by hand: 3 tokens through 344,457,216 matrix parameters and (8 * 16 / 128 = 1) held
    # pair each, 1 + 2 + 3 attended pairs of 128 x 128 twice in each of the four layers, one row of the head
    matrices = 344_461_312 - 4096
    want = 4 * 3 * 2 * (matrices + 50_331_648) + 4 * 128 * 128 * (3 * 6 + 1 * 6) + 2 * 4096 * 32768
    assert ref.prefill_flops(CONFIG, 3, 6, 6) == pytest.approx(want, rel=1e-12)
    # a whole 6,144-token prompt: the window layers attend a band, the global layer the triangle: the issue's
    # 24 TFLOP (19.4 in the matrices, 4.5 in attention)
    seen = np.arange(1, 6145)
    band, triangle = int(np.minimum(seen, 4096).sum()), int(seen.sum())
    full = ref.prefill_flops(CONFIG, 6144, band, triangle)
    assert 23.5e12 < full < 24.5e12 and full < ref.prefill_flops(CONFIG, 6144, triangle, triangle)
    flops, moved = ref.flash_forward_cost(CONFIG, 6144, band, triangle)
    assert flops == 4 * 128 * 128 * (3 * band + triangle) and 4.4e12 < flops < 4.6e12
    assert moved == 4 * 6144 * ((2 * 128 + 2 * 8) * 128 * 2 + 128 * 4)  # q and out at 128 heads, k and v at 8, the log-sum
    assert flops / 197e12 > 10 * moved / 819e9  # bound by compute
    with pytest.raises(NotImplementedError, match="train_flops_per_token.*no training cell"):
        ref.train_flops_per_token(CONFIG, 4096)


def test_masks_routing_and_shared_average_by_hand():
    import jax
    import jax.numpy as jnp

    # two sequences packed into one row of 12 (columns 0-6 and 7-10), one column of padding; a window of 3
    seg = jnp.asarray([[1] * 7 + [2] * 4 + [0]])
    cols = jnp.arange(12)
    window = np.asarray(ref.attention_mask(cols, cols, seg, seg, 3))[0]
    causal = np.asarray(ref.attention_mask(cols, cols, seg, seg, 0))[0]
    for q in range(12):
        for k in range(12):
            same = int(seg[0, q]) == int(seg[0, k]) != 0
            assert causal[q, k] == (same and k <= q), (q, k)
            assert window[q, k] == (same and 0 <= q - k < 3), (q, k)
    assert window[6].sum() == 3 and causal[6].sum() == 7 and window[8].sum() == 2 and not window[11].any()
    # a block of queries over the columns it can reach reads the same rows
    part = np.asarray(ref.attention_mask(cols[4:8], cols[2:8], seg[:, 4:8], seg[:, 2:8], 3))[0]
    np.testing.assert_array_equal(part, window[4:8, 2:8])
    # sigmoid scores, the 4 highest of 16 (ties: the lower index), normalised over the chosen
    scores = jax.nn.sigmoid(jnp.asarray(np.random.default_rng(0).normal(size=(5, 16)), jnp.float32))
    scores = scores.at[0, 3].set(scores[0, 9])  # a tie between experts 3 and 9
    weights = np.asarray(ref.routing(scores, TINY))
    s = np.asarray(scores, np.float64)
    for i in range(5):
        chosen = sorted(range(16), key=lambda e: (-s[i, e], e))[:4]
        want = np.zeros(16)
        want[chosen] = s[i, chosen] / s[i, chosen].sum()
        np.testing.assert_allclose(weights[i], want, rtol=1e-6)
    raw = np.asarray(ref.routing(scores, {**TINY, "norm_topk_prob": False}))
    np.testing.assert_allclose(raw[raw > 0], s[raw > 0], rtol=1e-6)  # sigmoid scores themselves: about 1/2 each
    # the expert layer by hand: each held expert under its weight, the shared experts' MEAN added whole
    lw = jax.jit(lambda k: ref.make_layer(TINY, k, 2))(ref.seed_key(3, 1))
    n = jax.random.normal(jax.random.key(1), (1, 9, 64))
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.experts(lw, n, TINY)
        tokens = np.asarray(n[0], np.float64)
        mlp = lambda x, g, u, d: ((x @ g) / (1 + np.exp(-(x @ g))) * (x @ u)) @ d  # noqa: E731
        w = np.asarray(ref.routing(jax.nn.sigmoid(n[0] @ lw["router.w"]), TINY), np.float64)
        f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
        want_routed = sum(w[:, e : e + 1] * mlp(tokens, f64(lw["experts.gate.w"][e]), f64(lw["experts.up.w"][e]),
                                               f64(lw["experts.down.w"][e])) for e in range(4))  # experts 0-3 held
        want_shared = sum(mlp(tokens, f64(lw["shared.gate.w"][j]), f64(lw["shared.up.w"][j]), f64(lw["shared.down.w"][j]))
                          for j in range(2)) / 2
    np.testing.assert_allclose(np.asarray(routed[0]), want_routed, atol=1e-6)
    np.testing.assert_allclose(np.asarray(shared[0]), want_shared, atol=1e-6)
    assert (w[:, 4:] > 0).any() and not np.allclose(want_routed, 0)  # some pairs went to absent experts, some were held


def test_choosers_only_and_query_blocks_give_what_the_dense_forms_give(monkeypatch):
    """The two things that keep the reference inside a traced run's time
    change no number: a held expert over the rows that chose it, 4 rows a
    pass (passes that overlap at the end included), against every expert over
    ALL tokens; attention 8 queries at a time over the columns they can
    reach, against one mask over the whole row."""
    import jax
    import jax.numpy as jnp

    key = ref.seed_key(11, 1)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 44)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits_fn({"key": key}, ids, UNCUT))  # 88 tokens: one pass an expert, one query block
        monkeypatch.setattr(ref, "EXPERT_ROWS", 4)
        monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
        got = np.asarray(ref.logits_fn({"key": key}, ids, UNCUT))
        lw = ref.make_layer(UNCUT, key, 0)
        n = jax.random.normal(jax.random.key(2), (1, 23, 64))
        routed, _ = ref.experts(lw, n, UNCUT)
        w = ref.routing(jax.nn.sigmoid(n[0] @ lw["router.w"]), UNCUT)
        dense = sum(w[:, e : e + 1] * ref._expert({p: lw[f"experts.{p}.w"][e] for p in ("gate", "up", "down")}, n[0], "f32")
                    for e in range(16))
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(np.asarray(routed[0]), np.asarray(dense), atol=1e-6)


def test_weights_are_pure_functions_of_key_leaf_layer_and_expert():
    import jax
    import jax.numpy as jnp

    key = ref.seed_key(2147483700, 1)
    whole = jax.jit(lambda k: ref.make_weights(TINY, k))(key)
    close = dict(rtol=3e-7, atol=1e-9)  # another program may fuse the draw differently: one float32 ulp
    alone = jax.jit(lambda k: ref.make_layer(TINY, k, 1))(key)  # one layer made alone is that layer
    for name, leaf in alone.items():
        np.testing.assert_allclose(whole["layers"][1][name], leaf, **close)
    layer = whole["layers"][0]
    assert layer["router.w"].shape == (64, 16) and layer["experts.gate.w"].shape == (4, 64, 64)
    assert layer["shared.down.w"].shape == (2, 64, 64) and layer["q.w"].shape == (64, 64) and layer["k.w"].shape == (64, 32)
    assert not np.array_equal(layer["q.w"], whole["layers"][1]["q.w"]) and whole["embed"].shape == (512, 64)
    assert "head" not in whole  # tied: the head is the embedding transposed
    # A share draws the SAME expert the whole layer would: experts 4-7 of the 16, alone or among all; the shared
    # experts are every holder's, and another draw than routed expert 0 or 1.
    everyone = jax.jit(lambda k: ref.make_layer(TINY, k, 1, held=(0, 16)))(key)
    share = jax.jit(lambda k: ref.make_layer(TINY, k, 1, held=(4, 4)))(key)
    for part in ("gate", "up", "down"):
        np.testing.assert_allclose(everyone[f"experts.{part}.w"][4:8], share[f"experts.{part}.w"], **close)
        np.testing.assert_allclose(everyone[f"experts.{part}.w"][:4], whole["layers"][1][f"experts.{part}.w"], **close)
        np.testing.assert_allclose(everyone[f"shared.{part}.w"], share[f"shared.{part}.w"], **close)
        assert not np.allclose(everyone[f"shared.{part}.w"], everyone[f"experts.{part}.w"][:2])
    one = jax.jit(lambda k: ref.make_expert(TINY, k, 1, 6))(key)
    np.testing.assert_allclose(one["experts.up.w"], share["experts.up.w"][2], **close)
    # What a server holds in bf16 is the rounding of what the reference holds; the router stays float32.
    low = jax.jit(lambda k: ref.make_weights(TINY, k, jnp.bfloat16))(key)
    np.testing.assert_array_equal(low["embed"], whole["embed"].astype(jnp.bfloat16))
    np.testing.assert_array_equal(low["layers"][1]["shared.down.w"], whole["layers"][1]["shared.down.w"].astype(jnp.bfloat16))
    assert low["layers"][1]["router.w"].dtype == jnp.float32 and low["layers"][1]["o.w"].dtype == jnp.bfloat16
    # the program's tree: the shared experts side by side as one gated MLP, expert j in columns / rows j * 64 ...
    tree = ref.program_tree(whole, TINY)["block_1"]["shared_experts"]
    assert tree["mlp_gate"]["kernel"].shape == (64, 128) and tree["mlp_down"]["kernel"].shape == (128, 64)
    np.testing.assert_array_equal(tree["mlp_up"]["kernel"][:, 64:], whole["layers"][1]["shared.up.w"][1])
    np.testing.assert_array_equal(tree["mlp_down"]["kernel"][64:], whole["layers"][1]["shared.down.w"][1])
    assert ref.init_weights(TINY, 5).keys() == {"key"}  # the reference's own copy is a handle
    assert ref.seed_key(2**31 + 5, 1) is not None  # seeds pass 32 signed bits
    with pytest.raises(ValueError, match="experts_held"):
        ref.dims({**TINY, "experts_held": [14, 4]})
    with pytest.raises(ValueError, match="layer_types"):
        ref.dims({**TINY, "layer_types": ["sliding_attention", "full_attention"]})


def test_layer_by_layer_packed_reference_reads_what_the_whole_model_reads(monkeypatch):
    w = ref.init_weights(TINY, 99)
    rng = np.random.default_rng(1)
    seqs = []
    for n in (12, 20, 5, 30):  # contexts of 15-40: every one crosses the window of 8
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
    shapes (one compiled program a stage, whatever the count; the hidden
    states wait on the host), and one line a layer says where the run is."""
    import jax

    w = ref.init_weights(TINY, 7)
    rng = np.random.default_rng(5)
    make = lambda n: [(rng.integers(0, 512, 30).astype(np.int32), rng.integers(0, 512, 20).astype(np.int32))  # noqa: E731
                      for _ in range(n)]
    monkeypatch.setattr(ref, "GROUP_POSITIONS", 128)  # two packed rows of 64 a group
    monkeypatch.setattr(ref, "HEAD_ROWS", 40)  # the head 40 served positions at a time
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
    assert set(shapes) == seen_small and len(shapes) > 2 * n_small  # more calls, no new shape: the same device memory
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("[reference cohere2_moe] layer")]
    assert len(lines) == 4 + 4 and "layer 3 (full_attention; f32), 6 groups" in lines[-1]  # 2 then 6 groups a layer


def test_served_token_gap_control_in_fp8_is_wider():
    """(At this size a tied head of 64-wide rows leaves wide margins: it takes
    some hundreds of positions for an fp8 product to move one argmax. At the
    published widths the logits are close calls: PERF.md's calibration.)"""
    w = ref.init_weights(TINY, 99)
    rng = np.random.default_rng(2)
    longer = [(rng.integers(0, 512, 12).astype(np.int32), rng.integers(0, 512, 50).astype(np.int32)) for _ in range(8)]
    control = ref.served_token_gaps(w, TINY, longer, precision="fp8")
    assert control["tokens"] == 400 and control["control_widest_gap"] > 0.0
    exact = ref.served_token_gaps(w, TINY, longer, precision="f32")
    assert exact["control_widest_gap"] == 0.0 and exact["widest_gap"] == control["widest_gap"]


def test_the_program_served_through_the_paged_engine_lands_on_the_reference():
    """The family's reference-against-program test in the form the cell
    uses: the program's model in float32 behind ``PagedDecodeEngine`` +
    ``ContinuousBatchingScheduler`` (prefill of whole prompts in buckets,
    decode through the global table and the window ring in compacted
    batches, contexts of 2-5x the window), its served tokens read by the
    layer-by-layer reference. float32 on both sides, so a served token lies
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
    model_section.update(dtype="float32", param_dtype="float32", attention="dense")
    cfg = RunConfig.model_validate({
        "schema_version": 1, "run": {"name": "t", "seed": 1, "device": "cpu"}, "model": model_section,
        "data": {"name": "dummy_text"}, "trainer": {"max_steps": 1, "micro_batch_size": 1, "warmup_steps": 0},
        "mlflow": {"enabled": False},
    })
    model = build_adapter(cfg).build_model(cfg)
    params = jax.jit(lambda k: ref.program_tree(ref.make_weights(TINY, k), TINY))(ref.seed_key(seed, 1))
    engine = PagedDecodeEngine(model, params, block_tokens=8, max_batch_slots=3,
                               prompt_buckets=[16, 32], batch_buckets=[3])
    assert engine.window_ring == 2 and engine.pool.window_num_blocks == 1 + 3 * 2
    scheduler = ContinuousBatchingScheduler(engine)
    reqs = [ServeRequest(prompt_ids=p, max_new_tokens=12, temperature=0.0, eos_token_id=None, seed=i)
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
    assert gaps["tokens"] == 72 and gaps["widest_gap"] <= tol < gaps["control_widest_gap"]


def _reader(name: str):
    spec = importlib.util.spec_from_file_location("reader_under_test", ROOT / "benchmarks/metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _span(name, t0, t1, **args):
    return (name, t0, t1, args)


def _chip_run(spans, **more):
    return {"reference": ref, "config": CONFIG, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "records": {"span_args": spans}, **more}


def test_decode_floor_share_by_hand():
    read = _reader("serve_decode_floor_share.caprag")
    run = _chip_run([
        _span("serve/engine.stage", 0.0, 0.001, call="decode", kv_live_tokens=96_000, kv_gathered_tokens=262_144,
              kv_window_tokens=80_000, window_blocks_bound=5_200, global_blocks_bound=6_100),
        _span("serve/engine.fetch", 0.0, 0.001, call="decode", expert_pairs=30, experts_hit=55),
        _span("serve/decode", 0.0, 0.040, tick=1),
        _span("serve/engine.stage", 0.2, 0.201, call="decode", kv_live_tokens=100_000, kv_gathered_tokens=262_144,
              kv_window_tokens=82_000, window_blocks_bound=5_300, global_blocks_bound=6_300),
        _span("serve/engine.fetch", 0.2, 0.201, call="decode", expert_pairs=33, experts_hit=57),
        _span("serve/decode", 0.2, 0.245, tick=2),
        _span("serve/engine.stage", 0.3, 0.301, call="prefill", prompt_tokens=100, bucket=1536, window_pairs=5050,
              causal_pairs=5050),
        _span("serve/engine.fetch", 0.3, 0.301, call="prefill"),
    ])
    # one global layer reads every live position, each of the three window layers those inside the window
    moved = 2 * ref.weight_bytes(CONFIG) + 112 * 100_663_296 + (196_000 * 1 + 162_000 * 3) * 4_096
    assert read(run) == pytest.approx(100.0 * moved / 819e9 / 0.085)
    assert 20.0 < read(run) < 40.0
    # Nothing to read: no kv_window_tokens counter (a model without window layers, or the parent of the PR
    # that added it), off the chip, another family.
    bare = _chip_run([
        _span("serve/engine.stage", 0.0, 0.001, call="decode", kv_live_tokens=1, kv_gathered_tokens=2),
        _span("serve/engine.fetch", 0.0, 0.001, call="decode", expert_pairs=1, experts_hit=1),
        _span("serve/decode", 0.0, 0.1)])
    assert read(bare) is None
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    from benchmarks.reference import axk1

    assert read({**run, "reference": axk1}) is None


def test_prefill_floor_share_and_window_blocks_share_by_hand():
    read = _reader("serve_prefill_floor_share.caprag")
    seen = np.arange(1, 6001)
    big = dict(prompt_tokens=6000, bucket=6144, causal_pairs=int(seen.sum()), window_pairs=int(np.minimum(seen, 4096).sum()))
    run = _chip_run([
        _span("serve/engine.stage", 0.0, 0.001, call="prefill", **big),
        _span("serve/prefill", 0.0, 0.300, tick=1),
        _span("serve/engine.stage", 0.4, 0.401, call="prefill", prompt_tokens=8, bucket=1536, causal_pairs=36, window_pairs=36),
        _span("serve/prefill", 0.4, 0.450, tick=2),
        _span("serve/engine.stage", 0.5, 0.501, call="decode", kv_live_tokens=5_000, kv_gathered_tokens=9_000,
              kv_window_tokens=4_500, window_blocks_bound=300, global_blocks_bound=400),
        _span("serve/engine.stage", 0.6, 0.601, call="decode", kv_live_tokens=5_000, kv_gathered_tokens=9_000,
              kv_window_tokens=4_500, window_blocks_bound=310, global_blocks_bound=600),
    ])
    # the long prompt is bound by its operations, the short one by the weights a call streams (all 64 held experts)
    ops_s = ref.prefill_flops(CONFIG, 6000, big["window_pairs"], big["causal_pairs"]) / 197e12
    weights_s = (ref.weight_bytes(CONFIG) + 64 * 100_663_296) / 819e9
    assert ops_s > weights_s > ref.prefill_flops(CONFIG, 8, 36, 36) / 197e12
    assert read(run) == pytest.approx(100.0 * (ops_s + weights_s) / 0.350)
    assert 0.0 < read(run) < 100.0
    bare = _chip_run([
        _span("serve/engine.stage", 0.0, 0.001, call="prefill", prompt_tokens=8, bucket=16),
        _span("serve/prefill", 0.0, 0.1)])
    assert read(bare) is None  # no window_pairs counter
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    # what the pool bound in window layers over what it would bind with no window (the global layer's blocks)
    blocks = _reader("serve_window_blocks_share.caprag")
    assert blocks(run) == pytest.approx(100.0 * 610 / 1000)
    assert blocks(bare) is None


def test_serving_flash_forward_roofline_counts_a_straddling_call_by_its_share_inside_the_traced_part():
    read = _reader("flash_attention_fwd_roofline.caprag")
    seen = np.arange(1, 3001)
    inside = dict(prompt_tokens=3000, bucket=4096, causal_pairs=int(seen.sum()), window_pairs=int(seen.sum()))
    spans = [
        _span("serve/prefill", 9.9, 10.2), _span("serve/engine.stage", 9.9, 9.901, call="prefill", **inside),  # 2/3 inside
        _span("serve/prefill", 10.5, 10.8), _span("serve/engine.stage", 10.5, 10.501, call="prefill", **inside),
        _span("serve/prefill", 11.0, 11.3), _span("serve/engine.stage", 11.0, 11.001, call="prefill", **inside),
        _span("serve/prefill", 12.9, 13.2), _span("serve/engine.stage", 12.9, 12.901, call="prefill", **inside),  # 1/3 inside
        _span("serve/prefill", 13.5, 13.8), _span("serve/engine.stage", 13.5, 13.501, call="prefill", **inside),  # outside
    ]
    run = _chip_run(spans, trace={"ops": [["flash_attention_fwd [pallas]", 0.020], ["fusion", 1.0]]})
    run["records"]["trace"] = {"t0": 10.0, "t1": 13.0}
    flops, moved = ref.flash_forward_cost(CONFIG, 3000, inside["window_pairs"], inside["causal_pairs"])
    least = max(flops / 197e12, moved / 819e9)
    assert read(run) == pytest.approx(100.0 * 3 * least / 0.020)  # two whole calls and the two straddlers' thirds
    assert 0.0 < read(run) < 100.0
    # the same calls 0.05 s later (5/6, two whole, 1/6): the share does not jump with where the edges fall
    later = _chip_run([(n, a + 0.05, b + 0.05, args) for n, a, b, args in spans], trace=run["trace"])
    later["records"]["trace"] = run["records"]["trace"]
    assert read(later) == pytest.approx(read(run))
    assert read({**run, "trace": None}) is None
    assert read({**run, "trace": {"ops": [["fusion", 1.0]]}}) is None  # no such kernel on the path
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None


def test_scheduler_occupancy_wait_and_useful_read_by_hand():
    run = _chip_run([
        _span("serve/tick", 0.000, 0.050, tick=1, worked=True), _span("serve/decode", 0.004, 0.040, tick=1, batch=32),
        _span("serve/engine.stage", 0.004, 0.006, call="decode", tick=1, kv_live_tokens=100_000, kv_gathered_tokens=262_144,
              kv_window_tokens=80_000, kv_window_gathered_tokens=131_584, window_blocks_bound=1, global_blocks_bound=2),
        _span("serve/engine.fetch", 0.008, 0.040, call="decode", tick=1, parent="serve/decode"),
        _span("serve/tick", 0.050, 0.350, tick=2, worked=True), _span("serve/prefill", 0.052, 0.300, tick=2),
        _span("serve/engine.fetch", 0.060, 0.300, call="prefill", tick=2, parent="serve/prefill"),
        _span("serve/decode", 0.302, 0.340, tick=2, batch=31),
        _span("serve/engine.stage", 0.302, 0.304, call="decode", tick=2, kv_live_tokens=60_000, kv_gathered_tokens=262_144,
              kv_window_tokens=50_000, kv_window_gathered_tokens=131_584, window_blocks_bound=1, global_blocks_bound=2),
        _span("serve/engine.fetch", 0.306, 0.340, call="decode", tick=2, parent="serve/decode"),
        _span("serve/tick", 0.350, 0.351, tick=3, worked=False),  # an idle poll is no working tick
    ])
    run["records"].update(slots=32, window_s=0.4)
    assert _reader("serve_scheduler_self_ms.caprag")(run) == pytest.approx((14.0 + 14.0) / 2)
    assert _reader("serve_occupancy.caprag")(run) == pytest.approx(100.0 * 31.5 / 32)
    assert _reader("serve_device_wait_share.caprag")(run) == pytest.approx(100.0 * (0.032 + 0.240 + 0.034) / 0.4)
    # one global layer attends every live position of its table's 8,192 a row, each of three window layers the
    # positions inside the window of its ring's 257 x 16
    useful = _reader("serve_kv_gather_useful_share.caprag")
    assert useful(run) == pytest.approx(100.0 * (160_000 + 3 * 130_000) / (2 * 262_144 + 3 * 2 * 131_584))
    bare = _chip_run([_span("serve/engine.stage", 0.0, 0.001, call="decode", kv_live_tokens=1, kv_gathered_tokens=2)])
    assert useful(bare) is None  # no kv_window_gathered_tokens counter: another model, or the parent


@pytest.mark.parametrize("name", READERS)
def test_the_cells_readers_return_nothing_without_records(name):
    run = {"records": {"spans": [], "span_args": []}, "trace": None, "reference": ref, "config": CONFIG,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    assert _reader(name)(run) is None


def test_the_cells_entries_name_their_readers_and_only_this_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in mine] == READERS
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" for m in mine)
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL and bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1 and len(bench["workloads"][-1]["why"]) <= 200
    traffic = json.loads((ROOT / "benchmarks/traffic/serve-rag.json").read_text())
    assert traffic["prompt_buckets"][-1] == traffic["prompt_tokens"]["max"] == 6144
    assert traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] == CONFIG["max_position_embeddings"]
    assert traffic["prompt_buckets"][-2] == CONFIG["sliding_window"]  # the top bucket is exactly the prompts past the window

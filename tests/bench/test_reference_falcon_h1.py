"""Tier-1 tests of the Falcon-H1 family's plain reference
(``benchmarks/reference/falcon_h1.py``) and of what the benchmark added
with it (CPU, tiny sizes): the reference made layer by layer and read in
slices of the vocabulary gives what the whole-model reference gives; the
weights the program is handed are the reference's own; the program, served
through the paged engine, lands on the reference's tokens; the fp8 control
does not; and the new per-layer reader's arithmetic by hand.
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

from benchmarks.reference import falcon_h1 as ref  # noqa: E402

CONFIG = json.loads((ROOT / "benchmarks/configs/falcon-h1-34b.json").read_text())
# The configuration's own rehearsal size: 2 layers, GQA 4 -> 2 heads of 16,
# 2 groups, the PUBLISHED multipliers.
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
    """Every key of the catalog row at its published value but the two that
    are ``reduced``, whose published values the file keeps beside them."""
    assert CONFIG["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert CONFIG["published"] == {"num_hidden_layers": 72, "max_position_embeddings": 262144}
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"], CONFIG["vocab_size"]) == (5120, 21504, 261120)
    assert (CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"], CONFIG["head_dim"]) == (20, 4, 128)
    assert (CONFIG["mamba_d_ssm"], CONFIG["mamba_d_state"], CONFIG["mamba_n_heads"], CONFIG["mamba_n_groups"],
            CONFIG["mamba_d_conv"], CONFIG["mamba_chunk_size"]) == (4096, 256, 32, 2, 4, 128)
    assert CONFIG["source"].endswith("tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json")
    assert {"in_proj_parts", "gated_norm", "key_multiplier", "rope", "dt", "initializer"} <= set(CONFIG["assumed"])
    assert "one chip shares each layer" in CONFIG["deployment"] and "4 of the 72 layers" in CONFIG["deployment"]
    s = ref.dims(CONFIG)
    assert (s["proj"], s["conv_dim"], s["p"]) == (9248, 5120, 128)


def test_weights_are_pure_functions_of_key_leaf_and_layer():
    import jax
    import jax.numpy as jnp

    key = ref.seed_key(2147483700, 1)
    whole = jax.jit(lambda k: ref.make_weights(TINY, k))(key)
    rows = ref.vocab_slice_rows(TINY)
    # Equal up to the last bit: the same draw compiled into another program
    # may fuse its arithmetic differently (one float32 ulp), never more.
    close = dict(rtol=3e-7, atol=1e-9)
    for i in (0, 7, ref.VOCAB_SLICES - 1):  # a vocabulary matrix is its slices, in order
        np.testing.assert_allclose(whole["embed"][i * rows : (i + 1) * rows], ref.embed_slice(TINY, key, i), **close)
        np.testing.assert_allclose(whole["head"][:, i * rows : (i + 1) * rows], ref.head_slice(TINY, key, i), **close)
    alone = jax.jit(lambda k: ref.make_layer(TINY, k, 1))(key)  # one layer made alone is that layer
    for name, leaf in alone.items():
        np.testing.assert_allclose(whole["layers"][1][name], leaf, **close)
    assert not np.array_equal(whole["layers"][0]["q.w"], whole["layers"][1]["q.w"])
    # What a server holds in bf16 is the rounding of what the reference holds.
    low = jax.jit(lambda k: ref.make_weights(TINY, k, jnp.bfloat16))(key)
    np.testing.assert_array_equal(low["embed"], whole["embed"].astype(jnp.bfloat16))
    np.testing.assert_array_equal(low["layers"][0]["in_proj.w"], whole["layers"][0]["in_proj.w"].astype(jnp.bfloat16))
    # The initialiser the configuration states.
    layer = whole["layers"][0]
    np.testing.assert_allclose(layer["A_log"], np.log(np.arange(1, 9)), rtol=1e-6)
    np.testing.assert_array_equal(layer["D"], np.ones(8, np.float32))
    dt = np.log1p(np.exp(np.asarray(layer["dt_bias"], np.float64)))  # softplus undoes the inverse
    assert (dt >= 0.001 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert float(jnp.abs(layer["conv.w"]).max()) <= 0.5
    assert ref.init_weights(TINY, 5).keys() == {"key"}  # the reference's own copy is a handle
    assert ref.seed_key(2**31 + 5, 1) is not None  # seeds pass 32 signed bits


def test_layer_by_layer_reference_reads_what_the_whole_model_reads():
    w = ref.init_weights(TINY, 99)
    rng = np.random.default_rng(1)
    seqs = []
    for n in (12, 20, 5):
        prompt = rng.integers(0, 512, n).astype(np.int32)
        seqs.append((prompt, _greedy(w, prompt, 10)))
    out = ref.served_token_gaps(w, TINY, seqs)  # its own greedy tokens: gap 0
    assert out["tokens"] == 30 and out["widest_gap"] == 0.0 and out["first_mean_gap"] == 0.0
    padded = ref.served_token_gaps(w, TINY, seqs, pad_to=(32,))  # another padded length, other batches
    assert padded["widest_gap"] == 0.0 and padded["tokens"] == 30
    exact = ref.served_token_gaps(w, TINY, seqs, precision="f32")
    assert exact["control_widest_gap"] == 0.0  # the reference in its own place loses nothing
    altered = [(p, (s + 1) % 512) for p, s in seqs]  # a token altered where it is produced
    assert ref.served_token_gaps(w, TINY, altered)["widest_gap"] > 0.0


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
    ``ContinuousBatchingScheduler`` (prefill in buckets, compacted decode
    batches, state rows reused), its served tokens read by the layer-by-layer
    reference. float32 on both sides, so a served token lies under the
    reference's best only where two logits tie to reduction order: 50x
    float32's epsilon at the logits' scale. (That lower precision lands
    outside is shown on the logits, tests/test_falcon_h1.py, and by the
    fp8 control above: a gap is 0 wherever the served token is still the
    reference's best, so 48 tokens of a tiny model need not show it.)"""
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
    gaps = {}
    for dtype in ("float32",):
        model_section = ref.program_model(TINY)
        model_section.update(dtype=dtype, param_dtype="float32")
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
        gaps[dtype] = ref.served_token_gaps(w, TINY, served, pad_to=(32, 64))
    assert gaps["float32"]["tokens"] == 48 and gaps["float32"]["widest_gap"] <= tol


def test_bytes_a_decode_call_must_move():
    # ISSUE 26's own arithmetic at the published widths, 4 layers.
    assert ref.state_bytes_per_row(CONFIG) == 4 * (3 * 5120 * 2 + 32 * 128 * 256 * 4) == 16_900_096
    assert ref.kv_bytes_per_position(CONFIG) == 2 * 4 * 4 * 128 * 2 == 8192
    assert ref.weight_bytes(CONFIG) == (4 * ref.layer_params(CONFIG) + 261120 * 5120 + 5120) * 2
    assert ref.total_params(CONFIG) == 4 * ref.layer_params(CONFIG) + 2 * 261120 * 5120 + 5120 == 4_394_354_048


def _reader(name: str):
    spec = importlib.util.spec_from_file_location("reader_under_test", ROOT / "benchmarks/metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_state_floor_share_by_hand():
    read = _reader("serve_state_floor_share.h1batch")
    stage = lambda **a: ("serve/engine.stage", 0.0, 0.001, a)  # noqa: E731
    run = {
        "reference": ref, "config": CONFIG, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "records": {"span_args": [
            stage(call="decode", kv_live_tokens=30_000, kv_gathered_tokens=98_304, state_rows=96,
                  state_bytes=2 * 96 * 16_900_096),
            ("serve/decode", 0.0, 0.100, {"tick": 1}),
            stage(call="decode", kv_live_tokens=20_000, kv_gathered_tokens=98_304, state_rows=90,
                  state_bytes=2 * 90 * 16_900_096),
            ("serve/decode", 0.2, 0.300, {"tick": 2}),
            stage(call="prefill", prompt_tokens=100, bucket=128, state_bytes=16_900_096, scan_chunks=1),
        ]},
    }
    moved = 2 * ref.weight_bytes(CONFIG) + 2 * (96 + 90) * 16_900_096 + 50_000 * 8192
    assert read(run) == pytest.approx(100.0 * moved / 819e9 / 0.2)
    assert 5.0 < read(run) < 20.0
    # Nothing to read: no state_bytes counter (a model without state rows, or
    # the parent of the PR that added it), or off the chip.
    bare = {**run, "records": {"span_args": [
        stage(call="decode", kv_live_tokens=1, kv_gathered_tokens=2), ("serve/decode", 0.0, 0.1, {})]}}
    assert read(bare) is None
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    from benchmarks.reference import gpt2

    assert read({**run, "reference": gpt2}) is None


@pytest.mark.parametrize("name", ["serve_decode_step_ms.h1batch", "serve_prefill_share.h1batch",
                                  "serve_engine_host_ms.h1batch", "device_idle_share.h1batch"])
def test_readers_over_the_readers_that_exist_return_nothing_without_records(name):
    assert _reader(name)({"records": {"spans": [], "span_args": []}, "trace": None}) is None

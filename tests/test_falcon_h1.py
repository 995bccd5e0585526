"""Falcon-H1 family (models/falcon_h1.py over ops/ssd.py), CPU, tiny sizes.

The chunked SSD scan and the one-token step against the sequential
recurrence; the whole model against the benchmark's plain reference
(``benchmarks/reference/falcon_h1.py``, which shares no code with the
program) on seeded random weights, forward and gradient; the adapter's
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

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.reference import falcon_h1 as ref  # noqa: E402
from llmtrain_tpu.ops.ssd import ssd_chunked_scan, ssd_step, ssm_conv  # noqa: E402

# 2 layers, GQA (4 query heads on 2 KV heads of 8, narrower than d / heads),
# 2 groups, every multiplier different from 1.
TINY = {
    "family": "falcon_h1", "vocab_size": 512, "max_position_embeddings": 64, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "intermediate_size": 96, "mamba_d_ssm": 64, "mamba_d_state": 16, "mamba_n_heads": 8, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 16, "mamba_expand": 2, "rope_theta": 1e11, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "embedding_multiplier": 5.6, "key_multiplier": 0.3,
    "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.4, "ssm_in_multiplier": 0.5,
    "ssm_out_multiplier": 0.7, "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.36], "mlp_multipliers": [0.6, 0.3],
    "lm_head_multiplier": 0.2,
    "program": {"model": {"name": "falcon_h1", "attention": "dense", "dtype": "float32",
                          "param_dtype": "float32", "dropout": 0.0, "extra": {"loss_impl": "dense"}}},
}


def run_config(model: dict, **sections) -> dict:
    return {
        "schema_version": 1, "run": {"name": "falcon_h1_test", "seed": 1, "device": "cpu"}, "model": model,
        "data": {"name": "dummy_text"}, "trainer": {"max_steps": 1, "micro_batch_size": 1, "warmup_steps": 0},
        "mlflow": {"enabled": False}, **sections,
    }


def build_model(dtype: str = "float32", **extra):
    """The program's model for TINY, built as the CLI builds it."""
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.models.lora import build_adapter
    from llmtrain_tpu.registry import initialize_registries

    initialize_registries()
    model = ref.program_model(TINY)
    model["dtype"] = dtype
    model["extra"].update(extra)
    cfg = RunConfig.model_validate(run_config(model))
    adapter = build_adapter(cfg)
    return adapter, adapter.build_model(cfg)


def tiny_params(seed: int = 1234):
    return jax.jit(lambda k: ref.program_tree(ref.make_weights(TINY, k), TINY))(ref.seed_key(seed, 1))


# --------------------------------------------------------------- the scan


def _inputs(rng, b, length, h=4, p=8, g=2, n=16):
    x = jnp.asarray(rng.normal(size=(b, length, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(b, length, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, size=(h,)), jnp.float32)
    b_mat = jnp.asarray(rng.normal(size=(b, length, g, n)), jnp.float32)
    c_mat = jnp.asarray(rng.normal(size=(b, length, g, n)), jnp.float32)
    return x, dt, a, b_mat, c_mat


def _sequential(x, dt, a, b_mat, c_mat, state):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t ; y_t = S_t C_t, one step at a time."""
    h, g = x.shape[2], b_mat.shape[2]
    ys = []
    for t in range(x.shape[1]):
        bt = np.repeat(b_mat[:, t], h // g, axis=1)
        ct = np.repeat(c_mat[:, t], h // g, axis=1)
        decay = np.exp(dt[:, t] * a)[..., None, None]
        state = decay * state + (dt[:, t, :, None] * x[:, t])[..., None] * bt[:, :, None, :]
        ys.append((state * ct[:, :, None, :]).sum(-1))
    return np.stack(ys, 1), state


# float32 against float64 arithmetic of the same recurrence: the chunked form
# reorders sums of at most `length` terms of size |x||B||C| ~ 10, so a few
# hundred float32 epsilons at that scale.
SCAN_TOL = 2e-4


@pytest.mark.parametrize("length,chunk", [(16, 16), (37, 16), (5, 8), (50, 16)])
@pytest.mark.parametrize("with_state", [False, True])
def test_chunked_scan_matches_the_sequential_recurrence(length, chunk, with_state):
    rng = np.random.default_rng(length + chunk)
    x, dt, a, b_mat, c_mat = _inputs(rng, 2, length)
    state0 = rng.normal(size=(2, 4, 8, 16)) if with_state else np.zeros((2, 4, 8, 16))
    want_y, want_state = _sequential(*(np.asarray(v, np.float64) for v in (x, dt, a, b_mat, c_mat)), state0)
    y, state = ssd_chunked_scan(
        x, dt, a, b_mat, c_mat, chunk=chunk,
        initial_state=jnp.asarray(state0, jnp.float32) if with_state else None,
    )
    assert y.shape == x.shape and state.shape == (2, 4, 8, 16)
    assert np.abs(np.asarray(y) - want_y).max() <= SCAN_TOL * max(1.0, np.abs(want_y).max())
    assert np.abs(np.asarray(state) - want_state).max() <= SCAN_TOL * max(1.0, np.abs(want_state).max())


def test_padded_tail_leaves_state_and_conv_tail_where_the_last_real_token_left_them():
    rng = np.random.default_rng(3)
    x, dt, a, b_mat, c_mat = _inputs(rng, 2, 40)
    true_len = jnp.asarray([23, 2], jnp.int32)
    state0 = jnp.asarray(rng.normal(size=(2, 4, 8, 16)), jnp.float32)
    y, state = ssd_chunked_scan(x, dt, a, b_mat, c_mat, chunk=16, initial_state=state0, true_len=true_len)
    for row, n in enumerate((23, 2)):
        cut = tuple(v[row : row + 1, :n] for v in (x, dt, b_mat, c_mat))
        y_cut, state_cut = ssd_chunked_scan(
            cut[0], cut[1], a, cut[2], cut[3], chunk=16, initial_state=state0[row : row + 1]
        )
        # The same arithmetic on the real positions, so equal to rounding of the padded chunk's sums.
        np.testing.assert_allclose(np.asarray(state[row]), np.asarray(state_cut[0]), rtol=0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y[row, :n]), np.asarray(y_cut[0]), rtol=0, atol=1e-5)
    assert np.isfinite(np.asarray(y)).all()

    # The conv's state: the last three REAL inputs, reaching into the old state when fewer are real.
    xbc = jnp.asarray(rng.normal(size=(2, 40, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    old = jnp.asarray(rng.normal(size=(2, 3, 6)), jnp.float32)
    out, new = ssm_conv(xbc, w, bias, old, true_len)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(xbc[0, 20:23]))
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(jnp.concatenate([old[1, 2:], xbc[1, :2]], 0)))
    full = np.concatenate([np.asarray(old), np.asarray(xbc)], 1)
    want = sum(full[:, k : k + 40] * np.asarray(w)[k] for k in range(4)) + np.asarray(bias)
    np.testing.assert_allclose(np.asarray(out), want, rtol=0, atol=1e-5)
    # Without true_len every position is real.
    np.testing.assert_array_equal(np.asarray(ssm_conv(xbc, w, bias, old)[1]), np.asarray(xbc[:, 37:]))


def test_step_iterated_equals_the_scan():
    rng = np.random.default_rng(5)
    x, dt, a, b_mat, c_mat = _inputs(rng, 3, 21)
    state0 = jnp.asarray(rng.normal(size=(3, 4, 8, 16)), jnp.float32)
    want_y, want_state = ssd_chunked_scan(x, dt, a, b_mat, c_mat, chunk=8, initial_state=state0)
    state, ys = state0, []
    for t in range(21):
        state, y = ssd_step(state, x[:, t], dt[:, t], a, b_mat[:, t], c_mat[:, t])
        ys.append(y)
    assert np.abs(np.asarray(jnp.stack(ys, 1)) - np.asarray(want_y)).max() <= SCAN_TOL * float(jnp.abs(want_y).max())
    assert np.abs(np.asarray(state) - np.asarray(want_state)).max() <= SCAN_TOL * float(jnp.abs(want_state).max())
    # dt = 0 leaves a row exactly as it is; keep = False starts it from zero.
    frozen, _ = ssd_step(state0, x[:, 0], jnp.zeros_like(dt[:, 0]), a, b_mat[:, 0], c_mat[:, 0])
    np.testing.assert_array_equal(np.asarray(frozen), np.asarray(state0))
    fresh, _ = ssd_step(state0, x[:, 0], dt[:, 0], a, b_mat[:, 0], c_mat[:, 0], keep=jnp.asarray([True, False, True]))
    zero, _ = ssd_step(jnp.zeros_like(state0), x[:, 0], dt[:, 0], a, b_mat[:, 0], c_mat[:, 0])
    np.testing.assert_array_equal(np.asarray(fresh[1]), np.asarray(zero[1]))


# ------------------------------------------------ the model and the reference


def test_reference_agrees_with_program_and_lower_precision_does_not():
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 50)), jnp.int32)
    want = np.asarray(jax.jit(lambda i: ref.logits_fn(ref.init_weights(TINY, 1234), i, TINY))(ids))
    params = tiny_params()
    got = {}
    for dtype in ("float32", "bfloat16"):
        _, model = build_model(dtype)
        got[dtype] = np.asarray(jax.jit(lambda p, x: model.apply({"params": p}, x))(params, ids), np.float32)
    # float32 against float32: reduction order only (the chunked scan against
    # the step-by-step one included). 50x float32's epsilon at the logits'
    # scale, as for GPT-2; bf16 (epsilon 2**-8) must miss it by ten times.
    tol = 50 * 2.0**-23 * float(np.abs(want).max()) * math.sqrt(64)
    assert np.abs(got["float32"] - want).max() <= tol
    assert np.abs(got["bfloat16"] - want).max() > 10 * tol


def test_loss_gradient_agrees_with_the_reference():
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(0, 512, (2, 40)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 512, (2, 40)), jnp.int32)
    mask = jnp.ones((2, 40), jnp.float32)
    w = jax.jit(lambda k: ref.make_weights(TINY, k))(ref.seed_key(77, 1))
    want = ref.program_tree(jax.jit(jax.grad(lambda w: ref.loss_sum(w, ids, labels, mask, TINY)))(w), TINY)
    adapter, model = build_model()
    batch = {"input_ids": ids, "labels": labels, "attention_mask": jnp.ones((2, 40), jnp.int32)}

    def loss(params):
        per_row, _ = adapter.compute_loss_components(model, params, batch)
        return jnp.sum(per_row)

    got = jax.jit(jax.grad(loss))(ref.program_tree(w, TINY))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    worst = 0.0
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        ref_g = np.asarray(flat_want[path])
        # Per leaf, at the leaf's own scale: float32 sums in another order,
        # through two layers and a 40-step recurrence: 1e-4 of the largest entry.
        worst = max(worst, float(np.abs(np.asarray(g) - ref_g).max() / max(np.abs(ref_g).max(), 1e-12)))
    assert worst <= 1e-4, worst


def test_streamed_ce_sees_the_logit_multiplier():
    rng = np.random.default_rng(9)
    batch = {"input_ids": jnp.asarray(rng.integers(0, 512, (2, 24)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 512, (2, 24)), jnp.int32),
             "attention_mask": jnp.ones((2, 24), jnp.int32)}
    params = tiny_params()
    losses = []
    for impl in ("dense", "chunked_ce"):
        adapter, model = build_model(loss_impl=impl, ce_chunk=128)
        per_row, count = adapter.compute_loss_components(model, params, batch)
        losses.append(float(jnp.sum(per_row)) / float(jnp.sum(count)))
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


def test_program_model_maps_the_published_config():
    cfg = json.loads((ROOT / "benchmarks/configs/falcon-h1-34b.json").read_text())
    model = ref.program_model(cfg)
    assert (model["d_model"], model["n_layers"], model["n_heads"], model["d_ff"]) == (5120, 4, 20, 21504)
    assert model["vocab_size"] == 261120 and model["block_size"] == ref.context_length(cfg) == 1024
    assert model["extra"]["mamba_d_ssm"] == 4096 and model["extra"]["n_kv_heads"] == 4
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    # The issue's own count: 430.12M a layer, 8.79 GB in bf16 with the whole vocabulary.
    assert ref.layer_params(cfg) == 430_120_032
    assert ref.total_params(cfg) * 2 == 8_788_708_096
    assert ref.state_bytes_per_row(cfg) == 4 * (3 * 5120 * 2 + 32 * 128 * 256 * 4)
    assert ref.kv_bytes_per_position(cfg) == 8192


# ------------------------------------------------------- validation, refusals


def test_unknown_setting_raises_at_adapter_build_time():
    with pytest.raises(ValueError, match="sliding_window"):
        build_model(sliding_window=8)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        build_model(kv_cache_dtype="int8")


def test_missing_size_raises():
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.models.lora import build_adapter

    model = ref.program_model(TINY)
    del model["extra"]["mamba_d_state"]
    cfg = RunConfig.model_validate(run_config(model))
    with pytest.raises(ValueError, match="mamba_d_state"):
        build_adapter(cfg).build_model(cfg)


def test_linear_cache_is_refused_by_name():
    from llmtrain_tpu.generation import generate

    _, model = build_model()
    with pytest.raises(ValueError, match="no linear decode cache"):
        model.for_decoding()
    with pytest.raises(ValueError, match="serving.mode: continuous"):
        generate(model, tiny_params(), jnp.zeros((1, 4), jnp.int32), max_new_tokens=2, temperature=0.0)


# ------------------------------------------------------------------ the preset


def test_preset_trains_and_serves_on_the_cpu(tmp_path, capsys):
    from llmtrain_tpu.cli import main

    preset = ROOT / "configs/presets/falcon_h1_smoke.yaml"
    config = tmp_path / "falcon_h1_smoke.yaml"
    config.write_text(preset.read_text().replace('root_dir: "runs"', f'root_dir: "{tmp_path}"'))
    assert main(["train", "--config", str(config), "--run-id", "fh1", "--json"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["train_result"]
    assert math.isfinite(result["final_loss"]) and result["final_loss"] < result["first_step_loss"]
    out_dir = tmp_path / "bench"
    assert main([
        "serve-bench", "--config", str(config), "--from", "fh1", "--requests", "6", "--rate-rps", "50",
        "--max-new-tokens", "6", "--out", str(out_dir),
    ]) == 0
    serving = json.loads((out_dir / "report.json").read_text())["serving"]
    assert serving["requests"]["completed"] == 6 and serving["requests"]["failed"] == 0
    # One state row a slot, all handed back; the engine saw the two state leaves of each layer.
    assert serving["kv_pool"]["state_rows_free"] == 4 and serving["kv_pool"]["state_rows_in_use"] == 0
    assert serving["compile"]["state_leaves"] == 4 and serving["compile"]["within_budget"] is True

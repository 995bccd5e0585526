#!/usr/bin/env python3
"""Bring-up proof: GPT-2-small training and paged serving on the chip.

One process drives the normal entry points (``llmtrain_tpu.cli.main``) at
the full width of GPT-2-small — 12 layers, d_model 768, 12 heads, d_ff
3072, vocab 50257, block 512, bf16, ``attention: flash`` — with random
weights made from a seed, and checks what comes out by the repo's own
means. It is the quickest proof that the system still starts on the chip;
its timings are a smoke's, not a benchmark's.

    python chip_smoke.py              # one chip: device, kernels, train, serve
    python chip_smoke.py --multichip  # four chips: {data: 2, fsdp: 2} vs one device

Contract: exits nonzero and prints no result line when JAX finds no TPU
(or when this file is alone in its directory); any failed phase raises,
so no failure is survived with exit 0; the LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Needs no network and starts no other process. Everything it writes goes
under ``runs/chip_smoke/`` of the checkout (git-ignored); the data is the
checkout's own tracked sources through the byte tokenizer, because
``dummy_text`` caps sequences at 8 tokens, which is neither block 512 nor
a length the flash kernel can tile.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import re
import shutil
import statistics
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / "runs" / "chip_smoke"

SEED = 20260926
MODEL = dict(
    block_size=512, d_model=768, n_layers=12, n_heads=12, d_ff=3072, vocab_size=50257
)
KERNEL_BATCH = 8  # kernels phase: B8/T512/H12/D64, 4096 tokens into the CE
TRAIN_BATCH = 16  # one chip; B=64 leaves no room beside eval + checkpoint
TRAIN_STEPS = 12
SERVE_REQUESTS, SERVE_NEW_TOKENS = 8, 32

# The CE-parity band, 5e-4 (tests/test_fused_ce.py holds CPU fits to the
# same): dense and fused CE compute the same loss from the same init and
# batch, so step 1 may differ by reduction order only.
CE_PARITY_RTOL = 5e-4
# After TRAIN_STEPS bf16 updates the two trajectories have amplified that
# noise; the final losses get a looser, stated band.
FINAL_LOSS_RTOL = 2e-2
# Serve: where bf16 batched paged decode and one-sequence generate() pick
# different greedy tokens, both must be within this many logit units of the
# f32 reference's maximum at the first divergent position.
SERVE_LOGIT_TOL = 0.05
# --multichip: the sharded run against the one-device run of the same seed.
MULTICHIP_STEP1_RTOL = 2e-3
MULTICHIP_FINAL_RTOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    log(f"ok: {what}")


def require_tpu(want_count: int):
    """The device phase. Exits 2 — before anything else of the repo is
    imported and with no result line — unless JAX selected a TPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: JAX selected platform {dev.platform!r}, not 'tpu'; "
            "this script runs nothing off the chip",
            file=sys.stderr,
        )
        raise SystemExit(2)
    if len(devices) != want_count:
        print(
            f"chip_smoke: needs {want_count} chip(s), JAX reports {len(devices)}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — version string is informational only
        libtpu = "unknown"
    log(
        f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}"
    )
    return dev, len(devices)


def cache_entries() -> int:
    from llmtrain_tpu.distributed import compilation_cache_entries

    return compilation_cache_entries()


# --------------------------------------------------------------------------
# kernels: compiled Pallas vs plain jax.numpy references, bf16 tolerance
# --------------------------------------------------------------------------


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmtrain_tpu.models.gpt import dense_attention
    from llmtrain_tpu.ops.flash_attention import flash_attention
    from llmtrain_tpu.ops.fused_ce import fused_ce_per_token
    from llmtrain_tpu.ops.fused_norm import fused_add_layer_norm

    b, t = KERNEL_BATCH, MODEL["block_size"]
    h, d, v = MODEL["n_heads"], MODEL["d_model"], MODEL["vocab_size"]
    dh = d // h
    keys = jax.random.split(jax.random.key(SEED), 8)

    def close(name, got, ref, atol, rtol=2e-2):
        got = np.asarray(jax.device_get(got), np.float32)
        ref = np.asarray(jax.device_get(ref), np.float32)
        check(bool(np.isfinite(got).all()), f"{name}: finite")
        np.testing.assert_allclose(got, ref, atol=atol, rtol=rtol, err_msg=name)
        log(f"ok: {name} matches reference (max abs diff {np.abs(got - ref).max():.3g})")

    # flash attention fwd+bwd against the dense (T x T) reference.
    q, k, vv = (jax.random.normal(kk, (b, t, h, dh), jnp.bfloat16) for kk in keys[:3])
    g = jax.random.normal(keys[3], (b, t, h, dh), jnp.bfloat16)

    def att_loss(fn):
        return lambda q_, k_, v_: jnp.sum(
            (fn(q_, k_, v_) * g).astype(jnp.float32)
        )

    # flash_attention is the model's dispatch (custom_vjp over the Pallas
    # fwd and fused bwd kernels); on platform tpu it can only be Pallas.
    flash = jax.jit(jax.value_and_grad(att_loss(flash_attention), argnums=(0, 1, 2)))
    dense = jax.jit(
        jax.value_and_grad(
            att_loss(lambda *a: dense_attention(*a, attention_mask=None)),
            argnums=(0, 1, 2),
        )
    )
    text = flash.lower(q, k, vv).compile().as_text()
    check(
        text.count("tpu_custom_call") >= 3,
        "flash attention fwd+bwd lowers to tpu_custom_call kernels (not interpret)",
    )
    (_, gf), (_, gd) = flash(q, k, vv), dense(q, k, vv)
    close(
        "flash_attention fwd",
        jax.jit(flash_attention)(q, k, vv),
        jax.jit(lambda *a: dense_attention(*a, attention_mask=None))(q, k, vv),
        atol=2e-2,
    )
    for name, a, r in zip(("dq", "dk", "dv"), gf, gd):
        close(f"flash_attention bwd {name}", a, r, atol=0.1, rtol=0.1)

    # fused lm-head + CE fwd+grad against dense f32 logits.
    hid = jax.random.normal(keys[4], (b, t, d), jnp.bfloat16)
    w = (jax.random.normal(keys[5], (v, d), jnp.float32) * 0.02).astype(jnp.bfloat16)
    lab = jax.random.randint(keys[6], (b, t), 0, v)

    def ce_ref(hid_, w_):
        logits = jnp.einsum(
            "btd,vd->btv", hid_, w_, preferred_element_type=jnp.float32
        )
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return lse - picked

    fused = jax.jit(
        jax.value_and_grad(lambda h_, w_: jnp.mean(fused_ce_per_token(h_, w_, lab)), argnums=(0, 1))
    )
    ref = jax.jit(jax.value_and_grad(lambda h_, w_: jnp.mean(ce_ref(h_, w_)), argnums=(0, 1)))
    check(
        "tpu_custom_call" in fused.lower(hid, w).compile().as_text(),
        "fused CE lowers to tpu_custom_call (not interpret)",
    )
    (lf, (dhf, dwf)), (lr, (dhr, dwr)) = fused(hid, w), ref(hid, w)
    close("fused_ce_per_token mean loss", lf, lr, atol=2e-3, rtol=1e-3)
    close("fused_ce_per_token dhidden", dhf, dhr, atol=2e-6, rtol=5e-2)
    close("fused_ce_per_token dW", dwf, dwr, atol=2e-5, rtol=5e-2)

    # fused residual-add + LayerNorm fwd+grad against jax.numpy.
    x = jax.random.normal(keys[7], (b, t, d), jnp.bfloat16)
    res = jax.random.normal(keys[0], (b, t, d), jnp.bfloat16)
    scale = 1.0 + 0.1 * jax.random.normal(keys[1], (d,), jnp.float32)
    bias = 0.1 * jax.random.normal(keys[2], (d,), jnp.float32)
    gy = jax.random.normal(keys[3], (b, t, d), jnp.bfloat16)

    def ln_ref(x_, r_, s_, b_):
        s = x_.astype(jnp.float32) + r_.astype(jnp.float32)
        mu = jnp.mean(s, -1, keepdims=True)
        var = jnp.mean(jnp.square(s - mu), -1, keepdims=True)
        y = (s - mu) * jax.lax.rsqrt(var + 1e-6) * s_ + b_
        return y.astype(x_.dtype), s.astype(x_.dtype)

    def ln_loss(fn):
        def loss(x_, r_, s_, b_):
            y, s = fn(x_, r_, s_, b_)
            return jnp.sum((y * gy).astype(jnp.float32)) + jnp.sum(s.astype(jnp.float32))

        return loss

    fused_ln = jax.jit(jax.value_and_grad(ln_loss(fused_add_layer_norm), argnums=(0, 1, 2, 3)))
    ref_ln = jax.jit(jax.value_and_grad(ln_loss(ln_ref), argnums=(0, 1, 2, 3)))
    check(
        "tpu_custom_call" in fused_ln.lower(x, res, scale, bias).compile().as_text(),
        "fused add+LayerNorm lowers to tpu_custom_call (not interpret)",
    )
    yf, sf = jax.jit(fused_add_layer_norm)(x, res, scale, bias)
    yr, sr = jax.jit(ln_ref)(x, res, scale, bias)
    close("fused_add_layer_norm y", yf, yr, atol=3e-2)
    close("fused_add_layer_norm sum", sf, sr, atol=3e-2)
    (_, gfl), (_, grl) = fused_ln(x, res, scale, bias), ref_ln(x, res, scale, bias)
    for name, a, r in zip(("dx", "dresidual"), gfl[:2], grl[:2]):
        close(f"fused_add_layer_norm {name}", a, r, atol=6e-2, rtol=5e-2)
    for name, a, r in zip(("dscale", "dbias"), gfl[2:], grl[2:]):
        # Sums over 4096 bf16 rows: compare at the scale of the sum.
        tol = 2e-2 * float(jnp.max(jnp.abs(r))) + 1e-2
        close(f"fused_add_layer_norm {name}", a, r, atol=tol)


# --------------------------------------------------------------------------
# train: `llmtrain train` at GPT-2-small width, dense CE then fused CE+norm
# --------------------------------------------------------------------------


def run_config(
    name: str,
    *,
    loss_impl: str,
    fused_norm: bool,
    micro_batch: int,
    mesh: dict | None = None,
    save: bool = True,
) -> dict:
    """The run config of one smoke fit, as the YAML a user would write."""
    return {
        "schema_version": 1,
        "run": {"name": name, "seed": SEED, "device": "tpu", "deterministic": True},
        "model": {
            "name": "gpt",
            **MODEL,
            "dropout": 0.0,
            "tie_embeddings": True,
            "dtype": "bfloat16",
            "attention": "flash",
            "extra": {
                "tokenizer": "byte",  # offline; ids < 256 into the V=50257 head
                "assume_packed": True,  # local_text windows are packed
                "loss_impl": loss_impl,
                "fused_norm": fused_norm,
            },
        },
        "data": {
            "name": "local_text",
            "cache_dir": str(WORK / "datasets"),
            "extra": {
                # Tracked files of this checkout only: real text, so the
                # loss falls within a handful of steps.
                "globs": [
                    str(REPO / "llmtrain_tpu" / "**" / "*.py"),
                    str(REPO / "docs" / "*.md"),
                    str(REPO / "README.md"),
                ],
                "val_fraction": 0.05,
            },
        },
        "trainer": {
            "max_steps": TRAIN_STEPS,
            "micro_batch_size": micro_batch,
            "grad_accum_steps": 1,
            "lr": 6e-4,
            "weight_decay": 0.1,
            "warmup_steps": 2,
            "max_grad_norm": 1.0,
            "log_every_steps": 1,
            "eval_every_steps": TRAIN_STEPS // 2,
            "save_every_steps": TRAIN_STEPS if save else 10 * TRAIN_STEPS,
        },
        "distributed": {"mesh": mesh or {"data": 1}},
        "serving": {
            "mode": "continuous",
            "max_batch_slots": SERVE_REQUESTS,
            "block_tokens": 16,
            "prompt_buckets": [32],
            "batch_buckets": [SERVE_REQUESTS],
            "max_new_tokens_cap": SERVE_NEW_TOKENS,
        },
        "mlflow": {"enabled": False},
        "logging": {"level": "INFO", "json_output": False, "log_to_file": True},
        "output": {"root_dir": str(WORK / "runs")},
    }


def write_config(cfg: dict) -> Path:
    import yaml

    path = WORK / f"{cfg['run']['name']}.yaml"
    path.write_text(yaml.safe_dump(cfg, sort_keys=False))
    return path


def run_cli(argv: list[str]) -> tuple[int, str]:
    """``llmtrain <argv>`` in this process; returns (exit code, stdout).
    The command's stdout is a machine-readable summary: captured here,
    never echoed, so this script's own last line stays the last line."""
    from llmtrain_tpu import cli

    buf = io.StringIO()
    log("$ llmtrain " + " ".join(argv))
    start = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    log(f"exit {rc} after {time.perf_counter() - start:.1f}s")
    return rc, buf.getvalue()


def step_times(run_dir: Path) -> list[float]:
    """Seconds of every optimizer step, from the run's own train.log."""
    text = (run_dir / "logs" / "train.log").read_text()
    return [float(t) for t in re.findall(r"step_time=([0-9.]+)s", text)]


def train_once(name: str, *, loss_impl: str, fused_norm: bool, dev) -> dict:
    cfg_path = write_config(
        run_config(name, loss_impl=loss_impl, fused_norm=fused_norm, micro_batch=TRAIN_BATCH)
    )
    before = cache_entries()
    rc, out = run_cli(["train", "--config", str(cfg_path), "--run-id", name, "--json"])
    check(rc == 0, f"{name}: `llmtrain train` exit code 0")
    result = json.loads(out.strip().splitlines()[-1])["train_result"]
    run_dir = WORK / "runs" / name
    report = json.loads((run_dir / "report.json").read_text())
    executed = report["precision"]

    first, final = result["first_step_loss"], result["final_loss"]
    check(
        all(x is not None and math.isfinite(x) for x in (first, final)),
        f"{name}: loss finite (step 1 {first:.4f}, step {result['final_step']} {final:.4f})",
    )
    check(final < first, f"{name}: loss falls ({first:.4f} -> {final:.4f})")
    check(result["final_val_loss"] is not None, f"{name}: eval ran (val loss {result['final_val_loss']})")
    check(
        executed["attention_impl"] == "pallas_flash",
        f"{name}: attention executed as {executed['attention_impl']}",
    )
    check(
        executed["loss_impl"] == loss_impl and executed["fused_norm"] == fused_norm,
        f"{name}: loss_impl executed as {executed['loss_impl']}, fused_norm {executed['fused_norm']}",
    )
    check(
        report["memory"]["source"] == "memory_stats",
        f"{name}: memory from the allocator ({report['memory']['source']}, "
        f"peak {report['memory'].get('hbm_peak_bytes', report['memory'])})",
    )
    check(
        executed["platform"] == "tpu" and executed["device_kind"] == dev.device_kind,
        f"{name}: report names the chip ({executed['device_kind']!r} x {executed['device_count']})",
    )
    ckpts = sorted((run_dir / "checkpoints").glob("step_*.ckpt"))
    check(bool(ckpts), f"{name}: checkpoint written ({[c.name for c in ckpts]})")

    # Per-step times from the run's own log (log_every_steps: 1). Step 1
    # holds the compile; the eval and checkpoint steps are outliers the
    # median ignores.
    times = step_times(run_dir)
    steady = statistics.median(times[1:])
    compile_sec = ((report.get("goodput") or {}).get("categories") or {}).get("compile")
    after = cache_entries()
    log(
        f"{name}: smoke timings (not a benchmark): first step {times[0]:.1f}s "
        f"(compile included), median step {steady * 1e3:.1f} ms over steps 2..{len(times)} "
        f"= {TRAIN_BATCH * MODEL['block_size'] / steady:.0f} tokens/s at batch {TRAIN_BATCH}, "
        f"goodput compile_sec={compile_sec} wall_sec={report['wall_clock']['total_sec']} "
        f"compile cache entries {before} -> {after}"
    )
    return {"first": first, "final": final, "run_dir": run_dir}


def phase_train(dev) -> Path:
    dense = train_once("dense_ce", loss_impl="dense", fused_norm=False, dev=dev)
    fused = train_once("fused_ce", loss_impl="fused_ce", fused_norm=True, dev=dev)
    for key, band in (("first", CE_PARITY_RTOL), ("final", FINAL_LOSS_RTOL)):
        rel = abs(dense[key] - fused[key]) / abs(dense[key])
        check(
            rel <= band,
            f"dense CE vs fused CE+norm {key}-step loss agree: "
            f"{dense[key]:.6f} vs {fused[key]:.6f} (rel {rel:.2e} <= {band:g})",
        )
    return fused["run_dir"]


# --------------------------------------------------------------------------
# serve: the checkpoint just written, through the continuous paged scheduler
# --------------------------------------------------------------------------


def logit_gaps(cfg_path: Path, run_dir: Path, mismatch: dict) -> tuple[int, float, float]:
    """At the first position where served and generate() tokens differ:
    how far each sits below the f32 dense reference's maximum logit."""
    import jax.numpy as jnp
    import numpy as np

    from llmtrain_tpu import cli
    from llmtrain_tpu.config import load_and_validate_config
    from llmtrain_tpu.utils.logging import get_logger

    cfg, _, _ = load_and_validate_config(str(cfg_path))
    adapter, _, model = cli._build_decode_stack(cfg, get_logger())
    _, params, _ = cli._load_checkpoint_params(cfg, adapter, model, str(run_dir))
    served, ref = mismatch["served"], mismatch["reference"]
    k = next(i for i, (a, b) in enumerate(zip(served, ref)) if a != b)
    ids = np.asarray(mismatch["prompt_ids"] + served[:k], np.int32)[None, :]
    reference = model.clone(attention="dense", dtype=jnp.float32, fused_norm=False)
    logits = np.asarray(
        reference.apply({"params": params}, jnp.asarray(ids), deterministic=True)
    )[0, -1].astype(np.float64)
    top = logits.max()
    return k, float(top - logits[served[k]]), float(top - logits[ref[k]])


def phase_serve(run_dir: Path) -> None:
    cfg_path = WORK / "fused_ce.yaml"
    out_dir = WORK / "serve_bench"
    rc, out = run_cli(
        [
            "serve-bench", "--config", str(cfg_path), "--from", str(run_dir),
            "--requests", str(SERVE_REQUESTS), "--max-new-tokens", str(SERVE_NEW_TOKENS),
            "--prompt-tokens-min", "16", "--prompt-tokens-max", "32",
            "--seed", str(SEED), "--verify-parity", "--out", str(out_dir),
        ]
    )
    summary = json.loads(out[out.index("{"):])
    block = summary["serving"]
    reqs = block["requests"]
    check(
        reqs["completed"] == SERVE_REQUESTS and not reqs["failed"] and not reqs["timed_out"],
        f"serve: {reqs['completed']}/{SERVE_REQUESTS} requests answered, "
        f"{reqs['failed']} failed, {reqs['timed_out']} timed out",
    )
    check(
        block["throughput"]["new_tokens"] == SERVE_REQUESTS * SERVE_NEW_TOKENS,
        f"serve: {SERVE_NEW_TOKENS} new tokens for each request "
        f"({block['throughput']['new_tokens']} in all)",
    )
    log(
        f"serve: smoke timings (not a benchmark): tokens_per_sec="
        f"{block['throughput']['tokens_per_sec']} ttft_ms={block['slo']['ttft_ms']} "
        f"per_token_ms={block['slo']['per_token_ms']} compile={block.get('compile')}"
    )
    parity = block["parity"]
    check(parity["checked"] == SERVE_REQUESTS, f"serve: parity checked on {parity['checked']} requests")
    if rc == 0:
        check(parity["bitwise_identical"], "serve: greedy tokens bitwise identical to generate()")
        return
    # The CLI's check is bitwise and stays so. Parity may be the ONLY
    # failure, and every divergence must be a bf16 near-tie under the f32
    # reference — anything else fails the smoke.
    check(
        len(summary.get("failures", [])) == 1 and parity["mismatched"] > 0,
        f"serve: greedy parity is the only serve-bench failure ({summary.get('failures')})",
    )
    for mismatch in parity["mismatches"]:
        k, gap_served, gap_ref = logit_gaps(cfg_path, run_dir, mismatch)
        log(
            f"serve: request {mismatch['request_id']} first diverges at new token {k}: "
            f"served {mismatch['served'][k]} (f32 reference logit {gap_served:.4f} below max) "
            f"vs generate() {mismatch['reference'][k]} ({gap_ref:.4f} below max)"
        )
        check(
            max(gap_served, gap_ref) <= SERVE_LOGIT_TOL,
            f"serve: divergence is a near-tie within {SERVE_LOGIT_TOL} logit units",
        )
    log(
        f"serve: FINDING — bf16 batched paged decode and one-sequence generate() "
        f"differ on {parity['mismatched']}/{parity['checked']} requests, each at a "
        f"near-tie (<= {SERVE_LOGIT_TOL} logit units under the f32 dense reference)"
    )


# --------------------------------------------------------------------------
# --multichip: {data: 2, fsdp: 2} against the one-device run of the same seed
# --------------------------------------------------------------------------


def fit_on_mesh(name: str, mesh: dict, micro_batch: int):
    from llmtrain_tpu.config.schemas import RunConfig
    from llmtrain_tpu.registry import initialize_registries
    from llmtrain_tpu.tracking.base import NullTracker
    from llmtrain_tpu.training import Trainer
    from llmtrain_tpu.utils.logging import configure_logging

    initialize_registries()
    cfg = RunConfig.model_validate(
        run_config(
            name, loss_impl="dense", fused_norm=False,
            micro_batch=micro_batch, mesh=mesh, save=False,
        )
    )
    run_dir = WORK / "runs" / name
    (run_dir / "logs").mkdir(parents=True)
    configure_logging(level="INFO", log_file=run_dir / "logs" / "train.log")
    trainer = Trainer(cfg, run_dir, NullTracker())
    start = time.perf_counter()
    result = trainer.fit()
    times = step_times(run_dir)
    log(
        f"{name}: mesh {dict(trainer._mesh.shape)} on "
        f"{trainer._mesh.devices.size} device(s): loss {result.first_step_loss:.6f} -> "
        f"{result.final_loss:.6f} in {time.perf_counter() - start:.1f}s (compiles included); "
        f"smoke timings (not a benchmark): first step {times[0]:.1f}s, median step "
        f"{statistics.median(times[1:]) * 1e3:.1f} ms at global batch {TRAIN_BATCH}"
    )
    return trainer, result


def phase_multichip() -> None:
    import jax
    from flax.linen import meta as nn_meta

    global_batch = TRAIN_BATCH
    sharded, res4 = fit_on_mesh("mesh_d2f2", {"data": 2, "fsdp": 2}, global_batch // 4)
    check(res4.final_loss < res4.first_step_loss, "four chips: loss falls")

    # State really sharded: the fsdp-split embedding (params AND both AdamW
    # moments) lives as four addressable shards on four distinct devices,
    # each half of d_model — not everything on device 0, not replicated.
    v, d = MODEL["vocab_size"], MODEL["d_model"]
    state = sharded._state
    emb = lambda tree: nn_meta.unbox(tree)["token_embedding"]["embedding"]  # noqa: E731

    def find_adam(node):
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node
        if isinstance(node, (tuple, list)):
            return next(filter(None, map(find_adam, node)), None)
        return None

    adam = find_adam(state.opt_state)
    for what, leaf in (
        ("params", emb(state.params)), ("adam mu", emb(adam.mu)), ("adam nu", emb(adam.nu)),
    ):
        shards = leaf.addressable_shards
        check(
            len({s.device for s in shards}) == 4
            and all(s.data.shape == (v, d // 2) for s in shards)
            and len({s.index for s in shards}) == 2,
            f"four chips: {what} token_embedding sharded {leaf.sharding.spec}: "
            f"{len(shards)} shards of {shards[0].data.shape} on "
            f"{sorted(s.device.id for s in shards)}",
        )

    # The compiled step holds the kernels and the collectives of the layout.
    with sharded._mesh:
        compiled = sharded._jit_train_step.lower(
            state, sharded._batch_struct, jax.random.key(0)
        ).compile()
    text = compiled.as_text()
    n_kernels = text.count("tpu_custom_call")
    check(n_kernels >= 3, f"four chips: compiled step contains {n_kernels} tpu_custom_call sites")
    check("all-gather" in text, "four chips: fsdp all-gather of the params in the step")
    check(
        "reduce-scatter" in text or "all-reduce" in text,
        "four chips: gradient reduce-scatter/all-reduce in the step",
    )
    mem = compiled.memory_analysis()
    log(
        f"four chips: per-device bytes: temp {mem.temp_size_in_bytes} "
        f"args {mem.argument_size_in_bytes} out {mem.output_size_in_bytes}"
    )
    del sharded, state, compiled

    _, res1 = fit_on_mesh("mesh_one_device", {"data": 1}, global_batch)
    for key, a, b, band in (
        ("step-1", res4.first_step_loss, res1.first_step_loss, MULTICHIP_STEP1_RTOL),
        ("final", res4.final_loss, res1.final_loss, MULTICHIP_FINAL_RTOL),
    ):
        rel = abs(a - b) / abs(b)
        check(
            rel <= band,
            f"four chips vs one device, {key} loss: {a:.6f} vs {b:.6f} "
            f"(rel {rel:.2e} <= {band:g})",
        )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--multichip",
        action="store_true",
        help="run ONLY the four-chip {data: 2, fsdp: 2} step and the "
        "one-device run it is compared with (needs four chips)",
    )
    args = parser.parse_args(argv)

    dev, count = require_tpu(4 if args.multichip else 1)
    sys.path.insert(0, str(REPO))
    # Alone in its directory this fails here (ImportError, no result line)
    # before it has written anything.
    from llmtrain_tpu.distributed import (
        configure_compilation_cache,
        resolve_compilation_cache_dir,
    )

    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)

    configure_compilation_cache()
    cache_dir, before = resolve_compilation_cache_dir(), cache_entries()
    log(f"compile cache: {cache_dir} ({before} entries before)")

    if args.multichip:
        phase_multichip()
    else:
        phase_kernels()
        run_dir = phase_train(dev)
        phase_serve(run_dir)

    log(f"compile cache: {cache_dir} ({cache_entries()} entries after, {before} before)")
    print(
        json.dumps(
            {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind, "count": count}}
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

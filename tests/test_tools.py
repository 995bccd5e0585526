"""Smoke coverage for the repo-root measurement tools.

A refactor that breaks one of these CLIs' imports or flags should fail
here on CPU rather than on the first chip session.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.slow


def _run(args, timeout=540):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, *args],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=env,
        timeout=timeout,
    )


def test_bench_decode_smoke():
    proc = _run(
        ["tools/bench_decode.py", "--batches", "1,2", "--kv-heads", "0",
         "--new-tokens", "8", "--repeats", "1"]
    )
    assert proc.returncode == 0, proc.stderr
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.strip()]
    cells = [x for x in lines if "batch" in x]
    assert {c["batch"] for c in cells} == {1, 2}
    assert all(c["tokens_per_sec"] > 0 for c in cells)


def test_bench_longctx_smoke():
    proc = _run(["tools/bench_longctx.py", "--seqs", "512", "--cpu-smoke",
                 "--steps", "1"])
    assert proc.returncode == 0, proc.stderr
    row = json.loads(proc.stdout.splitlines()[-1])
    assert row["seq"] == 512 and "error" not in row
    assert row["tokens_per_sec"] > 0
    # A 0.0 peak must self-diagnose (VERDICT r4 item 7): CPU PJRT reports
    # no memory stats, so the row carries the keys the device DOES expose.
    if row["peak_hbm_gb"] == 0:
        assert "memory_stats keys" in row.get("hbm_note", ""), row


def test_bench_cpu_sweep_smoke():
    proc = _run(["tools/bench_cpu_sweep.py", "--shapes", "64,1,2"])
    assert proc.returncode == 0, proc.stderr
    row = json.loads(proc.stdout.splitlines()[-1])
    assert "error" not in row, row
    assert row["mfu"] > 0 and row["tokens_per_sec"] > 0


def test_bench_interleave_smoke():
    proc = _run(["tools/bench_interleave.py", "--steps", "6"], timeout=560)
    assert proc.returncode == 0, proc.stderr
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.strip()]
    assert {r.get("virtual_chunks") for r in lines if "virtual_chunks" in r} == {1, 2}


def test_bench_family_smoke():
    proc = _run(["tools/bench_family.py", "--cpu-smoke", "--steps", "1"])
    assert proc.returncode == 0, proc.stderr
    rows = [json.loads(x) for x in proc.stdout.splitlines() if x.strip()]
    assert {r.get("family") for r in rows} == {"gpt", "llama", "qwen2", "gemma"}
    assert all("error" not in r and r["tokens_per_sec"] > 0 for r in rows)


def test_bench_speculative_smoke():
    proc = _run(["tools/bench_speculative.py", "--cpu-smoke", "--new-tokens",
                 "8", "--repeats", "1"])
    assert proc.returncode == 0, proc.stderr
    rows = [json.loads(x) for x in proc.stdout.splitlines() if x.strip()]
    assert {r["cell"] for r in rows} == {
        "plain", "speculative_self_draft", "speculative_fresh_draft",
    }
    assert all("error" not in r for r in rows)


def test_bench_lora_smoke():
    proc = _run(["tools/bench_lora.py", "--cpu-smoke", "--steps", "2"])
    assert proc.returncode == 0, proc.stderr
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    cells = {r["cell"]: r for r in lines if "cell" in r}
    assert cells["full"]["trainable_params"] == cells["full"]["params"]
    assert cells["lora_r8"]["trainable_params"] < cells["lora_r8"]["params"]
    summary = lines[-1]
    assert summary["predicted_speedup"] > 1.0


def test_interleave_attribution_smoke():
    proc = _run(
        ["tools/bench_interleave.py", "--no-trainer", "--attribute",
         "--repeats", "2"],
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr
    row = json.loads(proc.stdout.splitlines()[-1])["attribution"]
    assert row["phases"]["v1"]["ticks"] == 7
    assert row["phases"]["v2"]["ticks"] == 11
    assert row["predicted_compute_ratio_v2_v1"] == pytest.approx(11 / 14, abs=1e-3)

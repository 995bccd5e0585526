"""Contracts the round driver depends on: bench.py and __graft_entry__.py.

bench.py measures in its own process and prints one JSON line with the
agreed keys — or, when it finds no chip and was not explicitly pinned to
the CPU, exits nonzero with none; __graft_entry__ must expose
``entry()`` (jittable flagship forward) and ``dryrun_multichip(n)``.
These are the only invocations nothing else in the suite exercises.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _cpu_env(**extra: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


class TestBenchContract:
    def test_emits_one_json_line_and_exits_zero(self):
        proc = subprocess.run(
            [sys.executable, str(REPO / "bench.py")],
            capture_output=True,
            text=True,
            timeout=600,
            # Small batch/steps: the contract is the JSON line and exit 0,
            # not the throughput.
            env=_cpu_env(
                LLMTRAIN_BENCH_BATCH="4",
                LLMTRAIN_BENCH_STEPS="2",
            ),
            cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-500:]
        json_lines = [
            ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")
        ]
        assert len(json_lines) == 1, proc.stdout
        payload = json.loads(json_lines[0])
        assert payload["metric"] == "tokens_per_sec_per_chip"
        assert payload["unit"] == "tokens/s"
        assert payload["value"] > 0
        assert payload["vs_baseline"] > 0
        detail = payload["detail"]
        for key in ("backend", "mfu", "attention", "loss_impl", "batch", "final_loss"):
            assert key in detail, key

    def test_no_chip_without_explicit_cpu_refuses_without_json(self):
        """A run that was NOT explicitly ``JAX_PLATFORMS=cpu`` and finds no
        chip must exit nonzero with NO JSON line — otherwise a CPU number
        would be read as the chip's. The env var is absent here; JAX is
        held to the CPU through its config instead, so this process never
        loads the TPU library."""
        env = _cpu_env()
        del env["JAX_PLATFORMS"]
        proc = subprocess.run(
            [
                sys.executable,
                "-c",
                "import jax, runpy; jax.config.update('jax_platforms', 'cpu'); "
                f"runpy.run_path({str(REPO / 'bench.py')!r}, run_name='__main__')",
            ],
            capture_output=True,
            text=True,
            timeout=300,
            env=env,
            cwd=REPO,
        )
        assert proc.returncode == 3
        assert "no TPU found" in proc.stderr
        assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]

    def test_invalid_ce_knob_fails_loudly(self):
        proc = subprocess.run(
            [sys.executable, str(REPO / "bench.py")],
            capture_output=True,
            text=True,
            timeout=600,
            env=_cpu_env(LLMTRAIN_BENCH_CE="typo"),
            cwd=REPO,
        )
        assert proc.returncode != 0
        assert "LLMTRAIN_BENCH_CE" in proc.stderr


@pytest.mark.slow
class TestGraftEntry:
    def test_entry_compiles_single_device(self):
        """The driver compile-checks entry() single-chip; do the same on CPU."""
        proc = subprocess.run(
            [
                sys.executable,
                "-c",
                (
                    "import jax; jax.config.update('jax_platforms', 'cpu');\n"
                    "import __graft_entry__ as g\n"
                    "fn, args = g.entry()\n"
                    "out = jax.jit(fn)(*args)\n"
                    "print('entry ok', out.shape)"
                ),
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=_cpu_env(),
            cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-800:]
        assert "entry ok (8, 512, 50257)" in proc.stdout

    def test_dryrun_multichip_two_devices(self):
        """All three dryrun legs (dp/fsdp/tp/sp mesh, pipeline, MoE) run on
        a 2-virtual-device mesh — the cheapest even device count."""
        proc = subprocess.run(
            [
                sys.executable,
                "-c",
                "import __graft_entry__ as g; g.dryrun_multichip(2)",
            ],
            capture_output=True,
            text=True,
            timeout=900,
            env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=2"),
            cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr[-800:]
        for leg in ("dryrun_multichip ok", "dryrun_pipeline ok", "dryrun_moe ok"):
            assert leg in proc.stdout, proc.stdout

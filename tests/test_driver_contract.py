"""Contract of an earlier round driver's entry point, __graft_entry__.py.

It must expose ``entry()`` (jittable flagship forward) and
``dryrun_multichip(n)``. These are the only invocations nothing else in
the suite exercises.
"""

from __future__ import annotations

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

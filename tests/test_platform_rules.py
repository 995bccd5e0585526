"""The bring-up rules, as cheap CPU units (no training fit anywhere here).

* ``run.device`` names the platform — never a preference with a fallback;
* on platform ``tpu`` a requested kernel that cannot run, interpret mode,
  and an unknown ``device_kind`` are errors (the platform is steered to
  look like ``tpu`` in the tests, not through an option of the program);
* the compile cache is placed from outside by ``JAX_COMPILATION_CACHE_DIR``
  or lives at one fixed in-checkout path;
* Pallas call sites wrap themselves in ``shard_map`` on a multi-device mesh;
* ``chip_smoke.py`` runs nothing off the chip.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmtrain_tpu.config.schemas import MeshConfig, RunConfig
from llmtrain_tpu.distributed import (
    DEFAULT_COMPILATION_CACHE_DIR,
    PlatformError,
    build_mesh,
    configure_compilation_cache,
    resolve_compilation_cache_dir,
    resolve_devices,
)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def as_tpu(monkeypatch):
    """Make the dispatch code see platform ``tpu`` (the backend stays CPU)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# --------------------------------------------------------------------------
# run.device
# --------------------------------------------------------------------------


class TestRunDevice:
    def test_tpu_without_a_chip_is_a_named_error(self):
        with pytest.raises(PlatformError, match="run.device is 'tpu'.*'cpu'"):
            resolve_devices("tpu")

    def test_cpu_on_the_cpu_backend_returns_all_devices(self):
        assert resolve_devices("cpu") == jax.devices()

    def test_cpu_on_another_platform_is_an_error_too(self, monkeypatch):
        chip = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        monkeypatch.setattr(jax, "devices", lambda *a: [chip])
        with pytest.raises(PlatformError, match="run.device is 'cpu'.*'tpu'"):
            resolve_devices("cpu")

    def test_maps_to_the_config_exit_code(self):
        from llmtrain_tpu.resilience.exit_codes import (
            EXIT_CONFIG_ERROR,
            exit_code_for_exception,
        )

        assert exit_code_for_exception(PlatformError("x")) == EXIT_CONFIG_ERROR
        wrapped = RuntimeError("setup failed")
        wrapped.__cause__ = PlatformError("x")
        assert exit_code_for_exception(wrapped) == EXIT_CONFIG_ERROR

    def test_train_cli_exits_nonzero_naming_the_cause(
        self, tmp_path, monkeypatch, restore_llmtrain_logger
    ):
        """`llmtrain train` with run.device: tpu on a chipless machine dies
        at Trainer set-up — before any mesh, params or compile."""
        import yaml

        from llmtrain_tpu import cli

        cfg = {
            "schema_version": 1,
            "run": {"name": "nochip", "device": "tpu"},
            "model": {
                "name": "dummy_gpt", "block_size": 8, "d_model": 8,
                "n_layers": 1, "n_heads": 1, "d_ff": 8, "vocab_size": 16,
            },
            "data": {"name": "dummy_text"},
            "trainer": {"max_steps": 1, "micro_batch_size": 1, "warmup_steps": 0},
            "mlflow": {"enabled": False},
            "output": {"root_dir": str(tmp_path / "runs")},
        }
        path = tmp_path / "nochip.yaml"
        path.write_text(yaml.safe_dump(cfg))
        errors: list[str] = []
        monkeypatch.setattr(cli, "_emit_error", lambda msg, **kw: errors.append(msg))
        assert cli.main(["train", "--config", str(path)]) == 2
        err = " ".join(errors)
        assert "run.device is 'tpu'" in err and "no TPU is attached" in err

    def test_explicit_mesh_takes_fewer_devices_than_the_host_has(self, tmp_path):
        """One chip of a four-chip host: a fully explicit mesh smaller than
        the (single-process) host uses the leading devices."""
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.tracking.base import NullTracker
        from llmtrain_tpu.training import Trainer

        initialize_registries()
        cfg = RunConfig.model_validate(
            {
                "run": {"name": "subset", "device": "cpu"},
                "model": {
                    "name": "dummy_gpt", "block_size": 8, "d_model": 8,
                    "n_layers": 1, "n_heads": 1, "d_ff": 8, "vocab_size": 16,
                },
                "data": {"name": "dummy_text"},
                "trainer": {"max_steps": 1, "micro_batch_size": 2, "warmup_steps": 0},
                "distributed": {"mesh": {"data": 2}},
                "mlflow": {"enabled": False},
            }
        )
        assert len(jax.devices()) > 2
        trainer = Trainer(cfg, None, NullTracker())
        assert list(trainer._mesh.devices.flat) == jax.devices()[:2]


# --------------------------------------------------------------------------
# kernels on platform tpu: errors, never another implementation
# --------------------------------------------------------------------------


class TestKernelDispatchOnTpu:
    def test_interpret_mode_is_an_error(self, as_tpu):
        from llmtrain_tpu.ops.fused_ce import resolve_loss_impl
        from llmtrain_tpu.ops.fused_norm import resolve_fused_norm

        with pytest.raises(ValueError, match="pallas_interpret.*platform tpu"):
            resolve_loss_impl(
                "fused_ce", vocab_size=50257, ce_auto_vocab=32768, interpret=True
            )
        with pytest.raises(ValueError, match="pallas_interpret.*platform tpu"):
            resolve_fused_norm(True, interpret=True)
        # ... even when no kernel was asked for: the key itself is wrong there.
        with pytest.raises(ValueError, match="pallas_interpret"):
            resolve_loss_impl(
                "dense", vocab_size=64, ce_auto_vocab=32768, interpret=True
            )

    def test_model_build_rejects_interpret_mode(self, as_tpu):
        from llmtrain_tpu.models.gpt import GPTAdapter

        cfg = RunConfig.model_validate(
            {
                "run": {"name": "x", "device": "tpu"},
                "model": {
                    "name": "gpt", "block_size": 128, "d_model": 32, "n_layers": 1,
                    "n_heads": 2, "d_ff": 64, "vocab_size": 64,
                    "extra": {"pallas_interpret": True, "fused_norm": True},
                },
                "data": {"name": "dummy_text"},
                "trainer": {"max_steps": 1, "warmup_steps": 0},
            }
        )
        with pytest.raises(ValueError, match="pallas_interpret"):
            GPTAdapter().build_model(cfg)

    def test_requested_kernels_resolve_to_themselves(self, as_tpu):
        from llmtrain_tpu.ops.flash_attention import resolved_attention_impl
        from llmtrain_tpu.ops.fused_ce import resolve_loss_impl
        from llmtrain_tpu.ops.fused_norm import resolve_fused_norm

        assert (
            resolve_loss_impl("fused_ce", vocab_size=50257, ce_auto_vocab=32768)
            == "fused_ce"
        )
        assert resolve_fused_norm(True) is True
        assert resolved_attention_impl("flash") == "pallas_flash"
        assert resolved_attention_impl("ring") == "ring"

    def test_off_the_chip_flash_reports_blockwise(self):
        from llmtrain_tpu.ops.flash_attention import _use_pallas, resolved_attention_impl

        assert resolved_attention_impl("flash") == "blockwise"
        assert _use_pallas(200) is False  # the CPU tests' path, no error

    @pytest.mark.parametrize("t", [8, 200, 513])
    def test_untileable_flash_length_is_an_error(self, as_tpu, t):
        from llmtrain_tpu.ops.flash_attention import _use_pallas

        with pytest.raises(ValueError, match="multiple of 128"):
            _use_pallas(t)
        assert _use_pallas(384) is True

    def test_offload_tier_without_pinned_host_is_an_error(self, as_tpu, monkeypatch):
        from llmtrain_tpu.models import activation_policy

        monkeypatch.setattr(activation_policy, "offload_supported", lambda: False)
        with pytest.raises(ValueError, match="offload.*platform tpu"):
            activation_policy.resolve_activation_tiers(("offload", "full"))
        assert activation_policy.resolve_activation_tiers(("full",)) == ("full",)


# --------------------------------------------------------------------------
# peaks: an unknown TPU raises
# --------------------------------------------------------------------------


class TestUnknownTpuKind:
    def test_hw_peak_flops(self, as_tpu, monkeypatch):
        from llmtrain_tpu.utils.hw import peak_flops_per_chip

        monkeypatch.setattr(
            jax, "devices", lambda *a: [SimpleNamespace(device_kind="TPU v5 lite")]
        )
        assert peak_flops_per_chip() == 197e12
        monkeypatch.setattr(
            jax, "devices", lambda *a: [SimpleNamespace(device_kind="TPU v9 ultra")]
        )
        with pytest.raises(ValueError, match="TPU v9 ultra"):
            peak_flops_per_chip()

    def test_profiling_peaks_by_name(self):
        from llmtrain_tpu.telemetry.profiling import resolve_peaks

        assert resolve_peaks("TPU v5 lite")["peak_flops"] == 197e12
        with pytest.raises(ValueError, match="TPU v9 ultra"):
            resolve_peaks("TPU v9 ultra")
        # Off the chip the nominal cpu row still stands (trend numbers only).
        assert resolve_peaks("cpu")["peak_flops"] == 2e11
        assert resolve_peaks("AMD EPYC")["peak_flops"] == 2e11

    def test_profiling_peaks_from_the_device(self, as_tpu, monkeypatch):
        from llmtrain_tpu.telemetry.profiling import resolve_peaks

        # A TPU whose kind does not even say "tpu" is still on platform tpu.
        monkeypatch.setattr(
            jax, "devices", lambda *a: [SimpleNamespace(device_kind="Mystery Chip")]
        )
        with pytest.raises(ValueError, match="Mystery Chip"):
            resolve_peaks(None)


# --------------------------------------------------------------------------
# compile cache placement
# --------------------------------------------------------------------------


class TestCompilationCachePlacement:
    @pytest.fixture
    def updates(self, monkeypatch):
        seen: list[tuple[str, object]] = []
        monkeypatch.setattr(jax.config, "update", lambda k, v: seen.append((k, v)))
        return seen

    def test_env_set_means_code_sets_no_directory(self, monkeypatch, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
        configure_compilation_cache("/from/config")
        assert "jax_compilation_cache_dir" not in [k for k, _ in updates]
        assert resolve_compilation_cache_dir("/from/config") == "/placed/from/outside"

    def test_env_unset_uses_the_fixed_in_checkout_path(
        self, monkeypatch, updates, tmp_path
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.chdir(tmp_path)  # the default must not follow the cwd
        assert DEFAULT_COMPILATION_CACHE_DIR == str(REPO / ".cache" / "jax")
        configure_compilation_cache(None)
        assert ("jax_compilation_cache_dir", str(REPO / ".cache" / "jax")) in updates
        # Stable across calls: the path is part of the cache key.
        assert resolve_compilation_cache_dir() == resolve_compilation_cache_dir()

    def test_default_is_identical_in_another_process(self, tmp_path):
        env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
        proc = subprocess.run(
            [
                sys.executable,
                "-c",
                "from llmtrain_tpu.distributed import resolve_compilation_cache_dir"
                " as r; print(r())",
            ],
            capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path,
        )
        assert proc.returncode == 0, proc.stderr[-500:]
        assert proc.stdout.strip().splitlines()[-1] == DEFAULT_COMPILATION_CACHE_DIR


# --------------------------------------------------------------------------
# Pallas call sites partition themselves on a mesh
# --------------------------------------------------------------------------


class TestKernelShardMap:
    @pytest.fixture(scope="class")
    def mesh(self):
        return build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))

    def test_kernel_mesh_only_on_a_multi_device_ambient_mesh(self, mesh):
        from llmtrain_tpu.parallel.sharding import kernel_mesh

        assert kernel_mesh() is None
        with build_mesh(MeshConfig(data=1), jax.devices()[:1]):
            assert kernel_mesh() is None
        with mesh:
            assert kernel_mesh() is mesh
            inside = jax.shard_map(
                lambda x: x * (kernel_mesh() is None),
                mesh=mesh,
                in_specs=jax.sharding.PartitionSpec("data"),
                out_specs=jax.sharding.PartitionSpec("data"),
            )(jnp.ones(2))
            assert float(inside.sum()) == 2.0  # no nesting inside shard_map

    def test_shard_axes(self, mesh):
        from llmtrain_tpu.parallel.sharding import BATCH_AXES, shard_axes

        assert shard_axes(mesh, BATCH_AXES, 8) == ("data", "fsdp")
        assert shard_axes(mesh, BATCH_AXES, 1) is None  # the init probe batch
        assert shard_axes(mesh, ("tensor",), 4, 2) == "tensor"
        assert shard_axes(mesh, ("tensor",), 4, 1) is None  # MQA k/v
        assert shard_axes(mesh, ("sequence",), 64) is None  # axis of size 1

    def test_flash_attention_wrapped_matches_unwrapped(self, mesh):
        from llmtrain_tpu.ops.flash_attention import flash_attention

        ks = jax.random.split(jax.random.key(0), 4)
        q = jax.random.normal(ks[0], (4, 32, 4, 8))
        k, v = (jax.random.normal(kk, (4, 32, 2, 8)) for kk in ks[1:3])
        mask = (jax.random.uniform(ks[3], (4, 32)) > 0.2).astype(jnp.int32)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, attention_mask=mask) ** 2)

        ref = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        with mesh:
            fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            assert "shard_map" in fn.lower(q, k, v).as_text(debug_info=True)
            got = fn(q, k, v)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_fused_ce_wrapped_sums_dw_over_token_shards(self, mesh):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from llmtrain_tpu.ops.fused_ce import fused_ce_per_token

        ks = jax.random.split(jax.random.key(1), 3)
        h = jax.random.normal(ks[0], (4, 16, 16))
        w = jax.random.normal(ks[1], (40, 16)) * 0.1
        lab = jax.random.randint(ks[2], (4, 16), 0, 40)

        def loss(h, w):
            return jnp.sum(fused_ce_per_token(h, w, lab, 16, 128, None, 1e-4, True))

        ref = jax.value_and_grad(loss, argnums=(0, 1))(h, w)
        with mesh:
            # The training layout: tokens over data x fsdp, the tied
            # embedding vocab -> tensor, embed -> fsdp.
            got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
                jax.device_put(h, NamedSharding(mesh, P(("data", "fsdp")))),
                jax.device_put(w, NamedSharding(mesh, P("tensor", "fsdp"))),
            )
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_fused_norm_wrapped_sums_param_grads(self, mesh):
        from llmtrain_tpu.ops.fused_norm import fused_add_layer_norm

        ks = jax.random.split(jax.random.key(2), 2)
        x, r = (jax.random.normal(kk, (4, 16, 16)) for kk in ks)
        scale, bias = jnp.full((16,), 1.3), jnp.full((16,), 0.1)

        def loss(x, r, scale, bias):
            y, s = fused_add_layer_norm(x, r, scale, bias, 1e-6, 16, True)
            return jnp.sum(y**2) + jnp.sum(s)

        ref = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(x, r, scale, bias)
        with mesh:
            got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(x, r, scale, bias)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_fused_ce_on_a_tensor_axis_is_a_plan_error(self):
        from llmtrain_tpu.autotune.plan import MeshPlanError, ModelCaps, resolve_plan

        caps = ModelCaps(n_heads=4, block_size=128, loss_impl="fused_ce")
        with pytest.raises(MeshPlanError, match="fused_ce.*tensor axis is 2"):
            resolve_plan(
                mesh_sizes={"data": 2, "tensor": 2}, device_count=4, caps=caps,
                micro_batch_size=2,
            )
        plan = resolve_plan(
            mesh_sizes={"data": 2, "fsdp": 2}, device_count=4, caps=caps,
            micro_batch_size=2,
        )
        assert plan.axes["fsdp"] == 2

    def test_plan_resolves_loss_impl_from_the_config(self):
        from llmtrain_tpu.autotune.plan import MeshPlanError, plan_from_config
        from llmtrain_tpu.models.gpt import GPTAdapter

        cfg = RunConfig.model_validate(
            {
                "run": {"name": "x", "device": "cpu"},
                "model": {
                    "name": "gpt", "block_size": 128, "d_model": 32, "n_layers": 1,
                    "n_heads": 2, "d_ff": 64, "vocab_size": 64,
                    "extra": {"loss_impl": "fused_ce", "pallas_interpret": True},
                },
                "data": {"name": "dummy_text"},
                "trainer": {"max_steps": 1, "warmup_steps": 0},
                "distributed": {"mesh": {"data": 2, "tensor": 2}},
            }
        )
        with pytest.raises(MeshPlanError, match="fused_ce"):
            plan_from_config(cfg, 4, adapter=GPTAdapter)


# --------------------------------------------------------------------------
# chip_smoke.py off the chip
# --------------------------------------------------------------------------


class TestChipSmokeOffTheChip:
    @pytest.mark.parametrize("argv", [[], ["--multichip"]], ids=["default", "multichip"])
    def test_exits_nonzero_within_seconds_and_prints_no_result(self, argv, tmp_path):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, str(REPO / "chip_smoke.py"), *argv],
            capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path,
        )
        assert proc.returncode != 0
        assert "not 'tpu'" in proc.stderr
        assert '"ok"' not in proc.stdout
        assert "[chip_smoke]" not in proc.stdout  # no phase ran on the CPU

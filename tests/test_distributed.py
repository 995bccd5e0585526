"""Distributed-state + mesh tests on the 8-virtual-device CPU platform.

Mirrors the reference test tiers (tests/test_distributed.py): pure-unit state
invariants, real single-process setup/idempotency/teardown, env-beats-config
resolution — with the multi-rank tier exercised as *real* shardings over the
forced 8-device host platform instead of mocked collectives.
"""

import jax
import numpy as np
import pytest

from llmtrain_tpu.config import DistributedConfig, MeshConfig
from llmtrain_tpu.distributed import (
    DistState,
    active_state,
    build_mesh,
    resolve_mesh_axes,
    resolve_topology,
    setup_distributed,
    teardown_distributed,
)


class TestDistState:
    def test_valid(self):
        s = DistState(process_index=0, num_processes=2, local_device_count=1, is_main=True)
        assert s.rank == 0 and s.world_size == 2

    def test_is_main_invariant(self):
        with pytest.raises(ValueError, match="is_main"):
            DistState(process_index=1, num_processes=2, local_device_count=1, is_main=True)

    def test_rank_bounds(self):
        with pytest.raises(ValueError):
            DistState(process_index=2, num_processes=2, local_device_count=1, is_main=False)
        with pytest.raises(ValueError):
            DistState(process_index=0, num_processes=0, local_device_count=1, is_main=True)


class TestTopologyResolution:
    def test_env_beats_config(self, monkeypatch):
        monkeypatch.setenv("RANK", "1")
        monkeypatch.setenv("WORLD_SIZE", "4")
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
        monkeypatch.setenv("MASTER_PORT", "12345")
        cfg = DistributedConfig(process_id=0, num_processes=2, coordinator_addr="cfg-host")
        pid, n, coord = resolve_topology(cfg)
        assert (pid, n, coord) == (1, 4, "10.0.0.1:12345")

    def test_jax_native_env_beats_torch_names(self, monkeypatch):
        monkeypatch.setenv("RANK", "1")
        monkeypatch.setenv("JAX_PROCESS_ID", "2")
        monkeypatch.setenv("JAX_NUM_PROCESSES", "8")
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "coord:1234")
        pid, n, coord = resolve_topology(DistributedConfig())
        assert (pid, n, coord) == (2, 8, "coord:1234")

    def test_config_fallback(self):
        cfg = DistributedConfig(
            process_id=1, num_processes=2, coordinator_addr="host", coordinator_port=999
        )
        pid, n, coord = resolve_topology(cfg)
        assert (pid, n, coord) == (1, 2, "host:999")

    def test_defaults(self):
        assert resolve_topology(DistributedConfig()) == (0, 1, None)

    def test_bad_env_int(self, monkeypatch):
        monkeypatch.setenv("WORLD_SIZE", "banana")
        with pytest.raises(ValueError, match="not an integer"):
            resolve_topology(DistributedConfig())

    def test_multiprocess_unset_process_id_fails_fast(self, monkeypatch):
        monkeypatch.setenv("WORLD_SIZE", "4")
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
        with pytest.raises(ValueError, match="process id is unset"):
            resolve_topology(DistributedConfig())

    def test_empty_coordinator_env_falls_back(self, monkeypatch):
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "")
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.2")
        _, _, coord = resolve_topology(DistributedConfig())
        assert coord == "10.0.0.2:29500"


class TestSetup:
    def test_single_process_setup_and_teardown(self):
        state = setup_distributed(DistributedConfig())
        assert state.num_processes == 1 and state.is_main
        assert state.local_device_count == 8  # forced host platform
        assert active_state() is state
        teardown_distributed()
        assert active_state() is None

    def test_idempotent_returns_same_state(self):
        s1 = setup_distributed(DistributedConfig())
        s2 = setup_distributed(DistributedConfig())
        assert s1 is s2

    def test_multiprocess_requires_coordinator(self):
        with pytest.raises(ValueError, match="coordinator"):
            setup_distributed(DistributedConfig(num_processes=2, process_id=0))

    def test_tpu_autodetect_gate(self, monkeypatch):
        """Bare jax.distributed.initialize() only for MULTI-host TPU slices
        with no explicit topology (the GKE pod-slice path, docs/k8s.md)."""
        from llmtrain_tpu.distributed import _tpu_autodetect_available

        cfg = DistributedConfig()
        monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
        assert not _tpu_autodetect_available(cfg)
        # Single-host slice (one chip or one four-chip host): no init.
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        assert not _tpu_autodetect_available(cfg)
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-0,host-1,host-2,host-3")
        assert _tpu_autodetect_available(cfg)
        # Explicit topology always wins over auto-detection.
        monkeypatch.setenv("WORLD_SIZE", "4")
        assert not _tpu_autodetect_available(cfg)
        monkeypatch.delenv("WORLD_SIZE")
        assert not _tpu_autodetect_available(
            DistributedConfig(num_processes=4, process_id=0)
        )


class TestMesh:
    def test_wildcard_resolution(self):
        sizes = resolve_mesh_axes(MeshConfig(), 8)
        assert sizes["data"] == 8 and sizes["tensor"] == 1

    def test_explicit_axes(self):
        sizes = resolve_mesh_axes(MeshConfig(data=2, tensor=4), 8)
        assert sizes == {
            "data": 2, "fsdp": 1, "tensor": 4, "sequence": 1, "pipeline": 1, "expert": 1,
        }

    def test_indivisible_raises(self):
        with pytest.raises(ValueError, match="divisible"):
            resolve_mesh_axes(MeshConfig(data=-1, tensor=3), 8)

    def test_mismatched_product_raises(self):
        with pytest.raises(ValueError, match="devices"):
            resolve_mesh_axes(MeshConfig(data=2, tensor=2), 8)

    def test_build_mesh_and_psum(self):
        """A real psum over the data axis of a real 8-device mesh."""
        mesh = build_mesh(MeshConfig(data=4, tensor=2))
        assert mesh.shape["data"] == 4 and mesh.shape["tensor"] == 2

        from jax.sharding import NamedSharding, PartitionSpec as P

        x = np.arange(8, dtype=np.float32)
        sharded = jax.device_put(x, NamedSharding(mesh, P(("data", "tensor"))))

        @jax.jit
        def total(v):
            return jax.numpy.sum(v)

        assert float(total(sharded)) == float(x.sum())

    def test_build_mesh_sharded_matmul(self):
        """Tensor-parallel matmul: weight sharded on 'tensor', XLA all-gathers."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = build_mesh(MeshConfig(data=2, tensor=4))
        w = np.ones((16, 8), dtype=np.float32)
        x = np.ones((4, 16), dtype=np.float32)
        ws = jax.device_put(w, NamedSharding(mesh, P(None, "tensor")))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        out = jax.jit(lambda a, b: a @ b)(xs, ws)
        np.testing.assert_allclose(np.asarray(out), x @ w)


class TestParallelismEquivalence:
    """Different mesh layouts must compute the same training run.

    The TPU-native analogue of the reference's DDP-correctness concern.
    Parameters (same seed), global batch content (same sampler stream) and
    math are identical across layouts; only the sharding differs, so losses
    must agree to fp-reduction tolerance. Config caveat: dummy_text sizes
    its dataset as max_steps*micro_batch_size capped at 128 — the chosen
    max_steps/micro pairs drive every layout to the 128 cap so the datasets
    (and therefore the wrapped sampler streams) are identical too.
    """

    def _run(self, mesh_axes: dict, micro_batch_size: int, attention: str = "dense"):
        from unittest.mock import Mock

        from llmtrain_tpu.config import RunConfig
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.training import Trainer

        initialize_registries()
        cfg = RunConfig.model_validate(
            {
                "run": {"name": "eq", "seed": 11, "deterministic": True},
                "model": {
                    "name": "gpt",
                    "block_size": 8,
                    "vocab_size": 32,
                    "dropout": 0.0,
                    "d_model": 16,
                    "n_heads": 4,
                    "d_ff": 32,
                    "n_layers": 1,
                    "attention": attention,
                },
                "data": {"name": "dummy_text"},
                "trainer": {
                    "max_steps": 16,
                    "micro_batch_size": micro_batch_size,
                    "grad_accum_steps": 2,
                    "lr": 3e-3,
                    "warmup_steps": 0,
                    "log_every_steps": 16,
                    "eval_every_steps": 16,
                    "save_every_steps": 100,
                },
                "distributed": {"mesh": mesh_axes},
                "mlflow": {"enabled": False},
            }
        )
        result = Trainer(cfg, None, Mock(), None).fit()
        return result.first_step_loss, result.final_loss

    def test_layouts_agree(self):
        # micro_batch_size is per data shard: scale it so the GLOBAL batch
        # (micro x data-parallel degree = 64) — and hence the deterministic
        # sampler's index stream — is identical across layouts. 16 steps x
        # these micro sizes all reach dummy_text's 128-example cap.
        dp = self._run({"data": 8}, micro_batch_size=8)  # dp degree 8
        mixed = self._run(
            {"data": 2, "fsdp": 2, "tensor": 2}, micro_batch_size=16
        )  # dp degree 4
        sp = self._run({"data": 4, "sequence": 2}, micro_batch_size=16)  # dp 4
        # Step 1 is a single forward/backward on identical params+batch:
        # any disagreement beyond reduction-order noise is a sharding bug.
        assert abs(dp[0] - mixed[0]) < 1e-5, (dp, mixed)
        assert abs(dp[0] - sp[0]) < 1e-5, (dp, sp)
        # Final losses drift only by fp-noise amplification through training.
        assert abs(dp[1] - mixed[1]) < 5e-3, (dp, mixed)
        assert abs(dp[1] - sp[1]) < 5e-3, (dp, sp)

    def test_ring_attention_matches_dense(self):
        """Ring attention over the sequence axis computes the same training
        run as dense attention on the same mesh (exact-attention claim)."""
        dense = self._run({"data": 4, "sequence": 2}, micro_batch_size=16)
        ring = self._run(
            {"data": 4, "sequence": 2}, micro_batch_size=16, attention="ring"
        )
        assert abs(dense[0] - ring[0]) < 1e-5, (dense, ring)
        assert abs(dense[1] - ring[1]) < 5e-3, (dense, ring)

    def test_ulysses_attention_matches_dense(self):
        """Ulysses (all-to-all SP) computes the same training run as dense
        attention on the same mesh — the exact-attention claim for the
        second sequence-parallel scheme (ops/ulysses_attention.py)."""
        dense = self._run({"data": 4, "sequence": 2}, micro_batch_size=16)
        uly = self._run(
            {"data": 4, "sequence": 2}, micro_batch_size=16, attention="ulysses"
        )
        assert abs(dense[0] - uly[0]) < 1e-5, (dense, uly)
        assert abs(dense[1] - uly[1]) < 5e-3, (dense, uly)

"""ZeRO-style cross-replica sharded optimizer state (``trainer.zero``).

Contracts under test (docs/perf.md "Sharded optimizer state",
parallel/sharding.py:opt_state_shardings):

* every optimizer-state leaf with a dim divisible by the data-parallel
  product is partitioned across the combined ``data``/``fsdp``/``expert``
  axes, derived from the param-inherited spec; scalars and indivisible
  leaves stay replicated with a one-time named warning;
* loss trajectories are BITWISE-identical zero on/off at stage 1,
  including host offload (the memory-kind path: the installed jax gives
  the CPU backend a ``pinned_host`` space);
* checkpoints hold FULL host arrays regardless of the live sharding:
  zero→non-zero and non-zero→zero resumes continue the exact trajectory,
  as does an elastic world-size change with sharded state (device-subset
  emulation as in tests/test_elastic.py — this container's jax cannot run
  real multi-process collectives);
* report.json ``memory.opt_state_bytes_per_device`` measures the ~N_dp×
  reduction instead of claiming it.

Heavy multi-fit cases are ``@pytest.mark.slow``; ``make verify-zero``
runs everything.
"""

from __future__ import annotations

import json
import logging
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.linen import meta as nn_meta

from llmtrain_tpu.config import MeshConfig, RunConfig
from llmtrain_tpu.distributed import build_mesh
from llmtrain_tpu.parallel.sharding import (
    host_memory_kind,
    opt_state_shardings,
    state_shardings,
)
from llmtrain_tpu.registry import initialize_registries
from llmtrain_tpu.tracking import NullTracker
from llmtrain_tpu.training import CheckpointManager, Trainer


@pytest.fixture(autouse=True)
def _registries():
    initialize_registries()


@contextmanager
def _capture_llmtrain_warnings():
    """Attach a handler DIRECTLY to the llmtrain logger: earlier suites
    (in-process cli.main runs) can leave its propagate flag off, which
    blinds caplog's root-logger handler in full-suite order."""
    from llmtrain_tpu.utils.logging import get_logger

    messages: list[str] = []

    class _Collector(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    handler = _Collector(level=logging.WARNING)
    lg = get_logger()
    lg.addHandler(handler)
    try:
        yield messages
    finally:
        lg.removeHandler(handler)


@contextmanager
def _visible_devices(n):
    """Emulate a world size by restricting the devices the Trainer sees
    (same pattern as tests/test_elastic.py)."""
    all_cpu = jax.devices("cpu")
    assert len(all_cpu) >= n
    real = jax.devices
    jax.devices = lambda *a, **k: all_cpu[:n]
    try:
        yield
    finally:
        jax.devices = real


def _trees_equal(a, b) -> bool:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(np.array_equal(x, y) for x, y in zip(la, lb))


# --------------------------------------------------------------------------
# sharding derivation (pure, no fits)
# --------------------------------------------------------------------------


class TestOptStateShardings:
    def test_param_spec_extended_with_free_dp_axes(self):
        """An fsdp-annotated moment leaf gains the free ``data`` axis on
        its first divisible dim; the fsdp mapping is kept, not replaced."""
        mesh = build_mesh(MeshConfig(data=2, fsdp=2), jax.devices("cpu")[:4])
        state = {
            "mu": nn_meta.Partitioned(
                jax.ShapeDtypeStruct((8, 16), jnp.float32), names=("embed", None)
            )
        }
        sh = opt_state_shardings(mesh, state)
        assert sh["mu"].shard_shape((8, 16)) == (2, 16)  # fsdp(2) x data(2)
        axes = sh["mu"].spec[0]
        assert "fsdp" in axes and "data" in axes

    def test_plain_leaf_shards_over_dp_product(self):
        mesh = build_mesh(MeshConfig(data=4), jax.devices("cpu")[:4])
        state = {"nu": jax.ShapeDtypeStruct((8, 6), jnp.float32)}
        sh = opt_state_shardings(mesh, state)
        assert sh["nu"].shard_shape((8, 6)) == (2, 6)

    def test_scalar_and_indivisible_leaves_stay_replicated(self):
        mesh = build_mesh(MeshConfig(data=4), jax.devices("cpu")[:4])
        state = {
            "count": jax.ShapeDtypeStruct((), jnp.int32),
            "odd": jax.ShapeDtypeStruct((5, 3), jnp.float32),
        }
        with _capture_llmtrain_warnings() as messages:
            sh = opt_state_shardings(mesh, state)
        assert sh["count"].shard_shape(()) == ()
        assert sh["odd"].shard_shape((5, 3)) == (5, 3)
        # One-time warning NAMES the leaf that lost the memory win.
        assert any("ZeRO" in m and "odd" in m for m in messages)

    def test_second_dim_used_when_first_is_indivisible(self):
        mesh = build_mesh(MeshConfig(data=4), jax.devices("cpu")[:4])
        state = {"v": jax.ShapeDtypeStruct((6, 8), jnp.float32)}
        sh = opt_state_shardings(mesh, state)
        assert sh["v"].shard_shape((6, 8)) == (6, 2)

    def test_single_device_mesh_is_identity(self):
        mesh = build_mesh(MeshConfig(data=1), jax.devices("cpu")[:1])
        state = {"mu": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        sh = opt_state_shardings(mesh, state)
        assert sh["mu"].shard_shape((8, 8)) == (8, 8)

    def test_adafactor_style_placeholder_stays_silent(self):
        """(1,) placeholders are structural noise — replicated, NO warning."""
        mesh = build_mesh(MeshConfig(data=4), jax.devices("cpu")[:4])
        with _capture_llmtrain_warnings() as messages:
            sh = opt_state_shardings(
                mesh, {"ph": jax.ShapeDtypeStruct((1,), jnp.float32)}
            )
        assert sh["ph"].shard_shape((1,)) == (1,)
        assert not any("'ph'" in m for m in messages)

    def test_cpu_backend_exposes_pinned_host(self):
        # The installed jax (0.9.0) gives the CPU backend a pinned_host
        # memory space, so host offload takes the memory-kind path here
        # too; the explicit round-trip is the fallback for backends that
        # expose none.
        mesh = build_mesh(MeshConfig(data=4), jax.devices("cpu")[:4])
        assert host_memory_kind(mesh) == "pinned_host"


class TestStateShardingsRepair:
    def test_indivisible_param_spec_repairs_to_replicated_with_warning(self):
        """A sharded leaf whose dim the mapped axis product does not divide
        used to die at jit time with an opaque pjit error; now it stores
        replicated and warns ONCE, naming the leaf."""
        mesh = build_mesh(MeshConfig(data=2, tensor=2), jax.devices("cpu")[:4])
        tree = {
            "odd_vocab": nn_meta.Partitioned(
                jax.ShapeDtypeStruct((5, 4), jnp.float32), names=("vocab", None)
            )
        }
        with _capture_llmtrain_warnings() as messages:
            sh = state_shardings(mesh, tree)
            first = sum(
                "odd_vocab" in m and "REPLICATED" in m for m in messages
            )
            state_shardings(mesh, tree)  # re-derivation stays silent
            second = sum(
                "odd_vocab" in m and "REPLICATED" in m for m in messages
            )
        assert sh["odd_vocab"].shard_shape((5, 4)) == (5, 4)
        assert first == 1 and second == 1

    def test_divisible_param_spec_is_untouched(self):
        mesh = build_mesh(MeshConfig(data=2, tensor=2), jax.devices("cpu")[:4])
        tree = {
            "vocab": nn_meta.Partitioned(
                jax.ShapeDtypeStruct((8, 4), jnp.float32), names=("vocab", None)
            )
        }
        sh = state_shardings(mesh, tree)
        assert sh["vocab"].shard_shape((8, 4)) == (4, 4)


# --------------------------------------------------------------------------
# trainer-level parity on an emulated 4-device mesh
# --------------------------------------------------------------------------


def _zero_cfg(root, *, zero=False, stage=1, host_offload=False, micro=1, data=4):
    return RunConfig.model_validate(
        {
            "run": {"name": "zero", "seed": 11},
            "model": {
                "name": "gpt",
                "block_size": 8,
                "vocab_size": 256,
                "dropout": 0.0,
                "d_model": 32,
                "n_heads": 2,
                "d_ff": 64,
                "n_layers": 1,
                "extra": {"tokenizer": "byte"},
            },
            "data": {"name": "dummy_text"},
            "trainer": {
                "max_steps": 6,
                "micro_batch_size": micro,
                "grad_accum_steps": 1,
                "lr": 3e-3,
                "warmup_steps": 0,
                "log_every_steps": 1,
                "eval_every_steps": 100,
                "save_every_steps": 3,
                "zero": {
                    "enabled": zero,
                    "stage": stage,
                    "host_offload": host_offload,
                },
            },
            "distributed": {"mesh": {"data": data}},
            "mlflow": {"enabled": False},
            "output": {"root_dir": str(root)},
        }
    )


def _fit(root, run_dir, **kw):
    run_dir.mkdir(parents=True, exist_ok=True)
    ndev = kw.pop("ndev", 4)
    resume_from = kw.pop("resume_from", None)
    with _visible_devices(ndev):
        result = Trainer(_zero_cfg(root, **kw), run_dir, NullTracker(), None).fit(
            resume_from=resume_from
        )
    report = json.loads((run_dir / "report.json").read_text())
    return result, report


@pytest.fixture(scope="module")
def parity_runs(tmp_path_factory):
    """One zero-off and one zero-on fit over the same data/seed — the
    shared reference pair for the parity + round-trip tests."""
    tmp = tmp_path_factory.mktemp("zero_parity")
    out = {}
    for name, zero in (("off", False), ("on", True)):
        result, report = _fit(tmp, tmp / name, zero=zero)
        out[name] = {"dir": tmp / name, "result": result, "report": report}
    out["root"] = tmp
    return out


class TestZeroParity:
    @pytest.mark.slow
    def test_loss_trajectory_bitwise_identical_and_memory_measured(
        self, parity_runs
    ):
        # @slow with the rest of the fit-based contracts: tier-1 sits at
        # ~830s reported of the 870s kill budget, so every Trainer fit
        # belongs in `make verify-zero` (the sharding-derivation units
        # above stay tier-1).
        off, on = parity_runs["off"], parity_runs["on"]
        # Bitwise: every logged step's loss, not just the final one
        # (floats survive the JSON round-trip exactly via repr).
        assert off["report"]["loss"]["trajectory"] == on["report"]["loss"]["trajectory"]
        assert off["result"].final_loss == on["result"].final_loss
        # The final checkpoints hold identical FULL host arrays: the
        # sharded state gathers on save, so manifests stay topology- and
        # zero-portable.
        p_off = CheckpointManager.load(off["dir"] / "checkpoints" / "step_000006.ckpt")
        p_on = CheckpointManager.load(on["dir"] / "checkpoints" / "step_000006.ckpt")
        assert _trees_equal(p_off["params"], p_on["params"])
        assert _trees_equal(p_off["opt_state"], p_on["opt_state"])
        # Measured memory win: replicated keeps a full copy per device;
        # zero drops it ~4x on the 4-device mesh (scalar counts stay
        # replicated, hence the small remainder).
        mem_off = off["report"]["memory"]
        mem_on = on["report"]["memory"]
        assert mem_off["opt_state_bytes_per_device"] == mem_off["opt_state_bytes"]
        assert mem_on["opt_state_bytes"] == mem_off["opt_state_bytes"]
        ratio = mem_off["opt_state_bytes_per_device"] / mem_on["opt_state_bytes_per_device"]
        assert ratio > 3.5
        # report.md renders the accounting (observability satellite).
        md = (on["dir"] / "report.md").read_text()
        assert "optimizer state:" in md and "per device" in md

    @pytest.mark.slow
    def test_host_offload_roundtrip_bitwise_and_fully_host_resident(
        self, parity_runs, tmp_path
    ):
        result, report = _fit(
            parity_runs["root"], tmp_path / "offload", zero=True, host_offload=True
        )
        off = parity_runs["off"]
        assert report["loss"]["trajectory"] == off["report"]["loss"]["trajectory"]
        assert result.final_loss == off["result"].final_loss
        mem = report["memory"]
        assert mem["opt_state_bytes_host"] == mem["opt_state_bytes"]
        assert mem["opt_state_bytes_per_device"] == 0

    @pytest.mark.slow
    def test_stage2_reduce_scatter_tracks_replicated_closely(
        self, parity_runs, tmp_path
    ):
        """Stage 2 reassociates the global-norm sum (shard partials first):
        the documented contract is ~float-noise, not bitwise."""
        result, report = _fit(parity_runs["root"], tmp_path / "s2", zero=True, stage=2)
        off = parity_runs["off"]
        got = np.asarray([v for _, v in report["loss"]["trajectory"]])
        want = np.asarray([v for _, v in off["report"]["loss"]["trajectory"]])
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
        mem = report["memory"]
        assert (
            mem["opt_state_bytes_per_device"]
            < mem["opt_state_bytes"] / 3.5
        )


# --------------------------------------------------------------------------
# checkpoint round-trips and elastic resume with sharded state
# --------------------------------------------------------------------------


class TestZeroCheckpointRoundTrip:
    @pytest.mark.slow
    def test_zero_to_nonzero_and_back_bitwise(self, parity_runs, tmp_path):
        """A zero-on checkpoint resumes with zero off (and vice versa) and
        lands bitwise on the uninterrupted runs — the payload is full host
        arrays, the live sharding is purely a placement decision."""
        off, on = parity_runs["off"], parity_runs["on"]
        root = parity_runs["root"]
        # zero-on save at step 3 -> resumed WITHOUT zero.
        res_a, _ = _fit(
            root,
            tmp_path / "on_to_off",
            zero=False,
            resume_from=str(on["dir"] / "checkpoints" / "step_000003.ckpt"),
        )
        assert res_a.resumed_from_step == 3
        assert res_a.final_loss == off["result"].final_loss
        final_a = CheckpointManager.load(
            tmp_path / "on_to_off" / "checkpoints" / "step_000006.ckpt"
        )
        final_off = CheckpointManager.load(
            off["dir"] / "checkpoints" / "step_000006.ckpt"
        )
        assert _trees_equal(final_a["params"], final_off["params"])
        assert _trees_equal(final_a["opt_state"], final_off["opt_state"])
        # zero-off save at step 3 -> resumed WITH zero (incl. offload).
        res_b, report_b = _fit(
            root,
            tmp_path / "off_to_on",
            zero=True,
            host_offload=True,
            resume_from=str(off["dir"] / "checkpoints" / "step_000003.ckpt"),
        )
        assert res_b.resumed_from_step == 3
        assert res_b.final_loss == off["result"].final_loss
        assert report_b["memory"]["opt_state_bytes_per_device"] == 0


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Topology-independent dataset (same rationale as tests/test_elastic.py:
    local_text sizes itself from file contents, not the batch topology)."""
    tmp = tmp_path_factory.mktemp("zero_corpus")
    f = tmp / "corpus.txt"
    f.write_text("sharded optimizer state pays for bigger models. " * 200)
    return tmp


def _elastic_zero_cfg(corpus_dir, root, *, micro, data, zero=True):
    cfg = _zero_cfg(root, zero=zero, micro=micro, data=data)
    return cfg.model_copy(
        update={
            "data": cfg.data.model_copy(
                update={
                    "name": "local_text",
                    "cache_dir": str(corpus_dir / "cache"),
                    "extra": {
                        "globs": [str(corpus_dir / "corpus.txt")],
                        "val_fraction": 0.1,
                    },
                }
            )
        }
    )


class TestZeroElasticResume:
    @pytest.mark.slow
    def test_ws4_to_ws2_and_back_with_sharded_state(self, corpus, tmp_path, caplog):
        """Elastic dp resize with ZeRO on both sides: the step-3 manifest
        saved on a data=4 mesh resumes on data=2 (micro scaled inversely,
        global micro-batch preserved) and continues the ws2 reference
        trajectory bitwise — and the reverse direction too. The restored
        full-host state lands as 2-way (resp. 4-way) shards through
        reshard_state's jit identity."""
        r4 = tmp_path / "ws4"
        r4.mkdir()
        with _visible_devices(4):
            ref4 = Trainer(
                _elastic_zero_cfg(corpus, tmp_path, micro=1, data=4),
                r4,
                NullTracker(),
                None,
            ).fit()
        r2 = tmp_path / "ws2"
        r2.mkdir()
        with _visible_devices(2):
            ref2 = Trainer(
                _elastic_zero_cfg(corpus, tmp_path, micro=2, data=2),
                r2,
                NullTracker(),
                None,
            ).fit()
            with caplog.at_level(logging.WARNING, logger="llmtrain"):
                down = Trainer(
                    _elastic_zero_cfg(corpus, tmp_path, micro=2, data=2),
                    None,
                    NullTracker(),
                    None,
                ).fit(resume_from=str(r4 / "checkpoints" / "step_000003.ckpt"))
        assert down.resumed_from_step == 3
        assert down.final_loss == ref2.final_loss
        assert ref2.final_loss == ref4.final_loss
        assert any("elastic resume" in r.message for r in caplog.records)
        with _visible_devices(4):
            up = Trainer(
                _elastic_zero_cfg(corpus, tmp_path, micro=1, data=4),
                None,
                NullTracker(),
                None,
            ).fit(resume_from=str(r2 / "checkpoints" / "step_000003.ckpt"))
        assert up.resumed_from_step == 3
        assert up.final_loss == ref4.final_loss

"""Mesh planner + auto-tuner tests (docs/perf.md "Mesh planning and
auto-tuning"): wildcard/divisibility resolution tables, capability
feasibility rules, seeded candidate enumeration, the analytical pruning
pass (every discard carries a reason — no silent caps), the `llmtrain
plan` exit-code contract, and the @slow probe-fit tune -> train
round-trip on the smoke preset."""

import json
import os
import pathlib
import subprocess
import sys

import pytest
import yaml

from llmtrain_tpu.autotune.plan import (
    MESH_AXES,
    MeshPlanError,
    ModelCaps,
    caps_from_config,
    config_loss_impl,
    plan_from_config,
    predict_hbm_bytes,
    resolve_axis_sizes,
    resolve_plan,
)
from llmtrain_tpu.autotune.search import (
    Candidate,
    enumerate_candidates,
    prune_candidates,
    resolve_hbm_limit,
)
from llmtrain_tpu.config import RunConfig
from llmtrain_tpu.registry import initialize_registries
from llmtrain_tpu.resilience.harness import deep_merge
from llmtrain_tpu.telemetry.profiling import resolve_peaks
from llmtrain_tpu.utils.hw import DEVICE_TABLE

REPO = pathlib.Path(__file__).resolve().parent.parent
SMOKE_PRESET = REPO / "configs" / "presets" / "gpt_tune_smoke.yaml"


def _cfg(**overrides):
    base = {
        "run": {"name": "tune-t", "seed": 3},
        "model": {
            "name": "dummy_gpt",
            "block_size": 8,
            "vocab_size": 32,
            "dropout": 0.0,
            "d_model": 64,
            "n_heads": 2,
            "d_ff": 128,
            "n_layers": 1,
        },
        "data": {"name": "dummy_text"},
        "trainer": {
            "max_steps": 6,
            "micro_batch_size": 2,
            "grad_accum_steps": 1,
            "lr": 3e-3,
            "warmup_steps": 0,
        },
        "mlflow": {"enabled": False},
    }
    for section, values in overrides.items():
        base[section] = {**base.get(section, {}), **values}
    return RunConfig.model_validate(base)


CAPS = ModelCaps(n_heads=4, block_size=16)


class TestResolveAxisSizes:
    @pytest.mark.parametrize(
        "sizes,devices,expected",
        [
            ({"data": -1}, 8, {"data": 8}),
            ({"data": 2, "fsdp": -1}, 8, {"data": 2, "fsdp": 4}),
            ({"tensor": 2, "data": -1}, 8, {"data": 4, "tensor": 2}),
            (
                {"data": 2, "fsdp": 2, "tensor": 2},
                8,
                {"data": 2, "fsdp": 2, "tensor": 2},
            ),
            ({}, 1, {}),
        ],
    )
    def test_wildcard_table(self, sizes, devices, expected):
        out = resolve_axis_sizes(sizes, devices)
        want = {axis: expected.get(axis, 1) for axis in MESH_AXES}
        assert out == want

    def test_two_wildcards_rejected(self):
        with pytest.raises(MeshPlanError, match="at most one"):
            resolve_axis_sizes({"data": -1, "fsdp": -1}, 8)

    def test_wildcard_unfillable(self):
        # Messages keep the words the pre-refactor tests pinned:
        # "divisible" for wildcard failures, "devices" for tiling ones.
        with pytest.raises(MeshPlanError, match="divisible"):
            resolve_axis_sizes({"data": 3, "fsdp": -1}, 8)

    def test_product_must_tile_devices(self):
        with pytest.raises(MeshPlanError, match="devices"):
            resolve_axis_sizes({"data": 3}, 8)

    def test_zero_axis_rejected(self):
        with pytest.raises(MeshPlanError, match="positive"):
            resolve_axis_sizes({"data": 0}, 8)

    def test_distributed_entrypoint_delegates_here(self):
        # resolve_mesh_axes is now a thin wrapper over resolve_axis_sizes;
        # MeshPlanError is a ValueError so pre-existing callers still
        # catch it.
        from llmtrain_tpu.distributed import resolve_mesh_axes

        cfg = _cfg(distributed={"mesh": {"data": 3}})
        with pytest.raises(MeshPlanError, match="devices"):
            resolve_mesh_axes(cfg.distributed.mesh, 8)
        assert issubclass(MeshPlanError, ValueError)


class TestPlanRules:
    def _plan(self, mesh, caps=CAPS, mb=4, **kw):
        return resolve_plan(
            mesh_sizes=mesh,
            device_count=8,
            caps=caps,
            micro_batch_size=mb,
            **kw,
        )

    def test_pipeline_needs_capability(self):
        with pytest.raises(MeshPlanError, match="pipeline"):
            self._plan({"pipeline": 2, "data": 4})

    def test_pipeline_microbatch_divisibility(self):
        caps = ModelCaps(
            n_heads=4, block_size=16, supports_pipeline=True, pipeline_microbatches=4
        )
        with pytest.raises(MeshPlanError, match="pipeline_microbatches"):
            self._plan({"pipeline": 2, "data": 4}, caps=caps, mb=2)
        plan = self._plan({"pipeline": 2, "data": 4}, caps=caps, mb=4)
        assert plan.axes["pipeline"] == 2

    def test_sequence_dense_is_legal(self):
        # GSPMD handles a sequence axis under dense attention
        # (tests/test_distributed.py pins the layouts agree) — only the
        # ring/ulysses kernels demand exact context shards.
        plan = self._plan({"sequence": 2, "data": 4})
        assert plan.axes["sequence"] == 2

    def test_sequence_ring_needs_exact_shards(self):
        caps = ModelCaps(n_heads=4, block_size=6, attention="ring")
        with pytest.raises(MeshPlanError, match="block_size"):
            self._plan({"sequence": 4, "data": 2}, caps=caps)

    def test_sequence_ulysses_shards_heads_too(self):
        caps = ModelCaps(n_heads=2, block_size=16, attention="ulysses")
        with pytest.raises(MeshPlanError, match="n_heads"):
            self._plan({"sequence": 4, "data": 2}, caps=caps)

    def test_tensor_heads_divisibility(self):
        with pytest.raises(MeshPlanError, match="n_heads"):
            self._plan({"tensor": 8}, caps=ModelCaps(n_heads=6, block_size=16))

    def test_tensor_kv_heads_divisibility(self):
        caps = ModelCaps(n_heads=8, block_size=16, n_kv_heads=2)
        with pytest.raises(MeshPlanError, match="n_kv_heads"):
            self._plan({"tensor": 4, "data": 2}, caps=caps)

    def test_expert_dense_is_legal_batch_axis(self):
        # On a dense model `expert` is one of the ELASTIC data axes
        # (parallel/sharding.py) — it must count toward data_parallel.
        plan = self._plan({"expert": 2, "data": 4})
        assert plan.data_parallel == 8

    def test_expert_moe_divisibility(self):
        caps = ModelCaps(n_heads=4, block_size=16, n_experts=3)
        with pytest.raises(MeshPlanError, match="n_experts"):
            self._plan({"expert": 2, "data": 4}, caps=caps)

    def test_zero_stage_bounds(self):
        with pytest.raises(MeshPlanError, match="zero_stage"):
            self._plan({"data": 8}, zero_stage=3)

    def test_micro_batch_positive(self):
        with pytest.raises(MeshPlanError, match="micro_batch_size"):
            self._plan({"data": 8}, mb=0)


class TestMeshPlanObject:
    def test_key_and_round_trip(self):
        plan = resolve_plan(
            mesh_sizes={"data": -1, "tensor": 2},
            device_count=8,
            caps=CAPS,
            micro_batch_size=4,
            zero_stage=1,
        )
        assert plan.key() == "d4.f1.t2.s1.p1.e1|mb4|remat0|zero1"
        sizes = plan.mesh_axis_sizes()
        assert tuple(sizes) == MESH_AXES  # canonical order, manifest-legal
        assert resolve_axis_sizes(sizes, 8) == sizes  # no wildcard survives
        topo = plan.describe_topology()
        assert topo["mesh"] == sizes
        assert topo["global_micro_batch"] == 4 * plan.data_parallel

    def test_config_overrides_merge_into_valid_config(self):
        cfg = _cfg()
        plan = resolve_plan(
            mesh_sizes={"data": 4, "fsdp": 2},
            device_count=8,
            caps=caps_from_config(cfg),
            micro_batch_size=4,
            remat=True,
            zero_stage=2,
        )
        merged = deep_merge(cfg.model_dump(), plan.config_overrides())
        tuned = RunConfig.model_validate(merged)
        # The emitted config resolves back to the exact same plan — what
        # the tuner measured is what `llmtrain train` later runs.
        assert plan_from_config(tuned, 8).key() == plan.key()

    def test_predict_hbm_monotone_in_sharding(self):
        kw = dict(n_params=10_000_000, d_model=64, n_layers=2, vocab_size=256,
                  block_size=16)
        dense = resolve_plan(
            mesh_sizes={"data": 1}, device_count=1, caps=CAPS, micro_batch_size=4
        )
        sharded = resolve_plan(
            mesh_sizes={"fsdp": 8}, device_count=8, caps=CAPS, micro_batch_size=4
        )
        assert (
            predict_hbm_bytes(sharded, **kw)["total_bytes"]
            < predict_hbm_bytes(dense, **kw)["total_bytes"]
        )

    def test_predict_hbm_logits_term_per_loss_impl(self):
        """The logits-buffer table (docs/perf.md "Fused lm-head + CE"):
        dense charges tokens x V, chunked a tokens x min(ce_chunk, V)
        block, fused_ce nothing — the planner's verdict must track what
        the adapter's loss path actually allocates."""
        plan = resolve_plan(
            mesh_sizes={"data": 1}, device_count=1, caps=CAPS, micro_batch_size=4
        )
        kw = dict(n_params=1_000_000, d_model=64, n_layers=2, vocab_size=50_000,
                  block_size=16)
        tokens = 4 * 16
        table = {
            "dense": tokens * 50_000 * 4.0,
            "chunked_ce": tokens * 8192 * 4.0,  # default ce_chunk
            "fused_ce": 0.0,
        }
        for impl, want in table.items():
            hbm = predict_hbm_bytes(plan, loss_impl=impl, **kw)
            assert hbm["loss_impl"] == impl
            assert hbm["logits_bytes"] == want, impl
        # an oversized chunk clamps at the vocab — never charges more
        # than the dense buffer
        clamped = predict_hbm_bytes(
            plan, loss_impl="chunked_ce", ce_chunk=1 << 20, **kw
        )
        assert clamped["logits_bytes"] == table["dense"]

    def test_config_loss_impl_matches_adapter_resolution(self):
        # small vocab, nothing requested -> dense
        assert config_loss_impl(_cfg()) == ("dense", 8192)
        # explicit fused without Pallas degrades exactly like the adapter
        cfg = _cfg(model={"extra": {"loss_impl": "fused_ce"}})
        assert config_loss_impl(cfg)[0] == "chunked_ce"
        # ...and holds with the interpret escape hatch
        cfg = _cfg(
            model={"extra": {"loss_impl": "fused_ce", "pallas_interpret": True}}
        )
        assert config_loss_impl(cfg) == ("fused_ce", 8192)
        # invalid explicit value is config validation's error to raise,
        # not the planner's: estimate conservatively as dense
        cfg = _cfg(model={"extra": {"loss_impl": "typo", "ce_chunk": 64}})
        assert config_loss_impl(cfg) == ("dense", 64)


class TestSearch:
    def test_deterministic_seeded_order(self):
        cfg = _cfg()
        first = [c.key() for c in enumerate_candidates(cfg, 8, seed=7)]
        again = [c.key() for c in enumerate_candidates(cfg, 8, seed=7)]
        other = [c.key() for c in enumerate_candidates(cfg, 8, seed=8)]
        assert first == again
        assert sorted(first) == sorted(other)  # same grid...
        assert first != other  # ...different order

    def test_dense_model_skips_expert_shapes(self):
        # Dense expert>1 shapes are exact semantic twins of data-axis
        # shapes already in the grid — enumerating them would waste probes.
        cands = enumerate_candidates(_cfg(), 8, seed=0)
        assert cands
        assert all(c.mesh_sizes["expert"] == 1 for c in cands)

    def test_search_knobs_pin_dimensions(self):
        cfg = _cfg()
        cands = enumerate_candidates(
            cfg, 8, seed=0, search_mesh=False, search_remat=False, search_zero=False,
            microbatch_candidates=[4],
        )
        keys = {c.key() for c in cands}
        assert keys == {"d8.f1.t1.s1.p1.e1|mb4|remat0|zero0"}

    def test_prune_accounts_for_every_candidate(self):
        cfg = _cfg()
        cands = enumerate_candidates(cfg, 8, seed=0)
        res = prune_candidates(
            cands,
            cfg,
            device_count=8,
            caps=caps_from_config(cfg),
            peaks=resolve_peaks("cpu"),
            hbm_limit_bytes=resolve_hbm_limit("cpu"),
            max_probes=2,
        )
        assert res["enumerated"] == len(cands)
        # No silent caps: every enumerated candidate is a survivor or a
        # pruned entry with a named reason.
        assert len(res["survivors"]) + len(res["pruned"]) == res["enumerated"]
        assert len(res["survivors"]) <= 2
        reasons = [p["reason"] for p in res["pruned"]]
        assert all(r for r in reasons)
        # n_heads=2 makes tensor=8 shapes illegal -> recorded, not skipped.
        assert any(r.startswith("topology-illegal") for r in reasons)
        assert any(r.startswith("dominated") for r in reasons)
        assert any(r.startswith("probe-budget") for r in reasons)
        # Survivors come back best-predicted-first.
        times = [c.predicted["predicted_us_per_token"] for c in res["survivors"]]
        assert times == sorted(times)

    def test_prune_infeasible_hbm(self):
        cfg = _cfg()
        cands = enumerate_candidates(cfg, 8, seed=0)
        res = prune_candidates(
            cands,
            cfg,
            device_count=8,
            caps=caps_from_config(cfg),
            peaks=resolve_peaks("cpu"),
            hbm_limit_bytes=1.0,  # nothing fits in one byte
            max_probes=4,
        )
        assert res["survivors"] == []
        assert any(
            p["reason"].startswith("infeasible-hbm") for p in res["pruned"]
        )

    def test_ranking_is_per_token_not_per_step(self):
        # A half-size microbatch "wins" raw step time while losing
        # throughput; the pruner must rank on time per token so the
        # larger batch (which amortizes param traffic) comes first.
        cfg = _cfg()
        mesh = dict.fromkeys(MESH_AXES, 1)
        mesh["data"] = 8
        cands = [
            Candidate(mesh_sizes=dict(mesh), micro_batch_size=mb,
                      remat=False, zero_stage=0)
            for mb in (2, 4)
        ]
        res = prune_candidates(
            cands,
            cfg,
            device_count=8,
            caps=caps_from_config(cfg),
            peaks=resolve_peaks("cpu"),
            hbm_limit_bytes=resolve_hbm_limit("cpu"),
            max_probes=10,
        )
        assert res["survivors"][0].micro_batch_size == 4
        by_mb = {c.micro_batch_size: c.predicted for c in cands if c.predicted}
        assert (
            by_mb[4]["predicted_us_per_token"] < by_mb[2]["predicted_us_per_token"]
        )

    def test_preserve_topology_prunes_resume_illegal(self):
        cfg = _cfg()
        baseline = resolve_plan(
            mesh_sizes={"data": 8},
            device_count=8,
            caps=caps_from_config(cfg),
            micro_batch_size=2,
        )
        res = prune_candidates(
            enumerate_candidates(cfg, 8, seed=0),
            cfg,
            device_count=8,
            caps=caps_from_config(cfg),
            peaks=resolve_peaks("cpu"),
            hbm_limit_bytes=resolve_hbm_limit("cpu"),
            max_probes=8,
            baseline_topology=baseline.describe_topology(),
        )
        assert any(
            "(resume)" in p["reason"] for p in res["pruned"]
        )
        # Whatever survives really is adoptable by the running checkpoint.
        from llmtrain_tpu.resilience.elastic import classify_topology_change

        for cand in res["survivors"]:
            classify_topology_change(
                baseline.describe_topology(), cand.plan.describe_topology()
            )

    def test_resolve_hbm_limit(self):
        assert resolve_hbm_limit("TPU v5 lite") == DEVICE_TABLE["v5 lite"]["hbm_bytes"]
        assert resolve_hbm_limit("tpu v5p") == DEVICE_TABLE["v5p"]["hbm_bytes"]
        assert resolve_hbm_limit("weird accelerator") == DEVICE_TABLE["cpu"]["hbm_bytes"]
        assert resolve_hbm_limit("v4", override=123.0) == 123.0


class TestFailFast:
    @pytest.fixture(autouse=True)
    def _registries(self):
        initialize_registries()

    def test_mesh_plan_error_maps_to_config_exit(self):
        from llmtrain_tpu.resilience.exit_codes import (
            EXIT_CONFIG_ERROR,
            exit_code_for_exception,
        )

        assert exit_code_for_exception(MeshPlanError("boom")) == EXIT_CONFIG_ERROR
        wrapped = RuntimeError("trainer setup failed")
        wrapped.__cause__ = MeshPlanError("axis")
        assert exit_code_for_exception(wrapped) == EXIT_CONFIG_ERROR

    def test_trainer_fails_fast_on_untileable_mesh(self):
        # Regression: a mesh that cannot tile the device count must die as
        # a named MeshPlanError during trainer setup, before any mesh or
        # params materialize — not as an opaque pjit/XLA error later. (A
        # fully explicit mesh SMALLER than the host is legal: it takes the
        # leading devices — tests/test_platform_rules.py.)
        from llmtrain_tpu.tracking import NullTracker
        from llmtrain_tpu.training import Trainer

        cfg = _cfg(distributed={"mesh": {"data": 16}})
        with pytest.raises(MeshPlanError, match="devices"):
            Trainer(cfg, None, NullTracker(), None)


class TestPlanCLI:
    def _write(self, tmp_path, **overrides):
        dump = _cfg(**overrides).model_dump()
        path = tmp_path / "cfg.yaml"
        path.write_text(yaml.safe_dump(dump, sort_keys=False))
        return str(path)

    def test_plan_feasible_exit_zero(self, tmp_path, capsys):
        from llmtrain_tpu.cli import main

        rc = main(["plan", "--config", self._write(tmp_path), "--devices", "8",
                   "--json"])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["feasible"] is True
        assert payload["plan"]["key"].startswith("d")
        assert payload["roofline"]["class"] in {"compute", "memory", "comms"}
        assert payload["predicted_hbm"]["total_bytes"] > 0
        assert payload["predicted_hbm"]["total_bytes"] <= payload["hbm_limit_bytes"]

    def test_plan_prints_assumed_loss_impl(self, tmp_path, capsys):
        from llmtrain_tpu.cli import main

        cfg_path = self._write(
            tmp_path,
            model={"extra": {"loss_impl": "fused_ce", "pallas_interpret": True}},
        )
        rc = main(["plan", "--config", cfg_path, "--devices", "8", "--json"])
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["plan"]["loss_impl"] == "fused_ce"
        assert payload["predicted_hbm"]["loss_impl"] == "fused_ce"
        assert payload["predicted_hbm"]["logits_bytes"] == 0.0
        rc = main(["plan", "--config", cfg_path, "--devices", "8"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "loss      fused_ce (logits buffer 0.0 MiB)" in out

    def test_plan_infeasible_mesh_exit_two(self, tmp_path, capsys):
        from llmtrain_tpu.cli import main

        cfg_path = self._write(tmp_path, distributed={"mesh": {"data": 3}})
        rc = main(["plan", "--config", cfg_path, "--devices", "8"])
        assert rc == 2
        assert "infeasible plan" in capsys.readouterr().err

    def test_plan_hbm_over_limit_exit_two(self, tmp_path, capsys):
        from llmtrain_tpu.cli import main

        cfg_path = self._write(tmp_path, tune={"hbm_limit_bytes": 1.0})
        rc = main(["plan", "--config", cfg_path, "--devices", "8"])
        assert rc == 2
        assert "HBM" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Probe-fit e2e (@slow): real subprocess probes, real report.json scoring.
# ---------------------------------------------------------------------------


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


@pytest.mark.slow
class TestTuneEndToEnd:
    def test_tune_then_train_round_trip(self, tmp_path):
        workdir = tmp_path / "tune"
        tuned = tmp_path / "tuned.yaml"
        proc = subprocess.run(
            [
                sys.executable, "-m", "llmtrain_tpu", "tune",
                "--config", str(SMOKE_PRESET),
                "--workdir", str(workdir),
                "--output", str(tuned),
                "--json",
            ],
            capture_output=True,
            text=True,
            env=_env(),
            cwd=tmp_path,
            timeout=500,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        report = json.loads((workdir / "tune_report.json").read_text())

        # Observability contract: enumerated == survivors + pruned, every
        # pruned entry names its reason, the log shows the funnel.
        assert report["enumerated"] == len(report["survivors"]) + len(
            report["pruned"]
        )
        assert all(p["reason"] for p in report["pruned"])

        # The baseline probe ran and the winner's measured MFU is >= the
        # untuned config's (baseline is always probed, so a regression
        # can only happen by picking a worse measured candidate).
        baseline = report["baseline"]
        winner = report["winner"]
        assert baseline["status"] == "ok", baseline
        assert winner["status"] == "ok"
        assert winner["mfu"] >= baseline["mfu"]

        # The emitted YAML validates and trains unchanged.
        assert tuned.exists()
        merged = yaml.safe_load(tuned.read_text())
        RunConfig.model_validate(merged)
        train = subprocess.run(
            [
                sys.executable, "-m", "llmtrain_tpu", "train",
                "--config", str(tuned),
                "--run-id", "tuned_rt",
                "--json",
            ],
            capture_output=True,
            text=True,
            env=_env(),
            cwd=tmp_path,
            timeout=300,
        )
        assert train.returncode == 0, train.stderr[-2000:]
        rt_report = json.loads(
            (tmp_path / "runs" / "tuned_rt" / "report.json").read_text()
        )
        mfu = (rt_report.get("perf_attribution") or {}).get("mfu", {}).get(
            "measured"
        )
        assert mfu is not None and mfu > 0

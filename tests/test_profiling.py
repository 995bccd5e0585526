"""XLA cost attribution + roofline analysis (telemetry/profiling.py).

Tier-1 keeps to pure units — cost-dict normalization, the HLO op parser
on synthetic text, roofline classification, peak-table resolution, the
perf_attribution assembly, the serve-latency percentile reservoir, and
the perf gate's comparison core. Everything that lowers or compiles a
real program (the fit-path attribution, the ``llmtrain profile`` CLI) is
``@pytest.mark.slow`` under ``make verify-profile``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from llmtrain_tpu.telemetry.profiling import (
    MFU_RECONCILE_BAND,
    attribution_gauges,
    build_perf_attribution,
    classify_roofline,
    cost_summary,
    gradient_collective_bytes,
    normalize_cost,
    parse_hlo_ops,
    render_top_ops_markdown,
    resolve_peaks,
    top_ops,
)
from llmtrain_tpu.utils.hw import DEVICE_TABLE

REPO = Path(__file__).resolve().parents[1]


# --------------------------------------------------------------------------
# cost_analysis normalization
# --------------------------------------------------------------------------


class TestCostNormalization:
    def test_dict_list_and_none_shapes(self):
        assert normalize_cost(None) == {}
        assert normalize_cost({"flops": 10, "bytes accessed": 2.5}) == {
            "flops": 10.0,
            "bytes accessed": 2.5,
        }
        # Compiled.cost_analysis() returns a list of per-computation dicts;
        # the first entry is the entry computation.
        assert normalize_cost([{"flops": 7}, {"flops": 99}]) == {"flops": 7.0}
        assert normalize_cost([]) == {}

    def test_cost_summary_maps_xla_key_spelling(self):
        summary = cost_summary({"flops": 4.0, "bytes accessed": 8.0})
        assert summary == {"flops": 4.0, "bytes_accessed": 8.0, "transcendentals": 0.0}

    def test_cost_summary_garbage_degrades_to_zeros(self):
        assert cost_summary(object()) == {
            "flops": 0.0,
            "bytes_accessed": 0.0,
            "transcendentals": 0.0,
        }


# --------------------------------------------------------------------------
# peak table
# --------------------------------------------------------------------------


class TestResolvePeaks:
    def test_substring_match_prefers_longest_key(self):
        # "TPU v5 lite" must hit the v5e-class row, not a bare "v5" guess.
        peaks = resolve_peaks("TPU v5 lite")
        assert peaks["peak_flops"] == DEVICE_TABLE["v5 lite"]["peak_flops"]
        assert peaks["device_kind"] == "tpu v5 lite"

    def test_unknown_kind_falls_back_to_cpu_row(self):
        peaks = resolve_peaks("quantum-abacus")
        assert peaks["peak_flops"] == DEVICE_TABLE["cpu"]["peak_flops"]

    def test_config_overrides_win(self):
        peaks = resolve_peaks("TPU v4", {"peak_flops": 123.0})
        assert peaks["peak_flops"] == 123.0
        # non-overridden keys keep the table value
        assert peaks["hbm_bytes_per_sec"] == DEVICE_TABLE["v4"]["hbm_bytes_per_sec"]


# --------------------------------------------------------------------------
# HLO op parser (synthetic post-optimization HLO)
# --------------------------------------------------------------------------

_SYNTHETIC_HLO = """\
HloModule synthetic

%helper (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  %e = f32[64]{0} exponential(f32[64]{0} %p)
  ROOT %a = f32[64]{0} add(f32[64]{0} %e, f32[64]{0} %e)
}

ENTRY %main (lhs: f32[8,16], rhs: f32[16,32]) -> f32[8,32] {
  %lhs = f32[8,16]{1,0} parameter(0)
  %rhs = f32[16,32]{1,0} parameter(1)
  %d = f32[8,32]{1,0} dot(f32[8,16]{1,0} %lhs, f32[16,32]{1,0} %rhs), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,32]{1,0} all-reduce(f32[8,32]{1,0} %d), replica_groups={}, to_apply=%helper
  ROOT %r = f32[8,32]{1,0} add(f32[8,32]{1,0} %ar, f32[8,32]{1,0} %ar)
}
"""


class TestParseHloOps:
    def test_dot_flops_use_contracting_dims(self):
        parsed = parse_hlo_ops(_SYNTHETIC_HLO)
        # 2 * out_elems(8*32) * contracting(16)
        assert parsed["ops"]["dot"]["flops"] == 2.0 * 8 * 32 * 16

    def test_bytes_counted_in_entry_only(self):
        parsed = parse_hlo_ops(_SYNTHETIC_HLO)
        # helper's exponential does math (flops + transcendentals) but its
        # buffers are fusion-internal: no entry-level bytes.
        exp = parsed["ops"]["exponential"]
        assert exp["flops"] == 64 and exp["transcendentals"] == 64
        assert exp["bytes_accessed"] == 0.0
        # entry dot: output 8*32*4 plus operands (8*16 + 16*32)*4
        assert parsed["ops"]["dot"]["bytes_accessed"] == (8 * 32 + 8 * 16 + 16 * 32) * 4

    def test_collective_bytes_and_parameter_cost(self):
        parsed = parse_hlo_ops(_SYNTHETIC_HLO)
        assert parsed["collective_bytes"] == 8 * 32 * 4  # all-reduce operand
        assert parsed["ops"]["parameter"]["flops"] == 0.0
        assert parsed["ops"]["parameter"]["bytes_accessed"] == 0.0

    def test_top_ops_ranks_dot_first_and_classes_collectives(self):
        parsed = parse_hlo_ops(_SYNTHETIC_HLO)
        rows = top_ops(parsed, resolve_peaks("cpu"), k=10)
        assert rows[0]["op"] == "dot"
        by_op = {r["op"]: r for r in rows}
        assert by_op["all-reduce"]["class"] == "comms"
        assert "parameter" not in by_op  # zero-cost rows are dropped

    def test_markdown_table_renders_every_row(self):
        parsed = parse_hlo_ops(_SYNTHETIC_HLO)
        rows = top_ops(parsed, resolve_peaks("cpu"), k=3)
        lines = render_top_ops_markdown(rows)
        assert lines[0].startswith("| op |")
        assert len(lines) == 2 + len(rows)


# --------------------------------------------------------------------------
# roofline classification
# --------------------------------------------------------------------------


class TestRoofline:
    _PEAKS = {
        "peak_flops": 100.0,
        "hbm_bytes_per_sec": 10.0,
        "ici_bytes_per_sec": 1.0,
    }

    def test_compute_bound(self):
        roof = classify_roofline(flops=1000.0, bytes_accessed=50.0, peaks=self._PEAKS)
        assert roof["class"] == "compute"
        assert roof["arithmetic_intensity"] == pytest.approx(20.0)
        assert roof["ridge_intensity"] == pytest.approx(10.0)

    def test_memory_bound(self):
        roof = classify_roofline(flops=10.0, bytes_accessed=50.0, peaks=self._PEAKS)
        assert roof["class"] == "memory"

    def test_comms_bound(self):
        roof = classify_roofline(
            flops=10.0, bytes_accessed=5.0, collective_bytes=100.0, peaks=self._PEAKS
        )
        assert roof["class"] == "comms"

    def test_gradient_collective_bytes_ring_formula(self):
        assert gradient_collective_bytes({}, 100.0) == 0.0
        assert gradient_collective_bytes({"model": 8}, 100.0) == 0.0
        # dp=4 ring all-reduce: 2*(4-1)/4 * grad_bytes
        assert gradient_collective_bytes({"data": 2, "fsdp": 2}, 100.0) == 150.0


# --------------------------------------------------------------------------
# perf_attribution assembly + gauges
# --------------------------------------------------------------------------


class TestPerfAttribution:
    def _block(self, **kw):
        defaults = dict(
            executables=[
                {
                    "name": "train_step",
                    "flops": 1e6,
                    "bytes_accessed": 1e5,
                    "transcendentals": 0.0,
                }
            ],
            peaks=resolve_peaks("cpu"),
            step_time_ms=10.0,
            tokens_per_step=100.0,
            palm_flops_per_token=1e4,
            measured_mfu=0.1,
            span_totals={"data_wait": {"total_ms": 4.0}, "host_dispatch": {"total_ms": 6.0}},
            steps=2,
        )
        defaults.update(kw)
        return build_perf_attribution(**defaults)

    def test_mfu_ratio_is_deterministic_and_reconciled(self):
        block = self._block()
        # 1e6 / (100 * 1e4) == 1.0: inside the documented tolerance band.
        assert block["mfu"]["ratio_analytical_over_measured"] == pytest.approx(1.0)
        assert block["mfu"]["reconciled"] is True
        assert block["mfu"]["tolerance_band"] == list(MFU_RECONCILE_BAND)

    def test_flops_model_mismatch_flags_unreconciled(self):
        block = self._block(palm_flops_per_token=1e2)
        assert block["mfu"]["ratio_analytical_over_measured"] > MFU_RECONCILE_BAND[1]
        assert block["mfu"]["reconciled"] is False

    def test_step_split_accounts_host_spans_per_step(self):
        split = self._block()["step_time_split_ms"]
        assert split["step"] == 10.0
        assert split["measured_host"] == pytest.approx((4.0 + 6.0) / 2)
        total = (
            split["analytical_compute"]
            + split["analytical_collective"]
            + split["measured_host"]
            + split["unattributed_gap"]
        )
        assert total <= split["step"] + 1e-6

    def test_gauges_flatten_the_block(self):
        gauges = attribution_gauges(self._block())
        assert gauges["perf/flops_per_step"] == 1e6
        assert gauges["perf/mfu_reconcile_ratio"] == pytest.approx(1.0)
        assert gauges["perf/roofline_class"] in (0.0, 1.0, 2.0)
        assert "perf/step_unattributed_gap_ms" in gauges


# --------------------------------------------------------------------------
# serve-latency percentile reservoir (serving/http.py satellite)
# --------------------------------------------------------------------------


class TestServerStatsPercentiles:
    def test_ttft_and_per_token_gauges(self):
        from llmtrain_tpu.serving.http import ServerStats

        stats = ServerStats()
        for i in range(100):
            stats.record(latency_ms=float(i + 1), ttft_ms=float(i) / 2, tokens=11)
        gauges = stats.prometheus_gauges()
        for stem in ("serve/latency_ms", "serve/ttft_ms", "serve/per_token_ms"):
            for tag in ("p50", "p95", "p99"):
                assert f"{stem}_{tag}" in gauges
        assert gauges["serve/latency_ms_p50"] <= gauges["serve/latency_ms_p99"]
        # per-token = (latency - ttft) / (tokens - 1): decode-rate only
        assert gauges["serve/per_token_ms_p50"] == pytest.approx(
            (51.0 - 25.0) / 10, abs=0.5
        )

    def test_empty_reservoirs_export_nothing(self):
        from llmtrain_tpu.serving.http import ServerStats

        assert ServerStats().prometheus_gauges() == {}

    def test_legacy_record_without_ttft(self):
        from llmtrain_tpu.serving.http import ServerStats

        stats = ServerStats()
        stats.record(latency_ms=100.0, tokens=4)
        gauges = stats.prometheus_gauges()
        assert "serve/ttft_ms_p50" not in gauges
        assert gauges["serve/per_token_ms_p50"] == pytest.approx(25.0)

    def test_snapshot_gains_p95_and_ttft(self):
        from llmtrain_tpu.serving.http import ServerStats

        stats = ServerStats()
        for i in range(20):
            stats.record(latency_ms=float(i), ttft_ms=1.0, tokens=2)
        snap = stats.snapshot()
        assert snap["p95_latency_ms"] >= snap["p50_latency_ms"]
        assert snap["p50_ttft_ms"] == 1.0


# --------------------------------------------------------------------------
# slow: real lowering/compiles
# --------------------------------------------------------------------------


def _cli_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.slow
class TestFitAttribution:
    def test_fit_report_gains_perf_attribution(self, tmp_path):
        from llmtrain_tpu.config import RunConfig
        from llmtrain_tpu.registry import initialize_registries
        from llmtrain_tpu.tracking.base import NullTracker
        from llmtrain_tpu.training.trainer import Trainer

        initialize_registries()
        cfg = RunConfig.model_validate(
            {
                "run": {"name": "perf-attr"},
                "model": {
                    "name": "dummy_gpt",
                    "block_size": 8,
                    "d_model": 16,
                    "n_layers": 1,
                    "n_heads": 2,
                    "d_ff": 32,
                    "dropout": 0.0,
                    "vocab_size": 32,
                },
                "data": {"name": "dummy_text"},
                "trainer": {
                    "max_steps": 6,
                    "micro_batch_size": 2,
                    "grad_accum_steps": 1,
                    "log_every_steps": 3,
                    "eval_every_steps": 6,
                    "save_every_steps": 6,
                    "warmup_steps": 0,
                },
                "output": {"root_dir": str(tmp_path / "runs")},
            }
        )
        run_dir = tmp_path / "runs" / "perf-attr"
        (run_dir / "logs").mkdir(parents=True)
        Trainer(cfg, run_dir, NullTracker()).fit()

        report = json.loads((run_dir / "report.json").read_text())
        block = report["perf_attribution"]
        exe = block["executables"][0]
        assert exe["name"] == "train_step"
        assert exe["flops"] > 0 and exe["bytes_accessed"] > 0
        assert exe["roofline"]["class"] in ("compute", "memory", "comms")
        # The XLA flop count and the PaLM 6N model must agree within the
        # documented tolerance band on a plain dense GPT.
        assert block["mfu"]["reconciled"] is True, block["mfu"]
        assert set(block["step_time_split_ms"]) == {
            "step",
            "analytical_compute",
            "analytical_collective",
            "measured_host",
            "unattributed_gap",
        }

    def test_profile_cli_emits_report(self, tmp_path):
        """`llmtrain profile` acceptance: per-executable flops/bytes,
        roofline class, top-10 ops, and compiled memory footprint."""
        out = tmp_path / "profile_report.json"
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "llmtrain_tpu",
                "profile",
                "--config",
                "configs/presets/gpt_telemetry_smoke.yaml",
                "--steps",
                "2",
                "--output",
                str(out),
            ],
            capture_output=True,
            text=True,
            cwd=REPO,
            env=_cli_env(),
            timeout=420,
        )
        assert proc.returncode == 0, proc.stderr
        report = json.loads(out.read_text())
        assert report["schema"] == "llmtrain-profile-report/1"
        assert report["probe"]["steps"] == 2
        exes = {e["name"]: e for e in report["executables"]}
        train = exes["train_step"]
        assert train["flops"] > 0 and train["bytes_accessed"] > 0
        assert train["roofline"]["class"] in ("compute", "memory", "comms")
        assert 0 < len(train["top_ops"]) <= 10
        assert train["compile_time_s"] > 0
        assert report["memory"]["compiled_train_step"]["total_hbm_bytes"] > 0
        assert report["perf_attribution"]["mfu"]["reconciled"] is True

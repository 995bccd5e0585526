"""run_dir / metadata / logging / summary tests."""

import json
import logging
from types import SimpleNamespace

import pytest
import yaml

from llmtrain_tpu.config import RunConfig
from llmtrain_tpu.utils import (
    JsonFormatter,
    configure_logging,
    create_run_directory,
    format_run_summary,
    generate_meta,
    get_logger,
    write_meta_json,
    write_resolved_config,
)

MINIMAL = {
    "run": {"name": "t"},
    "model": {"name": "dummy_gpt"},
    "data": {"name": "dummy_text"},
    "trainer": {"max_steps": 10, "warmup_steps": 0},
}


def test_create_run_directory(tmp_path):
    d = create_run_directory(tmp_path, "abc")
    assert d.is_dir() and (d / "logs").is_dir()
    with pytest.raises(FileExistsError):
        create_run_directory(tmp_path, "abc")


def test_write_resolved_config_atomic(tmp_path):
    d = create_run_directory(tmp_path, "abc")
    cfg = RunConfig.model_validate(MINIMAL)
    path = write_resolved_config(d, cfg.model_dump())
    loaded = yaml.safe_load(path.read_text())
    assert loaded["run"]["name"] == "t"
    assert not list(d.glob("*.tmp"))


def test_meta_json(tmp_path, monkeypatch):
    monkeypatch.setenv("RANK", "3")
    meta = generate_meta(
        run_id="rid", run_name="t", config_path="c.yaml", resolved_config_path=None
    )
    assert meta["meta_version"] == 1
    assert meta["distributed_env"]["RANK"] == "3"
    assert meta["hostname"]
    path = write_meta_json(tmp_path, meta)
    assert json.loads(path.read_text())["run_id"] == "rid"


def test_json_formatter_single_line():
    record = logging.LogRecord("llmtrain", logging.INFO, "f", 1, "hello %s", ("x",), None)
    line = JsonFormatter().format(record)
    parsed = json.loads(line)
    assert parsed["message"] == "hello x"
    assert "\n" not in line


def test_configure_logging_idempotent(tmp_path):
    log_file = tmp_path / "t.log"
    logger = configure_logging(level="INFO", json_output=True, log_file=log_file)
    configure_logging(level="INFO", json_output=True, log_file=log_file)
    stream_handlers = [
        h for h in logger.handlers
        if isinstance(h, logging.StreamHandler) and not isinstance(h, logging.FileHandler)
    ]
    file_handlers = [h for h in logger.handlers if isinstance(h, logging.FileHandler)]
    assert len(stream_handlers) == 1
    assert len(file_handlers) == 1
    logger.info("written")
    for h in logger.handlers:
        h.flush()
    assert "written" in log_file.read_text()
    assert get_logger().propagate is False
    configure_logging(level="INFO", json_output=True, log_file=None)


def test_summary_json_and_text():
    cfg = RunConfig.model_validate(MINIMAL)
    s = format_run_summary(cfg, run_id="rid", run_dir="/tmp/rid", dry_run=True, as_json=True)
    assert isinstance(s, dict)
    assert s["run_id"] == "rid" and s["dry_run"] is True
    assert s["model"]["name"] == "dummy_gpt"
    text = format_run_summary(cfg, run_id="rid", run_dir=None, dry_run=True, as_json=False)
    assert isinstance(text, str) and text.startswith("Planned run:")
    assert "dummy_gpt" in text


def test_hw_flops_and_mfu():
    from llmtrain_tpu.utils import hw

    # 6N dominates when L*T*d is small
    fpt = hw.transformer_flops_per_token(
        n_params=1000, n_layers=1, seq_len=2, d_model=4
    )
    assert fpt == 6 * 1000 + 12 * 1 * 2 * 4

    # mfu is linear in throughput and inverse in peak
    m = hw.mfu(
        100.0, n_params=1000, n_layers=1, seq_len=2, d_model=4, peak_flops=1e6
    )
    assert m == pytest.approx(100.0 * fpt / 1e6)

    # CPU backend in tests -> nominal placeholder peak
    assert hw.peak_flops_per_chip() == hw.DEVICE_TABLE["cpu"]["peak_flops"]


class TestHW:
    """utils/hw.py: the MFU arithmetic every reported number rests on."""

    def test_transformer_flops_formula(self):
        from llmtrain_tpu.utils.hw import transformer_flops_per_token

        # PaLM appendix B: 6N + 12*L*T*d, hand-checked.
        assert transformer_flops_per_token(
            n_params=1000, n_layers=2, seq_len=8, d_model=4
        ) == 6 * 1000 + 12 * 2 * 8 * 4

    def test_mfu_hand_computed(self):
        from llmtrain_tpu.utils.hw import mfu

        # 10 tokens/s * 600 FLOPs/token = 6000 FLOP/s on a 60000-peak chip.
        got = mfu(
            10.0,
            n_params=100,
            n_layers=0,
            seq_len=8,
            d_model=4,
            peak_flops=60000.0,
        )
        assert abs(got - 0.1) < 1e-12

    def test_ledger_train_cell_mfu_reproduces(self):
        """The ledger's own figures as arithmetic cross-check: GPT-2 small
        (123.65M matmul parameters, 12 x d768, T 1,024) at the 93,716
        tokens/s of ``gpt2-small.train-64k`` gives its ``train_mfu`` of
        40.6% on the v5e peak (PERF_LEDGER.jsonl, PR 23)."""
        from llmtrain_tpu.utils.hw import DEVICE_TABLE, mfu

        got = mfu(
            93_716,
            n_params=123_650_000,
            n_layers=12,
            seq_len=1024,
            d_model=768,
            peak_flops=DEVICE_TABLE["v5e"]["peak_flops"],
        )
        assert abs(got - 0.406) < 0.002

    def test_peak_lookup_defaults_cpu(self):
        from llmtrain_tpu.utils.hw import DEVICE_TABLE, peak_flops_per_chip

        # conftest pins the CPU backend, so the nominal figure applies.
        assert peak_flops_per_chip() == DEVICE_TABLE["cpu"]["peak_flops"]


# --------------------------------------------------------------------------
# utils/hw.py: the one table of device kinds and its one lookup
# --------------------------------------------------------------------------

_TPU_KINDS = {
    "TPU v4": "v4",
    "TPU v5e": "v5e",
    "TPU v5 lite": "v5 lite",
    "TPU v5p": "v5p",
    "TPU v6e": "v6e",
    "TPU v6 lite": "v6 lite",
}


def _as_local_tpu(monkeypatch, kind):
    """The first local device is a TPU of ``kind`` (the backend stays CPU)."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [SimpleNamespace(device_kind=kind)])


def _lookup_entry_points():
    from llmtrain_tpu.autotune.search import resolve_hbm_limit
    from llmtrain_tpu.telemetry.profiling import resolve_peaks
    from llmtrain_tpu.utils import hw

    return {
        "mfu": lambda kind: hw.peak_flops_per_chip(),  # reads the local device
        "profile": resolve_peaks,
        "tune": resolve_hbm_limit,
    }


class TestDeviceTable:
    @pytest.mark.parametrize("kind", sorted(_TPU_KINDS))
    def test_every_entry_point_reads_the_same_row(self, monkeypatch, kind):
        from llmtrain_tpu.utils import hw

        row = hw.DEVICE_TABLE[_TPU_KINDS[kind]]
        assert hw.device_row(kind) is row
        _as_local_tpu(monkeypatch, kind)
        entry = _lookup_entry_points()
        assert entry["mfu"](kind) == row["peak_flops"]
        peaks = entry["profile"](kind)
        assert peaks == {
            "peak_flops": row["peak_flops"],
            "hbm_bytes_per_sec": row["hbm_bytes_per_sec"],
            "ici_bytes_per_sec": row["ici_bytes_per_sec"],
            "device_kind": kind.lower(),
        }
        assert entry["profile"](None) == peaks
        assert entry["tune"](kind) == row["hbm_bytes"]
        assert row["source"]

    def test_longest_key_wins(self, monkeypatch):
        from llmtrain_tpu.utils import hw

        shorter = dict(hw.DEVICE_TABLE["cpu"], source="a shorter key that also matches")
        monkeypatch.setitem(hw.DEVICE_TABLE, "v5", shorter)
        assert hw.device_row("TPU v5 lite") is hw.DEVICE_TABLE["v5 lite"]
        assert hw.device_row("TPU v5") is shorter

    @pytest.mark.parametrize("entry", ["mfu", "profile", "tune"])
    def test_unknown_tpu_kind_raises(self, monkeypatch, entry):
        _as_local_tpu(monkeypatch, "TPU v9")
        with pytest.raises(ValueError, match=r"TPU v9.*utils/hw\.py DEVICE_TABLE"):
            _lookup_entry_points()[entry]("TPU v9")

    @pytest.mark.parametrize("kind", ["cpu", "AMD EPYC 7B13", "NVIDIA A100-SXM4-40GB"])
    def test_a_kind_that_is_no_tpu_takes_the_cpu_row(self, kind):
        from llmtrain_tpu.utils import hw

        assert hw.device_row(kind) is hw.DEVICE_TABLE["cpu"]
        entry = _lookup_entry_points()
        assert entry["profile"](kind)["peak_flops"] == hw.DEVICE_TABLE["cpu"]["peak_flops"]
        assert entry["tune"](kind) == hw.DEVICE_TABLE["cpu"]["hbm_bytes"]

    def test_v5_lite_row_equals_the_benchmarks_table(self):
        """The program's table and the benchmark's stay two files by the
        benchmark's rule (no PR but a ``benchmark`` one edits it); the
        ledger's MFU rests on the benchmark's, so they must not disagree."""
        import importlib.util
        from pathlib import Path

        from llmtrain_tpu.utils import hw

        path = Path(__file__).resolve().parents[1] / "benchmarks" / "lib" / "peaks.py"
        spec = importlib.util.spec_from_file_location("_bench_peaks", path)
        peaks = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(peaks)
        theirs = peaks.peaks_for("TPU v5 lite")
        ours = hw.device_row("TPU v5 lite")
        assert ours["peak_flops"] == theirs["bf16_flops_per_s"]
        assert ours["hbm_bytes_per_sec"] == theirs["hbm_bytes_per_s"]
        assert ours["hbm_bytes"] == theirs["hbm_bytes"]
        assert ours["ici_bytes_per_sec"] == theirs["ici_bits_per_s"] / 8

"""run_dir / metadata / logging / summary tests."""

import json
import logging

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
    assert hw.peak_flops_per_chip() == hw.CPU_NOMINAL_FLOPS


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

    def test_headline_run_mfu_reproduces(self):
        """An older hand-taken figure as arithmetic cross-check: the 85.6M byte-level
        GPT at the measured 165.8k tokens/s gives the recorded 0.48 MFU on
        v5e peak."""
        from llmtrain_tpu.utils.hw import TPU_PEAK_FLOPS, mfu

        got = mfu(
            165_800,
            n_params=85_600_000,
            n_layers=12,
            seq_len=512,
            d_model=768,
            peak_flops=TPU_PEAK_FLOPS["v5e"],
        )
        assert abs(got - 0.48) < 0.01

    def test_peak_lookup_defaults_cpu(self):
        from llmtrain_tpu.utils.hw import CPU_NOMINAL_FLOPS, peak_flops_per_chip

        # conftest pins the CPU backend, so the nominal figure applies.
        assert peak_flops_per_chip() == CPU_NOMINAL_FLOPS

"""CLI integration tests (parity with reference tests/test_cli.py):
subcommands as subprocesses asserting exit codes, stdout JSON schema, and
artifacts on disk; resume via run dir and explicit ckpt path."""

import json
import os
import subprocess
import sys

import pytest
import yaml

CFG = {
    "schema_version": 1,
    "run": {"name": "cli-test", "seed": 5, "device": "cpu", "deterministic": True},
    "model": {
        "name": "dummy_gpt",
        "block_size": 8,
        "d_model": 48,
        "n_layers": 1,
        "n_heads": 2,
        "d_ff": 96,
        "dropout": 0.0,
        "vocab_size": 32,
    },
    "data": {"name": "dummy_text"},
    "trainer": {
        "max_steps": 6,
        "micro_batch_size": 2,
        "grad_accum_steps": 1,
        "lr": 0.003,
        "warmup_steps": 0,
        "log_every_steps": 3,
        "eval_every_steps": 3,
        "save_every_steps": 3,
    },
    "mlflow": {"enabled": False},
    "logging": {"level": "INFO", "json_output": True, "log_to_file": True},
    "output": {"root_dir": "runs"},
    # These tests pin CLI behavior; the end-of-fit cost-attribution lower
    # has its own e2e (test_profiling.py) and would add ~0.8s of cold
    # trace per train subprocess here.
    "telemetry": {"perf_attribution": False},
}


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    return env


def _run(args, cwd):
    return subprocess.run(
        [sys.executable, "-m", "llmtrain_tpu", *args],
        capture_output=True,
        text=True,
        cwd=cwd,
        env=_env(),
        timeout=420,
    )


@pytest.fixture()
def workdir(tmp_path):
    cfg_path = tmp_path / "config.yaml"
    cfg_path.write_text(yaml.safe_dump(CFG))
    return tmp_path


class TestValidate:
    def test_valid(self, workdir):
        proc = _run(["validate", "--config", "config.yaml"], workdir)
        assert proc.returncode == 0
        assert "succeeded" in proc.stdout

    def test_invalid_exit_2_with_json_stderr(self, workdir):
        (workdir / "bad.yaml").write_text(yaml.safe_dump({**CFG, "bogus": 1}))
        proc = _run(["validate", "--config", "bad.yaml"], workdir)
        assert proc.returncode == 2
        err = json.loads(proc.stderr.strip().splitlines()[-1])
        assert "error" in err and err["errors"]

    def test_missing_file_exit_2(self, workdir):
        proc = _run(["validate", "--config", "nope.yaml"], workdir)
        assert proc.returncode == 2


class TestPrintConfig:
    def test_yaml_defaults_materialized(self, workdir):
        proc = _run(["print-config", "--config", "config.yaml"], workdir)
        assert proc.returncode == 0
        resolved = yaml.safe_load(proc.stdout)
        assert resolved["trainer"]["weight_decay"] == 0.1
        assert resolved["distributed"]["mesh"]["data"] == -1

    def test_json(self, workdir):
        proc = _run(["print-config", "--config", "config.yaml", "--json"], workdir)
        assert proc.returncode == 0
        assert json.loads(proc.stdout)["run"]["name"] == "cli-test"


class TestTrain:
    def test_full_train_json_summary_and_artifacts(self, workdir):
        proc = _run(
            ["train", "--config", "config.yaml", "--json", "--run-id", "run1"], workdir
        )
        assert proc.returncode == 0, proc.stderr
        summary = json.loads(proc.stdout)
        tr = summary["train_result"]
        assert tr["final_step"] == 6
        assert tr["final_loss"] > 0 and tr["first_step_loss"] > 0
        assert tr["parameter_count"] > 0
        assert summary["run_id"] == "run1"

        run_dir = workdir / "runs" / "run1"
        assert (run_dir / "config.yaml").is_file()
        assert (run_dir / "meta.json").is_file()
        assert (run_dir / "logs" / "train.log").is_file()
        ckpts = sorted(p.name for p in (run_dir / "checkpoints").glob("step_*.ckpt"))
        assert ckpts == ["step_000003.ckpt", "step_000006.ckpt"]
        # Each checkpoint ships with its sha-256 integrity sidecar.
        sidecars = sorted(p.name for p in (run_dir / "checkpoints").glob("*.sha256"))
        assert sidecars == [n + ".sha256" for n in ckpts]
        # --json keeps stdout pure JSON; logs went to stderr/file
        assert proc.stdout.strip().startswith("{")

    def test_dry_run(self, workdir):
        proc = _run(["train", "--config", "config.yaml", "--dry-run", "--json"], workdir)
        assert proc.returncode == 0, proc.stderr
        summary = json.loads(proc.stdout)
        assert summary["dry_run"] is True
        assert summary["dry_run_resolution"]["steps_executed"] == 5
        assert summary["dry_run_resolution"]["model_adapter"] == "dummy_gpt"

    def test_resume_by_run_dir(self, workdir):
        first = _run(["train", "--config", "config.yaml", "--json", "--run-id", "runA"], workdir)
        assert first.returncode == 0, first.stderr
        second = _run(
            [
                "train",
                "--config",
                "config.yaml",
                "--json",
                "--run-id",
                "runB",
                "--resume",
                str(workdir / "runs" / "runA" / "checkpoints" / "step_000003.ckpt"),
            ],
            workdir,
        )
        assert second.returncode == 0, second.stderr
        tr = json.loads(second.stdout)["train_result"]
        assert tr["resumed_from_step"] == 3

    def test_auto_resume_fresh_then_continue(self, workdir):
        short = {**CFG, "trainer": {**CFG["trainer"], "max_steps": 3}}
        (workdir / "short.yaml").write_text(yaml.safe_dump(short))
        first = _run(
            ["train", "--config", "short.yaml", "--json", "--run-id", "runAR", "--auto-resume"],
            workdir,
        )
        assert first.returncode == 0, first.stderr
        tr1 = json.loads(first.stdout)["train_result"]
        assert tr1["resumed_from_step"] is None and tr1["final_step"] == 3

        # Simulated preemption restart with a longer schedule: same run id,
        # dir already exists, training continues from the checkpoint.
        second = _run(
            ["train", "--config", "config.yaml", "--json", "--run-id", "runAR", "--auto-resume"],
            workdir,
        )
        assert second.returncode == 0, second.stderr
        tr2 = json.loads(second.stdout)["train_result"]
        assert tr2["resumed_from_step"] == 3
        assert tr2["final_step"] == 6

    def test_auto_resume_requires_run_id(self, workdir):
        proc = _run(["train", "--config", "config.yaml", "--auto-resume"], workdir)
        assert proc.returncode == 2
        assert "stable run id" in proc.stderr

    def test_auto_resume_excludes_resume(self, workdir):
        proc = _run(
            ["train", "--config", "config.yaml", "--auto-resume", "--resume", "x"],
            workdir,
        )
        assert proc.returncode == 2  # argparse mutual exclusion

    def test_unknown_adapter_exit_2(self, workdir):
        bad = {**CFG, "model": {**CFG["model"], "name": "nonexistent"}}
        (workdir / "bad.yaml").write_text(yaml.safe_dump(bad))
        proc = _run(["train", "--config", "bad.yaml"], workdir)
        assert proc.returncode == 2
        assert "nonexistent" in proc.stderr

    def test_train_failure_exit_1(self, workdir):
        bad = {**CFG, "trainer": {**CFG["trainer"], "max_steps": 6}}
        bad["data"] = {"name": "hf_text"}  # no dataset_name -> setup raises
        (workdir / "bad.yaml").write_text(yaml.safe_dump(bad))
        proc = _run(["train", "--config", "bad.yaml", "--json"], workdir)
        assert proc.returncode == 1
        err = json.loads(proc.stderr.strip().splitlines()[-1])
        assert "training failed" in err["error"]


class TestAverageCheckpoints:
    def test_soup_is_the_uniform_average_and_resumable(self, workdir):
        """average-checkpoints writes the exact param mean of the inputs
        as a standard resumable step-0 checkpoint."""
        import numpy as np

        first = _run(["train", "--config", "config.yaml", "--json",
                      "--run-id", "runAV"], workdir)
        assert first.returncode == 0, first.stderr
        ckpt_dir = workdir / "runs" / "runAV" / "checkpoints"
        files = sorted(ckpt_dir.glob("step_*.ckpt"))
        assert len(files) >= 2

        proc = _run(
            ["average-checkpoints", "--config", "config.yaml", "--inputs",
             str(ckpt_dir), "--last-k", "2", "--output", "soup", "--json"],
            workdir,
        )
        assert proc.returncode == 0, proc.stderr
        out = json.loads(proc.stdout)
        assert len(out["inputs"]) == 2

        from flax import serialization

        def params_of(path):
            payload = serialization.msgpack_restore(path.read_bytes())
            return payload["params"]

        import jax

        a, b = params_of(files[-2]), params_of(files[-1])
        soup = params_of(workdir / "soup" / "step_000000.ckpt")
        want = jax.tree.map(lambda x, y: (np.asarray(x, np.float64) + y) / 2, a, b)
        for got, exp in zip(jax.tree.leaves(soup), jax.tree.leaves(want)):
            np.testing.assert_allclose(
                np.asarray(got, np.float64), exp, atol=1e-6
            )

        # The soup resumes/evals like any checkpoint.
        ev = _run(["eval", "--config", "config.yaml", "--from", "soup",
                   "--json"], workdir)
        assert ev.returncode == 0, ev.stderr
        assert np.isfinite(json.loads(ev.stdout)["metrics"]["val/loss"])

    def test_needs_two_inputs(self, workdir):
        proc = _run(
            ["average-checkpoints", "--config", "config.yaml", "--inputs",
             "onlyone", "--output", "soup2"],
            workdir,
        )
        assert proc.returncode == 2
        assert "at least 2" in proc.stderr


class TestGenerate:
    def test_generate_from_trained_run(self, workdir):
        first = _run(["train", "--config", "config.yaml", "--json", "--run-id", "runG"], workdir)
        assert first.returncode == 0, first.stderr
        proc = _run(
            [
                "generate",
                "--config",
                "config.yaml",
                "--from",
                "runG",
                "--prompt-ids",
                "1,2,3",
                "--max-new-tokens",
                "4",
                "--temperature",
                "0",
                "--json",
            ],
            workdir,
        )
        assert proc.returncode == 0, proc.stderr
        out = json.loads(proc.stdout)
        assert out["step"] == 6
        assert out["prompt_ids"] == [1, 2, 3]
        assert len(out["completion_ids"]) == 4  # newly generated only
        assert out["output_ids"] == out["prompt_ids"] + out["completion_ids"]
        assert all(0 <= t < CFG["model"]["vocab_size"] for t in out["output_ids"])
        # dummy adapter has no tokenizer -> no decoded text
        assert out["text"] is None

    def test_generate_quantized_int8(self, workdir):
        """--quantize int8 decodes on QuantizedArray weights end to end
        (ops/quant.py): same output contract, valid token range."""
        first = _run(["train", "--config", "config.yaml", "--json",
                      "--run-id", "runQ"], workdir)
        assert first.returncode == 0, first.stderr
        proc = _run(
            ["generate", "--config", "config.yaml", "--from", "runQ",
             "--prompt-ids", "1,2,3", "--max-new-tokens", "4",
             "--temperature", "0", "--quantize", "int8", "--json"],
            workdir,
        )
        assert proc.returncode == 0, proc.stderr
        out = json.loads(proc.stdout)
        assert len(out["completion_ids"]) == 4
        assert all(0 <= t < CFG["model"]["vocab_size"] for t in out["output_ids"])

    def test_generate_greedy_is_deterministic(self, workdir):
        first = _run(["train", "--config", "config.yaml", "--json", "--run-id", "runH"], workdir)
        assert first.returncode == 0, first.stderr
        args = [
            "generate",
            "--config",
            "config.yaml",
            "--from",
            str(workdir / "runs" / "runH" / "checkpoints"),
            "--prompt-ids",
            "5,6",
            "--max-new-tokens",
            "3",
            "--temperature",
            "0",
            "--json",
        ]
        a, b = _run(args, workdir), _run(args, workdir)
        assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
        assert json.loads(a.stdout)["completion_ids"] == json.loads(b.stdout)["completion_ids"]

    def test_decode_param_dtype_cast_and_optout(self, workdir):
        """bf16-compute models decode from bf16 weights by default (half the
        weight bandwidth); --decode-param-
        dtype param keeps the checkpoint's f32 master params."""
        cfg = {
            **CFG,
            "model": {
                "name": "gpt",
                "block_size": 8,
                "d_model": 32,
                "n_layers": 1,
                "n_heads": 2,
                "d_ff": 64,
                "dropout": 0.0,
                "vocab_size": 64,
                "dtype": "bfloat16",
                "param_dtype": "float32",
                "extra": {"tokenizer": "byte"},
            },
        }
        (workdir / "bf16.yaml").write_text(yaml.safe_dump(cfg))
        first = _run(
            ["train", "--config", "bf16.yaml", "--json", "--run-id", "runDD"],
            workdir,
        )
        assert first.returncode == 0, first.stderr
        base = [
            "generate", "--config", "bf16.yaml", "--from", "runDD",
            "--prompt-ids", "1,2", "--max-new-tokens", "3",
            "--temperature", "0", "--json",
        ]
        cast = _run(base, workdir)
        assert cast.returncode == 0, cast.stderr
        assert "cast floating params to bfloat16" in cast.stderr
        kept = _run([*base, "--decode-param-dtype", "param"], workdir)
        assert kept.returncode == 0, kept.stderr
        assert "cast floating params" not in kept.stderr
        # Both modes produce a full-length completion from the same ckpt.
        for proc in (cast, kept):
            assert len(json.loads(proc.stdout)["completion_ids"]) == 3

    @pytest.mark.slow  # budget: tier-1 siblings test_generate_greedy_is_deterministic + test_speculative greedy exactness
    def test_speculative_generate_matches_plain_greedy(self, workdir):
        """--draft-config/--draft-from: greedy speculative output through
        the CLI is bit-identical to the plain greedy path."""
        tgt = {
            **CFG,
            "model": {
                "name": "gpt", "block_size": 32, "d_model": 32,
                "n_layers": 2, "n_heads": 2, "d_ff": 64, "dropout": 0.0,
                "vocab_size": 32,
            },
        }
        drf = {**tgt, "model": {**tgt["model"], "n_layers": 1, "d_model": 16,
                                "d_ff": 32}}
        (workdir / "tgt.yaml").write_text(yaml.safe_dump(tgt))
        (workdir / "drf.yaml").write_text(yaml.safe_dump(drf))
        for cfg_name, rid in (("tgt.yaml", "runT"), ("drf.yaml", "runD")):
            proc = _run(["train", "--config", cfg_name, "--json",
                         "--run-id", rid], workdir)
            assert proc.returncode == 0, proc.stderr
        base = ["generate", "--config", "tgt.yaml", "--from", "runT",
                "--prompt-ids", "1,2,3", "--max-new-tokens", "8",
                "--temperature", "0", "--json"]
        plain = _run(base, workdir)
        assert plain.returncode == 0, plain.stderr
        spec = _run([*base, "--draft-config", "drf.yaml", "--draft-from",
                     "runD", "--gamma", "3"], workdir)
        assert spec.returncode == 0, spec.stderr
        assert (
            json.loads(spec.stdout)["completion_ids"]
            == json.loads(plain.stdout)["completion_ids"]
        )

    def test_generate_logprobs(self, workdir):
        first = _run(["train", "--config", "config.yaml", "--json",
                      "--run-id", "runLP"], workdir)
        assert first.returncode == 0, first.stderr
        proc = _run(
            ["generate", "--config", "config.yaml", "--from", "runLP",
             "--prompt-ids", "1,2", "--max-new-tokens", "4",
             "--temperature", "0", "--logprobs", "--json"],
            workdir,
        )
        assert proc.returncode == 0, proc.stderr
        out = json.loads(proc.stdout)
        assert len(out["logprobs"]) == 4
        assert all(lp <= 0.0 for lp in out["logprobs"])

    def test_logprobs_rejected_with_speculative(self, workdir):
        proc = _run(
            ["generate", "--config", "config.yaml", "--from", "x",
             "--prompt-ids", "1", "--logprobs", "--draft-config",
             "config.yaml", "--draft-from", "y"],
            workdir,
        )
        assert proc.returncode == 2
        assert "logprobs" in proc.stderr

    @pytest.mark.slow  # ~14s: CLI speculative parity stays tier-1 via
    # test_speculative_generate_matches_plain_greedy; this adds only the
    # prompts-file/length-group dimension on top of the same path.
    def test_speculative_prompts_file_matches_plain(self, workdir):
        """The per-row speculative loop over a prompts file (different
        prompt lengths → separate length groups) matches the plain
        batched path's completions exactly."""
        tgt = {
            **CFG,
            "model": {
                "name": "gpt", "block_size": 32, "d_model": 32,
                "n_layers": 2, "n_heads": 2, "d_ff": 64, "dropout": 0.0,
                "vocab_size": 257, "extra": {"tokenizer": "byte"},
            },
        }
        drf = {**tgt, "model": {**tgt["model"], "n_layers": 1}}
        (workdir / "tgt.yaml").write_text(yaml.safe_dump(tgt))
        (workdir / "drf.yaml").write_text(yaml.safe_dump(drf))
        for cfg_name, rid in (("tgt.yaml", "runPT"), ("drf.yaml", "runPD")):
            proc = _run(["train", "--config", cfg_name, "--json",
                         "--run-id", rid], workdir)
            assert proc.returncode == 0, proc.stderr
        (workdir / "prompts.txt").write_text("hello\nworld wide\n")
        base = ["generate", "--config", "tgt.yaml", "--from", "runPT",
                "--prompts-file", "prompts.txt", "--max-new-tokens", "5",
                "--temperature", "0", "--json"]
        plain = _run(base, workdir)
        assert plain.returncode == 0, plain.stderr
        spec = _run([*base, "--draft-config", "drf.yaml", "--draft-from",
                     "runPD", "--gamma", "2"], workdir)
        assert spec.returncode == 0, spec.stderr
        p_res = json.loads(plain.stdout)["results"]
        s_res = json.loads(spec.stdout)["results"]
        assert [r["completion_ids"] for r in p_res] == [
            r["completion_ids"] for r in s_res
        ]

    def test_speculative_flags_must_pair(self, workdir):
        proc = _run(
            ["generate", "--config", "config.yaml", "--from", "nope",
             "--prompt-ids", "1", "--draft-config", "config.yaml"],
            workdir,
        )
        assert proc.returncode == 2
        assert "together" in proc.stderr

    def test_generate_eos_token_stops_early(self, workdir):
        """--eos-token-id is wired through to generate(): once the EOS token
        is produced, the rest of the completion is EOS-filled (ADVICE r1)."""
        first = _run(["train", "--config", "config.yaml", "--json", "--run-id", "runE"], workdir)
        assert first.returncode == 0, first.stderr
        base = [
            "generate",
            "--config",
            "config.yaml",
            "--from",
            "runE",
            "--prompt-ids",
            "1,2,3",
            "--max-new-tokens",
            "5",
            "--temperature",
            "0",
            "--json",
        ]
        plain = _run(base, workdir)
        assert plain.returncode == 0, plain.stderr
        eos = json.loads(plain.stdout)["completion_ids"][0]
        stopped = _run(base + ["--eos-token-id", str(eos)], workdir)
        assert stopped.returncode == 0, stopped.stderr
        completion = json.loads(stopped.stdout)["completion_ids"]
        # Greedy decode reproduces the same first token, which is now EOS;
        # every subsequent slot must be EOS-filled.
        assert completion[0] == eos
        assert all(t == eos for t in completion)

    def test_generate_missing_checkpoint_exit_1(self, workdir):
        proc = _run(
            [
                "generate",
                "--config",
                "config.yaml",
                "--from",
                "no-such-run",
                "--prompt-ids",
                "1",
            ],
            workdir,
        )
        assert proc.returncode == 1
        assert "generation failed" in proc.stderr

    def test_generate_prompt_without_tokenizer_exit_1(self, workdir):
        first = _run(["train", "--config", "config.yaml", "--json", "--run-id", "runI"], workdir)
        assert first.returncode == 0, first.stderr
        proc = _run(
            ["generate", "--config", "config.yaml", "--from", "runI", "--prompt", "hi"],
            workdir,
        )
        assert proc.returncode == 1
        assert "prompt-ids" in proc.stderr


class TestPresets:
    def test_all_presets_validate(self, workdir):
        import pathlib

        presets = pathlib.Path(__file__).resolve().parent.parent / "configs" / "presets"
        assert presets.is_dir()
        paths = [str(p) for p in sorted(presets.glob("*.yaml"))]
        assert paths
        # One subprocess for ALL presets: each `validate` still goes
        # through the real CLI entrypoint (argparse, exit codes), but the
        # interpreter + jax import cost is paid once, not per preset —
        # at ~0.75s a spawn, per-preset subprocesses were >20s of tier-1.
        driver = (
            "import sys\n"
            "from llmtrain_tpu.cli import main\n"
            "bad = [p for p in sys.argv[1:]\n"
            "       if main(['validate', '--config', p]) != 0]\n"
            "print('INVALID PRESETS:', bad)\n"
            "sys.exit(1 if bad else 0)\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", driver, *paths],
            capture_output=True,
            text=True,
            cwd=workdir,
            env=_env(),
            timeout=420,
        )
        assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"

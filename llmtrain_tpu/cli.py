"""Command-line interface.

Parity target: reference ``src/llmtrain/cli.py`` — argparse CLI with
``train``/``validate``/``print-config`` subcommands (:145-157), required
``--config``, train-only ``--run-id``/``--dry-run``/``--json``/``-v``/
``--resume`` (:147-151), exit codes 0/1 (training failure, :304)/2 (config
error, :167), JSON errors to stderr (:63-76), and the train orchestration:
distributed setup → run dir → logging → registries → tracker → Trainer/dry
run → summary → artifact logging → teardown in ``finally`` (:201-328).
Under ``--json``, logs go to stderr so stdout carries only the summary JSON
(:281-288).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Any

from . import __version__
from .config import ConfigLoadError, load_and_validate_config
from .distributed import (
    DistState,
    configure_compilation_cache,
    configure_platform,
    setup_distributed,
    teardown_distributed,
)
from .registry import (
    RegistryError,
    get_data_module,
    get_model_adapter,
    initialize_registries,
)
from .resilience.exit_codes import (
    EXIT_CONFIG_ERROR,
    EXIT_OK,
    EXIT_RETRYABLE_INFRA,
    EXIT_TRAIN_FAILURE,
    exit_code_for_exception,
)
from .tracking import NullTracker, Tracker, build_tracker
from .utils import (
    configure_logging,
    create_run_directory,
    format_run_summary,
    generate_meta,
    generate_run_id,
    get_logger,
    write_meta_json,
    write_resolved_config,
)

# Exit codes come from the taxonomy module (resilience/exit_codes.py):
# 0 clean, 1 fatal training, 2 fatal config, 75/76 retryable infra/hang.
# The names are re-exported here so `cli.EXIT_*` keeps working.


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="llmtrain",
        description="TPU-native config-driven LLM training",
    )
    parser.add_argument("--version", action="version", version=__version__)
    sub = parser.add_subparsers(dest="command", required=True)

    train = sub.add_parser("train", help="run a training job")
    train.add_argument("--config", required=True, help="path to the YAML run config")
    train.add_argument("--run-id", default=None, help="override the generated run id")
    train.add_argument("--dry-run", action="store_true", help="forward-only sanity check")
    train.add_argument("--json", action="store_true", help="emit the run summary as JSON")
    train.add_argument("-v", "--verbose", action="store_true", help="DEBUG logging")
    resume_group = train.add_mutually_exclusive_group()
    resume_group.add_argument(
        "--resume",
        default=None,
        help="checkpoint file, checkpoint dir, or run id to resume from",
    )
    resume_group.add_argument(
        "--auto-resume",
        action="store_true",
        help=(
            "reuse the run dir for --run-id if it exists and resume from its "
            "latest checkpoint (fresh start otherwise); for preemptible pods"
        ),
    )

    gen = sub.add_parser(
        "generate", help="sample completions from a trained checkpoint"
    )
    gen.add_argument("--config", required=True, help="path to the YAML run config")
    gen.add_argument(
        "--from",
        dest="from_spec",
        required=True,
        help="checkpoint file, checkpoint dir, or run id to load params from",
    )
    prompt_group = gen.add_mutually_exclusive_group(required=True)
    prompt_group.add_argument("--prompt", default=None, help="prompt text (needs a tokenizer)")
    prompt_group.add_argument(
        "--prompt-ids",
        default=None,
        help="comma-separated token ids, bypassing the tokenizer",
    )
    prompt_group.add_argument(
        "--prompts-file",
        default=None,
        help="file with one prompt per line (blank lines skipped); prompts "
        "are batched per token length for the compiled decode loop",
    )
    gen.add_argument("--max-new-tokens", type=int, default=48)
    gen.add_argument(
        "--temperature", type=float, default=0.8, help="0 decodes greedily"
    )
    gen.add_argument("--top-k", type=int, default=40, help="0 disables top-k filtering")
    gen.add_argument(
        "--top-p",
        type=float,
        default=None,
        help="nucleus sampling: keep the smallest token set with this "
        "probability mass, 0 < p < 1 (0 or 1 disables, like --top-k 0)",
    )
    gen.add_argument(
        "--eos-token-id",
        type=int,
        default=None,
        help="stop early on this token (default: the tokenizer's EOS, if any)",
    )
    gen.add_argument("--seed", type=int, default=1234)
    gen.add_argument(
        "--decode-param-dtype",
        choices=("compute", "param"),
        default="compute",
        help="'compute' (default) casts floating checkpoint params to the "
        "model compute dtype before decoding — a bf16-compute model then "
        "streams half the weight bytes per token (decode is weight-bandwidth "
        "bound); 'param' keeps the "
        "checkpoint's master precision",
    )
    gen.add_argument(
        "--draft-config",
        default=None,
        help="YAML config of a DRAFT model for speculative decoding "
        "(requires --draft-from; same tokenizer/vocab as the target)",
    )
    gen.add_argument(
        "--draft-from",
        default=None,
        help="checkpoint file, dir, or run id for the draft model's params",
    )
    gen.add_argument(
        "--gamma",
        type=int,
        default=4,
        help="speculative lookahead: draft tokens proposed per target forward",
    )
    gen.add_argument(
        "--logprobs",
        action="store_true",
        help="include the model's log-probability of every emitted token "
        "in the JSON output (not supported with --draft-config)",
    )
    gen.add_argument(
        "--ema",
        action="store_true",
        help="decode with the EMA shadow weights tracked by "
        "trainer.extra.ema_decay (errors if the checkpoint has none)",
    )
    gen.add_argument(
        "--quantize",
        choices=("none", "int8"),
        default="none",
        help="weight-only quantization applied after checkpoint load "
        "(ops/quant.py): int8 halves the weight bytes each decoded token "
        "streams vs bf16 (decode is weight-bandwidth bound); applies to "
        "the draft model too under speculative decoding",
    )
    gen.add_argument("--json", action="store_true", help="emit the result as JSON")

    serve = sub.add_parser(
        "serve",
        help="HTTP inference server over the compiled decode loop "
        "(GET /healthz, POST /v1/generate)",
    )
    serve.add_argument("--config", required=True, help="path to the YAML run config")
    serve.add_argument(
        "--from",
        dest="from_spec",
        required=True,
        help="checkpoint file, checkpoint dir, or run id to serve",
    )
    serve.add_argument("--host", default="127.0.0.1")
    serve.add_argument(
        "--port",
        type=int,
        default=8000,
        help="0 binds an ephemeral port (printed on the ready line)",
    )
    serve.add_argument(
        "--max-new-tokens-cap",
        type=int,
        default=None,
        help="upper bound a request's max_new_tokens may ask for "
        "(default: serving.max_new_tokens_cap from the config)",
    )
    serve.add_argument(
        "--mode",
        choices=("simple", "continuous"),
        default=None,
        help="override serving.mode: 'simple' = one decode at a time "
        "behind the device lock; 'continuous' = paged-KV continuous "
        "batching (N in-flight sequences share one jitted program)",
    )
    serve.add_argument(
        "--draft-config",
        default=None,
        help="YAML config of a DRAFT model: switches the continuous "
        "scheduler to the speculative policy (requires --draft-from)",
    )
    serve.add_argument(
        "--draft-from",
        default=None,
        help="checkpoint file, dir, or run id for the draft model's params",
    )
    serve.add_argument(
        "--gamma",
        type=int,
        default=None,
        help="speculative lookahead (default: serving.speculative_gamma)",
    )
    serve.add_argument(
        "--decode-param-dtype",
        choices=("compute", "param"),
        default="compute",
        help="as in generate: 'compute' streams half the weight bytes "
        "per token for bf16-compute models",
    )
    serve.add_argument(
        "--ema",
        action="store_true",
        help="serve the EMA shadow weights (errors if the checkpoint has none)",
    )
    serve.add_argument(
        "--quantize",
        choices=("none", "int8"),
        default="none",
        help="serve weight-only int8 quantized weights (ops/quant.py)",
    )
    serve.add_argument(
        "--eos-token-id",
        type=int,
        default=None,
        help="default stop token (requests may override; default: the "
        "tokenizer's EOS, if any)",
    )
    serve.add_argument(
        "--router",
        action="store_true",
        help="run the fleet tier: a replica router placing each request "
        "by prefix-cache affinity and load, with rolling zero-downtime "
        "POST /reload (needs the continuous backend)",
    )
    serve.add_argument(
        "--replicas",
        type=int,
        default=None,
        help="in-process replica count for --router "
        "(default: serving.router.replicas)",
    )
    serve.add_argument(
        "--backends",
        default=None,
        help="comma-separated replica base URLs (http://host:port) — "
        "route across separate serve processes instead of in-process "
        "replicas (implies --router)",
    )
    serve.add_argument(
        "--discover",
        default=None,
        help="host[:port] DNS-resolved into one HTTP backend per A "
        "record (k8s headless Service discovery; implies --router)",
    )
    serve.add_argument(
        "--trace-dir",
        default=None,
        help="write per-process timeline.jsonl files (with tail-sampled "
        "request traces) plus a metrics.prom textfile snapshot under "
        "this dir, for `llmtrain trace` to merge; with --router each "
        "in-process replica gets its own subdir",
    )

    promote = sub.add_parser(
        "promote",
        help="continuous train→canary→promote lifecycle: watch a training "
        "run's manifest stream, canary each new commit on one replica, "
        "score it (eval loss + TTFT/per-token SLO soak), then promote "
        "fleet-wide or auto-roll-back (lifecycle/, docs/robustness.md "
        "'Canary, promote, rollback')",
    )
    promote.add_argument(
        "--config", required=True, help="path to the YAML run config"
    )
    promote.add_argument(
        "--watch",
        required=True,
        help="training run dir (or its checkpoints/ dir) whose manifest "
        "stream to watch; promotions.jsonl is written next to the run's "
        "other durable artifacts",
    )
    promote.add_argument(
        "--from",
        dest="from_spec",
        default=None,
        help="initial baseline checkpoint to serve (default: the last "
        "promoted entry in promotions.jsonl, else the stream's newest "
        "commit — promote waits for the first one if needed)",
    )
    promote.add_argument(
        "--replicas",
        type=int,
        default=None,
        help="in-process fleet size (default: serving.router.replicas)",
    )
    promote.add_argument(
        "--max-promotions",
        type=int,
        default=None,
        help="stop after this many promotions (default: promote.max_promotions; "
        "0 = run until the stream ends)",
    )
    promote.add_argument(
        "--idle-timeout-sec",
        type=float,
        default=None,
        help="exit after this long with no new commit and no training "
        "heartbeat (default: promote.idle_timeout_sec)",
    )
    promote.add_argument(
        "--no-eval",
        action="store_true",
        help="skip the held-out eval-loss gate (soak/SLO gates still run)",
    )
    promote.add_argument("--json", action="store_true", help="emit the result as JSON")
    # Decode-stack flags shared with serve's loaders (promote keeps the
    # defaults; the flags exist so _load_decode_params is reused as-is).
    promote.set_defaults(
        draft_config=None,
        draft_from=None,
        gamma=None,
        backends=None,
        discover=None,
        decode_param_dtype="compute",
        quantize="none",
        ema=False,
        mode="continuous",
        router=True,
    )

    bench = sub.add_parser(
        "serve-bench",
        help="seeded open-loop load generator against the continuous-"
        "batching scheduler: p50/p95/p99 TTFT + per-token latency, "
        "tokens/s, occupancy, compile budget — written to report.json/"
        "report.md (docs/serving.md)",
    )
    bench.add_argument("--config", required=True, help="path to the YAML run config")
    bench.add_argument(
        "--from",
        dest="from_spec",
        required=True,
        help="checkpoint file, checkpoint dir, or run id to serve",
    )
    bench.add_argument(
        "--requests", type=int, default=16, help="request population size"
    )
    bench.add_argument(
        "--rate-rps",
        type=float,
        default=8.0,
        help="open-loop Poisson arrival rate (requests/second); arrivals "
        "never wait for completions",
    )
    bench.add_argument("--seed", type=int, default=1234)
    bench.add_argument(
        "--prompt-tokens-min", type=int, default=4, help="shortest prompt"
    )
    bench.add_argument(
        "--prompt-tokens-max",
        type=int,
        default=0,
        help="longest prompt (0 = derived: min(32, block_size - max_new))",
    )
    bench.add_argument("--max-new-tokens", type=int, default=16)
    bench.add_argument(
        "--temperature",
        type=float,
        default=0.0,
        help="0 = greedy (the regime the parity check pins)",
    )
    bench.add_argument("--top-k", type=int, default=None)
    bench.add_argument("--top-p", type=float, default=None)
    bench.add_argument(
        "--timeout-sec",
        type=float,
        default=300.0,
        help="give up on unfinished requests after this long",
    )
    bench.add_argument(
        "--verify-parity",
        action="store_true",
        help="re-decode every request through sequential generate() and "
        "assert batched output token-ids are bitwise identical (exits "
        "nonzero on any mismatch)",
    )
    bench.add_argument(
        "--out",
        default=None,
        help="report directory (default: <output.root_dir>/serve_bench)",
    )
    bench.add_argument(
        "--decode-param-dtype",
        choices=("compute", "param"),
        default="compute",
        help="as in generate/serve",
    )
    bench.add_argument("--ema", action="store_true")
    bench.add_argument(
        "--quantize", choices=("none", "int8"), default="none",
        help="weight-only int8 quantization (ops/quant.py)",
    )
    bench.add_argument(
        "--draft-config",
        default=None,
        help="draft model config for the speculative scheduler policy",
    )
    bench.add_argument(
        "--draft-from", default=None, help="draft model checkpoint/run id"
    )
    bench.add_argument(
        "--gamma", type=int, default=None,
        help="speculative lookahead (default: serving.speculative_gamma)",
    )
    bench.add_argument(
        "--router",
        action="store_true",
        help="drive the replica-router tier instead of one scheduler "
        "(in-process replicas; the report gains fleet prefix hit rate "
        "and per-replica occupancy)",
    )
    bench.add_argument(
        "--replicas",
        type=int,
        default=None,
        help="in-process replica count for --router "
        "(default: serving.router.replicas)",
    )
    bench.add_argument(
        "--shared-prefix-tokens",
        type=int,
        default=0,
        help="prepend one of --shared-prefix-count fixed 'system "
        "prompts' of this many tokens to every request — the workload "
        "shared-prefix KV reuse and router affinity pay off on",
    )
    bench.add_argument(
        "--shared-prefix-count", type=int, default=1,
        help="distinct shared prefixes to draw from",
    )
    bench.add_argument(
        "--long-fraction",
        type=float,
        default=0.0,
        help="fraction of requests using --long-prompt-tokens prompts "
        "(the bimodal long/short mix chunked prefill exists for)",
    )
    bench.add_argument(
        "--long-prompt-tokens", type=int, default=0,
        help="prompt length of the long cohort",
    )
    bench.add_argument(
        "--max-per-token-p99-ms",
        type=float,
        default=None,
        help="fail the run if per-token p99 latency exceeds this bound "
        "(the head-of-line-blocking SLO chunked prefill protects)",
    )
    bench.add_argument(
        "--arrival",
        choices=("poisson", "burst"),
        default="poisson",
        help="arrival process: steady Poisson, or 'burst' (head/tail 20%% "
        "at --rate-rps, middle 60%% at rate * --burst-factor) — the "
        "seeded overload drill for admission control and brownout",
    )
    bench.add_argument(
        "--burst-factor",
        type=float,
        default=10.0,
        help="rate multiplier for the burst window of --arrival burst",
    )
    bench.add_argument(
        "--deadline-ms",
        type=float,
        default=None,
        help="stamp every request with this latency budget; the overload "
        "controller rejects/sheds requests that cannot meet it "
        "(needs serving.overload.enabled)",
    )
    bench.add_argument(
        "--batch-fraction",
        type=float,
        default=0.0,
        help="seeded fraction of requests submitted as priority=batch "
        "(the mixed-class workload the weighted dequeue serves)",
    )
    bench.add_argument(
        "--max-rejected-frac",
        type=float,
        default=None,
        help="fail the run if (rejected+shed)/submitted exceeds this "
        "bound — overload behavior gateable like parity",
    )

    evalp = sub.add_parser(
        "eval", help="run the validation loop on a checkpoint, no training"
    )
    evalp.add_argument("--config", required=True, help="path to the YAML run config")
    evalp.add_argument(
        "--from",
        dest="from_spec",
        default=None,
        help="checkpoint file, checkpoint dir, or run id to evaluate "
        "(default: the freshly initialized model)",
    )
    evalp.add_argument(
        "--ema",
        action="store_true",
        help="evaluate the EMA shadow weights tracked by "
        "trainer.extra.ema_decay (errors if the checkpoint has none)",
    )
    evalp.add_argument(
        "--quantize",
        choices=("none", "int8"),
        default="none",
        help="evaluate under weight-only int8 quantization (ops/quant.py) "
        "— measures the quality cost of the quantized serving path on "
        "the real validation split (composes with --ema)",
    )
    evalp.add_argument("--json", action="store_true", help="emit metrics as JSON")
    evalp.add_argument("-v", "--verbose", action="store_true", help="DEBUG logging")

    traintok = sub.add_parser(
        "train-tokenizer",
        help="train an offline byte-level BPE vocabulary on local text",
    )
    traintok.add_argument(
        "--input",
        required=True,
        action="append",
        help="text file or directory (repeatable); directories are read "
        "recursively for *.txt/*.md/*.py files",
    )
    traintok.add_argument("--vocab-size", type=int, default=8192)
    traintok.add_argument("--output", required=True, help="vocabulary JSON path")
    traintok.add_argument(
        "--max-bytes",
        type=int,
        default=64_000_000,
        help="cap on corpus bytes read for training",
    )
    traintok.add_argument("--json", action="store_true", help="emit stats as JSON")

    export = sub.add_parser(
        "export-checkpoint",
        help="export checkpoint weights as a torch state dict (gpt → "
        "reference GPT names, llama → HF LlamaForCausalLM names)",
    )
    export.add_argument("--config", required=True, help="path to the YAML run config")
    export.add_argument(
        "--from",
        dest="from_spec",
        required=True,
        help="checkpoint file, checkpoint dir, or run id to export",
    )
    export.add_argument("--output", required=True, help="output .pt path")
    export.add_argument(
        "--ema",
        action="store_true",
        help="export the EMA shadow weights tracked by "
        "trainer.extra.ema_decay (errors if the checkpoint has none)",
    )
    export.add_argument("--json", action="store_true", help="emit stats as JSON")

    imp = sub.add_parser(
        "import-checkpoint",
        help="build a resumable checkpoint from a torch state dict "
        "(gpt ← reference GPT names, llama ← HF LlamaForCausalLM names)",
    )
    imp.add_argument("--config", required=True, help="path to the YAML run config")
    imp.add_argument("--input", required=True, help="torch .pt state-dict path")
    imp.add_argument(
        "--output",
        required=True,
        help="checkpoint directory to write step_000000.ckpt into "
        "(use with train --resume <dir>)",
    )
    imp.add_argument("--json", action="store_true", help="emit stats as JSON")

    avg = sub.add_parser(
        "average-checkpoints",
        help="average the params of several checkpoints (model soup) into "
        "a resumable step-0 checkpoint",
    )
    avg.add_argument("--config", required=True, help="path to the YAML run config")
    avg.add_argument(
        "--inputs",
        required=True,
        help="comma-separated checkpoint files/dirs/run-ids (each resolved "
        "like --resume), OR one checkpoint dir with --last-k",
    )
    avg.add_argument(
        "--last-k",
        type=int,
        default=0,
        help="average the last K step_*.ckpt files of the single --inputs dir",
    )
    avg.add_argument(
        "--output",
        required=True,
        help="empty checkpoint directory to write step_000000.ckpt into",
    )
    avg.add_argument("--json", action="store_true", help="emit stats as JSON")

    fleet = sub.add_parser(
        "fleet",
        help="multi-tenant fleet supervisor: schedule fleet.tenants onto a "
        "bounded emulated device pool with preemption-aware scheduling, "
        "quotas, and the SIGTERM->SIGKILL escalation ladder (fleet/, "
        "docs/robustness.md)",
    )
    fleet.add_argument("--config", required=True, help="path to the YAML run config")
    fleet.add_argument(
        "--storm",
        action="store_true",
        help="run the seeded preemption-storm acceptance drill instead of "
        "a plain fleet run: capacity drop + seeded evictions + one "
        "mid-checkpoint kill, then per-tenant bitwise parity against "
        "uninterrupted references (fleet/chaos.py)",
    )
    fleet.add_argument(
        "--seed", type=int, default=0, help="seed for the storm schedule "
        "and the per-tenant respawn-backoff streams"
    )
    fleet.add_argument(
        "--max-steps",
        type=int,
        default=None,
        help="override trainer.max_steps for every tenant (keep it small)",
    )
    fleet.add_argument(
        "--save-every",
        type=int,
        default=None,
        help="override trainer.save_every_steps for every tenant",
    )
    fleet.add_argument(
        "--work-dir",
        default=None,
        help="supervisor working directory (default: "
        "{output.root_dir}/fleet_{run.name} or fleet_storm_{run.name}_s{seed})",
    )
    fleet.add_argument(
        "--timeout-sec",
        type=float,
        default=900.0,
        help="whole-fleet wall-clock budget",
    )
    fleet.add_argument(
        "--step-delay-sec",
        type=float,
        default=0.15,
        help="storm only: per-step tenant throttle so external evictions "
        "land mid-run (trainer.extra.step_delay_sec)",
    )
    fleet.add_argument(
        "--fresh",
        action="store_true",
        help="wipe the work dir's runs tree before starting (default: a "
        "restarted supervisor auto-resumes every tenant from its newest "
        "commit; --storm always starts fresh)",
    )
    fleet.add_argument("--json", action="store_true", help="emit the result as JSON")

    chaos = sub.add_parser(
        "chaos",
        help="seeded chaos-recovery drill: repeated SIGKILL/resume cycles "
        "with crash-consistency invariants checked after every cycle "
        "(resilience/chaos.py, docs/robustness.md)",
    )
    chaos.add_argument("--config", required=True, help="path to the YAML run config")
    chaos.add_argument(
        "--cycles",
        type=int,
        default=5,
        help="number of killed segments before the final uninterrupted one",
    )
    chaos.add_argument(
        "--seed", type=int, default=0, help="seed for the kill-step schedule"
    )
    chaos.add_argument(
        "--max-steps",
        type=int,
        default=None,
        help="override trainer.max_steps for the drill (keep it small)",
    )
    chaos.add_argument(
        "--save-every",
        type=int,
        default=None,
        help="override trainer.save_every_steps for the drill",
    )
    chaos.add_argument(
        "--work-dir",
        default=None,
        help="harness working directory (default: "
        "{output.root_dir}/chaos_{run.name}_s{seed})",
    )
    chaos.add_argument(
        "--timeout-sec",
        type=float,
        default=600.0,
        help="per-segment wall-clock budget",
    )
    chaos.add_argument("--json", action="store_true", help="emit the result as JSON")

    profile = sub.add_parser(
        "profile",
        help="N-step cost probe: XLA cost_analysis + roofline attribution "
        "of the jitted train step (and the paged serving buckets with "
        "--serve) written as profile_report.json "
        "(telemetry/profiling.py, docs/observability.md)",
    )
    profile.add_argument("--config", required=True, help="path to the YAML run config")
    profile.add_argument(
        "--steps",
        type=int,
        default=3,
        help="probe training steps to run for measured step time (default 3)",
    )
    profile.add_argument(
        "--serve",
        action="store_true",
        help="also AOT-profile the paged prefill/decode programs at their "
        "largest shape buckets (abstract shapes; no checkpoint needed)",
    )
    profile.add_argument(
        "--top-k",
        type=int,
        default=10,
        help="HLO op-category rows in each executable's top-ops table",
    )
    profile.add_argument(
        "--output",
        default=None,
        help="report path (default {output.root_dir}/profile_{run.name}/"
        "profile_report.json)",
    )
    profile.add_argument(
        "--json", action="store_true", help="print the full report JSON to stdout"
    )

    goodput = sub.add_parser(
        "goodput",
        help="render the wall-clock goodput ledger for any past run from "
        "its durable artifacts alone — no rerun, no live process "
        "(telemetry/goodput.py, docs/observability.md 'Goodput')",
    )
    goodput.add_argument(
        "--run-dir",
        required=True,
        help="run directory holding telemetry/timeline.jsonl (+ optional "
        "checkpoints/ and heartbeat)",
    )
    goodput.add_argument(
        "--json", action="store_true", help="emit the ledger as JSON"
    )

    trace = sub.add_parser(
        "trace",
        help="merge per-process fleet timelines and reassemble cross-"
        "process request traces (telemetry/trace_collect.py, docs/"
        "observability.md 'Distributed request tracing')",
    )
    trace.add_argument(
        "action",
        choices=("slowest", "show", "summary", "merge"),
        help="slowest: top-k traces by end-to-end latency; show: span "
        "tree + critical-path breakdown of one trace; summary: per-span-"
        "kind p50/p95/p99; merge: one Perfetto trace (track group per "
        "process, flow arrows across the router→replica hop)",
    )
    trace.add_argument(
        "trace_id",
        nargs="?",
        default=None,
        help="trace id (or unique prefix) for 'show' — from `trace "
        "slowest`, a response payload, or a /metrics exemplar",
    )
    trace.add_argument(
        "--run-dir",
        action="append",
        required=True,
        dest="run_dirs",
        help="directory (scanned recursively for *timeline*.jsonl) or a "
        "single timeline file; repeatable — pass every fleet process's "
        "dir to stitch the cross-process tree together",
    )
    trace.add_argument(
        "--k", type=int, default=10, help="how many traces 'slowest' lists"
    )
    trace.add_argument(
        "--out",
        default=None,
        help="output path for 'merge' (default: merged_trace.json under "
        "the first --run-dir; open it in ui.perfetto.dev)",
    )
    trace.add_argument(
        "--json", action="store_true", help="emit machine-readable JSON"
    )

    plan = sub.add_parser(
        "plan",
        help="dry-run the mesh planner: resolve the config's MeshPlan, "
        "predict its roofline class and per-device HBM, run nothing "
        "(autotune/plan.py; exit 2 on an infeasible plan)",
    )
    plan.add_argument("--config", required=True, help="path to the YAML run config")
    plan.add_argument(
        "--devices",
        type=int,
        default=None,
        help="plan against this many devices instead of the locally "
        "visible count (lets you vet a pod-slice plan from a laptop)",
    )
    plan.add_argument("--json", action="store_true", help="emit the plan as JSON")

    tune = sub.add_parser(
        "tune",
        help="auto-tune mesh shape x microbatch x activation tiers x zero stage: "
        "analytic roofline/HBM pruning, then short probe fits scored by "
        "measured perf_attribution MFU; emits the winner as a loadable "
        "config (autotune/, docs/perf.md 'Mesh planning and auto-tuning')",
    )
    tune.add_argument("--config", required=True, help="path to the YAML run config")
    tune.add_argument(
        "--output",
        default=None,
        help="emitted config path (default {output.root_dir}/"
        "tune_{run.name}/tuned.yaml)",
    )
    tune.add_argument(
        "--workdir",
        default=None,
        help="probe-run scratch dir (default {output.root_dir}/tune_{run.name})",
    )
    tune.add_argument(
        "--json", action="store_true", help="print the full tune report JSON"
    )

    validate = sub.add_parser("validate", help="validate a config file")
    validate.add_argument("--config", required=True)
    validate.add_argument("--json", action="store_true")

    printcfg = sub.add_parser("print-config", help="print the resolved config")
    printcfg.add_argument("--config", required=True)
    printcfg.add_argument("--json", action="store_true")

    return parser


def _emit_error(message: str, *, details: Any = None, errors: Any = None) -> None:
    payload = {"error": message}
    if details:
        payload["details"] = details
    if errors:
        payload["errors"] = errors
    print(json.dumps(payload), file=sys.stderr)


def _warn_unknown_extras(cfg) -> None:
    """Typos in the ``extra`` escape hatches are warnings, never errors
    (config/extras.py): the knobs are real but plugins may take keys the
    framework cannot know about."""
    try:
        from .config.extras import unknown_extra_keys

        for section, keys in unknown_extra_keys(cfg).items():
            print(
                f"warning: {section} keys not recognized by "
                f"'{cfg.model.name if section == 'model.extra' else cfg.data.name if section == 'data.extra' else 'trainer'}': "
                f"{', '.join(keys)} (typo? they will be ignored)",
                file=sys.stderr,
            )
    except Exception:  # the check must never break a run
        pass


def _lora_spec_error(cfg) -> str | None:
    """A malformed ``model.extra.lora`` is a CONFIG error (exit 2), not a
    training failure — catch it before any jax work (models/lora.py)."""
    try:
        from .models.lora import LoraSpec

        LoraSpec.from_extra(cfg.model.extra)
    except ValueError as exc:
        return str(exc)
    return None


def _handle_validate(args: argparse.Namespace) -> int:
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR
    _warn_unknown_extras(cfg)
    if args.json:
        print(json.dumps({"valid": True, "config": args.config}))
    else:
        print("Config validation succeeded.")
    return EXIT_OK


def _handle_print_config(args: argparse.Namespace) -> int:
    try:
        _, _, resolved = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    if args.json:
        print(json.dumps(resolved, indent=2))
    else:
        import yaml

        print(yaml.safe_dump(resolved, sort_keys=False), end="")
    return EXIT_OK


def _handle_plan(args: argparse.Namespace) -> int:
    """The analytical half of the tuner as a standalone debugging surface:
    resolve, predict, print — nothing runs, no params materialize."""
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR

    from .autotune.plan import MeshPlanError, plan_from_config
    from .autotune.search import analytic_candidate_cost, resolve_hbm_limit
    from .telemetry.profiling import classify_roofline, resolve_peaks

    initialize_registries()
    try:
        adapter = get_model_adapter(cfg.model.name)
    except RegistryError as exc:
        _emit_error(str(exc))
        return EXIT_CONFIG_ERROR
    if args.devices is not None:
        device_count = args.devices
    else:
        import jax

        device_count = jax.device_count()

    try:
        mesh_plan = plan_from_config(cfg, device_count, adapter=adapter)
    except MeshPlanError as exc:
        _emit_error(f"infeasible plan: {exc}")
        return EXIT_CONFIG_ERROR

    peaks = resolve_peaks(None, cfg.telemetry.device_peaks)
    cost = analytic_candidate_cost(mesh_plan, cfg)
    roofline = classify_roofline(
        flops=cost["flops"],
        bytes_accessed=cost["bytes_accessed"],
        collective_bytes=cost["collective_bytes"],
        peaks=peaks,
    )
    from .autotune.plan import config_loss_impl, predict_hbm_bytes

    # Resolve the loss implementation the run would build (dense /
    # chunked_ce / fused_ce) so the verdict charges the right logits
    # buffer — and say which one it assumed.
    loss_impl, ce_chunk = config_loss_impl(cfg)
    hbm = predict_hbm_bytes(
        mesh_plan,
        n_params=int(cost["n_params"]),
        d_model=cfg.model.d_model,
        n_layers=cfg.model.n_layers,
        vocab_size=int(cfg.model.vocab_size or 50257),
        block_size=cfg.model.block_size,
        dtype_bytes=2 if cfg.model.dtype == "bfloat16" else 4,
        param_dtype_bytes=2 if cfg.model.param_dtype == "bfloat16" else 4,
        loss_impl=loss_impl,
        ce_chunk=ce_chunk,
    )
    hbm_limit = resolve_hbm_limit(
        str(peaks.get("device_kind", "cpu")), cfg.tune.hbm_limit_bytes
    )
    feasible = hbm["total_bytes"] <= hbm_limit
    payload = {
        "plan": {
            "key": mesh_plan.key(),
            "mesh": mesh_plan.axes,
            "device_count": device_count,
            "data_parallel": mesh_plan.data_parallel,
            "global_micro_batch": mesh_plan.global_micro_batch,
            "micro_batch_size": mesh_plan.micro_batch_size,
            "grad_accum_steps": mesh_plan.grad_accum_steps,
            "remat": mesh_plan.remat,
            "zero_stage": mesh_plan.zero_stage,
            "activation_tiers": mesh_plan.activation_tiers,
            "loss_impl": loss_impl,
            "topology": mesh_plan.describe_topology(),
        },
        "roofline": roofline,
        "predicted_hbm": hbm,
        "hbm_limit_bytes": hbm_limit,
        "device_kind": peaks.get("device_kind", "unknown"),
        "feasible": feasible,
    }
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(f"plan      {mesh_plan.key()}")
        print(f"mesh      {mesh_plan.axes}")
        print(
            f"batch     micro={mesh_plan.micro_batch_size} "
            f"global_micro={mesh_plan.global_micro_batch} "
            f"accum={mesh_plan.grad_accum_steps}"
        )
        print(
            f"roofline  {roofline['class']} "
            f"(analytical ms: {roofline['analytical_ms']})"
        )
        print(
            f"hbm       {hbm['total_bytes'] / 2**30:.3f} GiB predicted vs "
            f"{hbm_limit / 2**30:.1f} GiB limit "
            f"[{payload['device_kind']}]"
        )
        print(
            f"loss      {loss_impl} "
            f"(logits buffer {hbm['logits_bytes'] / 2**20:.1f} MiB)"
        )
        by_tier = hbm.get("activation_bytes_by_tier", {})
        if by_tier:
            breakdown = " ".join(
                f"{tier}={v / 2**30:.3f}GiB"
                for tier, v in sorted(by_tier.items())
            )
            host_b = hbm.get("activation_host_bytes", 0)
            line = f"acts      {breakdown}"
            if host_b:
                line += f" host_offload={host_b / 2**30:.3f}GiB"
            print(line)
    if not feasible:
        _emit_error(
            "infeasible plan: predicted per-device HBM "
            f"{hbm['total_bytes'] / 2**30:.3f} GiB exceeds the "
            f"{hbm_limit / 2**30:.1f} GiB limit for "
            f"{payload['device_kind']} (override with tune.hbm_limit_bytes)"
        )
        return EXIT_CONFIG_ERROR
    return EXIT_OK


def _handle_tune(args: argparse.Namespace) -> int:
    try:
        cfg, _, resolved = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR

    from .autotune.plan import MeshPlanError
    from .autotune.tune import run_tune

    workdir = Path(args.workdir or Path(cfg.output.root_dir) / f"tune_{cfg.run.name}")
    output_path = Path(args.output or workdir / "tuned.yaml")
    try:
        report = run_tune(
            cfg, resolved, workdir=workdir, output_path=output_path
        )
    except MeshPlanError as exc:
        _emit_error(f"infeasible plan: {exc}")
        return EXIT_CONFIG_ERROR
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        pruned = report["pruned"]
        print(
            f"tune      {report['enumerated']} candidates enumerated, "
            f"{len(pruned)} pruned analytically, "
            f"{len(report['measured'])} probed "
            f"({report['elapsed_sec']:.1f}s of {report['budget_sec']:.0f}s budget)"
        )
        for record in report["measured"]:
            status = record.get("status")
            if status == "ok":
                marker = "*" if record["key"] == report["winner"]["key"] else " "
                print(
                    f"  {marker} {record['key']}: mfu={record['mfu']:.4f} "
                    f"step={record.get('step_time_sec') or 0:.4f}s"
                    + (" (baseline)" if record.get("baseline") else "")
                )
            else:
                print(f"    {record['key']}: {status} ({record.get('reason', '')})")
        print(f"winner    {report['winner']['key']}")
        print(f"emitted   {report['output_config']}")
        print(f"report    {workdir / 'tune_report.json'}")
    return EXIT_OK


def _abstract_params(cfg, adapter, model):
    """Unboxed abstract (shape/dtype) param tree for checkpoint restore."""
    import jax
    from flax.linen import meta as nn_meta

    return nn_meta.unbox(
        jax.eval_shape(
            lambda rng: adapter.init_params(model, cfg, rng), jax.random.key(0)
        )
    )


def _load_checkpoint_params(cfg, adapter, model, from_spec: str, *, ema: bool = False):
    """Shared inference-checkpoint load (generate / export-checkpoint):
    resolve the spec, restore params against the abstract shape tree, warn
    on config mismatch. Returns ``(ckpt_path, params, step)``.

    ``ema=True`` substitutes the trainable tree with the checkpoint's EMA
    shadow (trainer.extra.ema_decay) in the SAME payload read — for LoRA
    runs the shadow mirrors the factor subtree, the frozen base loads as
    stored."""
    import yaml

    from .training.checkpoint import load_inference_params, resolve_resume_path

    ckpt_path = resolve_resume_path(from_spec, cfg.output.root_dir)
    abstract = _abstract_params(cfg, adapter, model)
    expected_yaml = yaml.safe_dump(cfg.model_dump(), sort_keys=False)
    if not ema:
        params, step = load_inference_params(
            ckpt_path, abstract, expected_config_yaml=expected_yaml
        )
        return ckpt_path, params, step

    import jax
    import jax.numpy as jnp
    from flax import serialization

    from .models.lora import LoraAdapter
    from .training.checkpoint import (
        CheckpointManager,
        ema_from_payload,
        warn_on_config_mismatch,
    )

    payload = CheckpointManager.load(ckpt_path)
    warn_on_config_mismatch(payload, expected_yaml, ckpt_path)
    step = int(payload["step"])
    if isinstance(adapter, LoraAdapter):
        host = serialization.from_state_dict(abstract, payload["params"])
        params = {
            "base": jax.tree.map(jnp.asarray, host["base"]),
            "lora": ema_from_payload(payload, abstract["lora"]),
        }
    else:
        params = ema_from_payload(payload, abstract)
    return ckpt_path, params, step


def _handle_average_checkpoints(args: argparse.Namespace) -> int:
    """Model soup: uniform average of several checkpoints' params.

    Averaging the last few checkpoints of a run (or parallel fine-tunes
    of one init) often beats the final checkpoint alone — a cheap
    post-training win with no new training machinery: the result is a
    standard ``step_000000.ckpt`` (fresh optimizer state) that ``train
    --resume``, ``eval``, and ``generate`` all consume as usual.
    """
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    try:
        import jax
        import numpy as np

        from .training.checkpoint import (
            CheckpointManager,
            load_inference_params,
            resolve_resume_path,
            state_to_host,
        )
        from .training.optimizer import build_optimizer
        from .training.train_step import create_train_state

        initialize_registries()
        out_dir = Path(args.output)
        if out_dir.exists() and sorted(out_dir.glob("step_*.ckpt")):
            _emit_error(
                f"output dir {out_dir} already holds checkpoints; "
                "pass an empty directory"
            )
            return EXIT_TRAIN_FAILURE

        specs = [s.strip() for s in args.inputs.split(",") if s.strip()]
        if args.last_k:
            if len(specs) != 1:
                _emit_error("--last-k needs --inputs to be ONE checkpoint dir")
                return EXIT_CONFIG_ERROR
            if args.last_k < 2:
                _emit_error("averaging needs at least 2 checkpoints")
                return EXIT_CONFIG_ERROR
            files = sorted(Path(specs[0]).glob("step_*.ckpt"))
            if len(files) < args.last_k:
                _emit_error(
                    f"{specs[0]} holds {len(files)} checkpoints, "
                    f"fewer than --last-k {args.last_k}"
                )
                return EXIT_CONFIG_ERROR
            paths = files[-args.last_k :]
        else:
            if len(specs) < 2:
                _emit_error("averaging needs at least 2 checkpoints")
                return EXIT_CONFIG_ERROR
            paths = [
                resolve_resume_path(s, cfg.output.root_dir) for s in specs
            ]

        import yaml as _yaml

        from .models.lora import LoraAdapter, build_adapter

        adapter = build_adapter(cfg)
        if isinstance(adapter, LoraAdapter):
            # Averaging factors leafwise keeps the checkpoint resumable,
            # but avg(A) @ avg(B) != avg(A @ B): sound for the near-
            # collinear factors of ONE run's last-k checkpoints, wrong
            # for divergent parallel fine-tunes (merge via
            # export-checkpoint first for those).
            get_logger().warning(
                "LoRA soup: averaging A/B factors leafwise — only "
                "meaningful for checkpoints of a single run; for parallel "
                "fine-tunes, export-checkpoint (merged) and average those"
            )
        model = adapter.build_model(cfg)
        abstract = _abstract_params(cfg, adapter, model)
        expected_yaml = _yaml.safe_dump(cfg.model_dump(), sort_keys=False)

        acc = None
        steps = []
        for p in paths:
            # device=False: the average is pure host work — no reason to
            # round-trip every input through the accelerator. The config-
            # mismatch warning fires like every sibling loader's.
            params, step = load_inference_params(
                p, abstract, expected_config_yaml=expected_yaml, device=False
            )
            steps.append(step)
            # Accumulate FLOAT leaves in float64 (averaging N bf16/f32
            # trees in their own dtype loses low bits N times over);
            # non-float leaves (int buffers) keep the first checkpoint's
            # value — summing them would corrupt the soup.
            as64 = jax.tree.map(
                lambda a: np.asarray(a, np.float64)
                if np.issubdtype(np.asarray(a).dtype, np.floating)
                else np.asarray(a),
                params,
            )
            acc = (
                as64
                if acc is None
                else jax.tree.map(
                    lambda t, x: np.add(t, x)
                    if np.issubdtype(t.dtype, np.floating)
                    else t,
                    acc,
                    as64,
                )
            )
        import jax.numpy as jnp

        avg = jax.tree.map(
            # Divide in f64, THEN cast back to the param dtype.
            lambda s, like: (s / len(paths)).astype(like.dtype)
            if np.issubdtype(like.dtype, np.floating)
            else s,
            acc,
            params,
        )
        # The Trainer resumes against ITS optimizer layout: apply the same
        # adapter-level wrap (LoRA: moments only for the factors) or the
        # printed `train --resume` would hit an opt_state structure
        # mismatch. Mirrors the import-checkpoint path.
        avg_tx = build_optimizer(cfg.trainer)
        wrap_tx = getattr(adapter, "wrap_optimizer", None)
        if wrap_tx is not None:
            avg_tx = wrap_tx(avg_tx)
        state = create_train_state(
            jax.tree.map(jnp.asarray, avg), avg_tx
        )
        target = CheckpointManager(out_dir).save_host(
            0, state_to_host(state), cfg.model_dump()
        )
        stats = {
            "inputs": [str(p) for p in paths],
            "steps": steps,
            "checkpoint": str(target),
        }
        if args.json:
            print(json.dumps(stats))
        else:
            print(
                f"averaged {len(paths)} checkpoints (steps {steps}) -> {target}; "
                f"continue with: train --config {args.config} --resume {out_dir}"
            )
        return EXIT_OK
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        _emit_error(f"averaging failed: {exc}")
        return EXIT_TRAIN_FAILURE


def _handle_export_checkpoint(args: argparse.Namespace) -> int:
    """Export GPT weights to a torch-layout state dict (interop/).

    The layout transforms are the parity-proven ones
    (tests/test_torch_parity.py); output loads into a reference-spec torch
    GPT with `model.load_state_dict(torch.load(path))`.
    """
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    try:
        import torch

        from .interop import (
            is_llama_tree,
            is_pipeline_tree,
            llama_params_to_hf_state_dict,
            params_to_torch_state_dict,
            pipeline_params_to_gpt,
        )
        from .models.lora import build_adapter, to_inference_params

        initialize_registries()
        adapter = build_adapter(cfg)
        model = adapter.build_model(cfg)
        ckpt_path, params, step = _load_checkpoint_params(
            cfg, adapter, model, args.from_spec, ema=args.ema
        )
        # LoRA runs export their MERGED weights: the file stays the
        # family's lingua-franca full-rank state dict (models/lora.py).
        params = to_inference_params(adapter, params)
        if is_pipeline_tree(params):
            # Pipeline-trained run: unstack to the per-layer gpt tree
            # first (interop/pipeline_convert.py) — same math, so the
            # export is still reference-exact.
            params = pipeline_params_to_gpt(params)
        # Each family exports in its ecosystem's lingua franca: llama →
        # HF LlamaForCausalLM names (interop/llama_hf.py), gpt → the
        # reference torch GPT names (interop/torch_interop.py).
        convert = (
            llama_params_to_hf_state_dict
            if is_llama_tree(params)
            else params_to_torch_state_dict
        )
        sd = {k: torch.from_numpy(v) for k, v in convert(params).items()}
        out = Path(args.output)
        out.parent.mkdir(parents=True, exist_ok=True)
        torch.save(sd, out)
        n_params = int(sum(v.numel() for v in sd.values()))
        stats = {
            "checkpoint": str(ckpt_path),
            "step": step,
            "output": str(out),
            "tensors": len(sd),
            "parameters": n_params,
        }
        if args.json:
            print(json.dumps(stats))
        else:
            print(
                f"exported step-{step} checkpoint -> {out} "
                f"({len(sd)} tensors, {n_params:,} parameters)"
            )
        return EXIT_OK
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        _emit_error(f"export failed: {exc}")
        return EXIT_TRAIN_FAILURE


def _handle_import_checkpoint(args: argparse.Namespace) -> int:
    """torch state dict → a step-0 checkpoint this framework can resume.

    Inverse of export-checkpoint (interop/torch_interop.py): reference-
    trained GPT weights become ``step_000000.ckpt`` with a fresh optimizer
    state; continue with ``train --resume <output dir>``.
    """
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    try:
        import jax
        import numpy as np
        import torch

        from .interop import (
            gpt_params_to_pipeline,
            is_llama_tree,
            is_pipeline_tree,
            llama_params_from_hf_state_dict,
            params_from_torch_state_dict,
            pipeline_params_to_gpt,
        )
        from .models.lora import LoraAdapter, build_adapter, init_lora
        from .training.checkpoint import CheckpointManager, state_to_host
        from .training.optimizer import build_optimizer
        from .training.train_step import create_train_state

        initialize_registries()
        out_dir = Path(args.output)
        existing = sorted(out_dir.glob("step_*.ckpt")) if out_dir.exists() else []
        if existing:
            # keep-last-k pruning would otherwise silently delete the
            # imported step-0 file (or the user's own checkpoints).
            _emit_error(
                f"output dir {out_dir} already holds checkpoints "
                f"({existing[0].name}, ...); pass an empty directory"
            )
            return EXIT_TRAIN_FAILURE
        adapter = build_adapter(cfg)
        model = adapter.build_model(cfg)
        template = _abstract_params(cfg, adapter, model)
        # Importing into a LoRA config is THE fine-tuning entry point:
        # the torch weights fill the frozen base, the factors start at
        # their zero-delta init, and `train --resume` picks it up.
        lora_adapter = adapter if isinstance(adapter, LoraAdapter) else None
        if lora_adapter is not None:
            template = template["base"]
        raw = torch.load(args.input, weights_only=True)
        # .float() first: torch bf16 tensors cannot .numpy() directly, and
        # the converter works in float32 anyway.
        sd = {
            k: (v.float().numpy() if hasattr(v, "numpy") else v)
            for k, v in raw.items()
        }
        if is_pipeline_tree(template):
            # gpt_pipeline config: map the torch per-layer weights through
            # the gpt-shaped template, then restack for the pipeline tree
            # (interop/pipeline_convert.py — abstract-template capable).
            gpt_template = pipeline_params_to_gpt(template)
            params = gpt_params_to_pipeline(
                params_from_torch_state_dict(sd, gpt_template)
            )
        elif is_llama_tree(template):
            # llama config: the input is an HF LlamaForCausalLM state
            # dict (interop/llama_hf.py).
            params = llama_params_from_hf_state_dict(sd, template)
        else:
            params = params_from_torch_state_dict(sd, template)

        tx = build_optimizer(cfg.trainer)
        if lora_adapter is not None:
            params = {
                "base": params,
                "lora": init_lora(
                    params,
                    lora_adapter.spec,
                    jax.random.fold_in(jax.random.key(cfg.run.seed), 0x10A),
                ),
            }
            tx = lora_adapter.wrap_optimizer(tx)
        state = create_train_state(params, tx)
        target = CheckpointManager(out_dir).save_host(
            0, state_to_host(state), cfg.model_dump()
        )
        n_params = int(
            sum(np.prod(np.shape(x)) for x in jax.tree.leaves(params))
        )
        stats = {"input": args.input, "checkpoint": str(target), "parameters": n_params}
        if args.json:
            print(json.dumps(stats))
        else:
            print(
                f"imported {args.input} -> {target} ({n_params:,} parameters); "
                f"continue with: train --config {args.config} --resume {args.output}"
            )
        return EXIT_OK
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        _emit_error(f"import failed: {exc}")
        return EXIT_TRAIN_FAILURE


def _handle_train_tokenizer(args: argparse.Namespace) -> int:
    """Train an offline BPE vocabulary (data/bpe.py) on local text.

    New capability over the reference, whose only tokenizer is the
    downloaded tiktoken gpt2 (reference models/gpt.py:210-212); pairs with
    ``model.extra.tokenizer: "bpe:<output>"``.
    """
    from pathlib import Path

    from .data.bpe import train_bpe

    seen: set[Path] = set()
    files: list[Path] = []

    def _add(q: Path) -> None:
        r = q.resolve()
        if r not in seen:
            seen.add(r)
            files.append(q)

    for spec in args.input:
        p = Path(spec)
        if p.is_dir():
            for q in sorted(
                q for suf in ("*.txt", "*.md", "*.py") for q in p.rglob(suf)
            ):
                _add(q)
        elif p.is_file():
            _add(p)
        else:
            _emit_error(f"input path not found: {spec}")
            return EXIT_CONFIG_ERROR
    if not files:
        _emit_error("no input files found (looked for *.txt, *.md, *.py in dirs)")
        return EXIT_CONFIG_ERROR

    budget = args.max_bytes  # enforced on UTF-8 bytes read, not characters
    pieces: list[str] = []
    for f in files:
        if budget <= 0:
            break
        raw = f.open("rb").read(budget)
        budget -= len(raw)
        pieces.append(raw.decode("utf-8", errors="ignore"))
    corpus = "\n\n".join(pieces)

    import time

    start = time.perf_counter()
    tok = train_bpe(corpus, args.vocab_size)
    elapsed = time.perf_counter() - start
    tok.save(args.output)

    n_tokens = len(tok.encode(corpus[:1_000_000]))
    n_bytes = len(corpus[:1_000_000].encode("utf-8"))
    stats = {
        "output": args.output,
        "vocab_size": tok.n_vocab,
        "corpus_bytes": len(corpus.encode("utf-8")),
        "files": len(files),
        "train_seconds": round(elapsed, 2),
        "bytes_per_token": round(n_bytes / max(n_tokens, 1), 3),
    }
    if args.json:
        print(json.dumps(stats))
    else:
        print(
            f"trained {stats['vocab_size']}-token BPE on {stats['corpus_bytes']} bytes "
            f"({stats['files']} files) in {stats['train_seconds']}s -> {args.output} "
            f"[{stats['bytes_per_token']} bytes/token]"
        )
    return EXIT_OK


def _create_tracker(cfg, dist_state: DistState | None, run_id: str) -> Tracker:
    """A real tracker on the main process when enabled; Null otherwise
    (reference :246-248). Backend selection: tracking/__init__.py
    build_tracker (mlflow / native SQLite / auto)."""
    is_main = dist_state is None or dist_state.is_main
    if cfg.mlflow.enabled and is_main:
        return build_tracker(cfg.mlflow, run_id)
    return NullTracker()


def _log_run_artifacts(tracker: Tracker, run_dir: Path | None) -> None:
    if run_dir is None:
        return
    for name in ("config.yaml", "meta.json"):
        path = run_dir / name
        if path.is_file():
            tracker.log_artifact(str(path))


def _agree_run_id(candidate: str, dist_state: DistState | None) -> str:
    """Make every process use rank 0's run id.

    ``generate_run_id`` is wall-clock/filesystem dependent, so independent
    generation can diverge across hosts; rank 0's id is broadcast instead.
    """
    if dist_state is None or dist_state.num_processes == 1:
        return candidate
    import numpy as np
    from jax.experimental import multihost_utils

    buf = np.zeros(256, dtype=np.uint8)
    encoded = candidate.encode("utf-8")[:256]
    buf[: len(encoded)] = np.frombuffer(encoded, dtype=np.uint8)
    agreed = multihost_utils.broadcast_one_to_all(buf)
    return bytes(np.asarray(agreed)).rstrip(b"\x00").decode("utf-8")


def _agree_flag(local_ok: bool, dist_state: DistState | None) -> bool:
    """Broadcast rank 0's boolean to every process (single-process: identity)."""
    if dist_state is None or dist_state.num_processes == 1:
        return local_ok
    from .distributed import broadcast_int_from_main

    return bool(broadcast_int_from_main(1 if local_ok else 0))


def _build_decode_stack(cfg, logger, label: str = ""):
    """Adapter + (optional) tokenizer + model for an inference command.

    One implementation for generate/serve (and generate's draft model)
    so they stay bit-identical; raises with the actionable remediation
    when the model needs a vocab size the absent tokenizer would supply.
    """
    from .distributed import resolve_devices
    from .models.lora import build_adapter

    # Same rule as the Trainer: run.device names the platform, so an
    # inference command never decodes on the CPU under the name "tpu".
    resolve_devices(cfg.run.device)
    adapter = build_adapter(cfg)
    tokenizer = None
    try:
        tokenizer = adapter.build_tokenizer(cfg)
    except Exception as exc:  # offline environments: tokenizer optional
        logger.warning(
            "%sbuild_tokenizer failed (%s); continuing without one", label, exc
        )
    try:
        model = adapter.build_model(cfg)
    except Exception:
        if cfg.model.vocab_size is None and tokenizer is None:
            # e.g. gpt derives vocab_size from the tokenizer, which this
            # environment could not build (gpt.py:330-336).
            raise ValueError(
                "building the model needs a vocab size but no tokenizer is "
                "available; set model.vocab_size explicitly in the config"
            ) from None
        raise
    return adapter, tokenizer, model


def _load_decode_params(
    cfg,
    adapter,
    model,
    from_spec: str,
    *,
    ema: bool,
    decode_param_dtype: str,
    quantize: str,
    logger,
    label: str = "",
):
    """Checkpoint → decode-ready (model, params): the shared load tail.

    LoRA merge, EMA extraction, pipeline→gpt conversion, decode dtype
    cast, optional int8 quantization — generate, its draft branch, and
    serve all run THIS function, so a served model is bit-identical to
    the one ``generate`` would run.
    """
    from .models.lora import to_inference_params

    ckpt_path, params, step = _load_checkpoint_params(
        cfg, adapter, model, from_spec, ema=ema
    )
    logger.info("%sloaded checkpoint %s (step %d)", label, ckpt_path, step)
    if ema:
        logger.info("%susing EMA shadow weights", label)
    # LoRA checkpoints decode on the merged weights (models/lora.py).
    params = to_inference_params(adapter, params)
    model, params = _prepare_decode_model(
        model, params, decode_param_dtype, logger, label=label
    )
    if quantize == "int8":
        from .ops.quant import quant_stats, quantize_tree

        params = quantize_tree(params)
        stats = quant_stats(params)
        logger.info(
            "%sint8 weight quantization: %d/%d params quantized, "
            "%.2fx weight-byte compression",
            label,
            stats["quantized_params"],
            stats["total_params"],
            stats["compression"],
        )
    return model, params, ckpt_path, step


def _build_serving_backend(
    cfg,
    args: argparse.Namespace,
    model,
    params,
    logger,
    registry=None,
    trace_dir=None,
    name=None,
):
    """Continuous-batching scheduler + metrics registry for serve/serve-bench.

    Policy resolution: ``--draft-config`` forces ``speculative`` (and the
    config may also select it, in which case the draft flags are
    required); otherwise ``serving.policy`` from the config. Raises
    ``ValueError`` with the actionable message on a bad combination —
    callers map it to EXIT_CONFIG_ERROR.

    ``trace_dir`` (``serve --trace-dir`` / serve-bench's out dir) makes
    the timeline file-backed at ``{trace_dir}/{name}/timeline.jsonl`` so
    ``llmtrain trace`` can merge this process into the fleet-wide view.
    """
    from .serving import ContinuousBatchingScheduler, PagedDecodeEngine
    from .telemetry.registry import MetricsRegistry
    from .telemetry.timeline import EventTimeline

    scfg = cfg.serving
    if registry is None:
        registry = MetricsRegistry(None)
    # Serving timeline: request-id-tagged queue-wait/prefill/decode spans
    # (scheduler.py). Memory-only here unless --trace-dir asks for JSONL;
    # serve-bench exports the Perfetto trace next to its report.
    timeline = None
    if cfg.telemetry.enabled and cfg.telemetry.timeline:
        tl_path = (
            Path(trace_dir) / (name or "serve") / "timeline.jsonl"
            if trace_dir is not None
            else None
        )
        timeline = EventTimeline(
            tl_path,
            max_events=cfg.telemetry.max_events,
            xprof_annotations=cfg.telemetry.xprof_annotations,
        )
    overload = None
    if scfg.overload.enabled:
        from .serving import OverloadController

        overload = OverloadController.from_config(scfg.overload)
        logger.info(
            "overload control: queue_cap %d, classes %s, brownout %.0f/%.0f ms",
            scfg.overload.queue_cap,
            dict(scfg.overload.classes),
            scfg.overload.brownout_high_ms,
            scfg.overload.brownout_low_ms,
        )
    policy = "speculative" if args.draft_config is not None else scfg.policy
    if policy == "speculative":
        if args.draft_config is None or args.draft_from is None:
            raise ValueError(
                "the speculative serving policy needs --draft-config AND "
                "--draft-from (serving.policy: speculative in the config "
                "selects it; the draft checkpoint must come from the CLI)"
            )
        from .models.lora import build_adapter

        draft_cfg, _, _ = load_and_validate_config(args.draft_config)
        draft_adapter = build_adapter(draft_cfg)
        draft_model = draft_adapter.build_model(draft_cfg)
        draft_model, draft_params, _, _ = _load_decode_params(
            draft_cfg,
            draft_adapter,
            draft_model,
            args.draft_from,
            ema=False,
            decode_param_dtype=args.decode_param_dtype,
            quantize=args.quantize,
            logger=logger,
            label="draft ",
        )
        if draft_model.vocab_size != model.vocab_size:
            raise ValueError(
                f"draft vocab_size ({draft_model.vocab_size}) != target "
                f"vocab_size ({model.vocab_size}) — speculative decoding "
                "needs a shared vocabulary"
            )
        # Batched speculative: when both models support paged decoding,
        # attach target + draft engines so greedy requests draft in
        # batch and the target scores every row's slab in ONE bucketed
        # verify call. Otherwise the scheduler falls back to the batch-1
        # speculative_generate path.
        engine = draft_engine = None
        if hasattr(model, "for_paged_decoding") and hasattr(
            draft_model, "for_paged_decoding"
        ):
            engine_kwargs = dict(
                block_tokens=scfg.block_tokens,
                num_blocks=scfg.num_blocks or None,
                max_batch_slots=scfg.max_batch_slots,
                prompt_buckets=scfg.prompt_buckets or None,
                batch_buckets=scfg.batch_buckets or None,
            )
            engine = PagedDecodeEngine(model, params, **engine_kwargs)
            draft_engine = PagedDecodeEngine(
                draft_model, draft_params, **engine_kwargs
            )
            logger.info(
                "batched speculative serving: %d slots, gamma from %s",
                engine.max_batch_slots,
                "--gamma" if args.gamma is not None else "config",
            )
        scheduler = ContinuousBatchingScheduler(
            engine,
            policy="speculative",
            registry=registry,
            model=model,
            params=params,
            draft_model=draft_model,
            draft_params=draft_params,
            draft_engine=draft_engine,
            gamma=args.gamma if args.gamma is not None else scfg.speculative_gamma,
            timeline=timeline,
            overload=overload,
        )
    else:
        engine = PagedDecodeEngine(
            model,
            params,
            block_tokens=scfg.block_tokens,
            num_blocks=scfg.num_blocks or None,
            max_batch_slots=scfg.max_batch_slots,
            prompt_buckets=scfg.prompt_buckets or None,
            batch_buckets=scfg.batch_buckets or None,
            prefix_cache=scfg.prefix_cache,
            prefill_chunk=scfg.prefill_chunk,
        )
        logger.info(
            "continuous batching: %d slots, %d-token blocks x %d pool blocks, "
            "prompt buckets %s, batch buckets %s",
            engine.max_batch_slots,
            engine.block_tokens,
            engine.pool.num_blocks,
            engine.prompt_buckets,
            engine.batch_buckets,
        )
        scheduler = ContinuousBatchingScheduler(
            engine, registry=registry, timeline=timeline, overload=overload
        )
    _configure_request_tracer(cfg, scheduler, timeline)
    return scheduler, registry


def _configure_request_tracer(cfg, backend, timeline) -> None:
    """Replace a scheduler/router's auto-created request tracer with one
    built from ``telemetry.tracing`` (tail-sampling knobs), or strip it
    when tracing is disabled — the backends default to a tracer whenever
    they have a timeline, so the config gate must be applied here."""
    tcfg = cfg.telemetry.tracing
    if timeline is None or not tcfg.enabled:
        backend.tracer = None
        return
    from .telemetry.tracing import TailSampler, Tracer

    backend.tracer = Tracer(
        timeline,
        sampler=TailSampler(
            slow_frac=tcfg.slow_keep_frac,
            reservoir=tcfg.reservoir,
            warmup=tcfg.warmup_keep,
        ),
        max_spans=tcfg.max_spans_per_trace,
    )


def _build_router_backend(
    cfg,
    args: argparse.Namespace,
    model,
    params,
    logger,
    trace_dir=None,
):
    """Replica-router tier for ``serve --router`` / ``serve-bench --router``.

    Default: ``serving.router.replicas`` (or ``--replicas``) in-process
    replicas, each a full scheduler+engine stack behind one router.
    ``--backends``/``--discover`` route across separate serve processes
    over HTTP instead — the k8s shape, where each replica is its own pod
    behind a headless Service (k8s/router.yaml).
    """
    from .serving import (
        HTTPReplica,
        InProcessReplica,
        ReplicaRouter,
        resolve_backends,
    )
    from .telemetry.registry import MetricsRegistry

    rcfg = cfg.serving.router
    registry = MetricsRegistry(None)
    replicas: list[Any] = []
    if getattr(args, "backends", None):
        urls = [u.strip() for u in args.backends.split(",") if u.strip()]
        if not urls:
            raise ValueError("--backends must list at least one base URL")
        replicas = [
            HTTPReplica(
                u,
                timeout_sec=cfg.serving.request_timeout_sec,
                probe_timeout_sec=rcfg.probe_timeout_sec,
            )
            for u in urls
        ]
    elif getattr(args, "discover", None):
        replicas = [
            HTTPReplica(
                u,
                timeout_sec=cfg.serving.request_timeout_sec,
                probe_timeout_sec=rcfg.probe_timeout_sec,
            )
            for u in resolve_backends(args.discover)
        ]
    else:
        n = getattr(args, "replicas", None) or rcfg.replicas
        for i in range(n):
            # In-process replicas share the router's registry so the
            # scheduler-level overload series (rejected{reason}, brownout,
            # predicted wait) reach the fleet /metrics scrape; counters
            # sum across replicas, gauges are last-writer-wins.
            sched, _ = _build_serving_backend(
                cfg,
                args,
                model,
                params,
                logger,
                registry=registry,
                trace_dir=trace_dir,
                name=f"replica{i}",
            )
            sched.start()
            replicas.append(InProcessReplica(sched, f"replica{i}"))
    # The router gets its own timeline so its placement/failover/hop
    # spans land in a separate JSONL track (`{trace_dir}/router/`) that
    # `llmtrain trace` stitches to the replica tracks via traceparent.
    router_timeline = None
    if cfg.telemetry.enabled and cfg.telemetry.timeline:
        from .telemetry.timeline import EventTimeline

        router_timeline = EventTimeline(
            (Path(trace_dir) / "router" / "timeline.jsonl")
            if trace_dir is not None
            else None,
            max_events=cfg.telemetry.max_events,
            xprof_annotations=False,
        )
    router = ReplicaRouter(
        replicas,
        registry=registry,
        affinity_weight=rcfg.affinity_weight,
        max_affinity_entries=rcfg.max_affinity_entries,
        fail_threshold=rcfg.fail_threshold,
        revive_sec=rcfg.revive_sec,
        block_tokens=cfg.serving.block_tokens,
        retry_budget=rcfg.retry_budget,
        retry_window_sec=rcfg.retry_window_sec,
        timeline=router_timeline,
    )
    _configure_request_tracer(cfg, router, router_timeline)
    logger.info(
        "replica router: %d %s replicas, affinity_weight %.1f, "
        "fail_threshold %d",
        len(replicas),
        "HTTP" if isinstance(replicas[0], HTTPReplica) else "in-process",
        rcfg.affinity_weight,
        rcfg.fail_threshold,
    )
    return router, registry


def _handle_serve(args: argparse.Namespace) -> int:
    """Checkpoint → compiled decode loop → stdlib HTTP server (serving/).

    Loading mirrors ``generate`` exactly (LoRA merge, EMA extraction,
    pipeline→gpt conversion, decode dtype cast, int8 quantization) so a
    served model is bit-identical to the one ``generate`` would run.
    ``serving.mode: continuous`` (or ``--mode continuous``) swaps the
    one-decode-at-a-time device lock for the paged-KV continuous-batching
    scheduler — handler threads submit into the admission queue and N
    in-flight sequences share one jitted decode program (docs/serving.md).
    """
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR
    if (args.draft_config is None) != (args.draft_from is None):
        _emit_error("--draft-config and --draft-from must be given together")
        return EXIT_CONFIG_ERROR
    mode = args.mode or cfg.serving.mode
    if mode != "continuous" and args.draft_config is not None:
        # Silently ignoring the draft flags would serve plain
        # single-request decode while the user asked for speculative.
        _emit_error(
            "--draft-config/--draft-from need the continuous backend; "
            "set serving.mode: continuous (or pass --mode continuous)"
        )
        return EXIT_CONFIG_ERROR
    if args.backends and args.discover:
        _emit_error("--backends and --discover are mutually exclusive")
        return EXIT_CONFIG_ERROR
    use_router = bool(args.router or args.backends or args.discover)
    if use_router and mode != "continuous":
        _emit_error(
            "--router needs the continuous backend; set serving.mode: "
            "continuous (or pass --mode continuous)"
        )
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_compilation_cache(cfg.run.compilation_cache_dir)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    logger = get_logger()
    scheduler = None
    metrics_stop = None
    metrics_thread = None
    try:
        from .serving import ServerState, make_server

        initialize_registries()
        adapter, tokenizer, model = _build_decode_stack(cfg, logger)
        model, params, ckpt_path, step = _load_decode_params(
            cfg,
            adapter,
            model,
            args.from_spec,
            ema=args.ema,
            decode_param_dtype=args.decode_param_dtype,
            quantize=args.quantize,
            logger=logger,
        )
        eos = args.eos_token_id
        if eos is None and tokenizer is not None:
            eos = getattr(tokenizer, "eot_token", None)

        if mode == "continuous":
            try:
                trace_dir = getattr(args, "trace_dir", None)
                if use_router:
                    scheduler, registry = _build_router_backend(
                        cfg, args, model, params, logger, trace_dir=trace_dir
                    )
                else:
                    scheduler, registry = _build_serving_backend(
                        cfg, args, model, params, logger, trace_dir=trace_dir
                    )
            except ConfigLoadError as exc:
                _emit_error(exc.message, details=exc.details, errors=exc.errors)
                return EXIT_CONFIG_ERROR
            except ValueError as exc:
                _emit_error(str(exc))
                return EXIT_CONFIG_ERROR
            scheduler.start()
        else:
            # Simple mode still serves GET /metrics (request counter and
            # latency come from ServerStats; the scheduler gauges need
            # the continuous backend).
            from .telemetry.registry import MetricsRegistry

            registry = MetricsRegistry(None)

        cap = (
            args.max_new_tokens_cap
            if args.max_new_tokens_cap is not None
            else cfg.serving.max_new_tokens_cap
        )
        client_gate = None
        ocfg = cfg.serving.overload
        if ocfg.enabled and ocfg.client_rate_rps > 0:
            from .serving import ClientRateGate

            client_gate = ClientRateGate(
                ocfg.client_rate_rps,
                ocfg.client_burst,
                max_clients=ocfg.max_tracked_clients,
            )
        state = ServerState(
            model=model,
            params=params,
            tokenizer=tokenizer,
            step=step,
            checkpoint=str(ckpt_path),
            eos_token_id=eos,
            max_new_tokens_cap=cap,
            default_max_new_tokens=cfg.serving.default_max_new_tokens,
            scheduler=scheduler,
            registry=registry,
            request_timeout_sec=cfg.serving.request_timeout_sec,
            liveness_stale_sec=cfg.serving.liveness_stale_sec,
            client_gate=client_gate,
        )

        if mode == "continuous":
            # Zero-downtime checkpoint hot-swap: POST /reload re-resolves
            # the --from spec (a dir or run id resolves to the NEWEST
            # manifest-committed checkpoint, training/checkpoint.py) and
            # swaps the params without dropping a request — in-flight
            # sequences finish on the params they were admitted under,
            # new admissions use the new ones. With --router the swap
            # rolls one replica at a time.
            def _reload(body: dict) -> dict:
                spec = str(body.get("from") or args.from_spec)
                _, new_params, new_ckpt, new_step = _load_decode_params(
                    cfg,
                    adapter,
                    model,
                    spec,
                    ema=args.ema,
                    decode_param_dtype=args.decode_param_dtype,
                    quantize=args.quantize,
                    logger=logger,
                    label="reload ",
                )
                out: dict[str, Any] = {
                    "step": new_step,
                    "checkpoint": str(new_ckpt),
                }
                if hasattr(scheduler, "rolling_reload"):
                    out["replicas"] = scheduler.rolling_reload(
                        params=new_params,
                        step=new_step,
                        checkpoint=str(new_ckpt),
                    )
                else:
                    scheduler.hot_swap(
                        new_params, step=new_step, checkpoint=str(new_ckpt)
                    )
                state.params = new_params
                state.step, state.checkpoint = new_step, str(new_ckpt)
                return out

            state.reloader = _reload

        # Textfile fallback for serving replicas (mirrors the training
        # facade's metrics.prom snapshot): a node-exporter textfile
        # collector can pick up the scrape even when /metrics is behind
        # a router or the pod network is unreachable. Histograms ride
        # along, exemplar trace ids included.
        serve_trace_dir = getattr(args, "trace_dir", None)
        if (
            serve_trace_dir
            and cfg.telemetry.enabled
            and cfg.telemetry.prometheus_textfile
        ):
            import threading

            from .telemetry.prometheus import render_prometheus, write_textfile

            prom_path = Path(serve_trace_dir) / "metrics.prom"
            metrics_stop = threading.Event()

            def _snapshot_metrics() -> None:
                try:
                    write_textfile(
                        prom_path,
                        render_prometheus(
                            registry.latest(),
                            registry.counters(),
                            {"component": "serve"},
                            histograms=registry.histograms(),
                        ),
                    )
                except Exception:  # noqa: BLE001 — snapshot must not kill serving
                    pass

            def _metrics_loop() -> None:
                while True:
                    _snapshot_metrics()
                    if metrics_stop.wait(5.0):
                        _snapshot_metrics()
                        return

            metrics_thread = threading.Thread(
                target=_metrics_loop, name="metrics-prom", daemon=True
            )
            metrics_thread.start()

        httpd = make_server(state, args.host, args.port)
        host, port = httpd.server_address[:2]
        # Machine-readable ready line: tests (and orchestration) read the
        # bound port from here, which is what makes --port 0 usable.
        print(
            json.dumps(
                {
                    "serving": str(ckpt_path),
                    "host": host,
                    "port": port,
                    "mode": mode,
                    "policy": scheduler.policy if scheduler else None,
                    "router": (
                        len(scheduler.replicas) if use_router else None
                    ),
                }
            ),
            flush=True,
        )
        try:
            httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            httpd.server_close()
        return EXIT_OK
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        _emit_error(f"serve failed: {exc}")
        return exit_code_for_exception(exc)
    finally:
        if metrics_stop is not None:
            metrics_stop.set()
        if metrics_thread is not None:
            metrics_thread.join(timeout=10.0)
        if scheduler is not None:
            scheduler.close()


def _resolve_watch_dirs(watch: str) -> tuple[Path, Path]:
    """``--watch`` path → (run_dir, ckpt_dir). Accepts the run dir (the
    conventional layout puts checkpoints in ``{run_dir}/checkpoints``)
    or the checkpoints dir itself."""
    path = Path(watch)
    if path.name == "checkpoints":
        return path.parent, path
    if (path / "checkpoints").is_dir() or not any(
        p.name.startswith("step_") for p in (path.glob("step_*") if path.is_dir() else [])
    ):
        return path, path / "checkpoints"
    # A dir holding step_* files directly IS the checkpoint dir.
    return path.parent, path


def _handle_promote(args: argparse.Namespace) -> int:
    """Continuous train→canary→promote lifecycle (lifecycle/controller.py).

    Watches the training run's manifest stream (durable artifacts only),
    serves an in-process replica fleet from the promoted baseline,
    canaries each new commit on one replica, scores it over a soak
    window (held-out eval loss + TTFT/per-token percentiles, optional
    A/B traffic split), then promotes fleet-wide via rolling reload or
    auto-rolls the canary back. Every decision is a durable
    ``promotions.jsonl`` entry the goodput ledger attributes.

    Exit taxonomy: training finished (report.json) or the promotion
    budget spent → 0; the training run dying mid-stream (stale
    heartbeat, no report) → EXIT_TRAIN_FAILURE.
    """
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR
    pcfg = cfg.promote
    overrides: dict[str, Any] = {}
    if args.max_promotions is not None:
        overrides["max_promotions"] = args.max_promotions
    if args.idle_timeout_sec is not None:
        overrides["idle_timeout_sec"] = args.idle_timeout_sec
    if overrides:
        pcfg = pcfg.model_copy(update=overrides)

    run_dir, ckpt_dir = _resolve_watch_dirs(args.watch)
    if not run_dir.is_dir():
        _emit_error(f"--watch run dir not found: {run_dir}")
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_compilation_cache(cfg.run.compilation_cache_dir)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    logger = get_logger()
    router = None
    timeline = None
    try:
        from .lifecycle import (
            CheckpointWatcher,
            PromotionController,
            PromotionLedger,
            RouterFleet,
        )

        initialize_registries()
        ledger = PromotionLedger(run_dir / "promotions.jsonl")
        watcher = CheckpointWatcher(ckpt_dir, run_dir=run_dir)

        # Baseline: the last promoted checkpoint (ledger replay — a
        # SIGKILLed promote resumes where it decided, never re-promotes),
        # else --from, else the stream's first commit (waited for).
        spec = None
        promoted = ledger.last_promoted()
        if promoted and promoted.get("checkpoint") and Path(
            promoted["checkpoint"]
        ).exists():
            spec = promoted["checkpoint"]
            logger.info(
                "promote: resuming from ledger — step %d is the baseline",
                promoted["step"],
            )
        elif args.from_spec:
            spec = args.from_spec
        else:
            deadline = time.monotonic() + pcfg.idle_timeout_sec
            while spec is None:
                polled = watcher.poll(after_step=-1)
                if polled is not None:
                    spec = str(polled[0])
                    break
                if time.monotonic() > deadline:
                    _emit_error(
                        f"promote: no committed checkpoint appeared in "
                        f"{ckpt_dir} within {pcfg.idle_timeout_sec:.0f}s"
                    )
                    return EXIT_TRAIN_FAILURE
                time.sleep(pcfg.poll_sec)

        adapter, tokenizer, model = _build_decode_stack(cfg, logger)
        model, params, ckpt_path, step = _load_decode_params(
            cfg,
            adapter,
            model,
            str(spec),
            ema=args.ema,
            decode_param_dtype=args.decode_param_dtype,
            quantize=args.quantize,
            logger=logger,
            label="promote ",
        )
        router, registry = _build_router_backend(cfg, args, model, params, logger)
        if len(router.replicas) < 2:
            logger.warning(
                "promote: a 1-replica fleet has no reference replica — "
                "the SLO A/B gate is skipped (only failures and eval "
                "loss gate promotion)"
            )

        def load_params(ckpt: Path) -> Any:
            _, p, _, _ = _load_decode_params(
                cfg,
                adapter,
                model,
                str(ckpt),
                ema=args.ema,
                decode_param_dtype=args.decode_param_dtype,
                quantize=args.quantize,
                logger=logger,
                label="candidate ",
            )
            return p

        evaluator = None
        if not args.no_eval:
            from .tracking.base import NullTracker
            from .training.trainer import Trainer

            eval_trainer = Trainer(cfg, run_dir=None, tracker=NullTracker())

            def evaluator(ckpt: Path) -> float | None:
                metrics = eval_trainer.evaluate(resume_from=str(ckpt))
                if metrics is None:
                    return None
                return float(metrics["val/loss"])

        if cfg.telemetry.enabled and cfg.telemetry.timeline:
            from .telemetry.timeline import EventTimeline

            tdir = run_dir / "telemetry"
            tdir.mkdir(parents=True, exist_ok=True)
            # Separate file: appending promote segments into the
            # trainer's timeline.jsonl would corrupt the goodput
            # ledger's segment accounting.
            timeline = EventTimeline(
                tdir / "promote_timeline.jsonl",
                max_events=cfg.telemetry.max_events,
                xprof_annotations=False,
            )

        fleet = RouterFleet(
            router,
            vocab_size=model.vocab_size,
            max_new_tokens=min(8, cfg.serving.max_new_tokens_cap),
        )
        try:
            controller = PromotionController(
                cfg=pcfg,
                watcher=watcher,
                fleet=fleet,
                ledger=ledger,
                baseline_params=params,
                baseline_step=step,
                baseline_checkpoint=str(ckpt_path),
                load_params=load_params,
                evaluator=evaluator,
                registry=registry,
                timeline=timeline,
            )
        except ValueError as exc:
            _emit_error(str(exc))
            return EXIT_CONFIG_ERROR
        result = controller.run()

        payload = {
            "status": result.status,
            "promotions": result.promotions,
            "rollbacks": result.rollbacks,
            "aborts": result.aborts,
            "last_promoted_step": result.last_promoted_step,
            "ledger": str(ledger.path),
        }
        if args.json:
            print(json.dumps(payload))
        else:
            print(
                f"promote: {result.status} — {result.promotions} promoted, "
                f"{result.rollbacks} rolled back, {result.aborts} aborted "
                f"(serving step {result.last_promoted_step}); "
                f"ledger {ledger.path}"
            )
        if result.status == "training_dead":
            # The watched run died mid-stream: surface it on the exit
            # taxonomy so a supervisor treats promote like the trainer.
            return EXIT_TRAIN_FAILURE
        return EXIT_OK
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        _emit_error(f"promote failed: {exc}")
        return exit_code_for_exception(exc)
    finally:
        if timeline is not None:
            try:
                timeline.flush()
            except Exception:  # noqa: BLE001 — best-effort telemetry
                pass
        if router is not None:
            try:
                from .telemetry.prometheus import render_prometheus

                tdir = run_dir / "telemetry"
                tdir.mkdir(parents=True, exist_ok=True)
                (tdir / "promote_metrics.prom").write_text(
                    render_prometheus(
                        dict(router.registry.latest()),
                        router.registry.counters(),
                        {"component": "promote"},
                    ),
                    encoding="utf-8",
                )
            except Exception:  # noqa: BLE001 — best-effort telemetry
                pass
            router.close()


def _handle_serve_bench(args: argparse.Namespace) -> int:
    """Seeded open-loop load run against the continuous-batching scheduler.

    The SLO harness (docs/serving.md): a seeded request population
    arrives on an open-loop Poisson clock (arrivals never wait for
    completions — the regime under which tail latency means anything),
    the scheduler serves them with continuous batching, and the
    measurements land in three sinks: a ``serving`` block in
    ``report.json``/``report.md``, ``llmtrain_serve_*`` gauges, and the
    JSON summary on stdout. ``--verify-parity`` re-decodes every request
    through sequential single-request ``generate()`` and exits nonzero
    unless the batched token-ids are bitwise identical; a compile count
    over the bucket budget also fails the run.
    """
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR
    if (args.draft_config is None) != (args.draft_from is None):
        _emit_error("--draft-config and --draft-from must be given together")
        return EXIT_CONFIG_ERROR
    if args.requests < 1:
        _emit_error("--requests must be >= 1")
        return EXIT_CONFIG_ERROR
    if args.prompt_tokens_min < 1:
        _emit_error("--prompt-tokens-min must be >= 1")
        return EXIT_CONFIG_ERROR
    if args.max_new_tokens < 1:
        # 0 would "succeed" with one unavoidable prefill token per request
        # and then fail parity against generate()'s empty continuation —
        # a misleading EXIT_TRAIN_FAILURE instead of a config error.
        _emit_error("--max-new-tokens must be >= 1")
        return EXIT_CONFIG_ERROR
    if args.long_fraction and not args.long_prompt_tokens:
        _emit_error("--long-fraction needs --long-prompt-tokens")
        return EXIT_CONFIG_ERROR
    if not (0.0 <= args.long_fraction <= 1.0):
        _emit_error("--long-fraction must be in [0, 1]")
        return EXIT_CONFIG_ERROR
    if args.shared_prefix_tokens < 0 or args.shared_prefix_count < 1:
        _emit_error(
            "--shared-prefix-tokens must be >= 0 and "
            "--shared-prefix-count >= 1"
        )
        return EXIT_CONFIG_ERROR
    if args.burst_factor <= 0:
        _emit_error("--burst-factor must be > 0")
        return EXIT_CONFIG_ERROR
    if args.deadline_ms is not None and args.deadline_ms <= 0:
        _emit_error("--deadline-ms must be > 0")
        return EXIT_CONFIG_ERROR
    if not (0.0 <= args.batch_fraction <= 1.0):
        _emit_error("--batch-fraction must be in [0, 1]")
        return EXIT_CONFIG_ERROR
    if args.max_rejected_frac is not None and not (
        0.0 <= args.max_rejected_frac <= 1.0
    ):
        _emit_error("--max-rejected-frac must be in [0, 1]")
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_compilation_cache(cfg.run.compilation_cache_dir)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    logger = get_logger()
    scheduler = None
    try:
        import jax
        import numpy as np

        from .serving import build_requests, run_loadgen

        initialize_registries()
        adapter, tokenizer, model = _build_decode_stack(cfg, logger)
        model, params, ckpt_path, _step = _load_decode_params(
            cfg,
            adapter,
            model,
            args.from_spec,
            ema=args.ema,
            decode_param_dtype=args.decode_param_dtype,
            quantize=args.quantize,
            logger=logger,
        )
        block_size = int(model.block_size)
        if args.max_new_tokens >= block_size:
            _emit_error(
                f"--max-new-tokens ({args.max_new_tokens}) must leave room "
                f"for a prompt within block_size ({block_size})"
            )
            return EXIT_CONFIG_ERROR
        pmax = args.prompt_tokens_max or min(32, block_size - args.max_new_tokens)
        pmax = min(pmax, block_size - args.max_new_tokens)
        pmin = min(args.prompt_tokens_min, pmax)
        # The mix knobs can push prompts past what a request may hold.
        worst_prompt = args.shared_prefix_tokens + max(
            pmax, args.long_prompt_tokens if args.long_fraction else 0
        )
        if worst_prompt + args.max_new_tokens > block_size:
            _emit_error(
                f"longest possible prompt ({worst_prompt} tokens incl. "
                f"shared prefix) + --max-new-tokens "
                f"({args.max_new_tokens}) exceeds block_size ({block_size})"
            )
            return EXIT_CONFIG_ERROR

        # out_dir is resolved before the backend so per-process timeline
        # JSONL lands under {out_dir}/telemetry — `llmtrain trace
        # --run-dir {out_dir}` merges the run after the fact.
        out_dir = Path(args.out or (Path(cfg.output.root_dir) / "serve_bench"))
        bench_trace_dir = out_dir / "telemetry"
        try:
            if args.router:
                scheduler, registry = _build_router_backend(
                    cfg, args, model, params, logger,
                    trace_dir=bench_trace_dir,
                )
            else:
                scheduler, registry = _build_serving_backend(
                    cfg, args, model, params, logger,
                    trace_dir=bench_trace_dir,
                )
        except ConfigLoadError as exc:
            _emit_error(exc.message, details=exc.details, errors=exc.errors)
            return EXIT_CONFIG_ERROR
        except ValueError as exc:
            _emit_error(str(exc))
            return EXIT_CONFIG_ERROR

        requests = build_requests(
            num_requests=args.requests,
            seed=args.seed,
            vocab_size=int(model.vocab_size),
            prompt_tokens_min=pmin,
            prompt_tokens_max=pmax,
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            shared_prefix_tokens=args.shared_prefix_tokens,
            shared_prefix_count=args.shared_prefix_count,
            long_fraction=args.long_fraction,
            long_prompt_tokens=args.long_prompt_tokens,
            deadline_ms=args.deadline_ms,
            batch_fraction=args.batch_fraction,
        )
        logger.info(
            "serve-bench: %d requests, prompts %d-%d tokens, %d new tokens, "
            "%.1f rps %s open-loop (seed %d, policy %s)",
            len(requests), pmin, pmax, args.max_new_tokens,
            args.rate_rps, args.arrival, args.seed, scheduler.policy,
        )
        scheduler.start()
        block = run_loadgen(
            scheduler,
            requests,
            rate_rps=args.rate_rps,
            seed=args.seed,
            timeout_sec=args.timeout_sec,
            arrival=args.arrival,
            burst_factor=args.burst_factor,
        )
        scheduler.close()
        block["checkpoint"] = str(ckpt_path)
        tracer = getattr(scheduler, "tracer", None)
        if tracer is not None:
            block["tracing"] = tracer.stats()

        failures: list[str] = []
        compile_block = block.get("compile")
        if compile_block is not None and not compile_block["within_budget"]:
            failures.append(
                f"decode-loop compile count exceeded the bucket budget: "
                f"{compile_block['prefill_programs']} prefill + "
                f"{compile_block['decode_programs']} decode > "
                f"{compile_block['budget']}"
            )
        if block["requests"]["failed"] or block["requests"]["timed_out"]:
            failures.append(
                f"{block['requests']['failed']} failed / "
                f"{block['requests']['timed_out']} timed-out requests"
            )
        if args.max_per_token_p99_ms is not None:
            p99 = block["slo"]["per_token_ms"]["p99"]
            if p99 is None or p99 > args.max_per_token_p99_ms:
                failures.append(
                    f"per-token p99 {p99} ms exceeds the "
                    f"--max-per-token-p99-ms bound "
                    f"({args.max_per_token_p99_ms} ms)"
                )
        if args.max_rejected_frac is not None:
            reqs_blk = block["requests"]
            frac = (
                reqs_blk.get("rejected", 0) + reqs_blk.get("shed", 0)
            ) / max(1, reqs_blk["submitted"])
            if frac > args.max_rejected_frac:
                failures.append(
                    f"rejected+shed fraction {frac:.3f} exceeds the "
                    f"--max-rejected-frac bound ({args.max_rejected_frac})"
                )

        if args.verify_parity:
            # The exactness contract: batched continuous decode must emit
            # the SAME token ids sequential single-request generate()
            # produces for identical seeds/sampling params.
            from .generation import generate

            mismatched = 0
            mismatches: list[dict[str, Any]] = []
            for req in requests:
                if req.finish_reason not in ("eos", "length"):
                    continue
                out = generate(
                    model,
                    params,
                    req.prompt_ids[None, :],
                    max_new_tokens=req.max_new_tokens,
                    temperature=req.temperature,
                    top_k=req.top_k,
                    top_p=req.top_p,
                    eos_token_id=req.eos_token_id,
                    rng=jax.random.key(req.seed),
                )
                ref = [int(t) for t in np.asarray(out)[0, req.prompt_ids.shape[0]:]]
                if req.eos_token_id is not None and req.eos_token_id in ref:
                    ref = ref[: ref.index(req.eos_token_id) + 1]
                if ref != req.tokens:
                    mismatched += 1
                    if len(mismatches) < 8:
                        # Enough for a caller to judge the divergence at
                        # the logits (chip_smoke.py); the check itself
                        # stays bitwise.
                        mismatches.append(
                            {
                                "request_id": req.request_id,
                                "prompt_ids": [int(t) for t in req.prompt_ids],
                                "served": list(req.tokens),
                                "reference": ref,
                            }
                        )
                    logger.warning(
                        "parity mismatch on request %s: served %s != "
                        "generate() %s",
                        req.request_id, req.tokens, ref,
                    )
            checked = sum(
                1 for r in requests if r.finish_reason in ("eos", "length")
            )
            block["parity"] = {
                "checked": checked,
                "mismatched": mismatched,
                "bitwise_identical": mismatched == 0 and checked > 0,
            }
            if mismatches:
                block["parity"]["mismatches"] = mismatches
            if mismatched:
                failures.append(
                    f"{mismatched}/{checked} requests diverged from "
                    "sequential generate()"
                )

        # report.json / report.md with the serving block (telemetry
        # pipeline contract — the same writer training runs use).
        from .telemetry.report import build_report, write_reports
        from .telemetry.timeline import EventTimeline

        # The scheduler's request-id-tagged timeline (queue_wait → prefill
        # → decode spans) feeds the report AND a Perfetto-loadable trace.
        timeline = getattr(scheduler, "timeline", None) or EventTimeline(None)
        report = build_report(
            run_id="serve-bench",
            run_name=cfg.run.name,
            registry=registry,
            timeline=timeline,
            memory=None,
            wall_time_sec=block["throughput"]["wall_sec"],
            serving=block,
        )
        json_path, md_path = write_reports(out_dir, report)
        trace_path = timeline.export_perfetto(out_dir / "trace.json")
        summary = {
            "serving": block,
            "report_json": str(json_path) if json_path else None,
            "report_md": str(md_path) if md_path else None,
            "trace_json": str(trace_path) if trace_path else None,
            "trace_dir": str(bench_trace_dir),
            "ok": not failures,
        }
        if failures:
            summary["failures"] = failures
        print(json.dumps(summary, indent=2), flush=True)
        if failures:
            _emit_error("; ".join(failures))
            return EXIT_TRAIN_FAILURE
        return EXIT_OK
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        _emit_error(f"serve-bench failed: {exc}")
        return exit_code_for_exception(exc)
    finally:
        if scheduler is not None:
            scheduler.close()


def _handle_eval(args: argparse.Namespace) -> int:
    """Eval-only: restore a checkpoint and run the validation loop once.

    New capability over the reference (its eval exists only inside the
    train loop, reference trainer.py:243-289); pairs with the loss-parity
    story — evaluate any checkpoint against any config's val split.
    """
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_compilation_cache(cfg.run.compilation_cache_dir)
    level = "DEBUG" if args.verbose else cfg.logging.level
    configure_logging(level=level, json_output=cfg.logging.json_output)
    try:
        from .tracking.base import NullTracker
        from .training.trainer import Trainer

        initialize_registries()
        trainer = Trainer(cfg, run_dir=None, tracker=NullTracker())
        metrics = trainer.evaluate(
            resume_from=args.from_spec,
            use_ema=args.ema,
            quantize=args.quantize if args.quantize != "none" else None,
        )
        if metrics is None:
            _emit_error("data module has no validation split to evaluate")
            return EXIT_TRAIN_FAILURE
        if args.json:
            print(json.dumps({"checkpoint": args.from_spec, "metrics": metrics}))
        else:
            rendered = "  ".join(f"{k}={v:.6f}" for k, v in sorted(metrics.items()))
            print(rendered)
        return EXIT_OK
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        _emit_error(f"evaluation failed: {exc}")
        return exit_code_for_exception(exc)


def _prepare_decode_model(model, params, decode_param_dtype: str, logger, label=""):
    """Inference-load post-processing shared by the target and draft paths.

    * Pipeline-trained runs decode through the equivalent plain GPT
      (interop/pipeline_convert.py — same math), which has the KV-cache
      path; the stacked model would fall back to the windowed re-forward
      loop. The rebuild keeps the validated attention impl so a flash
      config doesn't revert to dense and materialize (T, T).
    * ``decode_param_dtype == "compute"`` casts floating params to the
      model compute dtype — decode is weight-bandwidth bound and a bf16
      model reading f32 weights pays 2x the bytes.
      Models without a dtype/param_dtype split (e.g. dummy_gpt) have
      nothing to cast.
    """
    import jax
    import jax.numpy as jnp

    from .interop import is_pipeline_tree, pipeline_params_to_gpt

    if is_pipeline_tree(params):
        from .models.gpt import GPT

        params = pipeline_params_to_gpt(params)
        model = GPT(
            vocab_size=model.vocab_size,
            block_size=model.block_size,
            d_model=model.d_model,
            n_layers=model.n_layers,
            n_heads=model.n_heads,
            d_ff=model.d_ff,
            dropout=0.0,
            tie_embeddings=model.tie_embeddings,
            dtype=model.dtype,
            param_dtype=model.param_dtype,
            attention=model.attention,
            n_kv_heads=model.n_kv_heads,
            # A windowed pipeline checkpoint must keep its window at
            # decode time (rolling cache + masked reads).
            sliding_window=getattr(model, "sliding_window", 0),
            kv_cache_dtype=getattr(model, "kv_cache_dtype", "model"),
        )
        logger.info(
            "%spipeline checkpoint converted to the gpt tree for KV-cache "
            "decoding",
            label,
        )

    if decode_param_dtype == "compute":
        if getattr(model, "dtype", None) is not None and (
            model.dtype != getattr(model, "param_dtype", model.dtype)
        ):
            params = jax.tree.map(
                lambda a: a.astype(model.dtype)
                if jnp.issubdtype(a.dtype, jnp.floating)
                else a,
                params,
            )
            logger.info(
                "%scast floating params to %s for decode (--decode-param-dtype "
                "param keeps the checkpoint's master precision)",
                label,
                jnp.dtype(model.dtype).name,
            )
    return model, params


def _handle_generate(args: argparse.Namespace) -> int:
    """First-class serving path: checkpoint → jit-compiled sampling.

    The reference exposes generation only as eager notebook cells
    (reference notebooks/trained_vs_random_completion.ipynb); here it is a
    CLI subcommand over the single-compile decode loop in
    ``llmtrain_tpu.generation``.
    """
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_compilation_cache(cfg.run.compilation_cache_dir)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    logger = get_logger()

    # Fail fast on inconsistent speculative flags — before any expensive
    # model/checkpoint work.
    if (args.draft_config is None) != (args.draft_from is None):
        _emit_error("--draft-config and --draft-from must be given together")
        return EXIT_CONFIG_ERROR
    if args.draft_config is not None and args.gamma < 1:
        _emit_error(f"--gamma must be >= 1, got {args.gamma}")
        return EXIT_CONFIG_ERROR
    if args.draft_config is not None and args.logprobs:
        _emit_error("--logprobs is not supported with speculative decoding")
        return EXIT_CONFIG_ERROR

    # Fail fast on a bad prompts file — before the expensive registry/
    # tokenizer/model build, and with a clean error instead of a traceback.
    file_prompts: list[str] | None = None
    if args.prompts_file is not None:
        try:
            lines = Path(args.prompts_file).read_text(encoding="utf-8").splitlines()
        except OSError as exc:
            _emit_error(f"cannot read --prompts-file: {exc}")
            return EXIT_TRAIN_FAILURE
        file_prompts = [ln for ln in lines if ln.strip()]
        if not file_prompts:
            _emit_error(f"{args.prompts_file}: no non-empty prompt lines")
            return EXIT_TRAIN_FAILURE

    try:
        import jax
        import numpy as np

        from .generation import generate
        from .models.lora import build_adapter

        initialize_registries()
        adapter, tokenizer, model = _build_decode_stack(cfg, logger)

        prompts: list[str] | None = None  # text prompts (file mode keeps all)
        if args.prompt_ids is not None:
            prompt_batches = [
                np.asarray(
                    [int(t) for t in args.prompt_ids.split(",") if t.strip()],
                    dtype=np.int32,
                )
            ]
        else:
            if tokenizer is None:
                _emit_error(
                    "no tokenizer available for --prompt/--prompts-file; "
                    "pass --prompt-ids instead"
                )
                return EXIT_TRAIN_FAILURE
            prompts = file_prompts if file_prompts is not None else [args.prompt]
            prompt_batches = [
                np.asarray(tokenizer.encode(p), dtype=np.int32) for p in prompts
            ]
        if any(ids.size == 0 for ids in prompt_batches):
            _emit_error("every prompt must contain at least one token")
            return EXIT_TRAIN_FAILURE
        if args.draft_config is not None:
            # Fail fast on a prompt that cannot fit the speculative
            # buffer — before any checkpoint I/O.
            longest = max(len(ids) for ids in prompt_batches)
            need = longest + args.max_new_tokens + args.gamma + 1
            if need > cfg.model.block_size:
                _emit_error(
                    f"prompt+max_new_tokens+gamma ({need}) exceeds the "
                    f"target model's block_size ({cfg.model.block_size})"
                )
                return EXIT_CONFIG_ERROR

        model, params, ckpt_path, step = _load_decode_params(
            cfg,
            adapter,
            model,
            args.from_spec,
            ema=args.ema,
            decode_param_dtype=args.decode_param_dtype,
            quantize=args.quantize,
            logger=logger,
        )

        # --- speculative decoding: load the draft model, then decode each
        # prompt via draft-and-verify (speculative.py). Exact w.r.t. the
        # target: greedy output is bit-identical, sampling follows the
        # target's distribution.
        draft = None
        if args.draft_config is not None:
            try:
                draft_cfg, _, _ = load_and_validate_config(args.draft_config)
            except ConfigLoadError as exc:
                _emit_error(exc.message, details=exc.details, errors=exc.errors)
                return EXIT_CONFIG_ERROR
            draft_lora_err = _lora_spec_error(draft_cfg)
            if draft_lora_err is not None:
                _emit_error(draft_lora_err)
                return EXIT_CONFIG_ERROR
            # Same fail-fast bound as the target's, BEFORE checkpoint I/O.
            longest = max(len(ids) for ids in prompt_batches)
            need = longest + args.max_new_tokens + args.gamma + 1
            if need > draft_cfg.model.block_size:
                _emit_error(
                    f"prompt+max_new_tokens+gamma ({need}) exceeds the "
                    f"draft model's block_size ({draft_cfg.model.block_size})"
                )
                return EXIT_CONFIG_ERROR
            draft_adapter = build_adapter(draft_cfg)
            draft_model = draft_adapter.build_model(draft_cfg)
            draft_model, draft_params, _, _ = _load_decode_params(
                draft_cfg,
                draft_adapter,
                draft_model,
                args.draft_from,
                ema=False,
                decode_param_dtype=args.decode_param_dtype,
                quantize=args.quantize,
                logger=logger,
                label="draft ",
            )
            if draft_model.vocab_size != model.vocab_size:
                _emit_error(
                    f"draft vocab_size ({draft_model.vocab_size}) != target "
                    f"vocab_size ({model.vocab_size}) — speculative decoding "
                    "needs a shared vocabulary"
                )
                return EXIT_CONFIG_ERROR
            draft = (draft_model, draft_params)

        eos_token_id = args.eos_token_id
        if eos_token_id is None and tokenizer is not None:
            # tiktoken encodings expose the end-of-text id as eot_token.
            eos_token_id = getattr(tokenizer, "eot_token", None)

        # Batch per prompt length: generate() takes a rectangular (B, Tp)
        # batch, so equal-length prompts share ONE compiled decode loop.
        by_len: dict[int, list[int]] = {}
        for i, ids in enumerate(prompt_batches):
            by_len.setdefault(len(ids), []).append(i)
        results: list[dict] = [{} for _ in prompt_batches]
        for tp, idxs in sorted(by_len.items()):
            stacked = np.stack([prompt_batches[i] for i in idxs])
            group_lps = None
            if draft is not None:
                from .speculative import speculative_generate

                # speculative_generate is batch-1: decode the group's
                # rows one at a time (same compiled program per length).
                rows = [
                    speculative_generate(
                        model,
                        params,
                        draft[0],
                        draft[1],
                        stacked[row : row + 1],
                        max_new_tokens=args.max_new_tokens,
                        gamma=args.gamma,
                        temperature=args.temperature,
                        top_k=args.top_k if args.top_k > 0 else None,
                        # generate()'s convention: 0 or 1 disables nucleus.
                        top_p=(
                            args.top_p
                            if args.top_p is not None and 0 < args.top_p < 1
                            else None
                        ),
                        eos_token_id=eos_token_id,
                        # Two folds (group, then row): collision-free
                        # streams however large a prompt-length group is.
                        rng=jax.random.fold_in(
                            jax.random.fold_in(jax.random.key(args.seed), tp),
                            row,
                        ),
                    )
                    for row in range(stacked.shape[0])
                ]
                out = np.concatenate(rows, axis=0)
            else:
                gen_out = generate(
                    model,
                    params,
                    stacked,
                    max_new_tokens=args.max_new_tokens,
                    # Fold the length-group in so different groups don't draw
                    # from identical sample streams at each decode step.
                    rng=jax.random.fold_in(jax.random.key(args.seed), tp),
                    temperature=args.temperature,
                    top_k=args.top_k,  # generate() maps <=0 to "disabled"
                    top_p=args.top_p,
                    eos_token_id=eos_token_id,
                    return_logprobs=args.logprobs,
                )
                if args.logprobs:
                    out, group_lps = gen_out
                else:
                    out = gen_out
            for row, i in enumerate(idxs):
                output_ids = [int(t) for t in out[row]]
                results[i] = {
                    "prompt_ids": [int(t) for t in prompt_batches[i]],
                    "completion_ids": output_ids[tp:],
                    "output_ids": output_ids,
                    "text": (
                        tokenizer.decode(output_ids) if tokenizer is not None else None
                    ),
                }
                if args.logprobs and group_lps is not None:
                    results[i]["logprobs"] = [
                        round(float(x), 6) for x in group_lps[row]
                    ]
                if prompts is not None:
                    results[i]["prompt"] = prompts[i]

        if args.json:
            payload: dict[str, Any] = {"checkpoint": str(ckpt_path), "step": step}
            if args.prompts_file is not None:
                # File mode ALWAYS emits "results" (even for one line) so
                # consumers get a stable schema per input mode.
                payload["results"] = results
            else:
                payload.update(results[0])  # single-prompt contract unchanged
            print(json.dumps(payload))
        else:
            rendered = [
                r["text"]
                if r["text"] is not None
                else " ".join(str(t) for t in r["output_ids"])
                for r in results
            ]
            print("\n\n---\n\n".join(rendered))
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        logger.exception("generation failed: %s", exc)
        _emit_error(f"generation failed: {exc}")
        return exit_code_for_exception(exc)
    return EXIT_OK


def _handle_chaos(args: argparse.Namespace) -> int:
    """Seeded kill/resume drill over real train subprocesses.

    Exit 0 only when every cycle's invariants held AND the final trajectory
    is bitwise-identical to the uninterrupted reference; exit 1 when the
    crash-consistency contract broke (that is the signal this command
    exists to produce); exit 2 for config problems."""
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    if args.cycles < 1:
        _emit_error("--cycles must be >= 1")
        return EXIT_CONFIG_ERROR
    configure_platform(cfg.run.device)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    logger = get_logger()
    from .resilience.chaos import ChaosInvariantError, run_chaos

    try:
        result = run_chaos(
            args.config,
            cycles=args.cycles,
            seed=args.seed,
            max_steps=args.max_steps,
            save_every=args.save_every,
            work_dir=args.work_dir,
            timeout_sec=args.timeout_sec,
        )
    except ChaosInvariantError as exc:
        logger.error("chaos drill FAILED: %s", exc)
        _emit_error(f"chaos invariant violated: {exc}")
        return EXIT_TRAIN_FAILURE
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        logger.exception("chaos drill errored: %s", exc)
        _emit_error(f"chaos drill errored: {exc}")
        return exit_code_for_exception(exc)
    if args.json:
        print(json.dumps(result))
    else:
        print(
            f"chaos drill passed: {result['kills_delivered']} kill(s) "
            f"(incl. {result['kill_during_checkpoint_cycles']} inside a "
            f"checkpoint write) over {result['max_steps']} steps; "
            f"{result['trajectory_points_compared']} trajectory point(s) and "
            f"the final checkpoint are bitwise-identical to the "
            f"uninterrupted reference (final_loss="
            f"{result['final_loss']}); artifacts in {result['work_dir']}"
        )
        if result.get("goodput"):
            gp = result["goodput"]
            print(
                f"goodput: {gp['goodput_frac']:.4f} of {gp['wall_clock_sec']}s "
                f"wall-clock across {gp['num_segments']} segment(s) "
                f"(recomputed {gp['categories']['recomputed']}s, "
                f"restart_overhead {gp['categories']['restart_overhead']}s) — "
                "full ledger via `llmtrain goodput --run-dir "
                f"{result['work_dir']}/runs/chaos`"
            )
    return EXIT_OK


def _handle_goodput(args: argparse.Namespace) -> int:
    """Post-hoc goodput ledger for any past run directory.

    Pure artifact read (timeline.jsonl + manifests + heartbeat mtime):
    works with every process of the run dead, which is the point. Exit 0
    with the ledger; exit 1 when the run dir has no segment-delimited
    timeline (pre-ledger run or telemetry disabled)."""
    from pathlib import Path

    from .telemetry.goodput import compute_goodput, render_goodput_md

    run_dir = Path(args.run_dir)
    if not run_dir.is_dir():
        _emit_error(f"run dir not found: {run_dir}")
        return EXIT_CONFIG_ERROR
    ledger = compute_goodput(run_dir)
    if ledger is None:
        _emit_error(
            f"no goodput ledger for {run_dir}: telemetry/timeline.jsonl is "
            "missing or carries no segment headers (run predates the "
            "ledger, or telemetry.timeline was disabled)"
        )
        return EXIT_TRAIN_FAILURE
    if args.json:
        print(json.dumps(ledger))
    else:
        print(f"# Goodput — {run_dir}\n")
        print(render_goodput_md(ledger), end="")
    return EXIT_OK


def _handle_trace(args: argparse.Namespace) -> int:
    """Fleet-wide request-trace reassembly (telemetry/trace_collect.py).

    Pure artifact read, like ``goodput``: scans every --run-dir for
    ``*timeline*.jsonl``, rebuilds cross-process span trees from the
    tail-sampled ``cat="trace"`` events (router root → traceparent-
    propagated replica children), and answers slowest/show/summary/merge.
    Works with every fleet process dead."""
    from .telemetry.trace_collect import (
        collect_traces,
        critical_path,
        discover_sources,
        format_tree,
        merge_perfetto,
        slowest,
        summarize,
    )

    missing = [d for d in args.run_dirs if not Path(d).exists()]
    if missing:
        _emit_error(f"run dir(s) not found: {', '.join(missing)}")
        return EXIT_CONFIG_ERROR
    sources = discover_sources(args.run_dirs)
    if not sources:
        _emit_error(
            "no *timeline*.jsonl under the given --run-dir(s) — serve "
            "with --trace-dir (or point at a serve-bench out dir) so "
            "each process writes its timeline"
        )
        return EXIT_CONFIG_ERROR
    traces = collect_traces(sources)

    if args.action == "merge":
        out = Path(
            args.out or (Path(args.run_dirs[0]) / "merged_trace.json")
        )
        merge_perfetto(sources, out, traces=traces)
        unaligned = [s.label for s in sources if s.start_unix_time is None]
        if unaligned and any(s.start_unix_time is not None for s in sources):
            print(
                "warning: timeline(s) with no segment header could not be "
                f"time-aligned with the fleet: {', '.join(unaligned)} — "
                "their events are rebased to the merge start, so cross-"
                "process ordering against them is not meaningful",
                file=sys.stderr,
            )
        print(
            json.dumps(
                {
                    "merged": str(out),
                    "processes": [s.label for s in sources],
                    "traces": len(traces),
                    "unaligned": unaligned,
                    "viewer": "https://ui.perfetto.dev",
                },
                indent=None if args.json else 2,
            )
        )
        return EXIT_OK

    if not traces:
        _emit_error(
            "timelines found but no sampled request traces in them — "
            "only slow/errored/failed-over/forced requests keep full "
            "detail (tail sampling); force one with the `X-Trace: force` "
            "header or check telemetry.tracing.enabled"
        )
        return EXIT_TRAIN_FAILURE

    if args.action == "summary":
        print(json.dumps(summarize(traces), indent=None if args.json else 2))
        return EXIT_OK

    if args.action == "slowest":
        rows = []
        for tr in slowest(traces, k=args.k):
            root = tr.root
            rows.append(
                {
                    "trace_id": tr.trace_id,
                    "total_ms": round(tr.duration_ms, 3),
                    "root": root.name if root else None,
                    "spans": len(tr.spans),
                    "processes": tr.sources,
                    "sampled": (root.args.get("sampled") if root else None),
                    "request_id": (
                        root.args.get("request_id") if root else None
                    ),
                }
            )
        if args.json:
            print(json.dumps(rows))
        else:
            print(json.dumps(rows, indent=2))
        return EXIT_OK

    # show
    if not args.trace_id:
        _emit_error(
            "`trace show` needs a trace id (or unique prefix) — list "
            "candidates with `llmtrain trace slowest`"
        )
        return EXIT_CONFIG_ERROR
    matches = [
        t for t in traces.values() if t.trace_id.startswith(args.trace_id)
    ]
    if not matches:
        _emit_error(f"no trace matching {args.trace_id!r} in the run dirs")
        return EXIT_TRAIN_FAILURE
    if len(matches) > 1:
        _emit_error(
            f"trace id prefix {args.trace_id!r} is ambiguous "
            f"({len(matches)} matches) — give more hex digits"
        )
        return EXIT_CONFIG_ERROR
    tr = matches[0]
    path = critical_path(tr)
    if args.json:
        print(json.dumps({"tree": format_tree(tr), "critical_path": path}))
    else:
        for line in format_tree(tr):
            print(line)
        print()
        print(json.dumps(path, indent=2))
    return EXIT_OK


def _handle_fleet(args: argparse.Namespace) -> int:
    """Multi-tenant fleet supervisor / preemption-storm drill.

    Exit 0 when every tenant completed (and, under --storm, every parity
    and scheduling invariant held); exit 1 when a tenant failed or an
    invariant broke; exit 2 for config problems."""
    try:
        cfg, _, resolved = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    configure_platform(cfg.run.device)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    logger = get_logger()
    from .resilience.harness import DrillInvariantError

    try:
        if args.storm:
            from .fleet.chaos import run_fleet_storm

            result = run_fleet_storm(
                args.config,
                seed=args.seed,
                max_steps=args.max_steps,
                save_every=args.save_every,
                work_dir=args.work_dir,
                timeout_sec=args.timeout_sec,
                step_delay_sec=args.step_delay_sec,
            )
            if args.json:
                print(json.dumps(result))
            else:
                parities = {
                    n: r["parity"] for n, r in result["tenants"].items()
                }
                print(
                    f"fleet storm passed: {result['total_evictions']} "
                    f"eviction(s) (mid-checkpoint kill on "
                    f"{result['mid_checkpoint_kill_tenant']}), "
                    f"{result['total_respawns']} respawn(s), "
                    f"{result['capacity_changes']} capacity change(s) across "
                    f"{len(result['tenants'])} tenant(s); per-tenant parity "
                    f"{parities}; artifacts in {result['work_dir']}"
                )
            return EXIT_OK

        from .fleet.supervisor import FleetSupervisor

        work_dir = args.work_dir or str(
            Path(cfg.output.root_dir) / f"fleet_{cfg.run.name}"
        )
        try:
            sup = FleetSupervisor(
                cfg,
                resolved,
                work_dir=work_dir,
                seed=args.seed,
                max_steps=args.max_steps,
                save_every=args.save_every,
                fresh=args.fresh,
            )
        except ValueError as exc:
            # Constructor-time validation only (no tenants, wrong device,
            # infeasible world sizes): deterministic config problems. A
            # ValueError INSIDE the run is a runtime failure and takes the
            # taxonomy path below.
            _emit_error(str(exc))
            return EXIT_CONFIG_ERROR
        try:
            report = sup.run(timeout_sec=args.timeout_sec)
        except DrillInvariantError:
            raise  # the outer handler maps it to EXIT_TRAIN_FAILURE
        except Exception as exc:  # noqa: BLE001 — run-time, NOT config
            # Includes ValueError: past construction, nothing about the
            # config is in question — route through the taxonomy instead
            # of the outer config-error mapping.
            logger.exception("fleet run errored: %s", exc)
            _emit_error(f"fleet run errored: {exc}")
            return exit_code_for_exception(exc)
        if args.json:
            print(json.dumps(report))
        else:
            print(
                f"fleet run finished: {report['totals']['completed']}/"
                f"{len(report['tenants'])} tenant(s) completed, "
                f"{report['totals']['evictions']} eviction(s), "
                f"{report['totals']['respawns']} respawn(s); report in "
                f"{sup.work_dir / 'fleet_report.json'}"
            )
        return EXIT_OK if report["totals"]["failed"] == 0 else EXIT_TRAIN_FAILURE
    except DrillInvariantError as exc:
        logger.error("fleet invariant violated: %s", exc)
        _emit_error(f"fleet invariant violated: {exc}")
        return EXIT_TRAIN_FAILURE
    except ValueError as exc:
        # Storm pre-run validation (tenant count, infeasible fault
        # windows, supervisor construction) raises ValueError before any
        # subprocess launches — deterministic config problems.
        _emit_error(str(exc))
        return EXIT_CONFIG_ERROR
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        logger.exception("fleet run errored: %s", exc)
        _emit_error(f"fleet run errored: {exc}")
        return exit_code_for_exception(exc)


def _handle_profile(args: argparse.Namespace) -> int:
    """N-step cost probe → ``profile_report.json`` (docs/observability.md).

    Runs ``--steps`` real training steps on the config (run-dir-less, so
    no checkpoints/reports are written), then AOT-lowers AND -compiles the
    jitted train step to mine XLA's cost_analysis, the per-op HLO table,
    compile wall-times and the compiled memory footprint — the probe-run
    signal ``llmtrain tune`` (ROADMAP item 3) will sweep over. ``--serve``
    additionally profiles the paged prefill/decode programs at their
    largest shape buckets against abstract parameters (no checkpoint
    needed; nothing executes).
    """
    try:
        cfg, _, _ = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    if args.steps < 1:
        _emit_error("--steps must be >= 1")
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_compilation_cache(cfg.run.compilation_cache_dir)
    configure_logging(level=cfg.logging.level, json_output=cfg.logging.json_output)
    logger = get_logger()
    initialize_registries()
    try:
        get_model_adapter(cfg.model.name)
        get_data_module(cfg.data.name)
    except RegistryError as exc:
        _emit_error(str(exc))
        return EXIT_CONFIG_ERROR

    # Probe config: N steps, every boundary logged, no endpoint bind, no
    # competing report/attribution work (the profile builds its own).
    # Config models are frozen — rebuild through validation.
    dump = cfg.model_dump()
    dump["trainer"]["max_steps"] = args.steps
    dump["trainer"]["log_every_steps"] = 1
    dump["telemetry"]["prometheus"] = False
    dump["telemetry"]["report"] = False
    dump["telemetry"]["perf_attribution"] = False
    probe_cfg = type(cfg).model_validate(dump)

    import jax

    from .telemetry import profiling
    from .training import Trainer
    from .utils.hw import transformer_flops_per_token

    try:
        trainer = Trainer(probe_cfg, run_dir=None, tracker=None)
        t0 = time.perf_counter()
        result = trainer.fit()
        probe_wall = time.perf_counter() - t0
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        logger.exception("profile probe run failed: %s", exc)
        _emit_error(f"profile probe run failed: {exc}")
        return exit_code_for_exception(exc)

    peaks = profiling.resolve_peaks(None, cfg.telemetry.device_peaks)
    latest = {k: v[0] for k, v in trainer._telemetry.metrics.latest().items()}
    step_time_sec = latest.get("train/step_time_sec") or 0.0
    run_key = jax.random.key(cfg.run.seed)

    executables: list[dict[str, Any]] = []
    if trainer._batch_struct is not None:
        train_prof = profiling.aot_profile(
            trainer._jit_train_step,
            (trainer._state, trainer._batch_struct, run_key),
            name="train_step",
            peaks=peaks,
            collective_bytes=profiling.gradient_collective_bytes(
                {a: s for a, s in trainer._mesh.shape.items()},
                float(trainer._trainable_count) * 4.0,
            ),
            top_k=args.top_k,
            n_chips=int(trainer._mesh.devices.size),
        )
        if train_prof is not None:
            executables.append(train_prof)

    if args.serve:
        executables += _profile_serving_buckets(
            cfg, peaks=peaks, top_k=args.top_k, logger=logger
        )

    if not executables:
        _emit_error("no executable could be profiled (see logs)")
        return EXIT_TRAIN_FAILURE

    palm = transformer_flops_per_token(
        n_params=trainer._param_count,
        n_layers=cfg.model.n_layers,
        seq_len=trainer._train_seqlen,
        d_model=cfg.model.d_model,
        n_trainable_params=trainer._trainable_count,
    )
    attribution = profiling.build_perf_attribution(
        executables=executables,
        peaks=peaks,
        n_chips=int(trainer._mesh.devices.size),
        step_time_ms=step_time_sec * 1e3 if step_time_sec > 0 else None,
        tokens_per_step=float(trainer._tokens_per_step) or None,
        palm_flops_per_token=palm,
        measured_mfu=latest.get("train/mfu"),
        span_totals=trainer._telemetry.timeline.span_totals(),
        steps=args.steps,
    )

    # HBM footprint, two views side by side: the memory monitor's live
    # accounting during the probe vs the compiled executable's static
    # buffer analysis — disagreement localizes fragmentation/runtime
    # overhead vs model-inherent footprint.
    memory_block: dict[str, Any] = {}
    if trainer._telemetry.memory is not None:
        memory_block["monitor_peaks"] = dict(trainer._telemetry.memory.peaks())
        memory_block["monitor_source"] = trainer._telemetry.memory.source
    primary_mem = (executables[0].get("memory") or {}) if executables else {}
    if primary_mem:
        memory_block["compiled_train_step"] = primary_mem

    report = {
        "schema": "llmtrain-profile-report/1",
        "config": str(args.config),
        "run_name": cfg.run.name,
        "device_kind": peaks.get("device_kind", "unknown"),
        "n_devices": int(trainer._mesh.devices.size),
        "peaks": {k: peaks[k] for k in ("peak_flops", "hbm_bytes_per_sec", "ici_bytes_per_sec")},
        "probe": {
            "steps": args.steps,
            "wall_time_sec": round(probe_wall, 3),
            "step_time_ms": round(step_time_sec * 1e3, 3),
            "tokens_per_sec": latest.get("train/tokens_per_sec"),
            "mfu_measured": latest.get("train/mfu"),
            "final_loss": result.final_loss,
        },
        "executables": executables,
        "perf_attribution": attribution,
        "memory": memory_block,
    }

    if args.output is not None:
        out_path = Path(args.output)
    else:
        out_path = (
            Path(cfg.output.root_dir)
            / f"profile_{cfg.run.name}"
            / "profile_report.json"
        )
    try:
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(
            json.dumps(report, indent=2, sort_keys=False), encoding="utf-8"
        )
    except (OSError, TypeError, ValueError) as exc:
        _emit_error(f"writing {out_path} failed: {exc}")
        return EXIT_TRAIN_FAILURE

    if args.json:
        print(json.dumps(report, indent=2))
    else:
        lines = [f"profile report: {out_path}"]
        for exe in executables:
            roof = exe.get("roofline") or {}
            lines.append(
                f"  {exe['name']}: {exe.get('flops', 0.0):.3g} flops, "
                f"{exe.get('bytes_accessed', 0.0):.3g} bytes, "
                f"compile {exe.get('compile_time_s', 0.0):.2f}s → "
                f"{roof.get('class', '?')}-bound"
            )
            for row in profiling.render_top_ops_markdown(exe.get("top_ops") or []):
                lines.append("    " + row)
        mfu_block = attribution.get("mfu") or {}
        if mfu_block:
            lines.append(
                f"  MFU analytical {mfu_block.get('analytical')} vs measured "
                f"{mfu_block.get('measured')} (ratio "
                f"{mfu_block.get('ratio_analytical_over_measured')}, "
                f"reconciled: {mfu_block.get('reconciled')})"
            )
        print("\n".join(lines))
    return EXIT_OK


def _profile_serving_buckets(
    cfg, *, peaks: dict[str, float], top_k: int, logger
) -> list[dict[str, Any]]:
    """AOT profiles of the paged prefill/decode programs, checkpoint-free.

    The engine's :meth:`cost_profile` only reads parameter SHAPES, so an
    ``eval_shape`` of ``model.init`` stands in for real weights — zero
    init work, nothing executes. Failures degrade to an empty list (the
    train-step profile stands on its own).
    """
    try:
        import jax
        import jax.numpy as jnp

        from .serving import PagedDecodeEngine

        adapter, _, model = _build_decode_stack(cfg, logger, label="profile: ")
        if not hasattr(model, "for_paged_decoding"):
            logger.warning(
                "model %s has no paged-decoding support; skipping serve profiles",
                cfg.model.name,
            )
            return []
        variables = jax.eval_shape(
            lambda: model.init(
                jax.random.key(0),
                jnp.zeros((1, int(model.block_size)), jnp.int32),
                deterministic=True,
            )
        )
        scfg = cfg.serving
        engine = PagedDecodeEngine(
            model,
            variables["params"],
            block_tokens=scfg.block_tokens,
            num_blocks=scfg.num_blocks or None,
            max_batch_slots=scfg.max_batch_slots,
            prompt_buckets=scfg.prompt_buckets or None,
            batch_buckets=scfg.batch_buckets or None,
        )
        return engine.cost_profile(peaks=peaks, top_k=top_k)
    except Exception as exc:  # noqa: BLE001 — serve profiles are additive
        logger.warning("serving bucket profile failed: %s", exc)
        return []


def _handle_train(args: argparse.Namespace) -> int:
    try:
        cfg, _, resolved = load_and_validate_config(args.config)
    except ConfigLoadError as exc:
        _emit_error(exc.message, details=exc.details, errors=exc.errors)
        return EXIT_CONFIG_ERROR
    lora_err = _lora_spec_error(cfg)
    if lora_err is not None:
        _emit_error(lora_err)
        return EXIT_CONFIG_ERROR

    configure_platform(cfg.run.device)
    configure_compilation_cache(cfg.run.compilation_cache_dir)
    dist_state: DistState | None = None
    if cfg.distributed.enabled:
        # Rendezvous against a coordinator that is still coming up (k8s pods
        # start in arbitrary order) is retried with exponential backoff
        # instead of failing the pod; the flaky() wrapper is the
        # fault-injection hook exercising this path in tests.
        from .distributed import resolve_topology
        from .resilience import FaultPlan, retry, retry_rng

        plan = FaultPlan.from_config(cfg.resilience.faults)
        # Full-jitter backoff seeded per (run seed, rank): every pod of a
        # Job retries the coordinator on its own decorrelated schedule —
        # synchronized ladders are exactly how a transient rendezvous blip
        # becomes a repeated thundering herd. The rank comes from the SAME
        # resolution setup_distributed uses (resolve_topology: JAX-native
        # env beats torch-style env beats config) so per-rank
        # decorrelation holds on every deployment flavor; a topology too
        # broken to resolve falls back to rank 0 and lets the retried
        # setup_distributed surface the real error.
        try:
            rank_hint, _, _ = resolve_topology(cfg.distributed)
        except Exception:  # noqa: BLE001 — jitter seeding must not mask it
            rank_hint = 0
        try:
            dist_state = retry(
                plan.flaky(
                    "distributed_init", lambda: setup_distributed(cfg.distributed)
                ),
                attempts=cfg.resilience.retry_attempts,
                base_delay=cfg.resilience.retry_base_delay,
                description="distributed init",
                rng=retry_rng(cfg.run.seed, rank_hint),
            )
        except ValueError as exc:
            # Topology/coordinator misconfiguration (resolve_topology and
            # setup_distributed raise ValueError for these) is deterministic
            # — restarting the pod replays it, so fail the Job fast.
            _emit_error(f"distributed init failed: {exc}")
            return EXIT_CONFIG_ERROR
        except Exception as exc:  # noqa: BLE001 — CLI boundary
            # Everything else at the rendezvous stage is environmental
            # (coordinator pod still scheduling, DNS not propagated,
            # timeout): exit EX_TEMPFAIL so the orchestrator restarts this
            # pod instead of failing the whole Job (k8s/job.yaml
            # podFailurePolicy).
            _emit_error(f"distributed init failed: {exc}")
            return EXIT_RETRYABLE_INFRA
    is_main = dist_state is None or dist_state.is_main

    logger = get_logger()
    tracker: Tracker = NullTracker()
    exit_code = EXIT_OK
    tracker_started = False
    try:
        run_id = args.run_id or cfg.output.run_id
        if args.auto_resume and run_id is None:
            _emit_error(
                "--auto-resume requires a stable run id (--run-id or output.run_id): "
                "a generated id is fresh on every restart"
            )
            return EXIT_CONFIG_ERROR
        if run_id is None:
            run_id = generate_run_id(cfg.run.name, cfg.output.root_dir)
        run_id = _agree_run_id(run_id, dist_state)

        # Rank-0-only I/O: non-main ranks never touch the run dir
        # (reference cli.py:246-248, trainer.py:402-406). All ranks must
        # agree on the outcome — if only rank 0 bailed here, the other ranks
        # would run on into the first collective and hang until timeout.
        run_dir: Path | None = None
        run_dir_ok = True
        resuming_existing = False
        if is_main:
            try:
                run_dir = create_run_directory(cfg.output.root_dir, run_id)
            except FileExistsError:
                if args.auto_resume:
                    # Preemption restart: reuse the dir, continue from its
                    # latest checkpoint if one exists (new capability — the
                    # reference only has manual --resume, SURVEY §5).
                    run_dir = Path(cfg.output.root_dir) / run_id
                    (run_dir / "logs").mkdir(parents=True, exist_ok=True)
                    from .training.checkpoint import CheckpointManager

                    resuming_existing = (
                        CheckpointManager(run_dir / "checkpoints").latest_checkpoint()
                        is not None
                    )
                else:
                    run_dir_ok = False
        if not _agree_flag(run_dir_ok, dist_state):
            if is_main:
                _emit_error(
                    f"run directory already exists for run id {run_id!r}",
                    details="pass a fresh --run-id or let the run id be generated",
                )
            return EXIT_TRAIN_FAILURE
        resume_spec = args.resume
        if _agree_flag(resuming_existing, dist_state):
            # Unambiguous dir spec, computable on every rank. (A bare run id
            # would first be tried as a CWD-relative path by
            # resolve_resume_path and can collide with unrelated entries.)
            resume_spec = str(Path(cfg.output.root_dir) / run_id / "checkpoints")

        log_file = None
        if cfg.logging.log_to_file and run_dir is not None:
            log_file = run_dir / "logs" / cfg.logging.file_name
        level = "DEBUG" if args.verbose else cfg.logging.level
        # Under --json, all logs go to stderr so stdout stays machine-parseable
        # (reference cli.py:281-288). Logs already default to stderr.
        configure_logging(
            level=level, json_output=cfg.logging.json_output, log_file=log_file
        )

        if run_dir is not None:
            if cfg.output.save_config_copy:
                write_resolved_config(run_dir, resolved)
            if cfg.output.save_meta_json:
                meta = generate_meta(
                    run_id=run_id,
                    run_name=cfg.run.name,
                    config_path=args.config,
                    resolved_config_path=run_dir / "config.yaml",
                )
                write_meta_json(run_dir, meta)

        initialize_registries()
        _warn_unknown_extras(cfg)
        try:
            get_model_adapter(cfg.model.name)
            get_data_module(cfg.data.name)
        except RegistryError as exc:
            _emit_error(str(exc))
            return EXIT_CONFIG_ERROR

        tracker = _create_tracker(cfg, dist_state, run_id)
        tracker.start_run(run_id, cfg.mlflow.run_name)
        tracker_started = True

        if args.dry_run:
            from .training import run_dry_run

            dry_result = run_dry_run(cfg)
            summary = format_run_summary(
                cfg,
                run_id=run_id,
                run_dir=str(run_dir) if run_dir else None,
                dry_run=True,
                dry_run_result=dry_result,
                as_json=args.json,
            )
        else:
            from .training import Trainer

            # Non-main ranks get the run-dir PATH too (never created or
            # written by them — every write stays rank-0-gated inside the
            # Trainer): on the shared runs volume it gives all ranks a
            # readable checkpoint dir, which is what makes the loss-spike
            # rollback consensus restore the same file on every host.
            trainer_run_dir = run_dir
            if (
                run_dir is None
                and dist_state is not None
                and dist_state.num_processes > 1
            ):
                trainer_run_dir = Path(cfg.output.root_dir) / run_id
            trainer = Trainer(cfg, trainer_run_dir, tracker, dist_state)
            result = trainer.fit(resume_from=resume_spec)
            summary = format_run_summary(
                cfg,
                run_id=run_id,
                run_dir=str(run_dir) if run_dir else None,
                dry_run=False,
                train_result=result,
                as_json=args.json,
            )
        if is_main:
            print(json.dumps(summary) if args.json else summary)
            _log_run_artifacts(tracker, run_dir)
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        logger.exception("training failed: %s", exc)
        # Taxonomy (resilience/exit_codes.py): transient infra causes exit
        # EX_TEMPFAIL-style retryable codes; deterministic failures exit
        # fatal so the orchestrator does not replay them.
        exit_code = exit_code_for_exception(exc)
        _emit_error(f"training failed: {exc} (exit {exit_code})")
    finally:
        try:
            if tracker_started:
                tracker.end_run("FINISHED" if exit_code == EXIT_OK else "FAILED")
        finally:
            teardown_distributed()
    return exit_code


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.command == "train":
        return _handle_train(args)
    if args.command == "chaos":
        return _handle_chaos(args)
    if args.command == "fleet":
        return _handle_fleet(args)
    if args.command == "generate":
        return _handle_generate(args)
    if args.command == "serve":
        return _handle_serve(args)
    if args.command == "serve-bench":
        return _handle_serve_bench(args)
    if args.command == "promote":
        return _handle_promote(args)
    if args.command == "eval":
        return _handle_eval(args)
    if args.command == "train-tokenizer":
        return _handle_train_tokenizer(args)
    if args.command == "export-checkpoint":
        return _handle_export_checkpoint(args)
    if args.command == "import-checkpoint":
        return _handle_import_checkpoint(args)
    if args.command == "average-checkpoints":
        return _handle_average_checkpoints(args)
    if args.command == "profile":
        return _handle_profile(args)
    if args.command == "plan":
        return _handle_plan(args)
    if args.command == "tune":
        return _handle_tune(args)
    if args.command == "goodput":
        return _handle_goodput(args)
    if args.command == "trace":
        return _handle_trace(args)
    if args.command == "validate":
        return _handle_validate(args)
    if args.command == "print-config":
        return _handle_print_config(args)
    parser.error(f"unknown command {args.command!r}")
    return EXIT_CONFIG_ERROR


if __name__ == "__main__":
    sys.exit(main())

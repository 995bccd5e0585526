.PHONY: test test-all lint verify-resilience verify-watchdog verify-prefetch verify-telemetry verify-elastic verify-serving verify-router verify-promote verify-overload verify-trace verify-zero verify-fleet verify-profile verify-quant verify-fusedce verify-goodput verify-tune verify-offload train-smoke train-multiproc \
	chip-smoke mlflow \
	k8s-cluster k8s-cluster-delete k8s-build k8s-train k8s-serve k8s-fleet k8s-logs k8s-clean \
	k8s-full k8s-e2e

# -n auto: xdist parallelism scales the gate to the host (1 worker on a
# 1-core box, 8+ on CI); the persistent compilation cache (conftest.py)
# is shared across workers, so compile-heavy tests pay each shape once.
test:
	python -m pytest tests/ -q -m "not slow" -n auto

test-serial:
	python -m pytest tests/ -q -m "not slow"

# Fast fault-injection suite: every resilience recovery path (non-finite
# guard, spike rollback, checkpoint integrity, SIGTERM, retry) end to end.
# These tests are deliberately unmarked so plain `make test` runs them too.
verify-resilience:
	JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py \
		tests/test_checkpoint.py tests/test_preemption.py -q -m "not slow"

# Hang watchdog + exit-code taxonomy suite: injected REAL host hang killed
# with a retryable exit + all-thread stack report, heartbeat freshness,
# straggler telemetry, bounded drain of a wedged checkpoint write.
verify-watchdog:
	JAX_PLATFORMS=cpu python -m pytest tests/test_watchdog.py -q -m "not slow"

# Async input pipeline suite: prefetch-on/off loss bitwise equality (incl.
# resume and spike-rollback replay), SIGTERM shutdown with a full queue,
# watchdog catching a hang injected inside the prefetch thread, and the
# compilation-cache dir resolution precedence.
verify-prefetch:
	JAX_PLATFORMS=cpu python -m pytest tests/test_prefetch.py -q -m "not slow"

# Crash consistency + elastic resume suite (docs/robustness.md): atomic
# manifest commits, orphan-stage GC, pre-manifest migration, emulated
# world-size-change resume, topology-mismatch exit codes — PLUS the seeded
# chaos harness (5 SIGKILL/resume cycles incl. one inside the async
# checkpoint write, bitwise-parity against an uninterrupted reference).
# The chaos drills are @pytest.mark.slow so plain `make test` skips them;
# this target runs everything except the env-gated soak
# (LLMTRAIN_CHAOS_SOAK=1 enables it).
verify-elastic:
	JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py -q

# ZeRO sharded-optimizer-state suite (docs/perf.md "Sharded optimizer
# state"): opt_state_shardings partition specs, bitwise loss-trajectory
# parity zero on/off (stage 1) incl. host offload, checkpoint round-trips
# zero<->non-zero, elastic ws2<->ws1 resume with sharded state, the
# indivisible-leaf replicated fallback warning, and the report.json
# opt_state_bytes accounting. Includes the @pytest.mark.slow cases plain
# `make test` skips.
verify-zero:
	JAX_PLATFORMS=cpu python -m pytest tests/test_zero.py -q

# Multi-tenant fleet suite (docs/robustness.md "Fleet: many tenants,
# shared capacity"): the deterministic scheduling-policy tables, tenant
# state machine, and SIGTERM->SIGKILL escalation ladder units — PLUS the
# @pytest.mark.slow drills plain `make test` skips: the 3-tenant seeded
# preemption storm (capacity drop + evictions + one mid-checkpoint kill,
# per-tenant bitwise parity vs uninterrupted references), the
# twice-evicted resume_count==2 fairness pin, the elastic 1->2-device
# resize, and the `llmtrain fleet` CLI round-trip.
verify-fleet:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py -q

# Telemetry subsystem suite (docs/observability.md): runs a real smoke fit
# and asserts report.json + report.md + a Perfetto-loadable trace.json are
# produced, train/mfu + mem/hbm_peak + span metrics land in the tracker AND
# in a live Prometheus scrape, timeline rollback tagging, and the
# failing-tracker degrade-to-warning regression.
verify-telemetry:
	JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py -q -m "not slow"

# Cost-attribution + roofline suite (docs/observability.md "Attribution and
# rooflines"): XLA cost-table extraction, HLO top-ops parsing, roofline
# classification, MFU reconciliation and serve-latency percentile gauges.
# The slow e2e pieces (fit-path attribution, `llmtrain profile` CLI) ride
# `make test-all`.
verify-profile:
	JAX_PLATFORMS=cpu python -m pytest tests/test_profiling.py -q -m "not slow"

# Mesh planner + auto-tuner suite (docs/perf.md "Mesh planning and
# auto-tuning"): wildcard/divisibility plan resolution, capability rules,
# dominated-candidate pruning with reasons, deterministic seeded candidate
# order, and the `llmtrain plan` exit-code contract. The @pytest.mark.slow
# probe-fit e2e and tune->train round-trip ride `make test-all`.
verify-tune:
	JAX_PLATFORMS=cpu python -m pytest tests/test_autotune.py -q -m "not slow"

# Quantized-training suite (docs/perf.md "Quantized training"):
# per-channel scale/STE-vjp units, QuantDense-vs-Dense drop-in parity,
# knob validation + fp8 capability fallback, chunked-CE auto-select —
# PLUS the @pytest.mark.slow fits plain `make test` skips: int8-vs-f32
# N-step loss-parity on a tiny GPT, grad-finiteness under the non-finite
# guard, and the checkpoint/elastic-resume round-trip with
# matmul_precision int8.
verify-quant:
	JAX_PLATFORMS=cpu python -m pytest tests/test_quant_train.py -q

# Fused lm-head + CE suite (docs/perf.md "Fused lm-head + CE"):
# interpret-mode Pallas kernel parity (fwd per-token loss + dhidden/dW)
# vs chunked_ce and dense across tied/untied heads, z_loss on/off and
# non-block-multiple shapes, the fused residual-add+LayerNorm kernel,
# loss_impl/fused_norm resolution + capability fallbacks, and the
# planner's logits-buffer accounting — PLUS the @pytest.mark.slow fits
# plain `make test` skips: 5-step fused-vs-dense loss parity, the
# checkpoint resume with loss_impl flipped across the boundary, and the
# attribution pin (no dot materializes the [B,T,V] logits under
# fused_ce).
verify-fusedce:
	JAX_PLATFORMS=cpu python -m pytest tests/test_fused_ce.py -q

# Activation-tier suite (docs/perf.md "Activation tiers and host
# offload"): spec grammar, per-layer jaxpr remat boundaries, forward
# bitwise parity, the remat->tiers deprecation shim, the per-tier HBM
# model + ladder enumeration, and the @slow Trainer fits (offload
# fallback warning, resume with tiers changed).
verify-offload:
	JAX_PLATFORMS=cpu python -m pytest tests/test_activation_tiers.py -q

# Goodput-ledger suite (docs/observability.md "Goodput"): synthetic-
# timeline taxonomy tables (exact second splits), the ledger-balances
# invariant through the real Telemetry facade + `llmtrain goodput` CLI,
# suspension-window carving — PLUS the @pytest.mark.slow drills plain
# `make test` skips: a mid-interval SIGKILL leaving a torn timeline that
# still balances, the 3-cycle chaos drill with recomputed_sec > 0 and
# post-mortem CLI reproducibility, and the fleet-storm goodput floor.
verify-goodput:
	JAX_PLATFORMS=cpu python -m pytest tests/test_goodput.py -q

# Continuous-batching serving suite (docs/serving.md): paged-KV pool
# invariants, batched-vs-generate() bitwise parity (greedy, per-request
# sampled knobs, speculative policy), bounded compile budget, continuous
# join/evict, the seeded open-loop load soak, and the full CLI round-trip
# (train -> serve-bench --verify-parity -> serve over HTTP). Includes the
# @pytest.mark.slow soaks plain `make test` skips.
verify-serving:
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving_engine.py \
		tests/test_serving.py -q

# Fleet serving tier (docs/serving.md "Fleet tier"): prefix-cache
# content addressing + refcount/COW/eviction invariants, router
# placement/affinity/eviction/failover, chunked prefill, checkpoint
# hot-swap epoch pinning, batched speculative parity — plus the
# @pytest.mark.slow 2-replica drill (mid-drill rolling hot swap, zero
# failed requests, bitwise parity on the params each request was
# admitted under) that plain `make test` skips.
verify-router:
	JAX_PLATFORMS=cpu python -m pytest tests/test_router.py -q

# Promotion-lifecycle drill (docs/robustness.md "Canary, promote,
# rollback"): ledger replay/idempotence, checkpoint-watch edge cases,
# controller decision units — plus the @pytest.mark.slow chaos drill
# (poisoned checkpoint canaried on a real 2-replica fleet, detected,
# rolled back with zero failed requests and bitwise parity on the
# admitted params; clean checkpoint promotes fleet-wide, every
# transition durable in promotions.jsonl) that plain `make test` skips.
verify-promote:
	JAX_PLATFORMS=cpu python -m pytest tests/test_promote.py -q

# Overload-control drill (docs/serving.md "Overload and SLOs"): token
# buckets, EWMA admission, weighted-class queue, brownout hysteresis,
# retry budget, shed-mid-prefill pool accounting — plus the
# @pytest.mark.slow seeded 10x-burst drill against a 2-replica router
# (fast 429s with the documented reason taxonomy, bitwise parity on
# accepted requests, brownout entry AND exit, exact pool accounting)
# that plain `make test` skips.
verify-overload:
	JAX_PLATFORMS=cpu python -m pytest tests/test_overload.py -q

# Distributed-tracing drill (docs/observability.md "Distributed request
# tracing"): traceparent round-trips, tail-sampling decisions, tracer
# flush, collector tree assembly — plus the @pytest.mark.slow 2-replica
# HTTP fleet drill (one forced failover; the merged trace must
# reconstruct the router→replica span tree via the propagated
# traceparent, the critical path must tile the end-to-end latency, and
# /metrics must carry exemplar trace ids) that plain `make test` skips.
verify-trace:
	JAX_PLATFORMS=cpu python -m pytest tests/test_tracing.py tests/test_trace_e2e.py -q

# Static gate (reference: pre-commit ruff+mypy, .pre-commit-config.yaml:1-24).
# Runs ruff+mypy when installed; otherwise the stdlib fallback checker.
lint:
	@if python -c "import ruff" 2>/dev/null; then \
		python -m ruff format --check llmtrain_tpu tests && \
		python -m ruff check llmtrain_tpu tests; \
	elif command -v ruff >/dev/null; then \
		ruff format --check llmtrain_tpu tests && \
		ruff check llmtrain_tpu tests; \
	else \
		echo "ruff not installed; using stdlib fallback"; \
	fi
	@if python -c "import mypy" 2>/dev/null; then \
		python -m mypy --config-file=pyproject.toml llmtrain_tpu; \
	else \
		echo "mypy not installed; using stdlib fallback"; \
	fi
	@JAX_PLATFORMS=cpu python tools/static_check.py

test-all:
	python -m pytest tests/ -q

train-smoke:
	JAX_PLATFORMS=cpu python -m llmtrain_tpu train --config configs/presets/gpt_smoke.yaml

# Two real OS processes forming a JAX distributed runtime on localhost
# (the analogue of the reference's `torchrun --nproc_per_node=2`).
train-multiproc:
	JAX_PLATFORMS=cpu WORLD_SIZE=2 MASTER_ADDR=127.0.0.1 MASTER_PORT=29511 \
		bash -c 'RANK=1 python -m llmtrain_tpu train --config configs/presets/ddp_smoke.yaml & \
		RANK=0 python -m llmtrain_tpu train --config configs/presets/ddp_smoke.yaml; wait'

# GPipe pipeline parallelism on the 8-virtual-device CPU mesh.
train-pipeline:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m llmtrain_tpu train --config configs/presets/gpt_pipeline_smoke.yaml

# Mixture-of-Experts with a 4-way expert-parallel mesh axis.
train-moe:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m llmtrain_tpu train --config configs/presets/gpt_moe_smoke.yaml

# On-chip bring-up proof (needs one TPU chip; exits nonzero without one).
chip-smoke:
	python chip_smoke.py

mlflow:
	mlflow ui --backend-store-uri sqlite:///./mlflow.db

# --------------------------------------------------------------------------
# Kubernetes (kind) targets
# --------------------------------------------------------------------------

k8s-cluster:
	mkdir -p runs mlflow-k8s
	kind create cluster --name llmtrain-tpu --config k8s/kind-config.yaml

k8s-cluster-delete:
	kind delete cluster --name llmtrain-tpu

k8s-build:
	docker build -t llmtrain-tpu:dev -f k8s/Dockerfile .
	kind load docker-image llmtrain-tpu:dev --name llmtrain-tpu

k8s-train:
	kubectl apply -f k8s/infra.yaml -f k8s/configmap.yaml -f k8s/job.yaml

# Inference tier (docs/serving.md): Deployment + Service serving the
# training Job's committed checkpoint with continuous batching.
k8s-serve:
	kubectl apply -f k8s/infra.yaml -f k8s/configmap.yaml -f k8s/serve.yaml

# Multi-tenant fleet supervisor Job (docs/robustness.md "Fleet: many
# tenants, shared capacity"): one pod schedules the ConfigMap's fleet
# tenants onto an emulated device pool with preemption-aware scheduling.
k8s-fleet:
	kubectl apply -f k8s/infra.yaml -f k8s/configmap.yaml -f k8s/fleet.yaml

k8s-logs:
	kubectl logs -l app=llmtrain-tpu --all-containers --prefix -f

k8s-clean:
	kubectl delete -f k8s/job.yaml -f k8s/configmap.yaml -f k8s/infra.yaml \
		--ignore-not-found

k8s-full: k8s-cluster k8s-build k8s-train k8s-logs

k8s-e2e:
	bash k8s/test_e2e.sh

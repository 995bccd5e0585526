#!/usr/bin/env bash
# Docker-free end-to-end run: the REAL k8s/entrypoint.sh drives the REAL
# CLI as two "pods", then k8s/assertions.sh is applied to the produced
# logs and artifacts — the closest executable thing to k8s/test_e2e.sh on
# a host with no Docker daemon (this image ships no docker/kind/kubectl). What is real here: the entrypoint's
# JOB_COMPLETION_INDEX/NUM_PROCESSES contract, coordinator discovery
# through the Kubernetes API codepath (curl + serviceaccount files —
# stubbed at the network edge only), the 2-process JAX rendezvous, the
# GPT training run, rank-0-only artifacts, the sqlite tracking DB, and
# every assertion test_e2e.sh would run. What is simulated: the cluster
# (processes instead of pods), the image build, and WikiText-2 (offline
# host -> local_text over the repo's own docs/tests as the corpus,
# byte tokenizer; same model family and mesh as k8s/configmap.yaml).
#
#   bash k8s/test_e2e_local.sh [out_dir]   # default runs/e2e_local
set -euo pipefail

cd "$(dirname "$0")/.."
OUT="${1:-runs/e2e_local}"
STEPS="${LLMTRAIN_E2E_STEPS:-60}"
PROM_PORT="${LLMTRAIN_E2E_PROM_PORT:-9237}"
FAILURES=0

say() { printf '==> %s\n' "$*"; }
. k8s/assertions.sh

rm -rf "$OUT"
mkdir -p "$OUT/volume/runs" "$OUT/volume/mlflow" "$OUT/podfs/sa" "$OUT/podfs/bin" "$OUT/logs"

say "preparing pod filesystem stubs (serviceaccount + curl network edge)"
printf 'llmtrain-e2e' > "$OUT/podfs/sa/namespace"
printf 'stub-token' > "$OUT/podfs/sa/token"
printf 'stub-ca' > "$OUT/podfs/sa/ca.crt"
# The stub replaces ONLY the network hop of coordinator discovery: the
# entrypoint still builds the real URL, reads the real SA files, and
# parses the real pods-list JSON shape through its python parser.
cat > "$OUT/podfs/bin/curl" <<'EOF'
#!/usr/bin/env bash
echo '{"items": [{"status": {"podIP": "127.0.0.1"}}]}'
EOF
chmod +x "$OUT/podfs/bin/curl"

say "writing offline train config (mirror of k8s/configmap.yaml train.yaml)"
cat > "$OUT/train.yaml" <<EOF
schema_version: 1
run:
  name: "k8s-gpt-local"
  seed: 42
  device: "cpu"
  deterministic: true
  notes: "Docker-free e2e: GPT via the real entrypoint.sh, 2 JAX processes."
model:
  name: "gpt"
  block_size: 128
  d_model: 256
  n_layers: 6
  n_heads: 8
  d_ff: 1024
  dropout: 0.1
  tie_embeddings: true
  extra:
    tokenizer: "byte"
data:
  name: "local_text"
  cache_dir: "$OUT/volume/cache"
  extra:
    globs: ["docs/*.md", "README.md", "tests/*.py"]
    val_fraction: 0.02
trainer:
  max_steps: $STEPS
  micro_batch_size: 2
  grad_accum_steps: 4
  lr: 0.0005
  weight_decay: 0.1
  warmup_steps: 10
  max_grad_norm: 1.0
  log_every_steps: 5
  eval_every_steps: 30
  save_every_steps: $STEPS
distributed:
  enabled: true
  timeout_sec: 600
  mesh:
    data: -1
resilience:
  # Arm the real watchdog (it must NEVER fire on this healthy run). No
  # explicit heartbeat_path: the default lands in the shared run dir with
  # a per-rank suffix (heartbeat for rank 0, heartbeat.r1 for rank 1), so
  # the assertions below can check EACH pod's beacon — one shared file
  # would let a healthy pod's touches mask a dead beacon on the other,
  # exactly the anti-pattern docs/k8s.md warns about.
  watchdog:
    enabled: true
    stall_timeout_sec: 600
telemetry:
  # Prometheus endpoint, mirroring the k8s Job's scrape annotations. Both
  # "pods" share localhost here, so one rank wins the bind and the other
  # degrades to a warning — exactly the documented single-netns behavior;
  # the scraper below asserts against whichever rank is serving.
  prometheus: true
  prometheus_port: $PROM_PORT
  prometheus_host: "127.0.0.1"
mlflow:
  enabled: true
  tracking_uri: "sqlite:///$PWD/$OUT/volume/mlflow/mlflow.db"
  experiment: "llm-train-k8s"
  run_name: "k8s-gpt-local"
output:
  root_dir: "$OUT/volume/runs"
EOF

say "launching 2 'pods' through the real k8s/entrypoint.sh"
PIDS=()
for IDX in 0 1; do
    env -i \
        PATH="$OUT/podfs/bin:$PATH" \
        HOME="$HOME" \
        JOB_COMPLETION_INDEX="$IDX" \
        NUM_PROCESSES=2 \
        JOB_NAME=llmtrain-tpu \
        POD_IP=127.0.0.1 \
        COORDINATOR_PORT=29531 \
        LLMTRAIN_CONFIG="$OUT/train.yaml" \
        LLMTRAIN_SA_DIR="$OUT/podfs/sa" \
        LLMTRAIN_DISCOVERY_TRIES=5 \
        LLMTRAIN_DISCOVERY_SLEEP=1 \
        JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=4" \
        JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.cache/jax-tests}" \
        PYTHONPATH="$PWD" \
        bash k8s/entrypoint.sh > "$OUT/logs/pod$IDX.log" 2>&1 &
    PIDS+=($!)
done

say "starting mid-run prometheus scraper against 127.0.0.1:$PROM_PORT"
# Real curl may be absent on this host (and the stubbed one only exists in
# the pods' PATH), so the metrics scrape uses python urllib — the transport
# matters less than the exercised endpoint. Polls until it captures a
# scrape with llmtrain_ gauges, is killed after the pods exit, or times out.
PYBIN=$(command -v python3 || command -v python)
"$PYBIN" - "$PROM_PORT" "$OUT/scrape.prom" <<'PY' &
import sys, time, urllib.request
port, target = sys.argv[1], sys.argv[2]
deadline = time.time() + 900
while time.time() < deadline:
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
            text = r.read().decode()
        if "llmtrain_" in text:
            with open(target, "w") as fh:
                fh.write(text)
            sys.exit(0)
    except OSError:
        pass
    time.sleep(1.0)
sys.exit(1)
PY
SCRAPER_PID=$!

# Bounded wait (same discipline as tests/test_multiprocess.py): a
# deadlocked collective must fail the run, not hang it forever.
DEADLINE=$(( $(date +%s) + ${LLMTRAIN_E2E_TIMEOUT:-1800} ))
for i in 0 1; do
    while kill -0 "${PIDS[$i]}" 2>/dev/null && [ "$(date +%s)" -lt "$DEADLINE" ]; do
        sleep 5
    done
    if kill -0 "${PIDS[$i]}" 2>/dev/null; then
        say "pod $i exceeded the deadline; killing both pods"
        kill -9 "${PIDS[0]}" "${PIDS[1]}" 2>/dev/null || true
    fi
done
CODES=()
for i in 0 1; do
    if wait "${PIDS[$i]}"; then CODES+=(0); else CODES+=($?); fi
done

say "collecting pod logs"
for IDX in 0 1; do
    sed "s/^/pod$IDX| /" "$OUT/logs/pod$IDX.log" | tail -n 5
done
LOGS0="$(cat "$OUT/logs/pod0.log")"

say "asserting rank-0 output"
assert_rank0_logs "$LOGS0" || true

say "asserting pod exit codes (taxonomy-clean 0: watchdog armed, never fired)"
for IDX in 0 1; do
    if [ "${CODES[$IDX]}" = "0" ]; then
        pass "pod $IDX exited 0"
    else
        fail "pod $IDX exited ${CODES[$IDX]} (75/76 = retryable infra/hang, 1/2 = fatal)"
    fi
done

say "asserting per-rank heartbeat files (livenessProbe contract)"
HB_RUN_DIR=$(find "$OUT/volume/runs" -mindepth 1 -maxdepth 1 -type d | head -n 1 || true)
assert_heartbeat "$HB_RUN_DIR/heartbeat" || true      # rank 0's beacon
assert_heartbeat "$HB_RUN_DIR/heartbeat.r1" || true   # rank 1's beacon

say "asserting no hang report was written (healthy run)"
if find "$OUT/volume/runs" -name 'hang_report_*.txt' | grep -q .; then
    fail "hang report present after a healthy run"
else
    pass "no hang_report_*.txt in the run dir"
fi

say "asserting host artifacts"
RUN_DIR=$(find "$OUT/volume/runs" -mindepth 1 -maxdepth 1 -type d | head -n 1 || true)
assert_artifact_tree "$RUN_DIR" || true
assert_tracking_db "$OUT/volume/mlflow/mlflow.db" || true

say "asserting telemetry artifacts (report + perfetto trace + textfile)"
assert_telemetry_artifacts "$RUN_DIR" || true

say "asserting checkpoint commit manifests (crash-consistency contract)"
assert_manifest "$RUN_DIR/checkpoints" || true

# ---------------------------------------------------------------------------
# Mid-run pod kill: SIGKILL a single-process training pod after its first
# checkpoint commit, then assert the commit SURVIVED (manifest verifies)
# and an --auto-resume restart finishes the run from it — the
# podFailurePolicy retry path in miniature, single-process so it runs on
# hosts without multi-process collective support too.
# ---------------------------------------------------------------------------
say "mid-run pod kill: training pod, SIGKILL after first commit, auto-resume"
KILL_ROOT="$OUT/volume/runs_kill"
mkdir -p "$KILL_ROOT"
"$PYBIN" - "$OUT/train.yaml" "$KILL_ROOT" <<'PY' > "$OUT/kill.yaml"
import sys, yaml
cfg = yaml.safe_load(open(sys.argv[1]))
cfg["distributed"]["enabled"] = False
cfg["trainer"]["max_steps"] = 200
cfg["trainer"]["save_every_steps"] = 10
cfg["trainer"]["log_every_steps"] = 5
cfg["trainer"]["eval_every_steps"] = 200
cfg["telemetry"] = dict(cfg.get("telemetry") or {}, prometheus=False)
cfg["mlflow"] = {"enabled": False}
cfg["output"] = {"root_dir": sys.argv[2]}
print(yaml.safe_dump(cfg, sort_keys=False), end="")
PY
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    "$PYBIN" -m llmtrain_tpu train --config "$OUT/kill.yaml" \
    --run-id killrun --auto-resume > "$OUT/logs/kill_a.log" 2>&1 &
KILL_PID=$!
KILL_CKPTS="$KILL_ROOT/killrun/checkpoints"
KDEADLINE=$(( $(date +%s) + 600 ))
while [ "$(date +%s)" -lt "$KDEADLINE" ]; do
    if ls "$KILL_CKPTS"/step_*.manifest.json >/dev/null 2>&1; then break; fi
    if ! kill -0 "$KILL_PID" 2>/dev/null; then break; fi
    sleep 0.2
done
if kill -0 "$KILL_PID" 2>/dev/null; then
    kill -9 "$KILL_PID" 2>/dev/null || true
    # The poll loop exits on first-commit OR deadline OR pod death:
    # distinguish them, or a >10min first save would be reported as a
    # crash-consistency failure later instead of the timeout it is.
    if ls "$KILL_CKPTS"/step_*.manifest.json >/dev/null 2>&1; then
        pass "pod SIGKILLed mid-run (after first commit)"
    else
        fail "poll deadline lapsed before the first checkpoint commit (host too slow?)"
    fi
elif ls "$KILL_CKPTS"/step_*.manifest.json >/dev/null 2>&1; then
    # A very fast host can finish all 200 steps inside the poll window:
    # the kill wasn't exercised, but nothing is broken — say so instead
    # of failing flakily.
    pass "pod finished before the kill landed (commits present; kill not exercised on this host)"
else
    fail "kill-phase pod exited before its first checkpoint commit"
fi
wait "$KILL_PID" 2>/dev/null || true
assert_manifest "$KILL_CKPTS" || true
# Guarded: under set -e an exit-nonzero resume (the exact regression this
# phase hunts) must fall through to the fail accounting below, not abort
# the whole e2e before the summary runs.
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    "$PYBIN" -m llmtrain_tpu train --config "$OUT/kill.yaml" \
    --run-id killrun --auto-resume --json > "$OUT/logs/kill_b.log" 2>&1 || true
if grep -q '"final_step": 200' "$OUT/logs/kill_b.log" \
   && grep -q "resumed from" "$KILL_ROOT/killrun/logs/train.log"; then
    pass "auto-resume finished the killed run from its surviving commit"
else
    fail "auto-resume after SIGKILL did not complete from a commit"
fi
assert_manifest "$KILL_CKPTS" || true

# ---------------------------------------------------------------------------
# Serving phase (docs/serving.md): the checkpoint the kill phase committed
# is served by the continuous-batching inference stack — (1) the seeded
# open-loop load harness runs with --verify-parity (batched token-ids must
# match sequential generate() bitwise) and its serving block must land in
# report.json; (2) the real `serve` HTTP server takes concurrent posts and
# its /metrics must expose the llmtrain_serve_* family the k8s/serve.yaml
# Deployment's scrape annotations advertise.
# ---------------------------------------------------------------------------
say "serving phase: continuous-batching load run over the killrun checkpoint"
"$PYBIN" - "$OUT/kill.yaml" <<'PY' > "$OUT/serve.yaml"
import sys, yaml
cfg = yaml.safe_load(open(sys.argv[1]))
cfg["serving"] = {
    "mode": "continuous",
    "max_batch_slots": 4,
    "block_tokens": 16,
    "prompt_buckets": [16, 32],
    "batch_buckets": [2, 4],
    "max_new_tokens_cap": 32,
    "default_max_new_tokens": 8,
}
print(yaml.safe_dump(cfg, sort_keys=False), end="")
PY
if JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    "$PYBIN" -m llmtrain_tpu serve-bench --config "$OUT/serve.yaml" \
    --from killrun --requests 8 --rate-rps 16 --max-new-tokens 8 \
    --prompt-tokens-max 24 --verify-parity --out "$OUT/serve_report" \
    > "$OUT/logs/serve_bench.log" 2>&1; then
    pass "serve-bench completed with bitwise parity vs generate()"
else
    fail "serve-bench failed (see $OUT/logs/serve_bench.log)"
fi
assert_serving_report "$OUT/serve_report/report.json" || true

say "serving phase: live HTTP server, concurrent posts, /metrics scrape"
if JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    "$PYBIN" - "$OUT/serve.yaml" "$OUT" > "$OUT/logs/serve_http.log" 2>&1 <<'PY'
import json, subprocess, sys, threading, urllib.request

cfg, out = sys.argv[1], sys.argv[2]
# stderr goes to its own file: the ready line must be the FIRST stdout
# line, and merging streams would race log lines ahead of it.
proc = subprocess.Popen(
    [sys.executable, "-m", "llmtrain_tpu", "serve", "--config", cfg,
     "--from", "killrun", "--port", "0"],
    stdout=subprocess.PIPE,
    stderr=open(out + "/logs/serve_http_stderr.log", "w"),
    text=True)
ok = False
try:
    ready = json.loads(proc.stdout.readline())
    assert ready["mode"] == "continuous", ready
    url = f"http://127.0.0.1:{ready['port']}"
    results = []

    def post(i):
        req = urllib.request.Request(
            url + "/v1/generate",
            data=json.dumps({"prompt_ids": [1 + i, 2, 3],
                             "max_new_tokens": 6,
                             "temperature": 0.0}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as r:
            results.append(json.loads(r.read()))

    threads = [threading.Thread(target=post, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    with urllib.request.urlopen(url + "/metrics", timeout=60) as r:
        open(out + "/serve_scrape.prom", "w").write(r.read().decode())
    with urllib.request.urlopen(url + "/healthz", timeout=60) as r:
        health = json.loads(r.read())
    print("healthz scheduler:", json.dumps(health.get("scheduler", {})))
    ok = (len(results) == 4
          and all("ttft_ms" in r for r in results)
          and health["scheduler"]["requests_finished"] >= 4)
finally:
    proc.terminate()
    proc.wait(timeout=30)
sys.exit(0 if ok else 1)
PY
then
    pass "continuous server answered 4 concurrent posts (healthz has scheduler stats)"
else
    fail "continuous serve HTTP round-trip failed (see $OUT/logs/serve_http.log)"
fi
assert_serving_scrape "$OUT/serve_scrape.prom" || true

say "asserting the mid-run prometheus scrape"
# The pods are done: the scrape either landed already or never will —
# kill a still-polling scraper instead of waiting out its deadline.
kill "$SCRAPER_PID" 2>/dev/null || true
wait "$SCRAPER_PID" 2>/dev/null || true
assert_prometheus_scrape "$OUT/scrape.prom" || true

if [ "$FAILURES" -eq 0 ]; then
    say "E2E (local, docker-free) SUCCEEDED"
else
    say "E2E (local, docker-free) FAILED ($FAILURES assertion(s))"
    exit 1
fi

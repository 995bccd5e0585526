"""Tier-1 tests of the benchmark's own yardstick (CPU, tiny sizes).

The trace reduction on a small recorded v5e trace, the load generator's
arithmetic, the FLOP formula and peaks table, that every cell of
BENCHMARK.json resolves to files, a tiny rehearsal of each runner, the
plain reference against the program's GPT, the lower-precision control,
and a run whose timed path is broken underneath (``correct`` must be false).
"""

from __future__ import annotations

import json
import math
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import run as harness  # noqa: E402
from benchmarks.lib import loadgen, peaks, trace  # noqa: E402
from benchmarks.reference import gpt2 as family  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]


# ------------------------------------------------------------ trace reduction


def _toy_trace():
    # One chip; a while loop [0, 100) enclosing two fusions and a kernel, a
    # gap [100, 150), then an all-gather [150, 190) with a fusion [170, 180)
    # running inside it, and a last fusion [190, 200); a host marker at 0.
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["while.1", 0, 100], ["fusion.1", 0, 40], ["fused_ce_kernel", 40, 30],
                ["fusion.2", 70, 30], ["all-gather.3", 150, 40], ["fusion.4", 170, 10],
                ["fusion.5", 190, 10]]},
            {"name": "XLA Modules", "events": [["jit_step", 0, 200]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [["bench_marker", 0, 1]]}]},
    ]}


def test_trace_reduction_toy_by_hand():
    red = trace.reduce_trace(_toy_trace(), (0, 200), kernel_markers=("fused_ce",))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx(150e-9)  # [0,100) + [150,200)
    assert red["kernel_s"] == pytest.approx(30e-9)
    assert red["collective_s"] == pytest.approx(40e-9)
    assert red["collective_exposed_s"] == pytest.approx(30e-9)  # [150,170) + [180,190)
    ops = dict(red["ops"])
    assert "while" not in ops  # control flow encloses, it is not work
    assert ops["fusion"] == pytest.approx(90e-9)  # .1, .2, .4 and .5 add up under one label
    assert ops["all-gather"] == pytest.approx(30e-9)  # self time: less the fusion inside it
    assert red["gaps"] == [(100, 150)]
    named = trace.name_gaps(red["gaps"], [("boundary_sync", 90, 140), ("data_wait", 140, 160)])
    assert named == [["boundary_sync", pytest.approx(50e-9)]]
    assert trace.find_marker(_toy_trace(), "bench_marker") == 0


def test_op_label_shortens_hlo_text():
    hlo = ('%pallas_flash_attention_bwd.7 = (bf16[384,1024,64]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[384] %x), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace.op_label(hlo) == "pallas_flash_attention_bwd [pallas]"
    assert trace.op_label("%fusion.12 = bf16[32,1024]{1,0:T(8,128)(2,1)} fusion(%p0), kind=kOutput") == "fusion [fusion]"
    assert trace.op_label("%all-gather-start.3 = (f32[8]) all-gather-start(%p)") == "all-gather-start [all-gather-start]"
    assert trace.op_label("copy.5") == "copy"


def test_trace_self_times_and_unions():
    events = [["outer", 0, 10], ["a", 1, 3], ["b", 5, 4]]
    assert {n: s for n, _, _, s in trace.self_times(events)} == {"outer": 3, "a": 3, "b": 4}
    assert trace.union_length([(0, 5), (3, 8), (10, 12)]) == 10
    assert trace.subtract_length([(0, 10)], [(2, 3), (5, 7)]) == 7


def test_trace_reduction_recorded_v5e_cut():
    """The small cut of a real v5e trace (PR 23, gpt2-small.train-64k) and
    the numbers worked out from it by hand (see recorded_trace.expected)."""
    rec = json.loads((ROOT / "benchmarks/lib/recorded_trace.json").read_text())
    want = json.loads((ROOT / "benchmarks/lib/recorded_trace.expected.json").read_text())
    red = trace.reduce_trace(rec)
    assert red["devices"] == want["devices"]
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    got_ops = dict(red["ops"])
    for name, seconds in want["ops"].items():
        assert got_ops[name] == pytest.approx(seconds, rel=1e-9)


# ------------------------------------------------------------- load generator

TRAFFIC = {
    "population_seed": 7, "rate_rps": 5.0,
    "prompt_tokens": {"median": 192, "sigma": 0.6, "min": 16, "max": 640},
    "output_tokens": {"median": 96, "sigma": 0.6, "min": 8, "max": 384},
    "clients": 6, "requests_per_client": 3,
}


def test_open_loop_schedule_is_exact_and_seed_changes_only_token_ids():
    a = loadgen.plan_open(TRAFFIC, 2**31 + 77, 20.0, 1000)
    b = loadgen.plan_open(TRAFFIC, 5, 20.0, 1000)
    again = loadgen.plan_open(TRAFFIC, 5, 20.0, 1000)
    assert len(a) == len(b) == 100  # round(rate * seconds), whatever the seed
    assert all(np.array_equal(x.prompt_ids, y.prompt_ids) for x, y in zip(b, again))
    for plans in (a, b):
        dues = [p.due_s for p in plans]
        assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 20.0
        assert all(16 <= len(p.prompt_ids) <= 640 and 8 <= p.max_new_tokens <= 384 for p in plans)
    # two seeds queue alike: the same lengths at the same due times, other token ids
    assert [(p.due_s, len(p.prompt_ids), p.max_new_tokens) for p in a] == [
        (p.due_s, len(p.prompt_ids), p.max_new_tokens) for p in b]
    assert not all(np.array_equal(x.prompt_ids, y.prompt_ids) for x, y in zip(a, b))
    # a ramp puts arrivals before the window at the same rate
    ramped = loadgen.plan_open(dict(TRAFFIC, ramp_seconds=10.0), 5, 20.0, 1000)
    assert len(ramped) == 150 and -10.0 < ramped[0].due_s < 0 < ramped[-1].due_s < 20.0


def test_probes_ask_for_one_token_over_the_mix_own_prompt_lengths():
    a, b = loadgen.plan_probes(TRAFFIC, 2**31 + 5, 1000, 32), loadgen.plan_probes(TRAFFIC, 6, 1000, 32)
    assert len(a) == 32 and all(p.max_new_tokens == 1 and 16 <= len(p.prompt_ids) <= 640 for p in a)
    assert [len(p.prompt_ids) for p in a] == [len(p.prompt_ids) for p in b]
    assert not all(np.array_equal(x.prompt_ids, y.prompt_ids) for x, y in zip(a, b))


def test_lognormal_lengths_clip_and_median():
    rng = np.random.default_rng(0)
    x = loadgen.lognormal_lengths(rng, 20000, {"median": 192, "sigma": 0.6, "min": 16, "max": 640})
    assert x.min() >= 16 and x.max() <= 640
    assert abs(float(np.median(x)) - 192) < 6


def test_closed_loop_plan():
    clients = loadgen.plan_closed(TRAFFIC, 11, 1000)
    assert len(clients) == 6 and all(len(c) == 3 for c in clients)
    other = loadgen.plan_closed(TRAFFIC, 12, 1000)
    flat = lambda cs: sorted((len(p.prompt_ids), p.max_new_tokens) for c in cs for p in c)  # noqa: E731
    assert flat(clients) == flat(other)


def test_latency_counts_from_due_time_and_missing_counts_as_late():
    plans = [loadgen.Planned(index=i, prompt_ids=np.zeros(4, np.int32), max_new_tokens=3, due_s=float(i))
             for i in range(20)]
    for p in plans[:19]:
        p.submitted_s = p.due_s + 0.25  # the generator ran late: the user still waited
        p.first_token_s = p.due_s + 0.5
        p.token_s = [p.first_token_s, p.first_token_s + 0.01, p.first_token_s + 0.03]
    assert loadgen.ttft_ms(plans)[:2] == [pytest.approx(500.0)] * 2
    assert loadgen.lateness_ms(plans)[0] == pytest.approx(250.0)
    assert sorted(set(round(v, 6) for v in loadgen.inter_token_ms(plans))) == [10.0, 20.0]
    # only gaps that END inside the bounds: request 0's second gap ends at 0.53
    assert len(loadgen.inter_token_ms(plans[:1], start_s=0.0, end_s=0.52)) == 1
    # 19 of 20 answered: the p95 rank (19) is still an answered one ...
    assert loadgen.p95_with_missing(loadgen.ttft_ms(plans), 20) == pytest.approx(500.0)
    # ... 18 of 20 answered: more than 5% missed the limit, no finite p95
    assert loadgen.p95_with_missing(loadgen.ttft_ms(plans)[:18], 20) is None
    assert loadgen.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert loadgen.percentile([], 95) is None


# ------------------------------------------------------------ flops and peaks


def test_the_chat_tail_is_the_99th_percentile_and_the_95th_is_read_per_layer():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["serve_itl_p99_ms"]["workloads"] == ["gpt2-xl.serve-chat"] and "serve_itl_p95_ms" not in e2e
    # Plateaus of gaps as the chat cell has them (plain ticks, ticks with a prefill call of a short
    # and of the longest bucket): 4.9% or 5.1% of them slow moves the 95th a whole step, not the 99th.
    for slow, p95 in ((49, 85.0), (51, 101.0)):
        gaps = [85.0] * (1000 - slow) + [101.0] * (slow - 20) + [123.0] * 20
        assert loadgen.percentile(gaps, 95) == p95 and loadgen.percentile(gaps, 99) == 123.0
    read = harness.load_module("metrics", "serve_itl_p95_ms.chat").read
    assert read({"records": {"stats": {"itl_p95_ms": 86.3}}}) == 86.3 and read({"records": {}}) is None


def test_flop_formula_and_param_counts():
    small = json.loads((ROOT / "benchmarks/configs/gpt2-small.json").read_text())
    assert family.total_params(small) == 124_439_808  # GPT-2 124M as published
    assert family.matmul_params(small) == 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 50257 * 768
    per_token = family.train_flops_per_token(small, 1024)
    assert per_token == 6 * family.matmul_params(small) + 12 * 12 * 1024 * 768
    assert family.kv_bytes_per_position(small) == 36_864
    xl = json.loads((ROOT / "benchmarks/configs/gpt2-xl.json").read_text())
    assert family.total_params(xl) == 1_557_611_200
    assert family.kv_bytes_per_position(xl) == 307_200


def test_train_mfu_reader_on_the_records_of_a_chip_run():
    small = json.loads((ROOT / "benchmarks/configs/gpt2-small.json").read_text())
    run = {"records": {"step_seconds": [0.7, 0.7, 0.9], "tokens_per_step": 65536, "seq_len": 1024},
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}, "config": small, "reference": family, "chips": 1}
    want = 100.0 * (65536 / 0.7) * family.train_flops_per_token(small, 1024) / 197e12
    assert harness.load_module("metrics", "train_mfu").read(run) == pytest.approx(want)
    assert 40.0 < want < 41.0  # 93.6k tokens/s is 40.6% of a v5e
    run["device"] = {"platform": "cpu", "kind": "cpu"}
    assert harness.load_module("metrics", "train_mfu").read(run) is None  # no share of a chip's peak off the chip


def test_peaks_table_unknown_device_raises():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


# ---------------------------------------------------------------- the contract


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= max(1, len(CELLS) // 4)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in e2e[m["moves"]].get("workloads", CELLS), (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files(cell):
    resolved = harness.resolve_cell(cell)
    assert resolved["config"]["family"] and resolved["traffic"]["runner"]
    assert (ROOT / "benchmarks/runners" / f"{resolved['traffic']['runner']}.py").is_file()
    assert (ROOT / "benchmarks/reference" / f"{resolved['config']['family']}.py").is_file()
    assert resolved["limits"]
    for metric in resolved["per_layer"]:
        assert hasattr(harness.load_module("metrics", metric["name"]), "read")
    assert len(resolved["end_to_end"]) >= 2 and resolved["per_layer"]
    for key in resolved["config"]["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and "n_embd" not in key and "n_inner" not in key


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_contract_line_and_no_device_metric(cell):
    for trace_flag in ("0", "1"):
        proc = subprocess.run(
            [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload", cell, "--seed", "2147483700",
             "--seconds", "2", "--trace", trace_flag, "--rehearse-cpu"],
            capture_output=True, text=True, timeout=600, cwd=ROOT,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
        assert line["metrics"] == {} and line["rehearsal"] is True
        assert "busy_s" not in line["device"] and line["attempted"] > 0
        assert any("check " in ln and "against limit" in ln for ln in proc.stdout.splitlines())
        if trace_flag == "0":  # the runner reports every end-to-end metric the cell lists, under its name
            said = next(ln for ln in proc.stdout.splitlines() if "values are not device numbers" in ln)
            assert all(f"'{m['name']}'" in said for m in harness.resolve_cell(cell)["end_to_end"]), said


def test_off_the_chip_there_is_no_result_line():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    assert proc.returncode == 2 and proc.stdout.strip() == ""
    assert "needs" in proc.stderr and "TPU" in proc.stderr


# ------------------------------------------------- the reference and the program

TINY = {"family": "gpt2", "vocab_size": 512, "n_positions": 64, "n_embd": 64, "n_layer": 2, "n_head": 4,
        "n_inner": 256, "activation_function": "gelu", "layer_norm_epsilon": 1e-6}


def _program_logits(dtype: str, ids):
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import gpt2 as ref
    from llmtrain_tpu.models.gpt import GPT

    model = GPT(vocab_size=512, block_size=64, d_model=64, n_layers=2, n_heads=4, d_ff=256, dropout=0.0,
                tie_embeddings=True, dtype=jnp.dtype(dtype), param_dtype=jnp.float32, attention="dense")
    params = ref.program_tree(ref.init_weights(TINY, 1234), TINY)
    return jax.jit(lambda p, x: model.apply({"params": p}, x, deterministic=True))(params, ids)


def test_reference_agrees_with_program_and_lower_precision_does_not():
    import jax.numpy as jnp

    from benchmarks.reference import gpt2 as ref

    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 64)), jnp.int32)
    want = np.asarray(ref.logits_fn(ref.init_weights(TINY, 1234), ids, TINY))
    scale = float(np.abs(want).max())
    got32 = np.asarray(_program_logits("float32", ids), np.float32)
    got16 = np.asarray(_program_logits("bfloat16", ids), np.float32)
    # float32 against float32: reduction order only. The tolerance is 50x
    # float32's epsilon at the logits' scale; bf16 (epsilon 2**-8) must fail it.
    tol = 50 * 2.0**-23 * scale * math.sqrt(64)
    assert np.abs(got32 - want).max() <= tol
    assert np.abs(got16 - want).max() > 10 * tol


def test_served_token_gap_control_in_fp8_is_wider():
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import gpt2 as ref

    w = ref.init_weights(TINY, 99)
    rng = np.random.default_rng(1)
    logits_of = jax.jit(lambda ids: ref.logits_fn(w, ids, TINY))
    seqs = []
    for _ in range(3):  # greedy tokens of the reference itself: gap 0
        prompt = rng.integers(0, 512, 12).astype(np.int32)
        ids = list(prompt)
        for _ in range(10):
            logits = np.asarray(logits_of(jnp.asarray([ids + [0] * (64 - len(ids))], jnp.int32)))
            ids.append(int(logits[0, len(ids) - 1].argmax()))
        seqs.append((prompt, np.asarray(ids[12:], np.int32)))
    out = ref.served_token_gaps(w, TINY, seqs)
    assert out["tokens"] == 30 and out["widest_gap"] == 0.0 and out["first_mean_gap"] == 0.0
    # a sequence padded to a prompt bucket instead of the whole context reads the same
    padded = ref.served_token_gaps(w, TINY, seqs, pad_to=(32,))
    assert padded["widest_gap"] == 0.0 and padded["tokens"] == 30
    # The control reads, at the SAME positions, the token fp8 would put first
    # there; over 150 close calls of this tiny model it leaves the best one.
    longer = [(p, rng.integers(0, 512, 50).astype(np.int32)) for p, _ in seqs]
    control = ref.served_token_gaps(w, TINY, longer, precision="fp8")
    assert control["tokens"] == 150
    assert control["control_widest_gap"] > 0.0
    many = [(rng.integers(0, 512, 20).astype(np.int32), np.zeros(1, np.int32)) for _ in range(64)]  # probes
    assert ref.served_token_gaps(w, TINY, many, precision="fp8")["control_first_mean_gap"] > 0.0
    exact = ref.served_token_gaps(w, TINY, longer, precision="f32")
    assert exact["control_widest_gap"] == 0.0  # the reference in its own place loses nothing
    altered = [(p, (s + 1) % 512) for p, s in seqs]  # a token altered where it is produced
    assert ref.served_token_gaps(w, TINY, altered)["widest_gap"] > control["control_widest_gap"]


def test_family_maps_the_published_config_onto_the_program():
    from benchmarks.reference import gpt2 as ref

    xl = json.loads((ROOT / "benchmarks/configs/gpt2-xl.json").read_text())
    model = ref.program_model(xl)
    assert (model["d_model"], model["n_layers"], model["n_heads"], model["d_ff"]) == (1600, 48, 25, 6400)
    assert model["block_size"] == ref.context_length(xl) == 1024 and model["vocab_size"] == ref.vocab_size(xl)
    assert model["extra"] == {"loss_impl": "dense", "fused_norm": False} and model["attention"] == "flash"
    assert ref.program_model(xl, 512)["block_size"] == 512
    assert xl["reduced"] == [] and set(xl["deviations"]) == {"activation_function", "layer_norm_epsilon", "why"}


def test_adamw_schedule_of_the_reference():
    from benchmarks.reference import gpt2 as ref

    hyper = {"lr": 6e-4, "warmup_steps": 10, "max_steps": 110}
    assert ref.lr_at(0, hyper) == 0.0 and ref.lr_at(5, hyper) == pytest.approx(3e-4)
    assert ref.lr_at(10, hyper) == pytest.approx(6e-4) and ref.lr_at(60, hyper) == pytest.approx(3e-4)
    assert ref.lr_at(110, hyper) == pytest.approx(0.0, abs=1e-12)


# ----------------------------------------- a run with the timed path broken


def _run_in_process(capsys, cell):
    argv = ["--workload", cell, "--seed", "424242", "--seconds", "1", "--trace", "0", "--rehearse-cpu"]
    assert harness.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _cell_with_runner(runner: str) -> str:
    for cell in CELLS:
        if harness.resolve_cell(cell)["traffic"]["runner"] == runner:
            return cell
    pytest.skip(f"no cell uses runner {runner!r}")


def test_train_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch, capsys):
    cell = _cell_with_runner("train")
    train = harness.load_module("runners", "train")
    real_init = train.Program.__init__

    def broken_init(self, ctx):
        real_init(self, ctx)
        import jax

        real = self.trainer._train_step_fn
        copy = jax.jit(lambda t: jax.tree.map(lambda x: x + 0, t))

        def step(state, batch, key):
            _, metrics = real(copy(state), batch, key)
            return state, metrics  # the loss is right, nothing is learned

        self.trainer._train_step_fn = step

    monkeypatch.setattr(train.Program, "__init__", broken_init)
    monkeypatch.setattr(harness, "load_module",
                        lambda kind, name, _real=harness.load_module: train if (kind, name) == ("runners", "train") else _real(kind, name))
    line = _run_in_process(capsys, cell)
    assert line["correct"] is False and line["attempted"] > 0


def test_serving_with_altered_tokens_is_not_correct(monkeypatch, capsys):
    cell = _cell_with_runner("serve_open")
    from benchmarks.runners import _serve_common as common

    real_init = common.Server.__init__

    def broken_init(self, ctx):
        real_init(self, ctx)
        real_decode = self.engine.decode
        self.engine.decode = lambda rows, **kw: [(t + 1) % self.vocab for t in real_decode(rows, **kw)]
        ctx.limits = {"served_token_logit_gap": 0.02, "first_token_mean_gap": 0.02}  # the tiny model's own scale

    monkeypatch.setattr(common.Server, "__init__", broken_init)
    line = _run_in_process(capsys, cell)
    assert line["correct"] is False and line["attempted"] > 0

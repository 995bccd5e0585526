"""The ``start-up`` metrics (fourteen of PR 39, ``setup_wall_s`` of PR 40): each
reader against arithmetic done by hand on a hand-made ``records["startup"]``
and the harness's own cache loads, a parent-shaped run, a run off the chip
reading ``None``, the entries of BENCHMARK.json, and ``setup_s`` as the result
line has it since PR 40: the wall clock less the cache loads before the ramp.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import run as harness  # noqa: E402
from benchmarks.lib import startup  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]
SIX = ["gpt2-small.train-64k", "gpt2-xl.serve-chat", "gpt2-small.serve-batch", "falcon-h1-34b.serve-batch",
       "ax-k1.serve-reason", "keye-vl2-30b-a3b.serve-video"]
CLOSED = [c for c in SIX if c not in ("gpt2-small.train-64k", "gpt2-xl.serve-chat")]
# name -> (unit, moves, workloads); the source is ``program_span`` but for the HARNESS_OWN
ENTRIES = {
    "setup_before_program_s": ("s", "setup_s", SIX),
    "setup_import_s": ("s", "setup_s", SIX),
    "setup_build_s": ("s", "setup_s", SIX),
    "setup_trace_lower_s": ("s", "setup_s", SIX),
    "setup_compile_s": ("s", "setup_s", SIX),
    "setup_cache_load_s": ("s", "setup_s", SIX),
    "setup_cache_load_mb": ("MB", "setup_s", SIX),
    "setup_cache_misses": ("count", "setup_s", SIX),
    "setup_first_call_s": ("s", "setup_s", SIX),
    "setup_stall_s": ("s", "setup_s", SIX),
    "setup_unnamed_s": ("s", "setup_s", SIX),
    "host_stall_share.closed": ("%", "serve_tokens_per_s", CLOSED),
    "host_stall_share.open": ("%", "serve_itl_p99_ms", ["gpt2-xl.serve-chat"]),
    "host_stall_share.train": ("%", "train_tokens_per_s", ["gpt2-small.train-64k"]),
    "setup_wall_s": ("s", "setup_s", SIX),
}
# What the harness reads with its own clock and listener: there without the program's buffer too.
HARNESS_OWN = ("setup_cache_load_s", "setup_wall_s")

T_PROCESS = 100.0  # the harness's stamp; set-up of 70 s ends at 170; a ramp of 25 s begins at 145


def _span(name, t0, t1, thread="MainThread", **args):
    return {"name": name, "cat": "host" if name == "host/stall" else "startup", "t0": t0, "t1": t1,
            "thread": thread, "args": args}


def _stall(t0, t1):
    return _span("host/stall", t0, t1, thread="host-stall-watch", late_ms=round((t1 - t0) * 1e3, 3))


SPANS = [
    _span("startup/import", 113.0, 114.25),
    # the engine: 1 s, its pool inside it (no phase of its own), 0.2 s of tracing inside it
    _span("startup/build", 115.0, 116.0, kind="engine"),
    _span("startup/pool", 115.5, 115.9, parent="startup/build", num_blocks=9, bytes=1 << 20),
    _span("startup/trace", 115.2, 115.4, parent="startup/build", fun="init"),
    # a first call that loads its program: 13.5 s, of which trace 3, lower 1, the key's hashing 0.5, the load 6
    _span("startup/first_call", 116.5, 130.0, thread="serve-scheduler", kind="prefill", bucket=2048),
    _span("startup/trace", 117.0, 120.0, thread="serve-scheduler", fun="_prefill_bound"),
    _span("startup/trace", 118.0, 118.5, thread="serve-scheduler", fun="inner"),  # nested: counted once
    _span("startup/lower", 120.0, 121.0, thread="serve-scheduler", fun="jit(_prefill_bound)"),
    _span("startup/compile", 121.0, 121.5, thread="serve-scheduler", cache="hit"),
    _span("startup/cache_load", 121.5, 127.5, thread="serve-scheduler"),
    # a first call that compiles: 10 s, of which 8 in the compiler
    _span("startup/first_call", 130.0, 140.0, thread="serve-scheduler", kind="decode", bucket=64),
    _span("startup/compile", 131.0, 139.0, thread="serve-scheduler", cache="miss"),
    # inside the ramp, and after the window opened: no phase's
    _span("startup/cache_load", 150.0, 151.0, thread="serve-scheduler"),
    _span("startup/compile", 180.0, 181.0, thread="serve-scheduler", cache="none"),
    _stall(118.0, 118.2),  # inside the trace: not added to anything
    _stall(168.0, 171.0),  # 2 s before the window opens, 1 s inside it
    _stall(200.0, 200.45),
    _span("startup/summary", 140.0, 140.0, cache_read_bytes=123_000_000, cache_dir_bytes=999_000_000),
]
WANT = {
    "setup_before_program_s": 13.0,  # 100 -> 113
    "setup_import_s": 1.25,
    "setup_build_s": 0.8,  # 1.0 less the 0.2 traced inside it
    "setup_trace_lower_s": 4.2,  # 0.2 + 3.0 + 1.0; the nested 0.5 is inside the 3.0
    "setup_compile_s": 8.5,  # 0.5 + 8.0; the one after the window opened is nobody's
    "setup_cache_load_s": 6.0,  # the one inside the ramp is the ramp's
    "setup_cache_load_mb": 123.0,
    "setup_cache_misses": 1.0,  # the stamp at 139; the one at 181 came after the window opened
    "setup_first_call_s": 5.0,  # (13.5 - 3 - 1 - 0.5 - 6) + (10 - 8)
    "setup_stall_s": 2.2,  # 0.2 + the 2 s before 170
    "setup_unnamed_s": 6.25,  # 114.25-115, 116-116.5, 140-145: what no span covers before the ramp
    "host_stall_share.closed": 100.0 * 1.45 / 45.0,  # 1 s of the straddling stall and 0.45 s, of a 45 s window
    "host_stall_share.open": 100.0 * 1.45 / 45.0,
    "host_stall_share.train": 100.0 * 1.45 / 45.0,
    "setup_wall_s": 70.0,
}
# The cache loads as the harness's listener stamps them: (t0, t1) on ``perf_counter``.
LOADS = [(121.5, 127.5), (150.0, 151.0)]


def _run(startup_records, *, wall=70.0, ramp=25, platform="tpu", window=(170.0, 215.0), loads=LOADS):
    records = {"window": window, "spans": []}
    if startup_records is not None:
        records["startup"] = startup_records
    traffic = {"runner": "serve_closed"} | ({"ramp_seconds": ramp} if ramp is not None else {})
    return {"records": records, "end_to_end": {"setup_wall_s": wall, "serve_tokens_per_s": 650.0},
            "traffic": traffic, "device": {"platform": platform}, "trace": None, "config": {}, "chips": 1,
            "t_process": T_PROCESS, "cache_loads": list(loads)}


def _records(spans=SPANS):
    return {"t_package": 113.0, "spans": spans, "counters": {"cache_misses": [139.0, 181.0], "cache_hits": [127.5]},
            "dropped": 0}


def _read(metric: str, run: dict):
    return harness.load_module("metrics", metric).read(run)


# ------------------------------------------------------------- the entries


@pytest.mark.parametrize("name", list(ENTRIES))
def test_entry_has_its_file_its_layer_and_an_explicit_list_of_cells(name):
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    unit, moves, workloads = ENTRIES[name]
    entry = by_name[name]
    source = "host_clock" if name in HARNESS_OWN else "program_span"
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                     "layer": "start-up", "moves": moves, "workloads": workloads}
    assert (ROOT / "benchmarks" / "metrics" / f"{name}.py").is_file()
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    assert all(cell in end_to_end[moves].get("workloads", CELLS) for cell in workloads)


def test_the_fourteen_are_appended_together_and_nothing_else_names_the_layer():
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("setup_before_program_s")
    assert names[first:first + 15] == list(ENTRIES)  # PR 40's ``setup_wall_s`` is the fifteenth
    assert [m["name"] for m in BENCH["per_layer"] if m["layer"] == "start-up"] == list(ENTRIES)


# ------------------------------------------------ the readers, by hand


@pytest.mark.parametrize("name", list(ENTRIES))
def test_reader_against_arithmetic_done_by_hand(name):
    assert _read(name, _run(_records())) == pytest.approx(WANT[name])


def test_the_parts_the_ramp_and_the_unnamed_make_up_setup_s():
    run = _run(_records())
    parts = [startup.read(run, f"{part}_s") for part in startup.PARTS] + [startup.read(run, "unnamed_s")]
    assert sum(parts) == pytest.approx(70.0) and startup.read(run, "ramp_s") == 25.0
    assert sum(parts) == pytest.approx(_read("setup_wall_s", run), abs=1e-9)  # the wall, loads and all


def test_the_loads_booked_are_the_harness_own_and_not_the_programs_spans():
    inflated = SPANS + [_span("startup/cache_load", 140.0, 144.0)]  # a program that calls more of its time a load
    run = _run(_records(inflated))
    assert _read("setup_cache_load_s", run) == pytest.approx(6.0) and _read("setup_unnamed_s", run) == pytest.approx(6.25)
    assert _read("setup_cache_load_s", _run(_records(), loads=[])) == 0.0  # nothing loaded: 0, and the spans do not count


def test_a_cell_without_a_ramp_books_everything_before_the_window():
    run = _run(_records(), ramp=None)  # the train cell: 145 .. 170 is now set-up like the rest
    assert _read("setup_cache_load_s", run) == pytest.approx(7.0)  # the load at 150 counts
    assert _read("setup_unnamed_s", run) == pytest.approx(6.25 + 24.0)
    assert startup.read(run, "ramp_s") == 0.0


def test_sizes_come_from_the_spans_where_they_say_them_else_from_the_summary():
    sized = [dict(s, args=dict(s["args"], bytes=40_000_000)) if s["name"] == "startup/cache_load" else s
             for s in SPANS]
    assert _read("setup_cache_load_mb", _run(_records(sized))) == pytest.approx(40.0)  # the one before the window
    only_dir = [s for s in SPANS if s["name"] != "startup/summary"] + [_span("startup/summary", 140.0, 140.0,
                                                                             cache_dir_bytes=999_000_000)]
    assert _read("setup_cache_load_mb", _run(_records(only_dir))) == pytest.approx(999.0)
    no_summary = [s for s in SPANS if s["name"] != "startup/summary"]
    assert _read("setup_cache_load_mb", _run(_records(no_summary))) is None
    assert _read("setup_cache_load_s", _run(_records(no_summary))) == pytest.approx(6.0)


def test_no_stall_reads_zero_and_not_nothing():
    quiet = [s for s in SPANS if s["name"] != "host/stall"]
    run = _run(_records(quiet))
    assert _read("setup_stall_s", run) == 0.0 and _read("host_stall_share.closed", run) == 0.0


# --------------------------------------- a program without the buffer, a CPU


@pytest.mark.parametrize("name", list(ENTRIES))
def test_a_parent_shaped_run_reads_none_and_does_not_raise(name, monkeypatch):
    from llmtrain_tpu.telemetry import timeline

    monkeypatch.delattr(timeline, "process_spans")  # the program as it was before PR 39
    if name in HARNESS_OWN:  # the harness's own clock and listener need no buffer
        assert _read(name, _run(None)) == pytest.approx(WANT[name])
    else:
        assert _read(name, _run(None)) is None


@pytest.mark.parametrize("name", list(ENTRIES))
def test_off_the_chip_the_programs_buffer_is_not_read(name):
    assert _read(name, _run(None, platform="cpu")) is None


def test_on_the_chip_the_reader_takes_the_programs_own_buffer(monkeypatch):
    from llmtrain_tpu.telemetry import timeline

    monkeypatch.setattr(timeline, "process_spans", _records)
    assert _read("setup_first_call_s", _run(None)) == pytest.approx(5.0)
    run = _run(None)
    del run["end_to_end"]["setup_wall_s"]  # nothing to cut into parts
    assert _read("setup_first_call_s", run) is None


# ------------------------------- ``setup_s`` on the result line (PR 40)
#
# ``harness.main`` itself, with a runner that does nothing but replay the hand-made set-up above on
# the harness's clock: each cache load is JAX's own event, so the harness's listener stamps it.

CELL = "keye-vl2-30b-a3b.serve-video"  # a ramp of 25 s, as the hand-made set-up has it
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
# case -> (the loads as (end, seconds), the program's buffer or none, what the harness subtracts)
SETUP_S_CASES = {
    "a load nested in a compile in a first call is subtracted once": ([(127.5, 6.0)], True, 6.0),
    "two loads at once on two threads count once": ([(127.5, 6.0), (126.0, 3.0)], True, 6.0),
    "a load inside the ramp is the ramp's": ([(127.5, 6.0), (151.0, 1.0)], True, 6.0),
    "a load that straddles the ramp's start is cut there": ([(127.5, 6.0), (147.0, 3.0)], True, 7.0),
    "no load: the wall": ([], True, 0.0),
    "a program without the buffer is held to the same clock": ([(127.5, 6.0), (151.0, 1.0)], False, 6.0),
}


class _Clock:
    """``time`` as ``benchmarks/run.py`` sees it: the replaying runner sets ``now``."""

    def __init__(self, now: float) -> None:
        self.now = now

    def perf_counter(self) -> float:
        return self.now


def _main_with_replayed_setup(monkeypatch, capsys, loads, *, buffer=True, argv=()):
    import jax.monitoring

    from llmtrain_tpu.telemetry import timeline

    clock = _Clock(T_PROCESS + 5.0)
    monkeypatch.setattr(harness, "time", clock)
    monkeypatch.setattr(harness, "_T_PROCESS", T_PROCESS)
    if not buffer:
        monkeypatch.delattr(timeline, "process_spans")

    class Runner:
        @staticmethod
        def run(ctx):
            for t1, seconds in loads:
                clock.now = t1
                jax.monitoring.record_event_duration_secs(harness.CACHE_LOAD_EVENT, seconds)
            clock.now = 145.0  # warm: the ramp begins, the window opens 25 s on
            ctx.mark_window_start(clock.now + float(ctx.traffic["ramp_seconds"]))
            records = {"window": (170.0, 215.0), "spans": []} | ({"startup": _records()} if buffer else {})
            return {"checks": [], "attempted": 3, "failed": 0, "memory_peak_bytes": 1 << 30, "records": records,
                    "end_to_end": {"serve_tokens_per_s": 650.0}}

    real = harness.load_module
    monkeypatch.setattr(harness, "load_module", lambda kind, name: Runner if kind == "runners" else real(kind, name))
    assert harness.main(["--workload", CELL, "--seed", "2147483999", "--seconds", "45", *argv]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), next(ln for ln in out if "end to end:" in ln)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("case", list(SETUP_S_CASES))
def test_setup_s_on_the_result_line_is_the_wall_less_the_loads_before_the_ramp(case, trace, monkeypatch, capsys):
    loads, buffer, subtracted = SETUP_S_CASES[case]
    monkeypatch.setattr(harness, "require_devices", lambda ctx: dict(TPU))
    line, log = _main_with_replayed_setup(monkeypatch, capsys, loads, buffer=buffer, argv=["--trace", str(trace)])
    assert f"wall 70.000000 s less cache loads {subtracted:.6f} s" in log and f"setup_s {70.0 - subtracted:.6f} s" in log
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert got == {"serve_tokens_per_s": 650.0, "setup_s": pytest.approx(70.0 - subtracted, abs=1e-9)}
        return
    assert "setup_s" not in got and got["setup_wall_s"] == 70.0
    assert got["setup_cache_load_s"] == pytest.approx(subtracted, abs=1e-9)
    parts = [f"setup_{part}_s" for part in startup.PARTS if part != "ramp"] + ["setup_unnamed_s"]
    if buffer:  # the parts, the ramp and the unnamed make up the wall; ``setup_s`` is that less the loads
        assert sum(got[p] for p in parts) + 25.0 == pytest.approx(got["setup_wall_s"], abs=1e-9)
        if loads:  # neither the compile nor the first call around it holds the load
            assert (got["setup_compile_s"], got["setup_first_call_s"]) == pytest.approx((8.5, 5.0))
    else:
        assert [p for p in parts if p in got] == ["setup_cache_load_s"]


def test_a_cpu_rehearsal_reports_the_wall_and_says_that_nothing_was_subtracted(monkeypatch, capsys):
    line, log = _main_with_replayed_setup(monkeypatch, capsys, [(127.5, 6.0)], argv=["--rehearse-cpu"])
    assert "less cache loads not read (off the chip): nothing subtracted = setup_s 70.000000 s" in log
    assert "'setup_s': 70.0" in log and "'setup_wall_s': 70.0" in log
    assert "import_jax_s" in log and "jax_devices_s" in log  # what lies before the program's first line
    assert line["metrics"] == {} and line["rehearsal"] is True

"""The fourteen ``start-up`` metrics (PR 39): each reader against arithmetic
done by hand on a hand-made ``records["startup"]``, a parent-shaped run and
a run off the chip reading ``None``, and the entries of BENCHMARK.json.
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
# name -> (unit, moves, workloads)
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
    "host_stall_share.open": ("%", "serve_itl_p95_ms", ["gpt2-xl.serve-chat"]),
    "host_stall_share.train": ("%", "train_tokens_per_s", ["gpt2-small.train-64k"]),
}

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
}


def _run(startup_records, *, setup_s=70.0, ramp=25, platform="tpu", window=(170.0, 215.0)):
    records = {"window": window, "spans": []}
    if startup_records is not None:
        records["startup"] = startup_records
    traffic = {"runner": "serve_closed"} | ({"ramp_seconds": ramp} if ramp is not None else {})
    return {"records": records, "end_to_end": {"setup_s": setup_s, "serve_tokens_per_s": 650.0},
            "traffic": traffic, "device": {"platform": platform}, "trace": None, "config": {}, "chips": 1}


def _records(spans=SPANS):
    return {"t_package": 113.0, "spans": spans, "counters": {"cache_misses": [139.0, 181.0], "cache_hits": [127.5]},
            "dropped": 0}


@pytest.fixture(autouse=True)
def _process_stamp(monkeypatch):
    """What ``setup_s`` subtracts: ``benchmarks/run.py`` is ``__main__`` in a real run."""
    monkeypatch.setattr(sys.modules["__main__"], "_T_PROCESS", T_PROCESS, raising=False)


def _read(metric: str, run: dict):
    return harness.load_module("metrics", metric).read(run)


# ------------------------------------------------------------- the entries


@pytest.mark.parametrize("name", list(ENTRIES))
def test_entry_has_its_file_its_layer_and_an_explicit_list_of_cells(name):
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    unit, moves, workloads = ENTRIES[name]
    entry = by_name[name]
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": "program_span",
                     "layer": "start-up", "moves": moves, "workloads": workloads}
    assert (ROOT / "benchmarks" / "metrics" / f"{name}.py").is_file()
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    assert all(cell in end_to_end[moves].get("workloads", CELLS) for cell in workloads)


def test_the_fourteen_are_appended_together_and_nothing_else_names_the_layer():
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("setup_before_program_s")
    assert names[first:first + 14] == list(ENTRIES)
    assert [m["name"] for m in BENCH["per_layer"] if m["layer"] == "start-up"] == list(ENTRIES)


# ------------------------------------------------ the readers, by hand


@pytest.mark.parametrize("name", list(ENTRIES))
def test_reader_against_arithmetic_done_by_hand(name):
    assert _read(name, _run(_records())) == pytest.approx(WANT[name])


def test_the_parts_the_ramp_and_the_unnamed_make_up_setup_s():
    run = _run(_records())
    parts = [startup.read(run, f"{part}_s") for part in startup.PARTS] + [startup.read(run, "unnamed_s")]
    assert sum(parts) == pytest.approx(70.0) and startup.read(run, "ramp_s") == 25.0


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
    assert _read(name, _run(None)) is None


@pytest.mark.parametrize("name", list(ENTRIES))
def test_off_the_chip_the_programs_buffer_is_not_read(name):
    assert _read(name, _run(None, platform="cpu")) is None


def test_on_the_chip_the_reader_takes_the_programs_own_buffer(monkeypatch):
    from llmtrain_tpu.telemetry import timeline

    monkeypatch.setattr(timeline, "process_spans", _records)
    assert _read("setup_first_call_s", _run(None)) == pytest.approx(5.0)
    run = _run(None)
    del run["end_to_end"]["setup_s"]  # nothing to cut into parts
    assert _read("setup_first_call_s", run) is None

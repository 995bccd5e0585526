#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in a new process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one runner
or one per-layer metric is a file of its own, found by the name in
BENCHMARK.json: ``configs/<config>.json``, ``traffic/<traffic>.json`` (it
names its ``runner``), ``runners/<runner>.py``, ``metrics/<metric>.py``,
``reference/<family>.py``, ``limits/<cell>.json``. Nothing here branches
on a cell's or a configuration's name.

The last line of stdout is the contract's JSON object. Without the chips
the cell asks for it prints no result line and exits 2 — unless
``--rehearse-cpu`` (the harness's own flag) asks for a tiny CPU rehearsal,
whose result line carries no metric at all.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"  # recorded on a hit only
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration", CACHE_LOAD_EVENT)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def resolve_cell(workload: str) -> dict:
    """The cell's entry and its files, straight from BENCHMARK.json."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "bench": bench,
        "cell": cell,
        "config": load_json(ROOT / config_entry["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(BENCH / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


class _CompileCounter:
    """Counts JAX's backend compiles and persistent-cache loads, and keeps
    when each load ran (``perf_counter``): the seconds ``setup_s`` leaves out
    are the harness's own reading, not the program's spans."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.cache_loads: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1
        if event == CACHE_LOAD_EVENT:  # fires at the load's end, on the thread that loaded
            t1 = time.perf_counter()
            self.cache_loads.append((t1 - float(duration), t1))


class _Since:
    def __init__(self, counter: _CompileCounter) -> None:
        self._counter, self._base = counter, counter.count

    def stop(self) -> int:
        return self._counter.count - self._base


class Context:
    """What a runner gets: the cell's data, the clock marks, the tracer."""

    def __init__(self, args, resolved: dict) -> None:
        self.workload = args.workload
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.rehearse = bool(args.rehearse_cpu)
        self.control = bool(getattr(args, "control", False))  # calibrate.py only
        self.dump_trace = args.dump_trace
        self.root = ROOT
        self.work_dir = ROOT / ".cache" / "bench" / self.workload
        self.cell = resolved["cell"]
        self.chips = int(self.cell["chips"])
        self.traffic = resolved["traffic"]
        self.limits = resolved["limits"]
        config = dict(resolved["config"])
        if self.rehearse:
            config.update(config.get("rehearsal", {}))
        self.config = config
        self.reference = load_module("reference", config["family"])
        self.window_start: float | None = None
        self.stamps: dict[str, float] = {}  # seconds before the program's first line, for the log
        self._compiles = _CompileCounter()
        self.work_dir.mkdir(parents=True, exist_ok=True)

    def log(self, msg: str) -> None:
        print(f"[bench {self.workload}] {msg}", flush=True)

    def compile_counter(self) -> _Since:
        return _Since(self._compiles)

    def cache_loads(self) -> list[tuple[float, float]]:
        return list(self._compiles.cache_loads)

    def mark_window_start(self, t: float) -> None:
        self.window_start = t

    def memory_peak_bytes(self) -> int:
        import jax

        # On the TPU the allocator's ``peak_bytes_in_use`` counts live arrays
        # only; the temporaries of compiled programs live in a region the
        # runtime reserves beside them (``peak_bytes_reserved``; PR 23 read
        # 10.4 GB there for a step whose memory_analysis says 10.96 GB).
        def peak(stats: dict) -> int:
            return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))

        self.memory_stats = max((d.memory_stats() or {} for d in jax.local_devices()), key=peak)
        return peak(self.memory_stats)

    # -- profiler: a short steady part of the window, this process only
    def start_trace(self) -> dict:
        import jax

        trace_dir = self.work_dir / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        t_marker = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_marker"):
            pass
        return {"dir": str(trace_dir), "marker_pc": t_marker, "t0": time.perf_counter()}

    def stop_trace(self, info: dict) -> None:
        import jax

        info["t1"] = time.perf_counter()
        jax.profiler.stop_trace()


def require_devices(ctx: Context) -> dict:
    import jax

    t0 = time.perf_counter()
    devices = jax.devices()  # the TPU runtime starts here
    ctx.stamps["jax_devices_s"] = time.perf_counter() - t0
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    if ctx.rehearse:
        return info
    if dev.platform != "tpu" or len(devices) != ctx.chips:
        print(
            f"run.py: cell {ctx.workload!r} needs {ctx.chips} TPU chip(s); JAX reports "
            f"{len(devices)} x {dev.platform!r}. Nothing runs off the chip "
            "(--rehearse-cpu rehearses at a tiny size).",
            file=sys.stderr,
        )
        raise SystemExit(2)
    from benchmarks.lib.peaks import peaks_for

    peaks_for(dev.device_kind)  # unknown device: an error, not a default
    return info


def reduce_run_trace(ctx: Context, records: dict) -> dict | None:
    """Trace of the run -> busy/idle, ops and named gaps (trace clock
    aligned to the host's perf_counter by the ``bench_marker`` event)."""
    info = records.get("trace")
    if not info:
        return None
    from benchmarks.lib import trace as tr

    raw = tr.load_xplane(info["dir"])
    marker = tr.find_marker(raw, "bench_marker")
    window = None
    to_trace = None
    if marker is not None:
        to_trace = lambda pc: int(marker + (pc - info["marker_pc"]) * 1e9)  # noqa: E731
        window = (to_trace(info["t0"]), to_trace(info["t1"]))
    reduced = tr.reduce_trace(raw, window)
    spans = records.get("spans", [])
    if to_trace is not None:
        host = [(name, to_trace(t0), to_trace(t1)) for name, t0, t1 in spans]
        reduced["idle_gaps"] = tr.name_gaps(reduced["gaps"], host)
    else:
        reduced["idle_gaps"] = []
    reduced["gaps"] = len(reduced["gaps"])
    if ctx.dump_trace:
        out = Path(ctx.dump_trace)
        out.mkdir(parents=True, exist_ok=True)
        summary = {
            "planes": [
                {"name": p["name"], "lines": [
                    {"name": ln["name"], "n": len(ln["events"]), "head": ln["events"][:30]}
                    for ln in p["lines"]
                ]}
                for p in raw["planes"]
            ],
            "reduced": reduced,
        }
        (out / f"trace_{ctx.workload}.json").write_text(json.dumps(summary))
        (out / f"trace_cut_{ctx.workload}.json").write_text(json.dumps(tr.cut(raw)))
    shutil.rmtree(info["dir"], ignore_errors=True)
    return reduced


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny sizes on the CPU; the result line carries no metric")
    parser.add_argument("--dump-trace", default=None,
                        help="directory for a summary and a small cut of the trace")
    args = parser.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    resolved = resolve_cell(args.workload)
    t0 = time.perf_counter()
    import jax  # noqa: F401 — this process's first import of JAX, stamped for the log

    import_jax_s = time.perf_counter() - t0
    ctx = Context(args, resolved)
    ctx.stamps["import_jax_s"] = import_jax_s
    device = require_devices(ctx)
    import llmtrain_tpu  # noqa: F401 — alone with its own files this fails here, no result line

    runner = load_module("runners", ctx.traffic["runner"])
    result = runner.run(ctx)
    if ctx.window_start is None:
        raise RuntimeError("the runner never marked the start of its window")

    correct = True
    for check in result["checks"]:
        ok = check["value"] <= check["limit"]
        correct = correct and ok
        ctx.log(
            f"check {check['name']}: {check['value']:.6g} against limit {check['limit']:.6g} "
            f"-> {'ok' if ok else 'FAIL'} ({check.get('detail', '')})"
        )
    if result.get("compiles_in_window"):
        ctx.log(f"FAIL: {result['compiles_in_window']} compilation(s) inside the window")
        correct = False

    from benchmarks.lib import startup

    values = dict(result["end_to_end"])
    values["setup_wall_s"] = wall = ctx.window_start - _T_PROCESS
    reduced = reduce_run_trace(ctx, result["records"]) if ctx.trace else None
    run_view = {
        "records": result["records"], "end_to_end": values, "trace": reduced,
        "config": ctx.config, "reference": ctx.reference, "traffic": ctx.traffic, "device": device,
        "chips": ctx.chips, "t_process": _T_PROCESS, "cache_loads": ctx.cache_loads(),
    }
    # ``setup_s``: the wall clock less the persistent cache's loads before the
    # ramp (lib/startup.py). Where they are not read, nothing is subtracted.
    loads_s = startup.read(run_view, "cache_load_s")
    values["setup_s"] = wall if loads_s is None else wall - loads_s
    loads = (
        "not read (off the chip): nothing subtracted" if loads_s is None
        else f"{loads_s:.6f} s (those before the ramp, of {len(run_view['cache_loads'])} in the process)"
    )
    stamps = ", ".join(f"{k} {v:.3f}" for k, v in ctx.stamps.items())
    ctx.log(
        f"end to end: {values}; set-up: wall {wall:.6f} s less cache loads {loads} = setup_s "
        f"{values['setup_s']:.6f} s; before the program: {stamps}; memory_stats: {getattr(ctx, 'memory_stats', None)}"
    )
    metrics: dict[str, dict] = {}
    device["memory_peak_bytes"] = int(result["memory_peak_bytes"])
    line: dict = {
        "correct": bool(correct),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if ctx.trace:
        for metric in resolved["per_layer"]:
            value = load_module("metrics", metric["name"]).read(run_view)
            if value is not None:
                metrics[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in reduced["ops"][:10]],
                "idle_gaps": reduced["idle_gaps"][:10],
            }
    else:
        for metric in resolved["end_to_end"]:
            if values.get(metric["name"]) is not None:
                metrics[metric["name"]] = {"value": float(values[metric["name"]]), "unit": metric["unit"]}
    if ctx.rehearse:
        ctx.log(f"rehearsal on {device['platform']}: values are not device numbers: {metrics}")
        line["metrics"] = {}
        line["rehearsal"] = True
        device.pop("busy_s", None)
        device.pop("window_s", None)
        line.pop("breakdown", None)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

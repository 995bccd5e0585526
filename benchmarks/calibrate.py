#!/usr/bin/env python3
"""Readings a ``correct`` limit is set from: the program's numbers over
many seeds, and the lower-precision control's, in ONE process.

    python benchmarks/calibrate.py --workload <cell> --seeds 11,12,13 [--control-seeds 21,22,23]

Calls the cell's runner's ``check_only(ctx)`` per seed (set-up and the
compared quantities, no measured window where the runner needs none) and
prints one JSON line per seed. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import run as harness  # noqa: E402


def _namespace(args, seed: int, control: bool = False) -> argparse.Namespace:
    return argparse.Namespace(
        workload=args.workload, seed=seed, seconds=args.seconds, trace=0,
        rehearse_cpu=args.rehearse_cpu, control=control, dump_trace=None,
    )


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument("--out", default=None)
    parser.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                        help="override a key of the traffic file for this sizing experiment")
    parser.add_argument("--sweep-rates", default="",
                        help="open-loop cells: run these rates on one warm server and print each row")
    args = parser.parse_args()
    resolved = harness.resolve_cell(args.workload)
    for item in args.set:
        key, _, value = item.partition("=")
        resolved["traffic"][key] = json.loads(value)
    rows = []
    if args.sweep_rates:
        ctx = harness.Context(_namespace(args, int(args.seeds.split(",")[0])), resolved)
        harness.require_devices(ctx)
        runner = harness.load_module("runners", ctx.traffic["runner"])
        for row in runner.sweep(ctx, [float(r) for r in args.sweep_rates.split(",")], args.seconds):
            print("SWEEP " + json.dumps(row), flush=True)
        return 0
    runner = harness.load_module("runners", resolved["traffic"]["runner"])
    if hasattr(runner, "check_seeds"):  # serving: one warm server for every seed
        ctx = harness.Context(_namespace(args, 0), resolved)
        harness.require_devices(ctx)
        rows = runner.check_seeds(ctx, [int(s) for s in args.seeds.split(",") if s])
        for row in rows:
            print("CALIBRATE " + json.dumps(row), flush=True)
        args.seeds = args.control_seeds = ""
    for control, seeds in ((False, args.seeds), (True, args.control_seeds)):
        for seed in [int(s) for s in seeds.split(",") if s]:
            ctx = harness.Context(_namespace(args, seed, control), resolved)
            if not rows:
                harness.require_devices(ctx)
            out = runner.check_only(ctx)
            row = {"seed": seed, "control": control,
                   "checks": {c["name"]: c["value"] for c in out["checks"]},
                   "detail": {c["name"]: c.get("detail") for c in out["checks"]}}
            rows.append(row)
            print("CALIBRATE " + json.dumps(row), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

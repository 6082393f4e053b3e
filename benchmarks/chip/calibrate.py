"""Calibration runs for one cell, many in one process (not a benchmark run).

    python benchmarks/chip/calibrate.py --workload <name> --seconds <s> \
        --seeds 1,2,3 [--scales 0.5,1,2] [--trace 0|1] [--out <file>]

Runs the cell once per (seed, rate scale) with no limit on the logit
gaps, and prints one JSON line per run: every metric of BENCHMARK.json
the run can read, the numbers `correct` compares for the served tokens
and for each control (W8A8, float8) at the same positions, the agreement share, the
set-up's parts and the drain.  It is how a cell's rate (the knee, from a sweep over
--scales) and its limits (from the readings over a dozen seeds) are
found; the numbers go into the traffic and limits files by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--scales", default="1")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("--drain-limit", type=float, default=bench.DRAIN_LIMIT_S)
    args = ap.parse_args(argv)
    spec = bench.load_spec()
    cell, _ = bench.find_cell(spec, args.workload)
    bench.require_chip(int(cell["chips"]))
    bench.enable_cache()
    # the cell's own check (its sample), with no number held to a limit
    limits = json.loads((bench.HERE / "limits" / f"{args.workload}.json")
                        .read_text())
    limits = {k: (float("inf") if k in bench.gap_numbers([]) else v)
              for k, v in limits.items()}
    out = open(args.out, "a") if args.out else None
    for scale in [float(x) for x in args.scales.split(",")]:
        for seed in [int(x) for x in args.seeds.split(",")]:
            t0 = time.perf_counter()
            res = bench.run_cell(spec, args.workload, seed, args.seconds,
                                 bool(args.trace), rate_scale=scale,
                                 limits=limits,
                                 t_start=t0, control=True,
                                 drain_limit_s=args.drain_limit,
                                 keep_trace=args.keep_trace or None,
                                 log=lambda m: print(m, file=sys.stderr,
                                                     flush=True))
            row = {"workload": args.workload, "seed": seed, "scale": scale,
                   "seconds": args.seconds, "trace": args.trace,
                   "wall_s": time.perf_counter() - t0,
                   "failed": res["failed"], "attempted": res["attempted"],
                   "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                   "device": res["device"], **res["_extra"]}
            if "breakdown" in res:
                row["breakdown"] = res["breakdown"]
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

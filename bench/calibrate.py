#!/usr/bin/env python3
"""Readings for the limits of a cell's check, on the chip.

    python bench/calibrate.py --workload <name> --seeds 12 --control-seeds 3 --seconds 5

In one process: the cell's timed path on `--seeds` seeds, then the
lower-precision control (the program's own bfloat16 path in the
program's place) on `--control-seeds` further seeds, each through a short
window at the cell's own size and load, followed by the cell's check.
Prints each run's compared numbers; the lower reading of a number is the
largest the program gives, the upper the smallest the control gives.
The benchmark's runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
sys.path.insert(0, BENCH_DIR)

CONTROL_DTYPE = "bfloat16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    args = ap.parse_args(argv)
    import harness
    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.chips)
    harness.use_compile_cache()
    readings = {"program": {}, "control": {}}
    seeds = [args.first_seed + 7919 * i
             for i in range(args.seeds + args.control_seeds)]
    for i, seed in enumerate(seeds):
        side = "program" if i < args.seeds else "control"
        dtype = None if side == "program" else CONTROL_DTYPE
        t = time.perf_counter()
        try:
            line = harness.run_cell(args.workload, seed, args.seconds, False,
                                    t, devices=devices, dtype=dtype)
            checks = {k: c["value"] for k, c in line["checks"].items()}
        except Exception as e:  # noqa: BLE001 — a crashed control fails
            checks = {"error": repr(e)[:300]}
        print(json.dumps({"side": side, "seed": seed, "checks": checks,
                          "s": round(time.perf_counter() - t, 1)}),
              flush=True)
        for k, v in checks.items():
            readings[side].setdefault(k, []).append(v)
    summary = {k: {"lower": max(v)} for k, v in readings["program"].items()
               if k != "error"}
    for k, v in readings["control"].items():
        if k in summary:
            summary[k]["upper"] = min(v)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

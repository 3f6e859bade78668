#!/usr/bin/env python3
"""Find the highest burst rate a serve cell sustains, on the chip.

    python bench/sweep.py --workload <serve cell> --intervals 3 2.5 2 --seconds 30

One set-up, then one window per burst interval, in the order given.  For
each it prints the latency median and 95th percentile over the requests
due in the window, the mean latency of the first and of the last third
of the bursts, and how many requests were still unfinished at the
window's close.  A backlog that grows shows as later bursts waiting
longer than the first ones.  The benchmark's runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
sys.path.insert(0, BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--intervals", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=424242)
    args = ap.parse_args(argv)
    import harness
    import window
    import yardstick
    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.chips)
    harness.use_compile_cache()
    serve = window.Serve(cell, args.seed, devices)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    base = dict(cell.traffic)
    for interval in args.intervals:
        serve.traffic = dict(base, burst_interval_s=interval)
        serve.engine._results.clear()
        serve.window(args.seconds)
        sched, lat = serve.sched, {}
        for j, r in enumerate(sched):
            t = serve.done.get(j)
            ok = serve.status.get(j) == "ok"
            lat[j] = (t - r.due_s) if (t is not None and ok) else float("inf")
        bursts = sorted({r.burst for r in sched})
        third = max(1, len(bursts) // 3)
        mean_of = lambda bs: statistics.fmean(
            lat[j] for j, r in enumerate(sched) if r.burst in bs)
        open_at_close = sum(1 for j in range(len(sched))
                            if serve.done.get(j, 1e9) > args.seconds)
        print(json.dumps({
            "interval_s": interval, "requests": len(sched),
            "p50_s": yardstick.percentile(list(lat.values()), 50),
            "p95_s": yardstick.percentile(list(lat.values()), 95),
            "first_third_mean_s": mean_of(set(bursts[:third])),
            "last_third_mean_s": mean_of(set(bursts[-third:])),
            "open_at_close": open_at_close,
            "rounds": serve.rounds,
            "round_ms": 1e3 * serve.pump_s / max(1, serve.rounds),
            "late_max_s": max(serve.late)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

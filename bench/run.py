#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it is started on.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells are `BENCHMARK.json`'s workloads.  Set-up (imports, inputs made
from the seed, compiles, warm-up) is timed from the start of this process;
then the window runs for `--seconds`, then its output is compared with the
plain reference.  The last line of standard output is one JSON object:
`correct`, `attempted`, `failed`, `metrics` (the end-to-end metrics, or
with `--trace 1` the per-layer ones read from a profiler trace of the
window), `device`, and last `checks`, each compared number beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
sys.path.insert(0, BENCH_DIR)
# The TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
# writes only inside its checkout.
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(BENCH_DIR, "_work", "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.chips)
    harness.use_compile_cache()
    line = harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START, devices=devices)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())

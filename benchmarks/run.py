"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (model-derived values labeled in
the derived column; this container is CPU-only so TPU numbers are
dry-run/model projections, wall-clock numbers are real).

Machine-readable output: individual modules write their own
``BENCH_*.json`` artifacts (``dycore_fused`` writes ``BENCH_dycore.json``);
this driver additionally dumps every emitted CSV row to ``BENCH_run.json``
so the full perf trajectory is diffable across PRs.  ``BENCH_DIR`` picks
the output directory; ``BENCH_SMOKE=1`` shrinks grids/iters for the CI
smoke job (see .github/workflows/ci.yml)."""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (common, copy_stencil, dryrun_table, dycore_fused,
                            energy, kernel_walltime, pe_scaling,
                            roofline_kernels, serve_forecast, table3,
                            tile_autotune)
    print("name,us_per_call,derived")
    failures = []
    for mod in (roofline_kernels, copy_stencil, tile_autotune, pe_scaling,
                energy, table3, kernel_walltime, dycore_fused, dryrun_table,
                serve_forecast):
        try:
            mod.run()
        except Exception as e:     # keep the suite going; record failure
            failures.append(f"{mod.__name__}: {type(e).__name__}: {e}")
            print(f"{mod.__name__},0.0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    common.write_json("BENCH_run.json", {"rows": common.records(),
                                         "errors": failures})
    if failures:   # fail the process so the CI smoke job goes red
        sys.exit(f"{len(failures)} benchmark module(s) failed: {failures}")


if __name__ == "__main__":
    main()

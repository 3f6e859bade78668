"""End-to-end weather driver: ensemble dycore simulation with the paper's
compound kernels, optionally domain-decomposed over a device mesh.

The execution strategy comes from ONE declarative plan
(`repro.weather.program.compile_dycore`): the spec names the grid,
ensemble, and policies; the planner resolves the variant (whole-state
fused / in-kernel k-step / unfused oracle via `--no-fused`), the
auto-tuned tile, the steps-per-round depth (`--k-steps`, `auto` lets the
exchange model pick), and — on a mesh — the ragged packed halo-exchange
schedule.  `plan.run` advances any step count (a shorter tail round
covers `steps % k`).  Ensemble members (`--ensemble N`) are
data-parallel: on a mesh with a "pod" axis they shard across it with zero
extra halo traffic — see docs/architecture.md ("Scale-out: domain
decomposition and ensemble pods").

Run:  PYTHONPATH=src python examples/weather_simulation.py --steps 10
      XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/weather_simulation.py --mesh 2,2
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.weather import domain, fields
from repro.weather.program import DycoreProgram, compile_dycore
from repro.launch.mesh import make_mesh
from repro.launch.cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", default="16,64,64")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--ensemble", type=int, default=2)
    ap.add_argument("--mesh", default="",
                    help="e.g. 2,2 -> ('data','model') decomposition")
    ap.add_argument("--k-steps", default="1",
                    help="timesteps per round (int, or 'auto' to let the "
                         "planner resolve the communication-avoiding k)")
    ap.add_argument("--op", default="dycore",
                    choices=("dycore", "hdiff", "vadvc"),
                    help="which registered stencil op to run (the paper "
                         "evaluates hdiff and vadvc separately)")
    ap.add_argument("--no-fused", action="store_true",
                    help="unfused oracle composition instead of the fused "
                         "Pallas pipeline (docs/architecture.md)")
    args = ap.parse_args()

    grid = tuple(int(x) for x in args.grid.split(","))
    k_steps = args.k_steps if args.k_steps == "auto" else int(args.k_steps)
    st = fields.initial_state(jax.random.PRNGKey(0), grid,
                              ensemble=args.ensemble)
    print(f"grid={grid} ensemble={args.ensemble} steps={args.steps}")

    if args.op == "vadvc" and k_steps not in (1, "auto"):
        raise SystemExit("vadvc has no k-step round (its footprint does "
                         "not deepen with k); use --k-steps 1")
    program = DycoreProgram(
        grid_shape=grid, ensemble=args.ensemble, op=args.op,
        variant="unfused" if args.no_fused else "auto", k_steps=k_steps)
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape, ("data", "model"))
        plan = compile_dycore(program, mesh=mesh)
        st = domain.shard_state(st, mesh, plan.state_spec)
        print(f"domain-decomposed over mesh {dict(mesh.shape)}")
    else:
        plan = compile_dycore(program)
    rep = plan.report()
    print(f"plan: variant={rep['variant']} k_steps={rep['k_steps']} "
          f"tile={rep['tile']['tile'] if rep['tile'] else None} "
          f"launches/round={rep['pallas_calls_per_round']} "
          f"collectives/round={rep['collectives_per_round']}")

    t0 = time.perf_counter()
    energy0 = float(sum(jnp.sum(jnp.square(f))
                        for f in st.fields.values()))
    st = plan.run(st, args.steps)   # full rounds + ragged tail if needed
    jax.block_until_ready(st.fields["t"])
    dt = time.perf_counter() - t0
    energy1 = float(sum(jnp.sum(jnp.square(f)) for f in st.fields.values()))
    pts = args.ensemble * np.prod(grid) * args.steps
    print(f"{args.steps} steps in {dt:.2f}s "
          f"({pts / dt / 1e6:.1f}M point-updates/s)")
    print(f"field energy {energy0:.1f} -> {energy1:.1f} "
          f"(diffusion dissipates: {energy1 < energy0})")
    assert np.isfinite(energy1)
    print("weather simulation OK")


if __name__ == "__main__":
    main()

"""The one generator of the benchmark's inputs: states from the seed, and
the schedule a traffic file describes.

A traffic file (`bench/traffic/<name>.json`) holds parameters only.  Its
`kind` picks the window that drives it:

* `batch`: back-to-back forecasts of `forecast_steps` steps of the whole
  ensemble, each from the initial state, advanced by `run()` calls of
  `steps_per_call` steps that continue from the last state.  A forecast
  has a length: the repo's dycore grows about 5% a step, program and
  reference alike, and float32 overflows after about 1850 steps;
* `serve`: an open loop of bursts, one every `burst_interval_s`.  A burst
  is one ensemble cycle: every member of the configuration submitted as
  its own ensemble-1 request.  Request lengths in a burst are the
  configuration's member count of lengths spread evenly over `steps`
  ([min, max]), submitted in an order rotated by `rotate` places from one
  burst to the next.  Sizes and arrivals are the same for every seed, so
  every seed offers the same work; the seed draws the initial states and
  which member each request starts from.  `check_sample` requests due in
  the window, the longest among them, are compared with the reference.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def load(path: str) -> Dict:
    with open(path) as f:
        traffic = json.load(f)
    if traffic.get("kind") not in ("batch", "serve"):
        raise ValueError(f"{path}: kind {traffic.get('kind')!r} is not "
                         f"'batch' or 'serve'")
    return traffic


def rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named use of the seed (any whole number,
    also past 32 bits)."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([seed % 2**64, tag])


def key_data(seed: int) -> np.ndarray:
    """A JAX PRNG key (raw uint32 pair) from any whole-number seed."""
    return np.random.SeedSequence(seed % 2**64).generate_state(
        2, dtype=np.uint32)


def _state_of(key, grid: Tuple[int, int, int], members: int,
              names: Tuple[str, ...], dtype: str):
    """Band-limited random fields (random values on a grid 8x coarser,
    trilinearly resized): fields ~N(0, 1), slow tendencies 0.01x, the
    vertical velocity 0.15x (a well-conditioned implicit solve), and zero
    stage tendencies."""
    shape = (members,) + tuple(grid)
    coarse = (members,) + tuple(max(2, s // 8) for s in grid)
    keys = jax.random.split(key, 2 * len(names) + 1)

    def smooth(k, scale):
        x = jax.random.normal(k, coarse, jnp.float32)
        x = scale * jax.image.resize(x, shape, method="trilinear")
        return x.astype(dtype)

    nf = len(names)
    return {"fields": {n: smooth(keys[i], 1.0) for i, n in enumerate(names)},
            "tens": {n: smooth(keys[nf + i], 0.01)
                     for i, n in enumerate(names)},
            "stage_tens": {n: jnp.zeros(shape, dtype) for n in names},
            "wcon": smooth(keys[-1], 0.15)}


_make_state = jax.jit(_state_of, static_argnums=(1, 2, 3, 4))


def make_state(seed: int, program: Dict, members: int,
               sharding: Optional[jax.sharding.Sharding] = None) -> Dict:
    """The initial state of `members` members, made on the device in one
    jitted call, in the program's dtype: {fields, tens, stage_tens: {name:
    (members, nz, ny, nx)}, wcon: (members, nz, ny, nx)}.  With `sharding`
    (every leaf's, on a mesh) the call makes each leaf where it lives, so
    no chip holds more than its share; the values are those made on one
    device, bit for bit (JAX's partitionable threefry)."""
    make = (_make_state if sharding is None else
            jax.jit(_state_of, static_argnums=(1, 2, 3, 4),
                    out_shardings=sharding))
    return make(jnp.asarray(key_data(seed)), tuple(program["grid_shape"]),
                int(members), tuple(program["fields"]), program["dtype"])


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float            # offset from the window's start
    steps: int
    member: int             # which state of the pool it starts from
    burst: int


def burst_lengths(steps: Sequence[int], members: int) -> List[int]:
    lo, hi = steps
    return [int(x) for x in np.rint(np.linspace(lo, hi, members))]


def serve_schedule(traffic: Dict, members: int, seed: int,
                   horizon_s: float) -> List[Request]:
    """Every request due before `horizon_s`, in due order.  Burst `b`
    submits the lengths rotated by `b * rotate`, the same for every seed;
    the seed draws which member's state each request starts from."""
    lengths = burst_lengths(traffic["steps"], members)
    pick = rng(seed, "members")
    out, b = [], 0
    while b * traffic["burst_interval_s"] < horizon_s:
        due = b * traffic["burst_interval_s"]
        shift = (b * traffic["rotate"]) % members
        order = lengths[shift:] + lengths[:shift]
        state_of = pick.permutation(members)
        out.extend(Request(due, order[p], int(state_of[p]), b)
                   for p in range(members))
        b += 1
    return out


def check_sample(traffic: Dict, schedule: Sequence[Request], seed: int
                 ) -> List[int]:
    """Indices into `schedule` of the requests compared with the
    reference: one of the longest and the rest drawn from the seed."""
    pick = rng(seed, "sample")
    longest = max(r.steps for r in schedule)
    tops = [i for i, r in enumerate(schedule) if r.steps == longest]
    first = int(pick.choice(tops))
    rest = [i for i in range(len(schedule)) if i != first]
    k = min(len(rest), traffic["check_sample"] - 1)
    return sorted([first] + [int(i) for i in pick.choice(rest, k,
                                                         replace=False)])

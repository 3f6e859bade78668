"""The benchmark's own arithmetic: compulsory bytes, rates, tails, the
gap to the reference, and the table of device peaks.

Nothing here reads the program's traffic or performance models: a
configuration states which operands a step must read and write, and the
count follows from that and the grid alone, whatever implements the step.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, Iterable, Optional, Sequence

import numpy as np

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")

# Arrays per field in each operand group of a weather state: the three
# per-field groups hold one array per prognostic field, wcon one in all.
_PER_FIELD = ("fields", "tens", "stage_tens")
_SHARED = ("wcon",)


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> Dict:
    """The published peaks of one chip of `device_kind`.  A kind that is
    not in the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _arrays(groups: Iterable[str], n_fields: int) -> int:
    n = 0
    for g in groups:
        if g in _PER_FIELD:
            n += n_fields
        elif g in _SHARED:
            n += 1
        else:
            raise ValueError(f"unknown operand group {g!r}")
    return n


def compulsory_arrays(config: Dict) -> int:
    """Arrays one member-step must read plus those it must write."""
    nf = len(config["program"]["fields"])
    comp = config["compulsory"]
    return _arrays(comp["reads"], nf) + _arrays(comp["writes"], nf)


def compulsory_bytes_per_point_step(config: Dict) -> int:
    """HBM bytes one grid-point-step of one member must move."""
    import jax.numpy as jnp
    itemsize = jnp.dtype(config["program"]["dtype"]).itemsize
    return compulsory_arrays(config) * itemsize


def gridpoints(config: Dict) -> int:
    nz, ny, nx = config["program"]["grid_shape"]
    return config["members"] * nz * ny * nx


def rate(work: float, seconds: float) -> float:
    """All the work of a window over all of its time."""
    if not seconds > 0:
        raise ValueError(f"window of {seconds} s")
    return work / seconds


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile of all values, a failure entered as +inf (so a
    tail that reaches a failure is infinite).  Nearest rank: the value
    itself, never an interpolation towards +inf."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def latencies(due_s: Dict[int, float], done_s: Dict[int, Optional[float]]
              ) -> Dict[int, float]:
    """Latency of each request from the time it was due; one that never
    finished (None) is +inf."""
    out = {}
    for rid, due in due_s.items():
        done = done_s.get(rid)
        out[rid] = math.inf if done is None else done - due
    return out


def rel_gap(diff_norms: Dict[str, float], ref_norms: Dict[str, float]
            ) -> float:
    """The worst leaf's L2 distance from the reference (`diff_norms`), over
    the larger of that leaf's reference norm and the median leaf's.  A NaN
    gives NaN, which no limit admits."""
    floor = float(np.median(list(ref_norms.values())))
    worst = 0.0
    for k, d in diff_norms.items():
        gap = float(d) / max(float(ref_norms[k]), floor, 1e-30)
        if math.isnan(gap):
            return math.nan
        worst = max(worst, gap)
    return worst


def step_roofline(run) -> Optional[float]:
    """The whole step's share of the HBM roofline, in %: the compulsory
    bytes of the grid-point-steps completed in the traced window over the
    device's busy time in it, over the peak bandwidth of the cell's chips.
    On several chips the bytes are all of them, the busy time is the mean
    over the chips, and the peak is `chips` times one chip's."""
    steps = run.counters.get("gridpoint_steps")
    if run.trace is None or not steps:
        return None
    moved = compulsory_bytes_per_point_step(run.config) * steps
    return 100.0 * moved / run.trace.busy_s / (
        run.cell.chips * run.peaks["hbm_bytes_per_s"])


def idle_share(run) -> Optional[float]:
    """The traced window's share, in %, in which no operation ran on the
    device.  On several chips, the mean over the cell's chips (busy time
    averaged by `trace_reduce.summarize(n_devices=chips)`)."""
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share

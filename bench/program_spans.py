"""The program's own host spans in a traced window, laid over the device's
operations on the profiler's one clock.

`ForecastEngine` writes `forecast.*` spans (pump, admit, round, step,
guard, retire, ...) and `ExecutionPlan` writes `plan.run`, whose `kernels`
metadata names the Pallas kernels its round launches, `;` between names.  From those this
module reduces, over the benchmark's `bench.window` span:

* the engine's host time per round, by span, and its readback time per
  retired request;
* the device's idle time inside `forecast.pump` spans, each stretch named
  by the innermost `forecast.*` span it fell in: how long the chip waited
  on the engine's host code rather than on arrivals;
* the lowering's share of device busy time: all but the self time of the
  operations that the `plan.run` spans name as kernels (an instance of a
  kernel reads `<name>.<n>` in the trace).

`summarize` is the reduction, on plain tuples; `for_run` reads a run's
trace once and keeps the result on the run for each metric that asks.
A program without these spans gives a summary in which each metric finds
nothing (None).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr

PREFIXES = ("forecast.", "plan.")
PUMP, ROUND, RETIRE, RUN = ("forecast.pump", "forecast.round",
                            "forecast.retire", "plan.run")
INSTANCE = re.compile(r"\.\d+$")

# (start ns, end ns, name, metadata)
Span = Tuple[float, float, str, Dict[str, Any]]


@dataclasses.dataclass
class Summary:
    rounds: int                       # forecast.round spans in the window
    retires: int
    span_ms_per_round: Dict[str, float]   # host ms of each span per round
    readback_ms: Optional[float]      # mean forecast.retire, ms
    idle_in_pump_s: Dict[str, float]  # device idle by innermost span, s
    kernels: Tuple[str, ...]          # named by the plan.run spans
    kernel_s: float                   # their device self time, s
    busy_s: float

    @property
    def host_gap_ms(self) -> Optional[float]:
        """Device idle inside `forecast.pump` per round, ms."""
        if not self.rounds or self.busy_s <= 0:
            return None
        return 1e3 * sum(self.idle_in_pump_s.values()) / self.rounds

    @property
    def lowering_share(self) -> Optional[float]:
        """100 x (1 - kernel self time / device busy time)."""
        if not self.kernels or self.busy_s <= 0:
            return None
        return 100.0 * (1.0 - self.kernel_s / self.busy_s)

    def per_round_ms(self, name: str) -> Optional[float]:
        return self.span_ms_per_round.get(name) if self.rounds else None


def kernel_of(op: str) -> str:
    """A device operation's kernel name, its instance suffix dropped."""
    return INSTANCE.sub("", tr.op_name(op))


def _window(spans: Sequence[Span], device_ops) -> Tuple[float, float]:
    windows = [(s, e) for s, e, n, _ in spans if n == tr.WINDOW_SPAN]
    if windows:
        return max(windows, key=lambda w: w[1] - w[0])
    every = [x for ops in device_ops.values() for x in ops]
    if not every:
        raise ValueError("trace holds no window span and no device op")
    return min(x[0] for x in every), max(x[1] for x in every)


def _idle_by_span(pumps, inner: List[Tuple[float, float, str]],
                  busy: List[tr.Interval]) -> Dict[str, float]:
    """Device idle inside each pump, cut at the boundaries of the spans in
    it and each piece named by the innermost span that holds it (ns)."""
    out: Dict[str, float] = {}
    starts = [s for s, _, _ in inner]
    bstarts = [s for s, _ in busy]
    for ps, pe in pumps:
        held = inner[bisect.bisect_left(starts, ps):
                     bisect.bisect_right(starts, pe)]
        b0 = max(0, bisect.bisect_right(bstarts, ps) - 1)
        b1 = bisect.bisect_left(bstarts, pe)
        for gs, ge in tr.gaps(busy[b0:b1], ps, pe):
            cuts = sorted({gs, ge} | {t for s, e, _ in held for t in (s, e)
                                       if gs < t < ge})
            for u, v in zip(cuts, cuts[1:]):
                name = tr.label((u + v) / 2, held)
                out[name] = out.get(name, 0.0) + (v - u)
    return out


def summarize(spans: Sequence[Span],
              device_ops: Dict[str, List[Tuple[float, float, str]]],
              n_devices: int = 1) -> Summary:
    """The reduction, on plain tuples (ns): the program's spans (start,
    end, name, metadata) with the benchmark's window span among them, and
    each device plane's operations (start, end, name)."""
    lo, hi = _window(spans, device_ops)
    mine = sorted(((s, e, n, m) for s, e, n, m in spans
                   if n.startswith(PREFIXES) and lo <= s < hi),
                  key=lambda x: (x[0], x[1]))
    totals: Dict[str, float] = {}
    count: Dict[str, int] = {}
    kernels: List[str] = []
    for s, e, n, m in mine:
        totals[n] = totals.get(n, 0.0) + (e - s)
        count[n] = count.get(n, 0) + 1
        if n == RUN:
            kernels += [k for k in m.get("kernels", "").split(";")
                        if k and k not in kernels]
    per_device = []
    for ops in device_ops.values():
        clipped = sorted(((max(s, lo), min(e, hi), n) for s, e, n in ops
                          if e > lo and s < hi), key=lambda x: (x[0], -x[1]))
        own = sum(t for (_, _, n), t in zip(clipped, tr.self_times(clipped))
                  if kernel_of(n) in kernels)
        busy = tr.union([(s, e) for s, e, _ in clipped])
        per_device.append((sum(e - s for s, e in busy), own, busy))
    per_device.sort(key=lambda x: -x[0])
    used = per_device[:n_devices]
    rounds, retires = count.get(ROUND, 0), count.get(RETIRE, 0)
    idle = {}
    if used and rounds:
        pumps = [(s, e) for s, e, n, _ in mine if n == PUMP]
        inner = [(s, e, n) for s, e, n, _ in mine
                 if n.startswith(PREFIXES[0])]
        idle = _idle_by_span(pumps, inner, used[0][2])
    return Summary(
        rounds=rounds, retires=retires,
        span_ms_per_round={n: 1e-6 * t / rounds for n, t in totals.items()
                           if n.startswith(PREFIXES[0])} if rounds else {},
        readback_ms=1e-6 * totals[RETIRE] / retires if retires else None,
        idle_in_pump_s={n: 1e-9 * t for n, t in idle.items()},
        kernels=tuple(kernels),
        kernel_s=1e-9 * sum(k for _, k, _ in used) / n_devices,
        busy_s=1e-9 * sum(b for b, _, _ in used) / n_devices)


def read(path: str):
    """(the program's spans and the window span, device ops per plane) of
    an `.xplane.pb` file."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans, device_ops = [], {}
    with warnings.catch_warnings():
        # The reader's event-stats type warns as it is first built.
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(PREFIXES):
                            spans.append((ev.start_ns, ev.end_ns, ev.name,
                                          dict(ev.stats)))
                        elif ev.name == tr.WINDOW_SPAN:
                            spans.append((ev.start_ns, ev.end_ns, ev.name,
                                          {}))
            elif tr.DEVICE_PLANE.match(plane.name):
                device_ops[plane.name] = [
                    (ev.start_ns, ev.end_ns, ev.name)
                    for line in plane.lines if line.name == tr.OPS_LINE
                    for ev in line.events]
    return spans, device_ops


def newest_trace(bench_dir: str) -> Optional[str]:
    """The newest `.xplane.pb` a traced run wrote under the benchmark's
    work directory, or None."""
    found = glob.glob(os.path.join(bench_dir, "_work", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def for_run(run) -> Optional[Summary]:
    """The summary of a traced run's window, read once per run; None for
    a run without a trace."""
    if run.trace is None:
        return None
    cached = getattr(run, "_program_spans", None)
    if cached is None:
        path = newest_trace(run.cell.bench_dir)
        if path is None:
            return None
        spans, device_ops = read(path)
        cached = summarize(spans, device_ops, n_devices=run.cell.chips)
        run._program_spans = cached
    return cached

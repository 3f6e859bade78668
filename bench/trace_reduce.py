"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the benchmark
reports: device busy time and idle share over the measured window, the
device operations that took the most time (their own time, less that of
operations nested in them), and the longest idle gaps, each named by the
innermost benchmark host span it fell in.

The window is the benchmark's `bench.window` span on the host.  Busy time
is the union of the intervals in which an operation ran on a device (the
`XLA Ops` line of each `/device:TPU:<n>` plane), inside the window,
averaged over the chips used; so is each operation's time, so that on
several chips the operations' times still add up to the busy time.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OUTSIDE = "host.no_bench_span"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def profile_options():
    """Options for a traced run: the benchmark's spans and the device's
    operations, without a Python call tracer or HLO protos."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


@dataclasses.dataclass
class TraceSummary:
    busy_s: float                           # mean over the chips used
    window_s: float
    top_ops: List[List]                     # [[op, self s per chip], ...]
    idle_gaps: List[List]                   # [[host span, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(t: float, spans: Sequence[Tuple[float, float, str]]) -> str:
    """The innermost (shortest) benchmark span that holds time `t`."""
    best, best_len = OUTSIDE, float("inf")
    for s, e, name in spans:
        if s <= t <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def op_name(event_name: str) -> str:
    """An operation's HLO instruction name, without its text
    (`%fusion.3 = f32[...] fusion(...)` -> `fusion.3`)."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def self_times(ops: Sequence[Tuple[float, float, str]]) -> List[float]:
    """Each operation's time less that of the operations nested in it
    (a `while` holds its body's operations on the same line)."""
    own = [e - s for s, e, _ in ops]
    stack: List[int] = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [max(0.0, t) for t in own]


def summarize(spans: Sequence[Tuple[float, float, str]],
              device_ops: Dict[str, List[Tuple[float, float, str]]],
              n_devices: int) -> TraceSummary:
    """The reduction itself, on plain tuples (ns): host spans (start, end,
    name) and each device plane's operations (start, end, name)."""
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = max(windows, key=lambda w: w[1] - w[0])
    else:
        every = [x for ops in device_ops.values() for x in ops]
        if not every:
            raise ValueError("trace holds no window span and no device op")
        lo, hi = min(x[0] for x in every), max(x[1] for x in every)
    per_device, totals = [], {}
    for ops in device_ops.values():
        clipped = sorted(((max(s, lo), min(e, hi), n) for s, e, n in ops
                          if e > lo and s < hi), key=lambda x: (x[0], -x[1]))
        for (_, _, n), t in zip(clipped, self_times(clipped)):
            totals[op_name(n)] = totals.get(op_name(n), 0.0) + t
        busy = union([(s, e) for s, e, _ in clipped])
        per_device.append((sum(e - s for s, e in busy), busy))
    per_device.sort(key=lambda x: -x[0])
    used = per_device[:n_devices]
    if not used or used[0][0] <= 0:
        raise ValueError("no device operation ran inside the window")
    busy_ns = sum(b for b, _ in used) / n_devices
    longest = sorted(gaps(used[0][1], lo, hi), key=lambda g: g[0] - g[1])
    idle = [(e - s, label((s + e) / 2, spans)) for s, e in longest[:10]]
    top = sorted(totals.items(), key=lambda kv: -kv[1])
    return TraceSummary(
        busy_s=busy_ns * 1e-9, window_s=(hi - lo) * 1e-9,
        top_ops=[[n, t / n_devices * 1e-9] for n, t in top],
        idle_gaps=[[n, t * 1e-9] for t, n in idle])


def read(path: str):
    """(host spans, device ops per plane) of an `.xplane.pb` file."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans, device_ops = [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns, ev.end_ns, ev.name)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
        elif DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.start_ns, ev.end_ns, ev.name)
                               for ev in line.events)
            device_ops[plane.name] = ops
    return spans, device_ops


def reduce(path: str, n_devices: int = 1) -> TraceSummary:
    spans, device_ops = read(path)
    return summarize(spans, device_ops, n_devices)

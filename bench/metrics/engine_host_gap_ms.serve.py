"""Device idle time inside the engine's `forecast.pump` spans per engine
round in the traced window: the part of each round in which the chip
waits on the engine's host code, not on arrivals."""

import program_spans


def read(run):
    s = program_spans.for_run(run)
    return None if s is None else s.host_gap_ms

"""Host time of the engine's `forecast.guard` spans per engine round in the
traced window: the slot guard's dispatch, the host's wait for its verdict
and any diagnosis."""

import program_spans


def read(run):
    s = program_spans.for_run(run)
    return None if s is None else s.per_round_ms("forecast.guard")

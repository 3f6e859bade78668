"""Host time of the engine's `forecast.step` spans per engine round in the
traced window: a round's dispatch and its wait for the device."""

import program_spans


def read(run):
    s = program_spans.for_run(run)
    return None if s is None else s.per_round_ms("forecast.step")

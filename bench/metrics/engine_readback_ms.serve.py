"""Mean host time of the engine's `forecast.retire` spans in the traced
window: one finished slot read back to the host."""

import program_spans


def read(run):
    s = program_spans.for_run(run)
    return None if s is None else s.readback_ms

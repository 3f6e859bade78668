"""Share of device busy time in the traced window, in %, that is not the
self time of the Pallas kernels the `plan.run` spans name: the glue the
lowering puts around the kernels."""

import program_spans


def read(run):
    s = program_spans.for_run(run)
    return None if s is None else s.lowering_share

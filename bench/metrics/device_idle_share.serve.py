"""Share of the traced window with no operation on the device."""

import yardstick


def read(run):
    return yardstick.idle_share(run)

"""The whole step's share of the HBM roofline (yardstick.step_roofline)."""

import yardstick


def read(run):
    return yardstick.step_roofline(run)

"""Percent of the traced window in which no operation ran on the chip."""


def read(run):
    return run.device_idle_pct()

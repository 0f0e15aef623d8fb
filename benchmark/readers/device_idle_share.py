"""Percent of the traced window in which no operation ran on the device:
1 - union of the device-op intervals over the window, per device, mean over
the devices (``xtrace.reduce``)."""


def read(run, args):
    return None if run.trace is None else 100.0 * run.trace["idle_share"]

"""Median refresh latency in ms, each refresh timed from when it was due
(host clock), over all refreshes due in the window. A refresh that
failed on the device makes the run not correct (harness.verdict), so a
correct run's refreshes are all answered on the device."""

import numpy as np


def read(run):
    lat = [r.latency_s for r in run.records]
    if not lat:
        return None
    return 1000.0 * float(np.median(lat))

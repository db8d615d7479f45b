"""Median wall time of a decode ``Engine.step`` in the window; the step
ends in the token fetch, which syncs with the device."""

import numpy as np


def read(run):
    dec = [s.t1 - s.t0 for s in run.steps if s.kind == "decode"]
    return float(np.median(dec)) * 1e3 if dec else None

"""Median host time of a decode iteration in the window: the
``engine.iteration`` span less its ``engine.fetch`` child, the time in
which the chip has no step queued (scheduling, building inputs,
dispatch, emitting tokens)."""

import numpy as np

from bench import spans


def read(run):
    its = spans.decode_iterations(run)
    if its is None:
        return None
    return float(np.median([it - fetch for it, fetch in its])) * 1e3

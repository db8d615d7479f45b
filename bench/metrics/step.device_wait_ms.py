"""Median ``engine.fetch`` span of a decode iteration in the window: the
host waiting on the chip for the model step's result, the step's device
time as the program sees it."""

import numpy as np

from bench import spans


def read(run):
    its = spans.decode_iterations(run)
    if its is None:
        return None
    return float(np.median([fetch for _, fetch in its])) * 1e3

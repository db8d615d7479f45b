"""95th percentile of every gap between consecutive tokens of one
request, both tokens in the window, over all requests."""

import numpy as np


def read(run):
    gaps = [b - a for r in run.reqs.values()
            for a, b in zip(r.stamps, r.stamps[1:])
            if run.w0 < a and b <= run.w1]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None

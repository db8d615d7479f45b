"""Generated tokens emitted in the window over the window's seconds."""


def read(run):
    n = sum(run.w0 < t <= run.w1 for r in run.reqs.values() for t in r.stamps)
    return n / (run.w1 - run.w0)

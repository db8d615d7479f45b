"""Device time of the linear kernels over the device's busy time."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or t.kernel_s <= 0:
        return None
    return 100.0 * t.kernel_s / t.busy_s

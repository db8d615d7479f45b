"""Least time of the window's linear calls over the device time of the
linear kernels (ops matched by ``bench/kernels/*.json``).

A call's least time is the larger of its operations over the bf16 peak
and its bytes (4-bit codes, scales, bf16 activations in and out) over
the HBM bandwidth, over the rows whose results are used
(``bench.work``): the same work whatever kernel computes it."""

from bench import work


def read(run):
    if run.trace is None or run.peaks is None or run.trace.kernel_s <= 0:
        return None
    c = run.cell.config
    least = sum(work.step_linear_least_s(c, s.rows, s.head_rows, run.peaks)
                for s in run.steps)
    return 100.0 * least / run.trace.kernel_s

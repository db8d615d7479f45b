"""Model operations of every token the window processed (prompt and
generated; ``bench.work.model_flops``) over the window's seconds times
the chips' bf16 peak."""

from bench import work


def read(run):
    if run.peaks is None:
        return None
    c = run.cell.config
    flops = sum(work.model_flops(c, s.rows, s.ctx) for s in run.steps)
    peak = run.peaks["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * flops / ((run.w1 - run.w0) * peak)

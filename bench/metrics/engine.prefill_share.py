"""Share of the window's ``Engine.step`` wall time spent in prefill
steps (the benchmark's span around each step, its kind from the
engine's step counters)."""


def read(run):
    total = sum(s.t1 - s.t0 for s in run.steps)
    pre = sum(s.t1 - s.t0 for s in run.steps if s.kind == "prefill")
    return 100.0 * pre / total if total > 0 else None

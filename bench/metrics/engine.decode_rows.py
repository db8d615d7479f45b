"""Mean sequences per decode step in the window."""


def read(run):
    dec = [s.rows for s in run.steps if s.kind == "decode"]
    return sum(dec) / len(dec) if dec else None

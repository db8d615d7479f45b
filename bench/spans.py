"""The window's decode iterations, as the program's own spans give them.

The engine records one ``engine.iteration`` span per ``Engine.step``,
with an ``engine.fetch`` child from the first blocking read of the
step's result until it returns: the host waiting on the chip.  The spans
sit in a bounded ring, ``repro.obs.tracer()``, on ``time.perf_counter``,
the clock of the benchmark's window.
"""

from __future__ import annotations


def decode_iterations(run) -> list[tuple[float, float]] | None:
    """(iteration seconds, fetch seconds) of each decode iteration that
    lies in the window and read its result back.

    None where the program keeps no span ring, where the ring no longer
    holds the whole window, or where the window holds no decode
    iteration."""
    from repro import obs

    ring = obs.tracer()
    if not (hasattr(ring, "spans") and hasattr(ring, "oldest")):
        return None
    if ring.oldest() > run.w0:
        return None
    held = ring.spans(run.w0, run.w1)
    fetch = {id(s.parent): s.t1 - s.t0 for s in held
             if s.name == "engine.fetch" and s.parent is not None}
    out = [(s.t1 - s.t0, fetch[id(s)]) for s in held
           if s.name == "engine.iteration"
           and s.args.get("kind") == "decode" and id(s) in fetch]
    return out or None

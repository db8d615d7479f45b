"""The per-layer metrics that read the program's span ring
(bench/spans.py, metrics engine.host_ms and step.device_wait_ms), on a
synthetic ring, including every case in which they find nothing."""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import layout  # noqa: E402
from repro.obs import trace as TR  # noqa: E402

MS = 1e-3


def reader(name):
    return layout.Benchmark(ROOT).reader(name)


def iteration(ring, t0, kind, host_ms, fetch_ms, *, fetch=True):
    """One engine.iteration of ``host_ms`` + ``fetch_ms``, its children
    closed first, as the engine records them."""
    it = TR.Span("engine.iteration", None, {"kind": kind, "rows": 8})
    t = t0
    for name, ms in (("engine.schedule", host_ms / 4),
                     ("engine.prepare", host_ms / 4),
                     ("engine.launch", host_ms / 4),
                     ("engine.fetch", fetch_ms if fetch else 0.0),
                     ("engine.emit", host_ms / 4)):
        if name == "engine.fetch" and not fetch:
            continue
        s = TR.Span(name, it, {})
        s.t0, s.t1 = t, t + ms * MS
        t = s.t1
        ring._append(s)
    it.t0, it.t1 = t0, t
    ring._append(it)
    return t


@pytest.fixture
def ring(monkeypatch):
    tr = TR.Tracer()
    monkeypatch.setattr(TR, "_TRACER", tr)
    return tr


def test_readers_split_each_decode_iteration_into_host_and_chip(ring):
    t = 100.0
    t = iteration(ring, t, "prefill", 3.0, 1400.0)  # before the window
    w0 = t
    t = iteration(ring, t, "decode", 4.0, 1100.0)
    t = iteration(ring, t, "prefill", 50.0, 0.0, fetch=False)
    t = iteration(ring, t, "decode", 8.0, 1150.0)
    t = iteration(ring, t, "decode", 6.0, 1120.0)
    run = NS(w0=w0, w1=t)
    assert reader("engine.host_ms")(run) == pytest.approx(6.0)
    assert reader("step.device_wait_ms")(run) == pytest.approx(1120.0)
    # the two tile the decode iteration
    its = [s for s in ring.spans(w0, t) if s.name == "engine.iteration"
           and s.args["kind"] == "decode"]
    assert sum(s.duration for s in its) / MS == pytest.approx(
        4 + 8 + 6 + 1100 + 1150 + 1120)


def test_a_window_without_decode_iterations_reads_nothing(ring):
    t = iteration(ring, 10.0, "prefill", 3.0, 1400.0)
    t = iteration(ring, t, "prefill", 3.0, 0.0, fetch=False)
    run = NS(w0=10.0, w1=t)
    assert reader("engine.host_ms")(run) is None
    assert reader("step.device_wait_ms")(run) is None
    # a decode iteration outside the window does not count either
    end = iteration(ring, t + 1.0, "decode", 4.0, 1100.0)
    assert end > run.w1
    assert reader("engine.host_ms")(run) is None


def test_a_ring_that_dropped_part_of_the_window_reads_nothing(monkeypatch):
    tr = TR.Tracer(maxlen=8)  # fewer than two iterations' spans
    monkeypatch.setattr(TR, "_TRACER", tr)
    t = iteration(tr, 10.0, "decode", 4.0, 1100.0)
    t = iteration(tr, t, "decode", 4.0, 1100.0)
    assert tr.oldest() > 10.0
    run = NS(w0=10.0, w1=t)
    assert reader("engine.host_ms")(run) is None
    assert reader("step.device_wait_ms")(run) is None
    # a window that starts after the dropped spans is whole
    late = NS(w0=tr.oldest(), w1=t)
    assert reader("step.device_wait_ms")(late) == pytest.approx(1100.0)


def test_a_program_without_a_span_ring_reads_nothing(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "tracer", lambda: NS(enabled=False))
    run = NS(w0=0.0, w1=1.0)
    assert reader("engine.host_ms")(run) is None
    assert reader("step.device_wait_ms")(run) is None

"""Host spans on the profiler's clock, kept in a bounded ring.

``with tracer().span("engine.fetch"): ...`` opens a
``jax.profiler.TraceAnnotation`` of that name, so any profiler trace
(``jax.profiler.trace``, ``serve --trace-out``) holds the span on the
same clock and timeline as the device ops.  On exit the span is also
appended to an in-memory ring of the last :data:`RING_SPANS` spans, with
its ``time.perf_counter`` bounds, its enclosing span (``parent``, so self
time can be computed) and its args; :meth:`Tracer.spans` reads an
interval of it back.

Recording is always on and costs a few ``perf_counter`` calls, one
TraceMe and one ``deque.append`` per span.  Nothing is ever staged into
jitted code: device-side names come from ``jax.named_scope`` in the
model code, which only touches op metadata.

``jax_compiles_total`` counts backend compiles, from one
``jax.monitoring`` listener registered on import.
"""

from __future__ import annotations

import collections
import math
import threading
import time
from contextlib import AbstractContextManager

import jax

from repro.obs import metrics as M

# a 51 s window of >= 5 ms iterations at ~7 spans each fits
RING_SPANS = 65_536

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span:
    """One closed span: ``t0``/``t1`` on ``time.perf_counter``,
    ``parent`` the enclosing :class:`Span` (None at the top)."""

    __slots__ = ("name", "t0", "t1", "parent", "args")

    def __init__(self, name: str, parent: "Span | None", args: dict):
        self.name = name
        self.parent = parent
        self.args = args
        self.t0 = self.t1 = 0.0

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def __repr__(self):
        return (f"Span({self.name!r}, {self.t0:.6f}, {self.t1:.6f}, "
                f"parent={getattr(self.parent, 'name', None)!r}, "
                f"args={self.args!r})")


class _Open(AbstractContextManager):
    """An open span: the profiler annotation plus the ring record.  The
    ``as`` target is the :class:`Span`, whose ``args`` may still grow
    before exit (e.g. ``compiled=True``)."""

    __slots__ = ("tracer", "span", "me")

    def __init__(self, tracer: "Tracer", span: Span, me):
        self.tracer = tracer
        self.span = span
        self.me = me

    def __enter__(self) -> Span:
        self.tracer._stack().append(self.span)
        self.me.__enter__()
        self.span.t0 = time.perf_counter()
        return self.span

    def __exit__(self, *exc):
        span = self.span
        span.t1 = time.perf_counter()
        if span.args:
            self.me.set_metadata(**span.args)
        self.me.__exit__(*exc)
        self.tracer._stack().pop()
        self.tracer._append(span)
        return False


class Tracer:
    def __init__(self, maxlen: int = RING_SPANS):
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._held_from = -math.inf

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _open(self, name: str, args: dict, me) -> _Open:
        stack = self._stack()
        return _Open(self, Span(name, stack[-1] if stack else None, args),
                     me)

    def _append(self, span: Span) -> None:
        ring = self._ring
        with self._lock:
            if len(ring) == ring.maxlen:
                # every dropped span ended at or before this
                self._held_from = max(self._held_from, ring[0].t1)
            ring.append(span)

    def span(self, name: str, **args) -> _Open:
        """Context manager recording one span; ``args`` become the
        annotation's metadata and the ring record's ``args``."""
        return self._open(name, args,
                          jax.profiler.TraceAnnotation(name))

    def step(self, name: str, step_num: int, **args) -> _Open:
        """A span that the profiler marks as step ``step_num``."""
        return self._open(name, args, jax.profiler.StepTraceAnnotation(
            name, step_num=step_num))

    def spans(self, t0: float = -math.inf, t1: float = math.inf
              ) -> list[Span]:
        """The held spans that lie inside [t0, t1], oldest end first."""
        with self._lock:
            held = list(self._ring)
        return [s for s in held if s.t0 >= t0 and s.t1 <= t1]

    def oldest(self) -> float:
        """The time from which the ring holds every span: a span that
        ended after it is held (``-inf`` until the ring first drops
        one)."""
        return self._held_from


_TRACER = Tracer()


def tracer() -> Tracer:
    return _TRACER


def compiles() -> int:
    """Backend compiles in this process so far (``jax_compiles_total``)."""
    return int(M.registry().value("counter", "jax_compiles_total") or 0)


def _on_event_duration(event: str, secs: float, **kw) -> None:
    if event == COMPILE_EVENT:
        M.registry().counter(
            "jax_compiles_total",
            help="XLA backend compiles in this process").inc()


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)

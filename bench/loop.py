"""Drive the continuous engine from the closed loop, and record it.

Everything here is timed by the benchmark's own host clock:
``Engine.submit`` stamps nothing the metrics read, and every token is
stamped by the engine's ``on_token`` hook, which runs after the step's
token fetch has synced with the device.  Each ``Engine.step`` is one
``engine.step`` span and the client's work after it one ``client`` span,
both written into the profiler's trace when one is taken.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import jax

WARM_RID = -1


@dataclass
class Req:
    """One request as its client saw it."""

    rid: int
    client: int
    prompt: tuple
    max_new_tokens: int
    t_sent: float
    tokens: list = field(default_factory=list)
    stamps: list = field(default_factory=list)
    status: str | None = None  # set when the engine hands it back
    seq: object = None


@dataclass
class Step:
    """One ``Engine.step`` as the benchmark saw it.

    rows: sequences in a decode step, prompt tokens in a prefill chunk.
    head_rows: rows whose next-token logits are used.
    ctx: sum over the rows of the positions each row attends to.
    ctx_local: the same sum with each row's positions capped at the
    configuration's sliding window (``ctx`` where it has none).
    """

    t0: float
    t1: float
    kind: str
    rows: int
    head_rows: int
    ctx: int
    tokens: int
    ctx_local: int


def _attended(positions, window: int) -> tuple[int, int]:
    """(sum, window-capped sum) of the positions each row attends to."""
    full = sum(positions)
    return full, (sum(min(p, window) for p in positions) if window
                  else full)


class Loop:
    def __init__(self, engine, traffic, *, window: int = 0,
                 clock=time.perf_counter):
        from repro.serving import Phase, Request

        self._Request, self._Phase = Request, Phase
        self.engine = engine
        self.traffic = traffic
        self.attn_window = window
        self.clock = clock
        self.reqs: dict[int, Req] = {}
        self.emitted = 0
        self.compiles = 0
        self.jax_events: list[tuple[float, str, float]] = []
        engine.on_token = self._on_token
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **kw):
        self.jax_events.append((self.clock(), event, secs))
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def events_in(self, t0: float, t1: float) -> str:
        """JAX's timed events (tracing, compiling, compile-cache loads)
        that ended in [t0, t1], by name: count and seconds."""
        got: dict[str, list] = {}
        for t, name, secs in self.jax_events:
            if t0 <= t <= t1:
                n = got.setdefault(name.rsplit("/", 1)[-1], [0, 0.0])
                n[0] += 1
                n[1] += secs
        return ", ".join(f"{k} x{n} {s:.4f} s"
                         for k, (n, s) in sorted(got.items())) or "none"

    def _on_token(self, rid, token, text):
        r = self.reqs[rid]
        r.tokens.append(int(token))
        r.stamps.append(self.clock())
        self.emitted += 1

    def _submit(self, rid, client, prompt, max_new_tokens) -> Req:
        r = Req(rid=rid, client=client, prompt=prompt,
                max_new_tokens=max_new_tokens, t_sent=self.clock())
        self.reqs[rid] = r
        r.seq = self.engine.submit(self._Request(
            rid=rid, prompt=prompt, max_new_tokens=max_new_tokens))
        return r

    def send(self, client: int) -> Req:
        job = self.traffic.job(client)
        return self._submit(job.rid, client, job.prompt, job.max_new_tokens)

    def step(self) -> Step:
        eng = self.engine
        sched = eng.scheduler
        pos0 = [(s, s.prefill_pos) for s in (*sched.running, *sched.waiting)]
        p0, d0, n0 = eng.num_prefill_steps, eng.num_decode_steps, self.emitted
        dec_ctx = _attended([s.num_tokens for s in sched.running
                             if s.phase is self._Phase.DECODE],
                            self.attn_window)
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("engine.step"):
            done = eng.step()
        t1 = self.clock()
        with jax.profiler.TraceAnnotation("client"):
            for seq in done:
                r = self.reqs[seq.req.rid]
                r.status = seq.status
                if r.client >= 0:
                    self.send(r.client)
        tokens = self.emitted - n0
        if eng.num_prefill_steps > p0:
            for s, old in pos0:
                if s.prefill_pos > old:
                    full, local = _attended(
                        range(old + 1, s.prefill_pos + 1),
                        self.attn_window)
                    return Step(t0, t1, "prefill", s.prefill_pos - old,
                                int(s.phase is not self._Phase.PREFILL),
                                full, tokens, local)
        if eng.num_decode_steps > d0:
            return Step(t0, t1, "decode", tokens, tokens, dec_ctx[0],
                        tokens, dec_ctx[1])
        return Step(t0, t1, "idle", 0, 0, 0, tokens, 0)

    # ---------------------------------------------------------- phases
    def warm(self) -> None:
        """Compile (or load from the cache) both step shapes the cell
        uses, with a one-token request: one prefill chunk, one decode."""
        r = self._submit(WARM_RID, -1, (0,), 2)
        while r.status is None:
            self.step()
        del self.reqs[WARM_RID]

    def fill(self) -> None:
        """Every client's first request sent, admitted and prefilled."""
        first = [self.send(c) for c in range(self.traffic.clients)]
        while any(r.seq.phase in (self._Phase.WAITING, self._Phase.PREFILL)
                  for r in first):
            self.step()

    def window(self, seconds: float):
        """Step until ``seconds`` have passed; the window ends with the
        step that crosses it.  Returns (t0, t1, steps, compiles)."""
        steps = []
        c0 = self.compiles
        with jax.profiler.TraceAnnotation("bench.window"):
            w0 = self.clock()
            while not steps or steps[-1].t1 - w0 < seconds:
                steps.append(self.step())
        return w0, steps[-1].t1, steps, self.compiles - c0


def longest(steps: list, w0: float, n: int = 5) -> str:
    """The ``n`` longest steps: kind, seconds and start in the window."""
    top = sorted(steps, key=lambda s: s.t0 - s.t1)[:n]
    return ", ".join(f"{s.kind} {s.t1 - s.t0:.4f} s at +{s.t0 - w0:.2f} s"
                     for s in top)


class GcPauses:
    """Every garbage collection's (start, end, generation), on the
    benchmark's clock, inside a ``with`` block."""

    def __init__(self, *, clock=time.perf_counter):
        self.clock = clock
        self.pauses: list[tuple[float, float, int]] = []
        self._t = 0.0

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t = self.clock()
        else:
            self.pauses.append((self._t, self.clock(), info["generation"]))

    def summary(self, t0: float, t1: float) -> str:
        got = [(b - a, g) for a, b, g in self.pauses if t0 <= a < t1]
        full = [d for d, g in got if g == 2]
        return (f"{len(got)} collections ({len(full)} full), "
                f"{sum(d for d, _ in got):.4f} s in all, longest "
                f"{max((d for d, _ in got), default=0.0):.4f} s")

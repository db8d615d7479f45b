"""Reduce a profiler trace of the window to device busy time, linear
kernel time and a breakdown.

The window is the ``bench.window`` span the benchmark writes into the
trace (``bench.loop``), so device time and the host's spans share one
clock.  Busy time is the union of the intervals in which an operation
ran on a chip, clipped to the window and averaged over the chips that
ran any.  Linear-kernel time sums the ops whose names match a pattern of
``bench/kernels/*.json``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path

OPS_LINE = "XLA Ops"
HOST_SPANS = ("engine.step", "client")
TOP = 10
# ops that hold other ops (a scanned layer loop): busy time, not a kernel
CONTAINERS = re.compile(r"^%(while|conditional|call)[.\s]")


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@dataclass
class TraceView:
    window_s: float
    busy_s: float
    kernel_s: float
    ops: dict            # op name -> device seconds (over the chips)
    gaps: list           # (seconds, host span the gap fell in)

    @classmethod
    def load(cls, trace_dir: Path, patterns) -> "TraceView":
        from jax.profiler import ProfileData

        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        data = ProfileData.from_file(str(files[-1]))
        return cls.from_planes(data.planes, patterns)

    @classmethod
    def from_planes(cls, planes, patterns) -> "TraceView":
        """``planes``: objects with ``name`` and ``lines``, each line with
        ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``),
        as ``jax.profiler.ProfileData`` gives them."""
        host, devices = [], []
        for plane in planes:
            if plane.name.startswith("/device:TPU:"):
                evs = [e for line in plane.lines if line.name == OPS_LINE
                       for e in line.events]
                if evs:
                    devices.append(evs)
            elif plane.name.startswith("/host:"):
                host += [e for line in plane.lines for e in line.events]
        win = [e for e in host if e.name == "bench.window"]
        if not win:
            raise ValueError("the trace holds no bench.window span")
        w0 = win[0].start_ns
        w1 = w0 + win[0].duration_ns
        spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in host if e.name in HOST_SPANS)
        busy = kernel = 0.0
        ops: dict = {}
        gaps = []
        for evs in devices:
            clipped = []
            for e in evs:
                a = max(e.start_ns, w0)
                b = min(e.start_ns + e.duration_ns, w1)
                if b <= a:
                    continue
                clipped.append((a, b))
                if not CONTAINERS.match(e.name):
                    key = op_name(e.name)
                    ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
                if any(p.search(e.name) for p in patterns):
                    kernel += (b - a) / 1e9
            merged = _union(clipped)
            busy += sum(b - a for a, b in merged) / 1e9
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append(((b - a) / 1e9, _label(spans, a, b)))
        n = max(len(devices), 1)
        return cls(window_s=(w1 - w0) / 1e9, busy_s=busy / n,
                   kernel_s=kernel / n,
                   ops={k: v / n for k, v in ops.items()}, gaps=gaps)

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[name, s] for s, name in gaps]}


def _label(spans, a: int, b: int) -> str:
    """The host span that covers most of the idle interval [a, b)."""
    best, cover = "none", 0
    for s0, s1, name in spans:
        if s0 >= b:
            break
        c = min(s1, b) - max(s0, a)
        if c > cover:
            best, cover = name, c
    return best


def op_name(text: str) -> str:
    """An HLO op's name and result shape, without its layout and
    operands: ``%msgemm_pallas.43 = f32[16,6144]``."""
    return text.split("{", 1)[0].split("(", 1)[0].strip()

"""The check that decides ``correct``: served tokens against the reference.

Once the window has closed, a sample of the requests it served, drawn
from the seed and always holding the one with the most served tokens,
is run through the float32 reference of the configuration's
architecture (``bench/reference/<kind>.py``) over its prompt and every
token the engine served it.  At each served token the reference's best
logit is compared with its logit for the served token: the widest of
those gaps over the sample is the number compared, against the limit in
the cell's file (``check.max_logit_gap``).  Served tokens are greedy, so
a sound run serves the reference's first choice up to rounding, and the
gap stays at the size of the rounding of the precision the configuration
states.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass

import numpy as np


@dataclass
class Verdict:
    """One comparison of served tokens with the reference: the program's,
    or the control's (the reference at the precision below, in the
    program's place), which a sound limit has to fail."""

    who: str
    value: float
    limit: float
    positions: int
    requests: int

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit

    def numbers(self) -> dict:
        return {"max_logit_gap": {"value": self.value, "limit": self.limit}}

    def lines(self) -> list[str]:
        return [f"check ({self.who}): {self.requests} requests, "
                f"{self.positions} served tokens against the float32 "
                f"reference",
                f"check ({self.who}) max_logit_gap {self.value!r} limit "
                f"{self.limit!r}: correct {str(self.ok).lower()}"]


def sample(reqs: dict, w0: float, w1: float, n: int, seed: int) -> list:
    """Up to ``n`` requests served in the window, drawn from the seed,
    the one with the most served tokens always among them."""
    served = sorted((r for r in reqs.values() if r.tokens
                     and any(w0 <= t <= w1 for t in r.stamps)),
                    key=lambda r: r.rid)
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(cell, seed: int, reqs: dict, w0: float, w1: float, *,
            control: bool = False) -> list[Verdict]:
    """The program's verdict, and with ``control`` the control's after
    it, each against the cell's limit."""
    chosen = sample(reqs, w0, w1, int(cell.check["requests"]), seed)
    limit = float(cell.check["max_logit_gap"])
    who = ("program", "control") if control else ("program",)
    if not chosen:
        return [Verdict(w, float("inf"), limit, 0, 0) for w in who]
    kind = cell.config["reference"]
    ref = importlib.import_module(f"bench.reference.{kind}")
    got = ref.readings(cell.config, seed,
                       [(r.prompt, r.tokens) for r in chosen],
                       length=cell.engine.get("max_model_len"),
                       control=control)
    gaps = {"program": got["max_logit_gap"],
            "control": got.get("control_max_logit_gap")}
    return [Verdict(w, gaps[w], limit, got["positions"], len(chosen))
            for w in who]

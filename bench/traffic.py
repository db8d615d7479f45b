"""The one traffic generator: a closed loop of clients, read from a mix file.

A mix (``bench/traffic/<name>.json``) gives the number of clients, the
distributions of prompt and output lengths (``lognormal`` with a mean
and a sigma, or ``uniform``, each clipped to ``min``..``max``), a
``size_seed``, the published statistics the lengths come from
(``source``) and what was assumed beside them (``assumed``).  Each
client sends its next request when the previous one finishes, with no
think time.

Sizes come from the mix's own ``size_seed``, so every ``--seed`` runs
the same set of sizes: the seed only chooses which client carries which
stream of sizes, and draws every prompt's token ids (uniform over the
vocabulary).  Runs of different seeds then differ in their inputs and
weights, not in the amount of work, which keeps the spread between runs
down to the system's own.

A client's first request gets a residual output budget, uniform over
1..its full draw, so that the first completions are spread out as in a
loop that has been running for a long time.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

MIX_KEYS = {"clients", "prompt", "output", "size_seed",
            "requests_per_client", "why", "source", "assumed"}


@dataclass(frozen=True)
class Job:
    """One request a client sends: ``rid`` is unique in a run."""

    rid: int
    client: int
    prompt: tuple
    max_new_tokens: int


def _draw(rng: np.random.Generator, dist: dict, n: int) -> np.ndarray:
    kind = dist["dist"]
    if kind == "lognormal":  # by its mean: median = mean / e^(sigma^2/2)
        sigma = dist["sigma"]
        median = dist["mean"] * np.exp(-sigma**2 / 2)
        v = np.rint(median * np.exp(sigma * rng.standard_normal(n)))
    elif kind == "uniform":
        v = rng.integers(dist["min"], dist["max"] + 1, size=n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


def check_mix(mix: dict) -> None:
    missing = MIX_KEYS - set(mix)
    extra = set(mix) - MIX_KEYS
    if missing or extra:
        raise ValueError(f"mix keys: missing {sorted(missing)}, "
                         f"unknown {sorted(extra)}")


class ClosedLoop:
    """Size streams for ``mix`` and token ids for ``seed``."""

    def __init__(self, mix: dict, vocab_size: int, seed: int):
        check_mix(mix)
        self.clients = int(mix["clients"])
        self.vocab_size = vocab_size
        self.seed = seed
        n = int(mix["requests_per_client"])
        size_rng = np.random.default_rng(mix["size_seed"])
        self._prompt = _draw(size_rng, mix["prompt"],
                             self.clients * n).reshape(self.clients, n)
        self._output = _draw(size_rng, mix["output"],
                             self.clients * n).reshape(self.clients, n)
        # residual life of each stream's first request: 1..full draw
        frac = size_rng.random(self.clients)
        self._first = 1 + np.floor(frac * self._output[:, 0]).astype(
            np.int64)
        self._stream = np.random.default_rng(
            np.random.SeedSequence([seed, 0])).permutation(self.clients)
        self._sent = [0] * self.clients
        self.max_len = int((self._prompt + self._output).max())

    def job(self, client: int) -> Job:
        """The client's next request (streams wrap around when spent)."""
        i = self._sent[client]
        self._sent[client] += 1
        s = int(self._stream[client])
        j = i % self._prompt.shape[1]
        out = int(self._first[s] if i == 0 else self._output[s, j])
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 1, client, i]))
        prompt = rng.integers(0, self.vocab_size,
                              size=int(self._prompt[s, j]))
        return Job(rid=client * 1_000_000 + i, client=client,
                   prompt=tuple(int(t) for t in prompt),
                   max_new_tokens=out)

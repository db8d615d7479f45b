"""The closed-loop traffic generator (bench/traffic.py) and the mixes."""

from __future__ import annotations

import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.traffic import ClosedLoop, check_mix  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
SEEDS = (2**31 + 3, 2**33 + 17)


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def jobs(m, seed, per_client=6, vocab=1000):
    t = ClosedLoop(m, vocab, seed)
    return [t.job(c) for _ in range(per_client) for c in range(t.clients)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    assert jobs(mix(name), SEEDS[0]) == jobs(mix(name), SEEDS[0])
    assert jobs(mix(name), SEEDS[0]) != jobs(mix(name), SEEDS[1])


@pytest.mark.parametrize("name", MIXES)
def test_sizes_lie_in_the_stated_ranges(name):
    m = mix(name)
    for j in jobs(m, SEEDS[0], per_client=m["requests_per_client"] + 3):
        assert m["prompt"]["min"] <= len(j.prompt) <= m["prompt"]["max"]
        assert 1 <= j.max_new_tokens <= m["output"]["max"]
        assert all(0 <= t < 1000 for t in j.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_runs_the_same_set_of_sizes(name):
    def sizes(seed):
        return Counter((len(j.prompt), j.max_new_tokens)
                       for j in jobs(mix(name), seed))
    assert sizes(SEEDS[0]) == sizes(SEEDS[1])


@pytest.mark.parametrize("name", MIXES)
def test_first_budgets_are_residual_lives(name):
    m = mix(name)
    t = ClosedLoop(m, 1000, SEEDS[0])
    first = [t.job(c).max_new_tokens for c in range(t.clients)]
    assert min(first) >= 1 and len(set(first)) > 1
    # a residual life is at most the stream's full first draw, and over
    # many streams it is spread evenly over 1..the full draw
    r = ClosedLoop(dict(m, clients=4000), 1000, SEEDS[0])
    res = r._first / r._output[:, 0]
    assert res.max() <= 1.0 and res.min() > 0
    assert abs(res.mean() - 0.5) < 0.02


def test_lognormal_clipped_median():
    # a lognormal is given by its published mean: its median is
    # mean / e^(sigma^2/2), here 128, and the clip leaves that alone
    m = {"clients": 1, "requests_per_client": 20000, "size_seed": 7,
         "why": "-", "source": "-", "assumed": "-",
         "prompt": {"dist": "lognormal", "mean": 128 * np.exp(0.125),
                    "sigma": 0.5, "min": 64, "max": 256},
         "output": {"dist": "uniform", "min": 256, "max": 1024}}
    t = ClosedLoop(m, 10, 1)
    assert abs(np.median(t._prompt) - 128) <= 2
    assert t._prompt.min() == 64 and t._prompt.max() == 256
    assert t._output.min() == 256 and t._output.max() == 1024


def test_a_mix_with_unknown_keys_is_refused():
    with pytest.raises(ValueError, match="unknown"):
        check_mix(dict(mix(MIXES[0]), rate=4.0))

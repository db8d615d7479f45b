"""Each plain reference (bench/reference/) against the program's own
forward at a tiny size of the same family: greedy tokens the program
generates in float32 are the reference's first choice at every step."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import model  # noqa: E402
from bench.rehearse import tiny_config  # noqa: E402
from bench.reference import dense  # noqa: E402

CONFIGS = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
SEED = 2**32 + 9
# both sides in float32 at highest precision: only summation order differs
F32_TOL = 1e-4


def tiny(name, dtype):
    c = tiny_config(json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                               .read_text()))
    c["name"] = name
    c["program"] = dict(c["program"], dtype=dtype)
    return c


def served(c, prompt, n):
    from repro.runtime import serve as SV

    params, cfg = model.build(c, SEED)
    with jax.default_matmul_precision("highest"):
        out = SV.generate(params, cfg, {"tokens": np.array([prompt])},
                          max_new_tokens=n)
    return [int(t) for t in np.asarray(out)[0]]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_program_in_float32(name):
    c = tiny(name, "float32")
    rng = np.random.default_rng(3)
    prompts = [tuple(int(t) for t in rng.integers(0, 512, n))
               for n in (5, 11)]
    seqs = [(p, served(c, p, 9)) for p in prompts]
    got = dense.readings(c, SEED, seqs, control=True)
    assert got["positions"] == 18
    assert got["max_logit_gap"] <= F32_TOL
    # tokens the program did not choose lie far below the best
    wrong = [(p, [(t + 1) % 512 for t in s]) for p, s in seqs]
    assert dense.readings(c, SEED, wrong)["max_logit_gap"] > 100 * F32_TOL


def test_seed_key_keeps_every_bit():
    keys = {tuple(np.asarray(jax.random.key_data(dense.seed_key(s))))
            for s in (5, 5 + 2**32, 5 + 2**33)}
    assert len(keys) == 3

"""Each plain reference (bench/reference/) against the program's own
forward at a tiny size of the same family: greedy tokens the program
generates in float32 are the reference's first choice at every step.
The dense reference is also held to the block kinds a configuration can
ask for in ``program.model``: windows and experts."""

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
from variants import VARIANTS, variant  # noqa: E402

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
    agrees(tiny(name, "float32"))


@pytest.mark.parametrize("kind", sorted(VARIANTS))
def test_reference_agrees_with_the_program_on_block_kinds(kind):
    # served sequences of 14 and 20 positions pass the window of 8
    c = variant(tiny("phi3-mini", "float32"), kind)
    agrees(c)
    if kind == "local":  # the window is what the program runs
        wide = variant(tiny("phi3-mini", "float32"), kind)
        wide["program"]["model"] = dict(wide["program"]["model"],
                                        sliding_window=64)
        seqs = [((1, 2, 3, 4, 5), served(c, (1, 2, 3, 4, 5), 12))]
        assert dense.readings(wide, SEED, seqs)["max_logit_gap"] > \
            100 * F32_TOL


def test_a_field_the_reference_does_not_model_is_an_error():
    c = tiny("phi3-mini", "float32")
    c["program"] = dict(c["program"], model={"embed_scale": True})
    with pytest.raises(ValueError, match="embed_scale"):
        dense.readings(c, SEED, [((1, 2), (3, 4))])


def agrees(c):
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

"""The check that decides ``correct`` fails a broken timed path.

Each test drives a whole run of the harness at a tiny size on the CPU
(the look for a chip skipped) with one fault planted under the timed
path, and sees ``correct`` come out false.  The faults a served cell on
one chip can have: a token altered where it is produced, and a step
that returns its state (the KV pool) unchanged.  A last test reads the
control, the reference with float8 linear inputs in the program's
place, which lies further from the reference than the program does.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import rehearse, run  # noqa: E402

CELL = "phi3-mini.chat-mixed"


@pytest.fixture
def tree(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    rehearse.tiny_tree(tmp_path)
    return tmp_path


def one_run(tree, seed=2**31 + 21, seconds=0.5, **kw):
    return run.run_cell(tree, CELL, seed, seconds, False,
                        require_chip=False, t_start=time.perf_counter(), **kw)


def test_a_sound_run_is_correct(tree):
    out = one_run(tree)
    assert out["correct"] is True
    assert out["check"]["max_logit_gap"]["value"] <= \
        out["check"]["max_logit_gap"]["limit"]


def test_a_token_altered_where_it_is_produced(tree, monkeypatch):
    from repro.serving import engine

    pick = engine.Engine._pick
    monkeypatch.setattr(
        engine.Engine, "_pick",
        lambda self, seq, tok, logits: (pick(self, seq, tok, logits) + 1)
        % self.cfg.vocab_size)
    out = one_run(tree)
    assert out["correct"] is False
    assert out["check"]["max_logit_gap"]["value"] > \
        out["check"]["max_logit_gap"]["limit"]


def test_a_step_that_returns_its_state_unchanged(tree, monkeypatch):
    from repro.runtime import serve as SV

    step = SV.paged_step

    def stale(params, cfg, tokens, pool, *rest):
        logits, _ = step(params, cfg, tokens, pool, *rest)
        return logits, pool

    monkeypatch.setattr(SV, "paged_step", stale)
    out = one_run(tree)
    assert out["correct"] is False


def test_the_control_lies_further_from_the_reference(tree):
    # every request of a longer window is compared, some hundreds of
    # served tokens as on the chip.  Two layers of width 72 round less
    # than the chip's cells do, so this size has its own limit, set as
    # on the chip between its readings: program 0.016, control 0.116
    path = tree / "bench" / "workloads" / f"{CELL}.json"
    cell = json.loads(path.read_text())
    path.write_text(json.dumps(dict(cell, check=dict(
        cell["check"], requests=64, max_logit_gap=0.05))))
    out = one_run(tree, seconds=3.0, control=True)
    ctl = out["control"]["check"]["max_logit_gap"]
    assert out["correct"] is True
    assert ctl["value"] > out["check"]["max_logit_gap"]["value"]
    assert ctl["value"] > ctl["limit"]
    assert out["control"]["correct"] is False

"""The work arithmetic behind linear_roofline and step.mfu (bench/work.py):
the GeMM's own operations and bytes, from the configurations' shapes."""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402

V5E = work.peaks("TPU v5 lite")


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


SHAPES = {
    "starcoder2-15b-stage13": [("wq", 6144, 6144), ("wk", 512, 6144),
                               ("wv", 512, 6144), ("wo", 6144, 6144),
                               ("up", 24576, 6144), ("down", 6144, 24576)],
    "phi3-mini": [("wq", 3072, 3072), ("wk", 3072, 3072),
                  ("wv", 3072, 3072), ("wo", 3072, 3072),
                  ("up", 8192, 3072), ("down", 3072, 8192),
                  ("gate", 8192, 3072)],
}
HEADS = {"starcoder2-15b-stage13": ("lm_head", 49152, 6144),
         "phi3-mini": ("lm_head", 32064, 3072)}
PARAMS = {"starcoder2-15b-stage13": 5_291_114_496, "phi3-mini": 3_722_379_264}
# least time of one step's linear calls, seconds: decode (8 rows through
# every linear and the head), prefill (16 rows, the head over the last
# row only), and a prefill chunk that does not end its prompt
LEAST = {"starcoder2-15b-stage13": (0.003975375550671551,
                                    0.003999656478632478,
                                    0.0037741064126984125),
         "phi3-mini": (0.002817998612942613, 0.002853886905982906,
                       0.0027801987594627594)}


def test_peaks_are_the_published_v5e_numbers():
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com/tpu/docs/v5e" in V5E["source"]


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_linear_shapes_and_parameters(name):
    c = config(name)
    assert work.layer_linears(c) == SHAPES[name]
    assert work.head_linear(c) == HEADS[name]
    assert work.linear_params(c) == PARAMS[name]


@pytest.mark.parametrize("b", [8, 16])
def test_bytes_are_codes_scales_and_activations(b):
    c = config("starcoder2-15b-stage13")
    m, k = 6144, 24576
    want = m * k / 2 + m * math.ceil(k / 36) * 4 + 2 * b * (k + m)
    assert work.linear_bytes(c, m, k, b) == want
    # memory-bound at decode and prefill widths: bytes over bandwidth
    assert work.least_time_s(c, m, k, b, V5E) == want / 819e9


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_step_least_time_is_pinned(name):
    c = config(name)
    dec, pre, mid = LEAST[name]
    assert work.step_linear_least_s(c, 8, 8, V5E) == pytest.approx(
        dec, rel=1e-12)
    assert work.step_linear_least_s(c, 16, 1, V5E) == pytest.approx(
        pre, rel=1e-12)
    assert work.step_linear_least_s(c, 16, 0, V5E) == pytest.approx(
        mid, rel=1e-12)


def test_compute_bound_least_time_counts_two_ops_per_weight_per_row():
    c = config("phi3-mini")
    m, k, b = 3072, 3072, 4096
    assert work.least_time_s(c, m, k, b, V5E) == 2 * m * k * b / 197e12


def test_no_rows_need_no_time():
    c = config("phi3-mini")
    assert work.least_time_s(c, 3072, 3072, 0, V5E) == 0.0


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_model_flops(name):
    c = config(name)
    h, dh = c["num_attention_heads"], c["head_dim"]
    want = 2 * PARAMS[name] * 8 + 4 * c["num_hidden_layers"] * h * dh * 1600
    assert work.model_flops(c, 8, 1600) == want

"""The work arithmetic behind linear_roofline and step.mfu (bench/work.py):
the GeMM's own operations and bytes, from the configurations' shapes, per
block kind and over the cell's chips; and the configuration file's
mapping onto the program's ModelConfig (bench/model.py)."""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import model, work  # noqa: E402
from bench.rehearse import tiny_config  # noqa: E402
from variants import VARIANTS, variant  # noqa: E402

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


# (tokens, head rows, attended positions) of a few steps, and what the
# counts read for them before block kinds and chips were counted: the
# cells' numbers may not move
STEPS = [(8, 8, 1600), (16, 1, 136), (16, 0, 2200), (5, 5, 4321), (1, 1, 1)]
TODAY = {
    "phi3-mini": [(60187213824.0, 0.002817998612942613),
                  (119169613824.0, 0.002853886905982906),
                  (119981211648.0, 0.0027801987594627594),
                  (38922878976.0, 0.0028040578656898657),
                  (7445151744.0, 0.0027854702026862024)],
    "starcoder2-15b-stage13": [(85169012736.0, 0.003975375550671551),
                               (169359114240.0, 0.003999656478632478),
                               (170018537472.0, 0.0037741064126984125),
                               (54291652608.0, 0.003965510642246642),
                               (10582548480.0, 0.003952357431013431)],
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_cells_counts_are_todays_to_the_last_digit(name):
    c = config(name)
    assert work.layer_kinds(c) == ["attn"] * c["num_hidden_layers"]
    assert work.window(c) == 0
    assert work.linear_params(c) == PARAMS[name]
    got = [(work.model_flops(c, n, ctx, ctx),
            work.step_linear_least_s(c, n, head, V5E))
           for n, head, ctx in STEPS]
    assert got == TODAY[name]
    assert [work.model_flops(c, n, ctx) for n, _, ctx in STEPS] == \
        [f for f, _ in TODAY[name]]


def todays_model_config(c):
    """How the builder mapped a configuration before ``program.model``."""
    from repro.core.spec import QuantSpec
    from repro.models.config import ModelConfig

    p = c["program"]
    return ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or 0,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        max_seq_len=c["max_position_embeddings"],
        block_pattern=(p["block"],), rope_theta=float(c["rope_theta"]),
        mlp_activation=p["mlp_activation"], norm=p["norm"],
        tie_embeddings=c["tie_word_embeddings"], dtype=p["dtype"],
        quant=QuantSpec(**p["quant"]))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_cells_model_config_is_todays(name):
    c = dict(config(name), name=name)
    assert "model" not in c["program"]
    assert model.model_config(c) == todays_model_config(c)


@pytest.mark.parametrize("kind", sorted(VARIANTS))
def test_program_model_is_applied_as_model_config_fields(kind):
    c = variant(dict(config("phi3-mini"), name="v"), kind)
    cfg = model.model_config(c)
    for key, value in VARIANTS[kind].items():
        want = tuple(value) if key == "block_pattern" else value
        assert getattr(cfg, key) == want
    # what the file does not name stays as the published keys map it
    assert cfg.d_model == 3072 and cfg.num_heads == 32


def test_a_program_model_key_model_config_lacks_is_an_error():
    c = dict(config("phi3-mini"), name="v")
    c["program"] = dict(c["program"], model={"num_expertz": 4,
                                              "sliding_window": 8})
    with pytest.raises(ValueError, match="num_expertz"):
        model.model_config(c)


def test_counts_follow_the_block_kinds():
    base = dict(config("phi3-mini"), num_hidden_layers=4)
    local = variant(base, "local")
    moe = variant(base, "moe")
    assert work.layer_kinds(local) == ["local", "attn"] * 2
    assert work.window(local) == 8 and work.window(moe) == 0
    dense = work.layer_linears(base)
    # same linears in a local block; attention capped at the window
    assert work.layer_linears(local, "local") == dense
    assert work.linear_params(local) == work.linear_params(base)
    att = 4.0 * 32 * 96
    assert work.model_flops(local, 8, 900, 64) == pytest.approx(
        work.model_flops(base, 8, 900) - 2 * att * (900 - 64), rel=1e-12)
    with pytest.raises(ValueError, match="window"):
        work.model_flops(local, 8, 900)
    # an expert block: attention linears and the shared expert run as
    # linear kernels; the router and the routed experts do not
    assert work.layer_linears(moe, "moe") == dense[:4] + [
        ("shared_up", 72, 3072), ("shared_down", 3072, 72),
        ("shared_gate", 72, 3072)]
    per_token = (sum(m * k for _, m, k in dense[:4]) + 3 * 72 * 3072
                 + 3072 * 4 + 2 * 3 * 36 * 3072)
    assert work.layer_params(moe, "moe") == per_token
    # a file that holds 4 of 16 published experts counts a quarter of
    # the routed work a token does
    held = dict(moe, published={"num_local_experts": 16})
    assert work.layer_params(held, "moe") == per_token - 2 * 3 * 36 * 3072 \
        * (1 - 4 / 16)
    with pytest.raises(ValueError, match="mamba"):
        work.layer_kinds(dict(base, program=dict(
            base["program"], model={"block_pattern": ["mamba"]})))


def test_several_chips_share_the_least_time():
    c = config("starcoder2-15b-stage13")
    one = work.step_linear_least_s(c, 8, 8, V5E)
    assert work.step_linear_least_s(c, 8, 8, V5E, 4) == one / 4


def _actual_least_s(c, step, routed, pk):
    """Least time of the linear work a step ran: every linear a block
    runs over the step's rows, a moe block's router (float32 weights)
    over them too, and each held expert over the rows routed to it."""
    rows = step.rows
    t = 0.0
    for kind in work.layer_kinds(c):
        t += sum(work.least_time_s(c, m, k, rows, pk)
                 for _, m, k in work.layer_linears(c, kind))
        if kind == "moe":
            e, d = len(routed), c["hidden_size"]
            t += max(2.0 * e * d * rows / pk["bf16_flops_per_s"],
                     4.0 * e * d / pk["hbm_bytes_per_s"])
            eff = VARIANTS["moe"]["moe_d_ff"]
            for n in routed:
                t += sum(work.least_time_s(c, m, k, int(n), pk) for m, k in
                         ((eff, d), (d, eff), (eff, d)))
    _, m, k = work.head_linear(c)
    return t + work.least_time_s(c, m, k, step.head_rows, pk)


def _window_steps(c, seed):
    """The steps of a short window of a tiny engine on seeded traffic."""
    from bench import loop as L
    from bench.traffic import ClosedLoop
    from repro.serving import Engine

    params, cfg = model.build(c, seed)
    eng = Engine(params, cfg, max_slots=4, block_size=8, max_model_len=64)
    mix = json.loads((ROOT / "bench" / "traffic" / "sharegpt-turns.json")
                     .read_text())
    mix.update(clients=4, requests_per_client=4,
               prompt={"dist": "uniform", "min": 4, "max": 30},
               output={"dist": "uniform", "min": 2, "max": 30})
    drive = L.Loop(eng, ClosedLoop(mix, cfg.vocab_size, seed),
                   window=work.window(c))
    drive.warm()
    drive.fill()
    return drive.window(1.0)[2]


@pytest.mark.parametrize("kind", sorted(VARIANTS))
def test_no_counted_step_needs_more_than_the_work_it_ran(kind):
    """A roofline share or an mfu over these counts cannot pass 100%:
    for every step of a seeded window, on seeded routing of its rows
    over the held experts, the counted least time and operations are no
    more than those of the work the step ran."""
    seed = 2**31 + 41
    c = variant(dict(tiny_config(config("phi3-mini")), name=kind), kind)
    steps = _window_steps(c, seed)
    assert {s.kind for s in steps} >= {"decode"}
    rng = np.random.default_rng(seed)
    E, K = VARIANTS["moe"]["num_experts"], \
        VARIANTS["moe"]["num_experts_per_tok"]
    w = work.window(c)
    h, dh = c["num_attention_heads"], c["head_dim"]
    for s in steps:
        picks = np.argsort(-rng.random((max(s.rows, 1), E)), -1)[:, :K]
        routed = np.bincount(picks[:s.rows].ravel(), minlength=E)
        assert work.step_linear_least_s(c, s.rows, s.head_rows, V5E) <= \
            _actual_least_s(c, s, routed if kind == "moe" else [], V5E)
        assert s.ctx_local <= s.ctx
        if kind == "local":
            assert s.ctx_local <= w * s.rows
        ran = 4.0 * h * dh * sum(s.ctx_local if k == "local" else s.ctx
                                 for k in work.layer_kinds(c))
        for k in work.layer_kinds(c):
            ran += 2.0 * s.rows * sum(m * kk for _, m, kk in
                                      work.layer_linears(c, k))
            if k == "moe":
                ran += 2.0 * s.rows * E * c["hidden_size"]
                ran += 2.0 * routed.sum() * 3 * 36 * c["hidden_size"]
        # the head runs over every row of the step, used or not
        ran += 2.0 * s.rows * c["vocab_size"] * c["hidden_size"]
        assert work.model_flops(c, s.rows, s.ctx, s.ctx_local) <= \
            ran * (1 + 1e-12)

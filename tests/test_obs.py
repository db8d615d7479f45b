"""Observability tests: metric registry semantics, snapshot/trace schema
validation, Chrome-trace span recording (host spans + jit marks under
jit/scan), the zero-overhead-when-disabled contract, and the engine's
token-identity invariant with tracing on vs off."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import trace as TR
from repro.obs.metrics import Registry


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Tracing is process-global; leave it off and empty around every
    test so obs tests cannot leak spans into each other (or stage
    callbacks into other tests' compiles)."""
    obs.disable_tracing()
    obs.tracer().clear()
    yield
    obs.disable_tracing()
    obs.tracer().clear()


# ------------------------------------------------------------- registry
def test_registry_get_or_create_and_value():
    reg = Registry()
    c = reg.counter("t_total", region="us")
    c.inc()
    c.inc(2)
    assert reg.counter("t_total", region="us") is c
    assert reg.value("counter", "t_total", region="us") == 3
    assert reg.value("counter", "t_total", region="eu") is None
    reg.gauge("t_depth").set(7)
    assert reg.value("gauge", "t_depth") == 7


def test_registry_reset_prefix():
    reg = Registry()
    reg.counter("serving_x").inc()
    reg.counter("dispatch_y").inc()
    reg.reset(prefix="serving_")
    assert reg.value("counter", "serving_x") is None
    assert reg.value("counter", "dispatch_y") == 1
    reg.reset()
    assert reg.value("counter", "dispatch_y") is None


def test_histogram_percentile_edge_cases():
    reg = Registry()
    h = reg.histogram("t_s")
    assert h.percentile(50) is None  # empty: null, never raises
    empty = h.as_dict()
    assert empty["p50"] is None and empty["p95"] is None
    h.observe(0.25)
    assert h.percentile(50) == h.percentile(95) == 0.25  # single sample
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v)
    assert 0.1 <= h.percentile(50) <= h.percentile(95) <= 0.4
    d = h.as_dict()
    assert d["count"] == 5 and d["buckets"]["+Inf"] == 5
    assert d["min"] == 0.1 and d["max"] == 0.4


def test_snapshot_accepts_null_percentiles():
    """A snapshot taken before any observation carries null percentiles
    for the empty histogram — the validator accepts them (and still
    rejects non-numeric junk, and null p50 on a non-empty series)."""
    reg = Registry()
    reg.histogram("t_empty_s")  # created, never observed
    snap = reg.snapshot()
    row = snap["histograms"][0]
    assert row["count"] == 0 and row["p50"] is None
    assert obs.validate_snapshot(snap) == []
    assert obs.validate_snapshot(json.loads(json.dumps(snap))) == []
    bad = json.loads(json.dumps(snap))
    bad["histograms"][0]["p95"] = "oops"
    assert any("p95" in e for e in obs.validate_snapshot(bad))
    bad2 = json.loads(json.dumps(snap))
    bad2["histograms"][0]["count"] = 3
    assert any("null p50" in e for e in obs.validate_snapshot(bad2))


def test_snapshot_roundtrip_and_validation(tmp_path):
    reg = Registry()
    reg.counter("t_reqs", mode="msgemm").inc(4)
    reg.histogram("t_lat_s").observe(0.01)
    snap = reg.snapshot(extra={"arch": "test"})
    assert obs.validate_snapshot(snap) == []
    p = tmp_path / "m.json"
    p.write_text(json.dumps(snap))
    assert obs.validate_snapshot_file(p) == []
    # the validator actually catches breakage
    bad = dict(snap, schema_version=999)
    assert any("schema_version" in e for e in obs.validate_snapshot(bad))
    del bad["counters"]
    assert any("counters" in e for e in obs.validate_snapshot(bad))


def test_prometheus_text_and_endpoint():
    import urllib.request

    reg = Registry()
    reg.counter("t_total", help="reqs", mode="msgemm").inc(2)
    reg.histogram("t_s").observe(0.5)
    text = reg.prometheus_text()
    assert "# TYPE t_total counter" in text
    assert 't_total{mode="msgemm"} 2' in text
    assert 't_s_bucket{le="+Inf"} 1' in text and "t_s_count 1" in text
    srv = obs.serve_prometheus(0, reg)  # port 0: OS-assigned
    try:
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert 't_total{mode="msgemm"} 2' in body
    finally:
        srv.shutdown()


# --------------------------------------------------------------- tracer
def test_host_span_nesting_and_roundtrip(tmp_path):
    obs.enable_tracing(clear=True)
    with obs.tracer().span("outer", cat="test", k=1):
        with obs.tracer().span("inner", cat="test"):
            pass
    obs.tracer().instant("mark", cat="test")
    obs.tracer().counter("queue", waiting=3)
    p = tmp_path / "t.json"
    doc = obs.tracer().save(p)
    assert obs.validate_trace(doc) == []
    assert obs.validate_trace_file(p) == []
    loaded = obs.tracer().load(p)
    by_name = {e["name"]: e for e in loaded["traceEvents"]}
    assert by_name["outer"]["ph"] == by_name["inner"]["ph"] == "X"
    # inner completes first and sits inside outer's window
    assert by_name["inner"]["dur"] <= by_name["outer"]["dur"]
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]
    assert by_name["mark"]["ph"] == "i"
    assert by_name["queue"]["ph"] == "C"
    assert by_name["queue"]["args"] == {"waiting": 3}


def test_jit_marks_pair_under_jit():
    obs.enable_tracing(clear=True)

    def g(x):
        x = TR.jit_begin(x, "outer")
        y = TR.jit_begin(x, "inner")
        y = TR.jit_end(y + 1.0, "inner", cat="test")
        return TR.jit_end(y * 2.0, "outer", cat="test")

    jax.block_until_ready(jax.jit(g)(jnp.ones((2,))))
    jax.effects_barrier()
    evs = {e["name"]: e for e in obs.tracer().events()}
    assert evs["outer"]["ph"] == evs["inner"]["ph"] == "X"
    assert evs["inner"]["dur"] <= evs["outer"]["dur"]


def test_jit_marks_under_scan_fire_per_iteration():
    """Marks staged once at trace time fire every scan iteration, each
    pairing into its own complete event."""
    obs.enable_tracing(clear=True)

    def step(c, _):
        c = TR.jit_begin(c, "scan.step")
        c = TR.jit_end(c * 2.0, "scan.step", cat="test")
        return c, c

    f = jax.jit(lambda x: jax.lax.scan(step, x, None, length=4))
    out, _ = f(jnp.ones(()))
    jax.block_until_ready(out)
    jax.effects_barrier()
    evs = [e for e in obs.tracer().events() if e["name"] == "scan.step"]
    assert len(evs) == 4
    assert all(e["ph"] == "X" for e in evs)


def test_jit_end_records_histogram():
    obs.enable_tracing(clear=True)
    obs.registry().reset(prefix="t_kernel_")

    def g(x):
        x = TR.jit_begin(x, "m")
        return TR.jit_end(x * 2.0, "m", hist="t_kernel_s",
                          hist_labels={"k": "8"})

    jax.block_until_ready(jax.jit(g)(jnp.ones((8,))))
    jax.effects_barrier()
    assert obs.registry().value("histogram", "t_kernel_s", k="8") == 1


def test_tracing_off_is_zero_overhead():
    """The hard contract: with tracing disabled, span() returns the
    shared no-op singleton and jit_begin/jit_end stage NOTHING into the
    jitted computation (jit_marks_staged counts stagings)."""
    assert not obs.tracer().enabled
    assert obs.tracer().span("a") is obs.tracer().span("b")
    before = TR.jit_marks_staged

    def g(x):
        x = TR.jit_begin(x, "m")
        return TR.jit_end(x * 2.0, "m")

    jax.block_until_ready(jax.jit(g)(jnp.ones((4,))))
    jax.effects_barrier()
    assert TR.jit_marks_staged == before
    assert obs.tracer().events() == []


# ----------------------------------------------------------- cost model
def test_costs_eq9_produce_pinned_d124():
    """Eq.-9 produce accounting: the d-digit tuple table is built from
    shared lower-order prefix tables — sum_{i<=d} 16^i adds per d-wide
    chunk, NOT 16^d * d (the old formula scaled the shared build — and
    the matching transient LUT traffic — linearly in d)."""
    from repro.obs import costs

    k, b = 960, 8  # divisible by 1, 2, 4
    for d, table_ops in ((1, 16), (2, 16 + 256),
                         (4, 16 + 256 + 4096 + 65536)):
        assert costs.produce_table_ops(d) == table_ops
        cost = costs.gemm_cost(512, k, b, quant="msgemm", d=d)
        assert cost["produce_flops"] == 2.0 * table_ops * (k / d) * b
        assert cost["consume_ops"] == 512 * (k / d) * b
        # LUT spill traffic: table entries (16^d per chunk) written +
        # read at f32 — table *size* is unaffected by the shared build
        assert cost["lut_bytes"] == 2 * 16**d * (k / d) * b * 4.0
        assert cost["lut_bytes"] not in (0,) and \
            cost["lut_bytes"] + cost["bytes"] > cost["bytes"]
    # d=1 has no shared prefixes: old and new formulas coincide
    c1 = costs.gemm_cost(512, k, b, quant="msgemm", d=1)
    assert c1["produce_flops"] == 2 * 16 * k * b
    # the d=4 overcount the fix removes was ~3.75x (65536*4 / 69904)
    c4 = costs.gemm_cost(512, k, b, quant="msgemm", d=4)
    assert c4["produce_flops"] < 2 * 16**4 * k * b / 3


def test_costs_roofline_annotation():
    from repro.obs import costs

    cost = costs.gemm_cost(2048, 768, 8, quant="msgemm", d=3)
    # paper Eq. 9: shared-prefix table build per d-wide chunk
    assert cost["produce_flops"] == \
        2 * (16 + 16**2 + 16**3) * (768 / 3) * 8
    assert cost["consume_ops"] == 2048 * (768 // 3) * 8
    row = costs.annotate(1e-3, 2048, 768, 8, quant="msgemm", d=3,
                         dev=costs.DEVICES["cpu"])
    assert row["attainable_s"] > 0
    # peaks are keyed by device_kind; an unknown kind is an error, never
    # a fall back to another device's numbers
    assert costs.device() is costs.DEVICES[jax.devices()[0].device_kind]
    v5e = costs.device("TPU v5 lite")
    assert (v5e.matmul_flops, v5e.mem_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v9"):
        costs.device("TPU v9")
    assert 0 < row["roofline_fraction"] <= 1.0 or row["measured_s"] == 0
    dense = costs.gemm_cost(2048, 768, 8, quant="dense")
    assert dense["produce_flops"] == 2 * 2048 * 768 * 8
    assert dense["consume_ops"] == 0


# ------------------------------------------------- engine token identity
CFG = None


def _small_model():
    from repro.models import transformer as T
    from repro.models.config import ModelConfig

    global CFG
    if CFG is None:
        CFG = ModelConfig(num_layers=2, d_model=64, num_heads=4,
                          num_kv_heads=2, d_ff=128, vocab_size=211,
                          max_seq_len=128)
    return T.init_params(jax.random.PRNGKey(0), CFG), CFG


def _drive(params, cfg):
    from repro.serving import Engine, Request

    rng = np.random.default_rng(7)
    prompts = [tuple(int(t) for t in rng.integers(0, cfg.vocab_size,
                                                  size=n))
               for n in (5, 9)]
    eng = Engine(params, cfg, max_slots=2, block_size=4, prefill_chunk=4,
                 max_model_len=32)
    res = eng.run([Request(rid=i, prompt=p, max_new_tokens=4)
                   for i, p in enumerate(prompts)])
    return eng, {rid: seq.generated for rid, seq in res.items()}


def test_engine_tokens_identical_tracing_on_vs_off():
    """Tracing must be observational only: the engine generates the
    exact same greedy tokens with tracing enabled as disabled, and the
    traced run yields the request-lifecycle + gemm spans."""
    params, cfg = _small_model()
    _, toks_off = _drive(params, cfg)

    obs.enable_tracing(clear=True)  # BEFORE build: jit marks stage now
    _, toks_on = _drive(params, cfg)
    jax.effects_barrier()
    obs.disable_tracing()
    assert toks_on == toks_off

    names = {e["name"] for e in obs.tracer().events()}
    assert "engine.prefill_chunk" in names
    assert "engine.decode_step" in names
    assert any(n.startswith("gemm.") for n in names)


def test_engine_metrics_edge_cases_and_reset():
    from repro.serving import Engine, Request

    params, cfg = _small_model()
    eng = Engine(params, cfg, max_slots=2, block_size=4, prefill_chunk=4,
                 max_model_len=32)
    m0 = eng.metrics()  # nothing finished: counts 0, percentiles None
    assert m0["requests"] == 0 and m0["tok_per_s"] == 0.0
    assert m0["latency_p50_s"] is None and m0["ttft_p95_s"] is None

    # mid-flight (submitted, nothing finished yet): still no raise
    eng.submit(Request(rid=9, prompt=(1, 2), max_new_tokens=2))
    eng.step()
    mf = eng.metrics()
    assert mf["requests"] == 0 and mf["latency_p95_s"] is None

    eng.run([Request(rid=0, prompt=(1, 2, 3), max_new_tokens=3)])
    m1 = eng.metrics()  # exactly one finished: p50 == p95, no raise
    assert m1["requests"] >= 1
    assert m1["latency_p50_s"] > 0 and m1["latency_p95_s"] > 0
    assert eng.summary() == m1

    eng.reset_metrics()
    m2 = eng.metrics()
    assert m2["requests"] == 0 and m2["generated_tokens"] == 0
    assert m2["latency_p50_s"] is None
    assert obs.registry().value(
        "histogram", "serving_ttft_s") in (None, 0)

"""Observability tests: metric registry semantics, snapshot schema
validation, the span ring and its windowing, the engine's spans in a
profiler trace, the model's device-side scopes, the compile counter, and
the engine's token-identity invariant with a profiler trace running and
without one."""

import json
import math

import jax
import numpy as np
import pytest

from repro import obs
from repro.obs import trace as TR
from repro.obs.metrics import Registry

ENGINE_CHILDREN = ("engine.schedule", "engine.prepare", "engine.launch",
                   "engine.fetch", "engine.emit")


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
def test_ring_nesting_parent_and_self_time():
    tr = TR.Tracer()
    with tr.span("outer", k=1) as outer:
        with tr.span("inner") as inner:
            pass
        with tr.span("inner2"):
            pass
        outer.args["late"] = True  # args may grow until exit
    got = tr.spans()
    # spans close in order: children first, the parent last
    assert [s.name for s in got] == ["inner", "inner2", "outer"]
    assert inner.parent is outer and got[1].parent is outer
    assert outer.parent is None
    assert outer.args == {"k": 1, "late": True}
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    kids = [s for s in got if s.parent is outer]
    self_s = outer.duration - sum(s.duration for s in kids)
    assert 0.0 <= self_s <= outer.duration


def test_spans_windowing_and_ring_bound():
    tr = TR.Tracer(maxlen=4)
    for i in range(3):
        with tr.span(f"s{i}"):
            pass
    first = tr.spans()
    assert tr.oldest() == -math.inf  # nothing dropped yet
    # [t0, t1] keeps the spans that lie wholly inside it
    assert [s.name for s in tr.spans(first[1].t0, first[2].t1)] \
        == ["s1", "s2"]
    assert tr.spans(first[0].t0, first[0].t1 - 1e-9) == []
    for i in range(3, 6):
        with tr.span(f"s{i}"):
            pass
    held = tr.spans()
    assert [s.name for s in held] == ["s2", "s3", "s4", "s5"]
    # everything that ended after the last dropped span is still held
    assert tr.oldest() == first[1].t1
    assert all(s.t1 >= tr.oldest() for s in held)
    assert TR.RING_SPANS == 65_536
    assert obs.tracer()._ring.maxlen == TR.RING_SPANS


def test_ring_holds_every_thread_s_spans_under_contention():
    """More threads than cores, each nesting spans on its own stack: no
    append is lost, every parent is the same thread's outer span, and
    every span the ring dropped ended by ``oldest()``."""
    import sys
    import threading

    n_threads, per_thread = 16, 200
    whole, small = TR.Tracer(), TR.Tracer(maxlen=500)
    opened = [[] for _ in range(n_threads)]  # the small ring's spans

    def work(i):
        for _ in range(per_thread):
            for tr in (whole, small):
                with tr.span("outer", thread=i) as outer:
                    with tr.span("inner", thread=i) as inner:
                        pass
            opened[i] += [inner, outer]

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in threads)
    got = whole.spans()
    assert len(got) == 2 * n_threads * per_thread
    for s in got:
        if s.name == "inner":
            assert s.parent.name == "outer"
            assert s.parent.args["thread"] == s.args["thread"]
        else:
            assert s.parent is None
    held = {id(s) for s in small.spans()}
    assert len(held) == 500
    dropped = [s for spans in opened for s in spans if id(s) not in held]
    assert len(dropped) == 2 * n_threads * per_thread - 500
    assert all(s.t1 <= small.oldest() for s in dropped)


def test_tracing_off_is_zero_overhead():
    """Nothing is staged into jitted code: the lowered engine step holds
    no host-callback custom call."""
    import re

    eng = _engine()
    for decode in (True, False):
        text = _lowered_step(eng, debug=False, decode=decode)
        targets = re.findall(r"custom_call @([\w.]+)", text)
        assert not [t for t in targets if "callback" in t], targets


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


def _engine(**kw):
    from repro.serving import Engine

    params, cfg = _small_model()
    return Engine(params, cfg, **dict(dict(
        max_slots=2, block_size=4, prefill_chunk=4, max_model_len=32), **kw))


def _prompts(cfg):
    rng = np.random.default_rng(7)
    return [tuple(int(t) for t in rng.integers(0, cfg.vocab_size, size=n))
            for n in (5, 9)]


def _drive(params, cfg):
    from repro.serving import Request

    eng = _engine()
    res = eng.run([Request(rid=i, prompt=p, max_new_tokens=4)
                   for i, p in enumerate(_prompts(cfg))])
    return eng, {rid: seq.generated for rid, seq in res.items()}


def _lowered_step(eng, *, debug: bool, decode: bool = True) -> str:
    """The engine's jitted step lowered at its decode (or prefill) shape,
    with the engine's policy active, as StableHLO text."""
    from repro import dispatch

    nb, nt = (eng.max_slots, 1) if decode else (1, eng.prefill_chunk)
    W = eng.max_blocks_per_seq * eng.block_size
    ints = [np.zeros(s, np.int32) for s in
            ((nb, nt), (nb, nt), (nb, nt), (nb, W), (nb,))]
    with dispatch.using_policy(eng._policy):
        lowered = eng._step_fn.lower(eng.params, eng.kv, *ints)
    return lowered.as_text(debug_info=debug)


def _host_events(trace_dir):
    from pathlib import Path

    from jax.profiler import ProfileData

    pb = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    return [e for p in ProfileData.from_file(str(pb)).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]


def test_engine_tokens_identical_tracing_on_vs_off(tmp_path):
    """Spans are observational only: the engine generates the exact
    same greedy tokens with a jax.profiler trace running as without."""
    params, cfg = _small_model()
    _, toks_off = _drive(params, cfg)
    with jax.profiler.trace(str(tmp_path / "trace")):
        _, toks_on = _drive(params, cfg)
    assert toks_on == toks_off
    names = {e.name for e in _host_events(tmp_path / "trace")}
    assert {"engine.iteration", *ENGINE_CHILDREN} <= names


def test_profiler_trace_holds_engine_iteration_and_its_children(tmp_path):
    from repro.serving import Request

    params, cfg = _small_model()
    eng = _engine()
    # a one-chunk prompt: its prefill reads the first token back
    eng.submit(Request(rid=0, prompt=(1, 2, 3), max_new_tokens=5))
    eng.step()  # compile both shapes outside the traced steps
    eng.step()
    with jax.profiler.trace(str(tmp_path)):
        eng.step()
        eng.step()
    evs = sorted((e for e in _host_events(tmp_path)
                  if e.name.startswith("engine.")),
                 key=lambda e: e.start_ns)
    its = [e for e in evs if e.name == "engine.iteration"]
    assert len(its) == 2
    for it in its:
        end = it.start_ns + it.duration_ns
        kids = [e.name for e in evs if e.name != "engine.iteration"
                and it.start_ns <= e.start_ns
                and e.start_ns + e.duration_ns <= end]
        assert tuple(kids) == ENGINE_CHILDREN
        stats = dict(it.stats)
        assert stats["kind"] == "decode" and "step_num" in stats
    # the ring saw the same two iterations, children in the same order
    ring = [s for s in obs.tracer().spans()
            if s.name == "engine.iteration"][-2:]
    for it in ring:
        kids = [s.name for s in obs.tracer().spans(it.t0, it.t1)
                if s.parent is it]
        assert tuple(kids) == ENGINE_CHILDREN
        assert it.args["kind"] == "decode" and it.args["rows"] == 1


def test_lowered_step_names_scopes_and_stages_no_callback():
    eng = _engine()
    for decode in (True, False):
        dbg = _lowered_step(eng, debug=True, decode=decode)
        for scope in ("linear.wq", "linear.down", "linear.lm_head",
                      "attn.core", "attn.kv_write", "/norm/", "/embed/"):
            assert scope in dbg, scope
        assert "callback" not in _lowered_step(eng, debug=False,
                                               decode=decode)


def test_new_shape_sets_compiled_and_counts_compiles():
    from repro.serving import Request

    # a prefill chunk no other test uses: its first call compiles (the
    # persistent cache is off, so no earlier run's entry is found)
    eng = _engine(prefill_chunk=6)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        c0 = obs.compiles()
        eng.submit(Request(rid=0, prompt=(1, 2, 3), max_new_tokens=2))
        eng.step()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert obs.compiles() > c0
    assert obs.registry().value("counter", "jax_compiles_total") \
        == obs.compiles()
    launch = [s for s in obs.tracer().spans() if s.name == "engine.launch"]
    assert launch[-1].args.get("compiled") is True
    # the same shape again: no compile, no compiled arg
    eng.submit(Request(rid=1, prompt=(4, 5, 6), max_new_tokens=2))
    while eng.scheduler.has_work():
        eng.step()
    c1 = obs.compiles()
    eng.submit(Request(rid=2, prompt=(7, 8, 9), max_new_tokens=2))
    eng.step()
    assert obs.compiles() == c1
    launch = [s for s in obs.tracer().spans() if s.name == "engine.launch"]
    assert "compiled" not in launch[-1].args


def test_serving_step_s_is_the_whole_iteration():
    from repro.serving import Request

    eng = _engine()
    obs.registry().reset(prefix="serving_")
    eng.run([Request(rid=0, prompt=(1, 2, 3), max_new_tokens=3)])
    its = [s for s in obs.tracer().spans() if s.name == "engine.iteration"
           and s.args.get("kind") == "decode"]
    h = obs.registry().histogram("serving_step_s", phase="decode")
    dec = [s.duration for s in its[-h.count:]]
    assert h.count >= 1
    assert h.as_dict()["max"] == pytest.approx(max(dec))


def test_engine_metrics_edge_cases_and_reset():
    from repro.serving import Request

    eng = _engine()
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

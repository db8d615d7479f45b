"""Dispatch-subsystem tests: QuantSpec/QuantConfig shim split, backend
registry capability + priority selection, ExecPlan planning, the
persistent autotune cache, and engine-level backend parity."""

import dataclasses
import json
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import dispatch
from repro.core import linear, scales
from repro.core.spec import DENSE, QuantSpec, as_spec
from repro.dispatch import ExecPlan, ExecPolicy, registry
from repro.dispatch import autotune as at
from repro.kernels import ops

MS = QuantSpec(mode="msgemm", d=3, scale_block=12)


@pytest.fixture
def lin():
    key = jax.random.PRNGKey(0)
    p_dense = linear.init(key, 24, 16, DENSE)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 24))
    return p_dense, x


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets its own plan-cache file (and leaves the global
    default policy untouched)."""
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans.json"))
    dispatch.set_cache_path(None)
    yield
    dispatch.set_cache_path(None)
    dispatch.set_default_policy(None)


def _shim(**kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return linear.QuantConfig(**kw)


# ----------------------------------------------------------------- spec
def test_quantspec_validation_and_defaults():
    s = QuantSpec(mode="msgemm", d=3)
    assert s.scale_block == 36  # 0 -> 12*d
    assert QuantSpec(mode="msgemm", d="adaptive").scale_block == 12
    for bad in (dict(mode="fp8"), dict(storage="zip"), dict(codebook="x"),
                dict(mode="msgemm", d=5), dict(mode="msgemm", d=0),
                dict(mode="msgemm", d=3, scale_block=10)):
        with pytest.raises(ValueError):
            QuantSpec(**bad)


def test_as_spec_coercion():
    assert as_spec(MS) is MS
    cfg = _shim(mode="msgemm", d=3, scale_block=12)
    assert as_spec(cfg) == MS
    with pytest.raises(TypeError):
        as_spec("msgemm")


# ----------------------------------------------------------------- shim
def test_quantconfig_shim_warns_and_splits():
    with pytest.warns(DeprecationWarning, match="QuantConfig is deprecated"):
        cfg = linear.QuantConfig(mode="msgemm", d=3, scale_block=36,
                                 impl="pallas", interpret=True,
                                 consume_chunk=2, storage="packed_u8",
                                 codebook="learned")
    assert cfg.spec == QuantSpec(mode="msgemm", d=3, scale_block=36,
                                 storage="packed_u8", codebook="learned")
    assert cfg.policy == ExecPolicy(backend="msgemm_pallas", interpret=True,
                                    consume_chunk=2)
    # impl='jnp' pins the scan backend (the old default branch); non-
    # msgemm modes leave selection to the registry
    assert _shim(mode="msgemm").policy.backend == "msgemm_jnp"
    assert _shim(mode="int4_dequant").policy.backend is None
    assert _shim(mode="bf16").policy.backend is None


def test_quantconfig_shim_still_validates():
    for bad in (dict(impl="cuda"), dict(consume_chunk=0),
                dict(storage="zip"), dict(mode="msgemm", d=7)):
        with pytest.raises(ValueError), warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            linear.QuantConfig(**bad)


def test_shim_apply_equals_spec_apply(lin):
    """The acceptance invariant: the shim path is bit-identical to the
    explicit spec+policy path for every mode."""
    p_dense, x = lin
    for mode, policy in (("msgemm", ExecPolicy(backend="msgemm_jnp")),
                         ("int4_dequant", ExecPolicy()),
                         ("bf16", ExecPolicy())):
        cfg = _shim(mode=mode, d=3, scale_block=12)
        spec = cfg.spec
        p = linear.from_dense(p_dense["w"], spec)
        y_shim = linear.apply(p, x, cfg, in_dim=24)
        y_spec = linear.apply(p, x, spec, in_dim=24, policy=policy)
        assert np.array_equal(np.asarray(y_shim), np.asarray(y_spec)), mode


# ------------------------------------------------------ serving_config
def test_serving_config_mode_transitions():
    # spec -> spec
    s = linear.serving_config(QuantSpec(mode="bf16", d=3, scale_block=36),
                              "msgemm")
    assert isinstance(s, QuantSpec) and s.mode == "msgemm"
    assert s.scale_block == 36
    s2 = linear.serving_config(s, "int4_dequant")
    assert s2.mode == "int4_dequant" and s2.d == s.d
    # shim -> shim (type preserved; policy fields ride along)
    cfg = _shim(mode="msgemm", d=2, scale_block=16, impl="pallas")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        c2 = linear.serving_config(cfg, "int4_dequant")
    assert isinstance(c2, linear.QuantConfig)
    assert c2.mode == "int4_dequant" and c2.impl == "pallas" and c2.d == 2


# -------------------------------------------------------------- infer_k
def test_infer_k_adaptive_error_is_actionable():
    spec = QuantSpec(mode="msgemm", d="adaptive")
    p = linear.from_dense(jnp.ones((4200, 24)), spec)
    with pytest.raises(ValueError) as ei:
        linear.apply(p, jnp.ones((3, 24)), spec)  # no in_dim
    msg = str(ei.value)
    assert "in_dim=" in msg          # the remedy
    assert "idx" in msg and "scales" in msg  # the params keys
    # and the remedy works
    y = linear.apply(p, jnp.ones((3, 24)), spec, in_dim=24)
    assert y.shape == (3, 4200)


def test_infer_k_bf16_and_fixed_d():
    assert linear._infer_k({"w": jnp.ones((8, 24))}, DENSE) == 24
    p = linear.from_dense(jnp.ones((8, 24)), MS)
    assert linear._infer_k(p, MS) == 24
    pu = linear.from_dense(jnp.ones((8, 24)),
                           dataclasses.replace(MS, storage="packed_u8"))
    assert linear._infer_k(pu, dataclasses.replace(MS, storage="packed_u8")) \
        == 24


# ------------------------------------------------------------- registry
def test_registry_backends_and_selection():
    names = dispatch.backend_names()
    for expected in ("dense", "msgemm_jnp", "msgemm_pallas", "int4_jnp",
                     "int4_pallas"):
        assert expected in names
    assert dispatch.select_backend(DENSE, 0, "cpu").name == "dense"
    assert dispatch.select_backend(MS, 3, "cpu").name == "msgemm_jnp"
    # on a TPU uniform codes run on the MXU; the LUT kernel keeps the
    # learned codebooks, which msgemm_mxu does not declare
    assert dispatch.select_backend(MS, 3, "tpu").name == "msgemm_mxu"
    ms_cb = dataclasses.replace(MS, codebook="learned")
    assert dispatch.select_backend(ms_cb, 3, "tpu").name == "msgemm_pallas"
    assert dispatch.select_backend(ms_cb, 3, "cpu").name == "msgemm_jnp"
    i4 = QuantSpec(mode="int4_dequant", d=3, scale_block=12)
    assert dispatch.select_backend(i4, 3, "cpu").name == "int4_jnp"
    # capability: int4_pallas dequantizes the uniform grid only
    i4cb = dataclasses.replace(i4, codebook="learned")
    avail = [b.name for b in dispatch.available_backends(i4cb, 3, "cpu")]
    assert "int4_pallas" not in avail and "int4_jnp" in avail


def test_backend_counter_counts_msgemm_linears_per_call_site(lin):
    """dispatch_backend_total{backend} counts each traced msgemm-mode
    linear by the backend it ran on; dense linears are not counted."""
    from repro import obs

    p_dense, x = lin
    p = linear.from_dense(p_dense["w"], MS)

    def count(name):
        return obs.registry().value("counter", "dispatch_backend_total",
                                    backend=name) or 0

    before = {n: count(n) for n in ("msgemm_jnp", "msgemm_mxu", "dense")}
    linear.apply(p, x, MS, in_dim=24)
    linear.apply(p, x, MS, in_dim=24,
                 policy=ExecPolicy(backend="msgemm_mxu", interpret=True))
    f = jax.jit(lambda p, x: linear.apply(p, x, MS, in_dim=24))
    f(p, x)
    f(p, x)  # a cached executable traces nothing: no count
    linear.apply(p_dense, x, DENSE)
    assert count("msgemm_jnp") == before["msgemm_jnp"] + 2
    assert count("msgemm_mxu") == before["msgemm_mxu"] + 1
    assert count("dense") == before["dense"]


def test_register_backend_duplicate_and_priority():
    with pytest.raises(ValueError):
        dispatch.register_backend("dense", modes=("bf16",), run=lambda: None)
    try:
        dispatch.register_backend(
            "msgemm_custom", modes=("msgemm",), priority=999,
            run=lambda spec, plan, params, x, *, k, precision=None: x)
        assert dispatch.select_backend(MS, 3, "cpu").name == "msgemm_custom"
    finally:
        dispatch.unregister_backend("msgemm_custom")
    assert dispatch.select_backend(MS, 3, "cpu").name == "msgemm_jnp"


def test_forced_backend_falls_back_for_unsupported_specs():
    """A forced backend applies only to specs it can execute; other
    linears auto-select (a model-wide --backend msgemm_pallas must not
    crash the int4_dequant experts inside an MoE msgemm model)."""
    pol = ExecPolicy(backend="msgemm_pallas")
    assert dispatch.plan(MS, 16, 24, 8, policy=pol).backend \
        == "msgemm_pallas"
    i4 = QuantSpec(mode="int4_dequant", d=3, scale_block=12)
    assert dispatch.plan(i4, 16, 24, 8, policy=pol).backend == "int4_jnp"
    assert dispatch.plan(DENSE, 16, 24, 8, policy=pol).backend == "dense"


def test_explicit_plan_capability_error(lin):
    """Explicit plans bypass selection but not the capability check:
    int4_pallas cannot dequantize a learned codebook — pinning it must
    raise instead of silently using the uniform grid."""
    p_dense, x = lin
    spec = QuantSpec(mode="int4_dequant", d=3, scale_block=12,
                     storage="packed_u8", codebook="learned")
    p = linear.from_dense(p_dense["w"], spec)
    with pytest.raises(ValueError, match="cannot execute"):
        linear.apply(p, x, spec, in_dim=24,
                     plan=dispatch.ExecPlan(backend="int4_pallas",
                                            interpret=True))


# ----------------------------------------------------------------- plan
def test_plan_is_frozen_and_hashable():
    p = dispatch.plan(MS, 16, 24, 8)
    assert isinstance(hash(p), int)
    with pytest.raises(dataclasses.FrozenInstanceError):
        p.backend = "dense"
    assert p == dispatch.plan(MS, 16, 24, 8)  # deterministic


def test_plan_heuristic_matches_ops_tiles():
    pol = ExecPolicy(backend="msgemm_pallas")
    p = dispatch.plan(MS, 64, 72, 16, policy=pol)
    kc = -(-72 // 3)
    assert (p.tm, p.tj, p.tb) == ops.msgemm_tiles(64, kc, 16, 3, 12)
    pj = dispatch.plan(MS, 64, 72, 16,
                       policy=ExecPolicy(backend="msgemm_jnp",
                                         consume_chunk=4))
    assert pj.consume_chunk == 4 and pj.tm is None


def test_explicit_plan_override(lin):
    p_dense, x = lin
    p = linear.from_dense(p_dense["w"], MS)
    want = linear.apply(p, x, MS, in_dim=24)
    plan = ExecPlan(backend="msgemm_pallas", tm=16, tj=4, tb=16,
                    interpret=True, source="explicit")
    got = linear.apply(p, x, MS, in_dim=24, plan=plan)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------- autotune
def test_autotune_persists_and_reloads(tmp_path):
    cache_file = tmp_path / "c.json"
    dispatch.set_cache_path(cache_file)
    p1 = at.autotune(MS, 16, 24, 8, "msgemm_pallas", interpret=True, reps=1)
    assert p1.source == "autotuned" and cache_file.exists()
    raw = json.loads(cache_file.read_text())
    assert raw["version"] == 3 and len(raw["plans"]) == 1
    key = next(iter(raw["plans"]))
    assert "msgemm_pallas" in key and "m16|k24|b8" in key

    # interpret is runtime policy, never persisted with the tuning
    assert "interpret" not in next(iter(raw["plans"].values()))

    # a fresh in-memory cache over the same file serves from disk
    dispatch.set_cache_path(cache_file)
    before = at.num_timed_candidates
    p2 = at.autotune(MS, 16, 24, 8, "msgemm_pallas", interpret=True, reps=1)
    assert p2 == p1
    assert at.num_timed_candidates == before  # zero re-timing
    # ...and a compiled-mode (interpret=None) resolution of the same key
    # gets the tuned tiles WITHOUT the tuning run's interpret mode
    p3 = dispatch.plan(MS, 16, 24, 8,
                       policy=ExecPolicy(backend="msgemm_pallas"))
    assert (p3.tm, p3.tj, p3.tb) == (p1.tm, p1.tj, p1.tb)
    assert p3.interpret is None


def test_autotuned_plan_flows_through_plan(tmp_path):
    dispatch.set_cache_path(tmp_path / "c.json")
    pol = ExecPolicy(backend="msgemm_jnp", autotune=True)
    p = dispatch.plan(MS, 16, 24, 8, policy=pol)
    assert p.source == "autotuned"
    # second resolution is a pure cache hit, same plan
    assert dispatch.plan(MS, 16, 24, 8, policy=pol) == p


def test_autotune_candidates_include_heuristic():
    cands = at.candidate_plans(MS, 3, 64, 72, 16, "msgemm_pallas", True)
    kc = -(-72 // 3)
    tm, tj, tb = ops.msgemm_tiles(64, kc, 16, 3, 12)
    assert any((c.tm, c.tj, c.tb) == (tm, tj, tb) for c in cands)
    cpb = 12 // 3
    assert all(c.tj % cpb == 0 for c in cands)


def test_corrupt_cache_degrades_gracefully(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    c = dispatch.PlanCache(bad)
    assert len(c) == 0
    c.put("k", ExecPlan(backend="dense"))
    assert dispatch.PlanCache(bad).get("k") == ExecPlan(backend="dense")


def test_v2_cache_migrates_to_unsharded_keys(tmp_path):
    """Format migration: a v2 cache file (no mesh/shard tags — written
    before sharded planning existed) loads with its keys mapped to the
    unsharded '-' tag: single-device lookups keep their tuned plans with
    zero re-timing, and a sharded (mesh-tagged) lookup can NEVER be
    served from it."""
    d = dispatch.plan_d(MS, 16, 24)
    v2_key = (f"cpu|msgemm_pallas|msgemm|d{d}|sb{MS.scale_block}|"
              f"{MS.storage}|cb{MS.codebook}|m16|k24|b8|accfloat32")
    cache_file = tmp_path / "v2.json"
    cache_file.write_text(json.dumps({"version": 2, "plans": {
        v2_key: {"backend": "msgemm_pallas", "tm": 16, "tj": 8, "tb": 8,
                 "consume_chunk": 1, "acc_in_vmem": True,
                 "acc_dtype": "float32", "epilogue": True}}}))
    dispatch.set_cache_path(cache_file)

    # the migrated entry serves the v3 single-device key...
    v3_key = dispatch.plan_key("msgemm_pallas", MS, d, 16, 24, 8, "cpu")
    assert v3_key == v2_key + "|sh-"
    hit = dispatch.cache().get(v3_key)
    assert hit is not None and (hit.tm, hit.tj, hit.tb) == (16, 8, 8)

    # ...with zero re-timing through the autotuner front-end...
    before = at.num_timed_candidates
    p = at.autotune(MS, 16, 24, 8, "msgemm_pallas", interpret=True, reps=1)
    assert at.num_timed_candidates == before
    assert (p.tm, p.tj, p.tb) == (16, 8, 8)

    # ...and never satisfies a mesh-tagged (sharded) lookup
    sharded_key = dispatch.plan_key(
        "msgemm_pallas", MS, d, 16, 24, 8, "cpu",
        shard="data2.model4/m=model/k=-/b=data/psum")
    assert dispatch.cache().get(sharded_key) is None

    # a save after migration writes the current (v3) format
    dispatch.cache().put("x|shdata2.model4", ExecPlan(backend="dense"))
    raw = json.loads(cache_file.read_text())
    assert raw["version"] == 3
    assert set(raw["plans"]) == {v3_key, "x|shdata2.model4"}


def test_unknown_cache_version_degrades_to_empty(tmp_path):
    f = tmp_path / "v9.json"
    f.write_text(json.dumps({"version": 9, "plans": {"k": {
        "backend": "dense"}}}))
    assert len(dispatch.PlanCache(f)) == 0


def test_autotune_suppressed_inside_trace(lin):
    """plan() must never time candidates while a jax trace is active
    (omnistaging would stage the 'timed' ops into the ambient trace) —
    it falls back to the heuristic and the traced computation still
    works end to end."""
    p_dense, x = lin
    p = linear.from_dense(p_dense["w"], MS)
    pol = ExecPolicy(backend="msgemm_jnp", autotune=True)
    before = at.num_timed_candidates

    @jax.jit
    def f(p, x):
        return linear.apply(p, x, MS, in_dim=24, policy=pol)

    y = f(p, x)
    assert at.num_timed_candidates == before  # no mid-trace timing
    np.testing.assert_allclose(y, linear.apply(p, x, MS, in_dim=24),
                               rtol=2e-5, atol=2e-5)


def test_collecting_records_requests():
    with dispatch.collecting() as reqs:
        dispatch.plan(MS, 16, 24, 8)
        dispatch.plan(MS, 16, 24, 8)
    assert len(reqs) == 2
    assert reqs[0][:5] == (MS, 16, 24, 8, "msgemm_jnp")
    assert reqs[0].shard is None and reqs[0].tag == "-"  # no mesh active
    warmed = dispatch.warm(reqs)
    assert len(warmed) == 1  # deduped


# -------------------------------------------------- default policy scope
def test_using_policy_scoped(lin):
    p_dense, x = lin
    p = linear.from_dense(p_dense["w"], MS)
    with dispatch.using_policy(ExecPolicy(backend="msgemm_pallas",
                                          interpret=True)):
        assert dispatch.get_default_policy().backend == "msgemm_pallas"
        y = linear.apply(p, x, MS, in_dim=24)
    assert dispatch.get_default_policy().backend is None
    np.testing.assert_allclose(y, linear.apply(p, x, MS, in_dim=24),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------- backend parity
@pytest.mark.parametrize("backend",
                         ["msgemm_jnp", "msgemm_pallas", "msgemm_mxu"])
def test_msgemm_backends_match_dequant(lin, backend):
    p_dense, x = lin
    p = linear.from_dense(p_dense["w"], MS)
    qt = scales.quantize_int4(p_dense["w"], 12)
    want = x @ scales.dequantize(qt).T
    got = linear.apply(p, x, MS, in_dim=24,
                       policy=ExecPolicy(backend=backend, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", ["int4_jnp", "int4_pallas"])
def test_int4_backends_match_dequant(lin, backend):
    p_dense, x = lin
    spec = QuantSpec(mode="int4_dequant", d=3, scale_block=12,
                     storage="packed_u8")
    p = linear.from_dense(p_dense["w"], spec)
    qt = scales.quantize_int4(p_dense["w"], 12)
    want = x @ scales.dequantize(qt).T
    got = linear.apply(p, x, spec, in_dim=24,
                       policy=ExecPolicy(backend=backend, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# -------------------------------------------------------------- engine
def _engine_tokens(params, cfg, **eng_kw):
    from repro.serving import Engine, Request

    eng = Engine(params, cfg, max_slots=2, block_size=4, prefill_chunk=4,
                 max_model_len=32, **eng_kw)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=tuple(
        int(t) for t in rng.integers(0, cfg.vocab_size, size=n)),
        max_new_tokens=5) for i, n in enumerate((5, 9))]
    res = eng.run(reqs)
    return eng, {rid: seq.generated for rid, seq in res.items()}


@pytest.fixture(scope="module")
def small_model():
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.quant import quantize_model

    cfg = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                      d_ff=128, vocab_size=211, max_seq_len=64)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    spec = QuantSpec(mode="msgemm", d=3, scale_block=36)
    return quantize_model(params, cfg, spec), cfg.replace(quant=spec)


def test_engine_token_identity_across_backends(small_model):
    """Serving outputs stay token-identical whichever registered backend
    executes the quantized linears."""
    p, c = small_model
    _, base = _engine_tokens(p, c)
    _, jnp_toks = _engine_tokens(p, c, backend="msgemm_jnp")
    _, pallas_toks = _engine_tokens(p, c, backend="msgemm_pallas")
    _, mxu_toks = _engine_tokens(p, c, backend="msgemm_mxu")
    assert base == jnp_toks == pallas_toks == mxu_toks


def test_engine_autotune_resolves_plans_at_build(small_model, tmp_path):
    p, c = small_model
    cache_file = tmp_path / "engine_plans.json"
    eng, toks = _engine_tokens(p, c, autotune=True,
                               autotune_cache=cache_file)
    assert eng.exec_plans, "no plans resolved at build"
    assert all(pl.source == "autotuned" for pl in eng.exec_plans.values())
    assert cache_file.exists()
    # tuned plans must not change tokens
    _, base = _engine_tokens(p, c)
    assert toks == base
    # a second engine over the same cache file re-times nothing
    dispatch.set_cache_path(cache_file)
    before = at.num_timed_candidates
    eng2, toks2 = _engine_tokens(p, c, autotune=True,
                                 autotune_cache=cache_file)
    assert at.num_timed_candidates == before
    assert toks2 == toks


# ------------------------------------------------------------- epilogue
def test_epilogue_capability_predicates():
    """Pallas kernels advertise fused-epilogue support; jnp/dense paths
    fall back to the unfused tail in dispatch.execute."""
    from repro.core.epilogue import Epilogue

    ep = Epilogue(act="gelu", residual=True)
    assert registry.get_backend("msgemm_pallas").epilogue_ok(ep)
    assert registry.get_backend("msgemm_mxu").epilogue_ok(ep)
    assert registry.get_backend("int4_pallas").epilogue_ok(ep)
    assert not registry.get_backend("msgemm_jnp").epilogue_ok(ep)
    assert not registry.get_backend("dense").epilogue_ok(ep)


@pytest.mark.parametrize("backend",
                         ["msgemm_jnp", "msgemm_pallas", "msgemm_mxu"])
def test_epilogue_through_linear_apply(lin, backend):
    """linear.apply(epilogue=...) equals separate elementwise ops for
    both a fusing backend (Pallas) and the unfused fallback (jnp)."""
    from repro.core.epilogue import Epilogue

    p_dense, x = lin
    p = linear.from_dense(p_dense["w"], MS)
    pol = ExecPolicy(backend=backend, interpret=True)
    plain = linear.apply(p, x, MS, in_dim=24, policy=pol)
    bias = jax.random.normal(jax.random.PRNGKey(3), (16,))
    res = jax.random.normal(jax.random.PRNGKey(4), x.shape[:-1] + (16,))
    got = linear.apply(p, x, MS, in_dim=24, policy=pol,
                       epilogue=Epilogue(act="silu", bias=True,
                                         residual=True),
                       bias=bias, residual=res)
    want = jax.nn.silu(plain + bias) + res
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_epilogue_array_without_flag_rejected(lin):
    """A bias/residual array that the epilogue does not declare would be
    silently dropped — execute rejects the mismatch instead."""
    from repro.core.epilogue import Epilogue

    p_dense, x = lin
    p = linear.from_dense(p_dense["w"], MS)
    bias = jax.random.normal(jax.random.PRNGKey(6), (16,))
    res = jax.random.normal(jax.random.PRNGKey(7), x.shape[:-1] + (16,))
    with pytest.raises(ValueError, match="bias"):
        linear.apply(p, x, MS, in_dim=24, bias=bias)
    with pytest.raises(ValueError, match="bias"):
        linear.apply(p, x, MS, in_dim=24, epilogue=Epilogue(act="relu"),
                     bias=bias)
    with pytest.raises(ValueError, match="residual"):
        linear.apply(p, x, MS, in_dim=24, residual=res)


def test_plan_epilogue_false_forces_unfused(lin):
    """ExecPlan.epilogue=False disables fusion but computes the same
    function (execute applies the tail after the kernel)."""
    from repro.core.epilogue import Epilogue

    p_dense, x = lin
    p = linear.from_dense(p_dense["w"], MS)
    ep = Epilogue(act="relu", residual=True)
    res = jax.random.normal(jax.random.PRNGKey(5), x.shape[:-1] + (16,))
    kc = -(-24 // 3)
    tm, tj, tb = ops.msgemm_tiles(16, kc, 10, 3, 12)
    fused_plan = ExecPlan(backend="msgemm_pallas", tm=tm, tj=tj, tb=tb,
                          interpret=True)
    unfused_plan = dataclasses.replace(fused_plan, epilogue=False)
    got_f = linear.apply(p, x, MS, in_dim=24, plan=fused_plan,
                         epilogue=ep, residual=res)
    got_u = linear.apply(p, x, MS, in_dim=24, plan=unfused_plan,
                         epilogue=ep, residual=res)
    np.testing.assert_allclose(got_f, got_u, rtol=2e-5, atol=2e-5)


def test_plan_acc_knobs_validation_and_cache_roundtrip(tmp_path):
    """acc_in_vmem/acc_dtype/epilogue survive the JSON cache; bad
    acc_dtype is rejected eagerly; the key separates acc dtypes."""
    with pytest.raises(ValueError):
        ExecPlan(backend="msgemm_pallas", acc_dtype="int8")
    with pytest.raises(ValueError):
        ExecPolicy(acc_dtype="int8")
    c = dispatch.PlanCache(tmp_path / "p.json")
    plan = ExecPlan(backend="msgemm_pallas", tm=16, tj=4, tb=8,
                    acc_in_vmem=False, acc_dtype="bfloat16",
                    epilogue=False)
    c.put("k", plan)
    reloaded = dispatch.PlanCache(tmp_path / "p.json").get("k")
    assert reloaded.acc_in_vmem is False
    assert reloaded.acc_dtype == "bfloat16"
    assert reloaded.epilogue is False
    k32 = dispatch.plan_key("msgemm_pallas", MS, 3, 16, 24, 8, "cpu",
                            "float32")
    kbf = dispatch.plan_key("msgemm_pallas", MS, 3, 16, 24, 8, "cpu",
                            "bfloat16")
    assert k32 != kbf


def test_autotune_candidates_cover_acc_knob():
    """The candidate grid includes the legacy-accumulation variant for
    both Pallas backends (measurement can still pick it per shape)."""
    cands = at.candidate_plans(MS, 3, 64, 258, 16, "msgemm_pallas", True)
    assert any(not c.acc_in_vmem for c in cands)
    assert any(c.acc_in_vmem for c in cands)
    spec4 = QuantSpec(mode="int4_dequant", d=3, scale_block=8,
                      storage="packed_u8")
    cands4 = at.candidate_plans(spec4, 3, 64, 128, 16, "int4_pallas", True)
    assert any(not c.acc_in_vmem for c in cands4)


def test_decode_plan_small_batch_tb():
    """Engine decode shapes plan with tb sized to the actual batch (not
    padded to 128) and taller decode m tiles."""
    pln = dispatch.plan(MS, 2048, 768, batch=4)
    assert pln.backend in ("msgemm_jnp", "msgemm_pallas")
    hp = dispatch.heuristic_plan(MS, 3, 2048, 768, 4, "msgemm_pallas",
                                 ExecPolicy())
    assert hp.tb == 8 and hp.tm == 512


def test_untied_head_logits_are_float32_from_the_kernel():
    """A bf16 model's untied LM head emits float32 logits, as the tied
    head does: no bf16 rounding of the logits before the greedy pick."""
    from repro.models import transformer as T
    from repro.models.config import ModelConfig

    spec = QuantSpec(mode="msgemm", d=3, scale_block=36)
    cfg = ModelConfig(num_layers=1, d_model=72, num_heads=4, num_kv_heads=2,
                      d_ff=144, vocab_size=256, max_seq_len=16,
                      dtype="bfloat16", quant=spec)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 72), jnp.bfloat16)
    for backend in ("msgemm_pallas", "msgemm_mxu"):  # fusing kernels
        with dispatch.using_policy(ExecPolicy(backend=backend)):
            lg = T.logits_from_hidden(params, cfg, x)
        assert lg.dtype == jnp.float32
        rounded = lg.astype(jnp.bfloat16).astype(jnp.float32)
        assert not bool(jnp.all(lg == rounded)), backend


def test_model_epilogue_fusion_matches_unfused(small_model):
    """End-to-end: the model stack (attention residuals, MLP activation +
    residual in linear epilogues) computes the same logits whichever
    backend runs — i.e. fused epilogues did not change model math."""
    from repro.models import transformer as T

    p, c = small_model
    toks = np.arange(12, dtype=np.int32)[None] % c.vocab_size
    with dispatch.using_policy(ExecPolicy(backend="msgemm_pallas",
                                          interpret=True)):
        lg_pallas, _ = T.forward(p, c, {"tokens": jnp.asarray(toks)},
                                 mode="eval")
    with dispatch.using_policy(ExecPolicy(backend="msgemm_jnp")):
        lg_jnp, _ = T.forward(p, c, {"tokens": jnp.asarray(toks)},
                              mode="eval")
    np.testing.assert_allclose(lg_pallas, lg_jnp, rtol=2e-3, atol=2e-3)

"""Tensor-parallel serving over a device mesh (repro.dispatch.shard +
Engine(mesh=)).

The mesh tests need >= 8 host devices and skip otherwise; CI runs them
in a dedicated step with ``XLA_FLAGS=--xla_force_host_platform_device_
count=8``, and ``test_sharded_suite_subprocess`` re-runs the whole
in-process set under that flag from the plain tier-1 session so the
sharded path is exercised on every ``pytest -q``.

Acceptance invariants covered here:

* Engine(mesh=...) continuous-batching output is token-identical to the
  single-device engine for the same requests — msgemm + int4 + MoE
  specs, the forced Pallas backend, reduce-scatter collectives, and
  mid-stream preemption;
* autotuner cache round-trips keyed by mesh shape with zero re-timing
  on reload;
* pipelined collectives (ISSUE 10): the chunked-contraction + ring
  path is token-identical across psum/reduce_scatter, odd chunk counts
  clamp (never reject) with the fallback counter bumped, the ring
  reductions match the XLA natives bit-for-bit, and shard_pipeline=0
  tunes the variant grid once into the additive shard_variants table.
"""

import functools
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import dispatch
from repro.core.spec import QuantSpec
from repro.dispatch import autotune as at
from repro.dispatch.shard import ShardSpec, shard_spec_for
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.quant import quantize_model
from repro.serving import Engine, Request

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
HAVE8 = jax.device_count() >= 8
needs_mesh = pytest.mark.skipif(
    not HAVE8, reason="needs >= 8 host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

# dims chosen so every linear shards on model=4 with d=2/sb=8 quant:
# wq m=4*8=32, wk/wv m=2*8=16, wo k=32 (k_local 8 | sb), up m=64,
# down k=64 (k_local 16 | sb), lm_head m=vocab=64
CFG = ModelConfig(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                  d_ff=64, vocab_size=64, max_seq_len=64)
MOE_CFG = ModelConfig(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                      d_ff=64, vocab_size=64, max_seq_len=64,
                      block_pattern=("attn", "moe"), num_experts=4,
                      num_experts_per_tok=2)
SPEC = QuantSpec(mode="msgemm", d=2, scale_block=8)


def _mesh():
    return make_mesh((2, 4), ("data", "model"))


def _prompts(lens, seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    return [tuple(int(t) for t in rng.integers(0, vocab, size=L))
            for L in lens]


def _model(cfg=CFG, mode="msgemm", seed=0):
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    if mode == "bf16":
        return params, cfg
    spec = QuantSpec(mode=mode, d=2, scale_block=8)
    return quantize_model(params, cfg, spec), cfg.replace(quant=spec)


def _run(params, cfg, prompts, new=5, mesh=None, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("max_model_len", 32)
    eng = Engine(params, cfg, mesh=mesh, **kw)
    res = eng.run([Request(rid=i, prompt=p, max_new_tokens=new)
                   for i, p in enumerate(prompts)])
    return eng, {rid: seq.generated for rid, seq in res.items()}


# --------------------------------------------------- ShardSpec derivation
class FakeMesh:
    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH42 = FakeMesh(data=2, model=4)


def test_shard_spec_column_parallel():
    s = shard_spec_for(SPEC, ("mlp", "embed"), 64, 32, 32, MESH42,
                       lead_batch=4)
    assert (s.m, s.k, s.batch) == ("model", None, "data")
    assert s.local_mkb(64, 32, 32) == (16, 32, 16)
    assert "model4" in s.tag() and "m=model" in s.tag()


def test_shard_spec_row_parallel_and_alignment():
    # down-proj: k=mlp takes model; packed storage must split cleanly
    s = shard_spec_for(SPEC, ("embed", "mlp"), 32, 64, 32, MESH42,
                       lead_batch=4)
    assert (s.m, s.k) == (None, "model") and s.collective == "psum"
    # k_local = 9 violates scale_block alignment -> no k sharding, and
    # batch=3 rows don't divide data=2 either -> fully GSPMD (None)
    assert shard_spec_for(SPEC, ("embed", "mlp"), 32, 36, 3, MESH42,
                          lead_batch=3) is None


def test_shard_spec_reduce_scatter_fallback():
    s = shard_spec_for(SPEC, ("embed", "mlp"), 32, 64, 32, MESH42,
                       lead_batch=4, collective="reduce_scatter")
    assert s.collective == "reduce_scatter"
    # m=30 cannot scatter over model=4 -> psum fallback
    s = shard_spec_for(SPEC, ("embed", "mlp"), 30, 64, 32, MESH42,
                       lead_batch=4, collective="reduce_scatter")
    assert s.collective == "psum"


def test_shard_spec_respects_rule_set():
    """The derivation honors the selected rule set: serve_tp's batch
    rule is empty, so activations never batch-shard even when the rows
    would divide — the shard_map specs must agree with what constrain()
    does under the same rules."""
    s = shard_spec_for(SPEC, ("mlp", "embed"), 64, 32, 32, MESH42,
                       lead_batch=4, rules="serve_tp")
    assert (s.m, s.batch) == ("model", None)
    s = shard_spec_for(SPEC, ("mlp", "embed"), 64, 32, 32, MESH42,
                       lead_batch=4, rules="serve")
    assert (s.m, s.batch) == ("model", "data")


def test_shard_spec_adaptive_d_never_shards():
    spec = QuantSpec(mode="msgemm", d="adaptive", scale_block=12)
    assert shard_spec_for(spec, ("mlp", "embed"), 64, 36, 32, MESH42,
                          lead_batch=4) is None


def test_shard_spec_validation():
    with pytest.raises(ValueError):
        ShardSpec(mesh_axes=(("model", 4),), m="model", k="model")
    with pytest.raises(ValueError):
        ShardSpec(collective="allreduce")
    with pytest.raises(ValueError):
        dispatch.ExecPolicy(shard_collective="bogus")


def test_plan_key_carries_shard_tag():
    key = dispatch.plan_key("msgemm_jnp", SPEC, 2, 16, 32, 8, "cpu",
                            shard="data2.model4/m=model/k=-/b=data/psum")
    assert key.endswith("|shdata2.model4/m=model/k=-/b=data/psum")


# ------------------------------------ pipelined collectives (derivation)
def _fallbacks(kind, **labels):
    from repro import obs

    # registry.value()'s series-kind positional shadows the 'kind'
    # label, so read through the getter (creates-at-zero when unseen)
    return obs.registry().counter(
        "dispatch_shard_collective_fallback_total",
        kind=kind, **labels).value


def test_shard_spec_pipelined_tag_additive():
    """The plan-cache key discipline: pipelining is an additive tag
    suffix — a one-shot spec keys byte-identically to pre-pipelining
    caches, and the pipelined spec only appends to that key."""
    base = shard_spec_for(SPEC, ("embed", "mlp"), 32, 64, 32, MESH42,
                          lead_batch=4)
    piped = shard_spec_for(SPEC, ("embed", "mlp"), 32, 64, 32, MESH42,
                           lead_batch=4, pipeline_chunks=2,
                           collective_impl="ring")
    assert not base.is_pipelined and "/pc" not in base.tag()
    assert piped.is_pipelined and piped.tag() == base.tag() + "/pc2.ring"
    # exec shapes: tiles are planned per chunk — k divides by the chunks
    assert base.exec_mkb(32, 64, 32) == base.local_mkb(32, 64, 32)
    lm, lk, lb = piped.local_mkb(32, 64, 32)
    assert piped.exec_mkb(32, 64, 32) == (lm, lk // 2, lb)


def test_reduce_scatter_fallback_counted():
    """Satellite: the reduce_scatter->psum downgrade (m doesn't divide
    the k axis) is no longer silent, one-shot and pipelined alike."""
    before = _fallbacks("reduce_scatter_to_psum", axis="model")
    s = shard_spec_for(SPEC, ("embed", "mlp"), 30, 64, 32, MESH42,
                       lead_batch=4, collective="reduce_scatter")
    assert s.collective == "psum"
    assert _fallbacks("reduce_scatter_to_psum", axis="model") == before + 1
    # the pipelined derivation takes the same fallback AND keeps its
    # chunked ring layout (the fallback changes the collective, not the
    # pipeline)
    s = shard_spec_for(SPEC, ("embed", "mlp"), 30, 64, 32, MESH42,
                       lead_batch=4, collective="reduce_scatter",
                       pipeline_chunks=2, collective_impl="ring")
    assert s.collective == "psum"
    assert (s.pipeline_chunks, s.collective_impl) == (2, "ring")
    assert _fallbacks("reduce_scatter_to_psum", axis="model") == before + 2


def test_pipeline_chunks_clamped_counted():
    # k_local = 64/4 = 16: 3 doesn't divide -> clamp to 2 (chunk 8 stays
    # scale_block-aligned), counted
    before = _fallbacks("pipeline_chunks_clamped", axis="model",
                        requested=3, clamped=2)
    s = shard_spec_for(SPEC, ("embed", "mlp"), 32, 64, 32, MESH42,
                       lead_batch=4, pipeline_chunks=3)
    assert s.pipeline_chunks == 2
    assert _fallbacks("pipeline_chunks_clamped", axis="model",
                      requested=3, clamped=2) == before + 1
    # k_local = 32/4 = 8: chunk 4 breaks scale_block=8 alignment -> all
    # the way back to one-shot (requested 2, clamped 1)
    s = shard_spec_for(SPEC, ("embed", "mlp"), 32, 32, 32, MESH42,
                       lead_batch=4, pipeline_chunks=2)
    assert s.pipeline_chunks == 1 and "/pc" not in s.tag()
    assert _fallbacks("pipeline_chunks_clamped", axis="model",
                      requested=2, clamped=1) >= 1


def test_pipelined_spec_validation():
    with pytest.raises(ValueError):
        ShardSpec(mesh_axes=(("model", 4),), k="model",
                  collective_impl="bogus")
    with pytest.raises(ValueError):
        ShardSpec(mesh_axes=(("model", 4),), k="model", pipeline_chunks=0)
    with pytest.raises(ValueError):  # pipelining needs a k axis
        ShardSpec(mesh_axes=(("model", 4),), m="model", pipeline_chunks=2)
    with pytest.raises(ValueError):
        dispatch.ExecPolicy(shard_impl="bogus")
    with pytest.raises(ValueError):
        dispatch.ExecPolicy(shard_pipeline=-1)


def test_plan_cache_shard_variants_roundtrip(tmp_path):
    """shard_variants is an additive v3 table: files without it load
    (and answer None), files with it round-trip."""
    path = tmp_path / "plans.json"
    c1 = at.PlanCache(path)
    assert c1.shard_variant("k") is None  # no file at all
    c1.put_shard_variant("k", {"pipeline_chunks": 2,
                               "collective_impl": "ring", "rows": []})
    c2 = at.PlanCache(path)
    assert c2.shard_variant("k")["pipeline_chunks"] == 2
    # strip the table from the file -> still loads, answers None
    import json

    doc = json.loads(path.read_text())
    doc.pop("shard_variants")
    doc.pop("crc", None)
    from repro.obs import artifacts

    artifacts.atomic_write_json(path, artifacts.stamp_crc(doc))
    c3 = at.PlanCache(path)
    assert c3.shard_variant("k") is None
    assert len(c3) == len(c2)


# ------------------------------------------------------ sharded engines
@needs_mesh
@pytest.mark.parametrize("mode", ["msgemm", "int4_dequant", "bf16"])
def test_sharded_engine_token_identity(mode):
    p, c = _model(CFG, mode)
    prompts = _prompts((5, 9, 3, 7), seed=1)
    _, base = _run(p, c, prompts)
    _, sharded = _run(p, c, prompts, mesh=_mesh())
    assert sharded == base


@needs_mesh
def test_sharded_moe_token_identity():
    p, c = _model(MOE_CFG, "msgemm", seed=2)
    prompts = _prompts((4, 8, 6), seed=2)
    _, base = _run(p, c, prompts)
    _, sharded = _run(p, c, prompts, mesh=_mesh())
    assert sharded == base


@needs_mesh
def test_sharded_pallas_backend_token_identity():
    """The fused Pallas msGeMM path inside shard_map (interpret mode on
    CPU): per-shard LUT produce + VMEM accumulation under the mesh."""
    p, c = _model(CFG, "msgemm")
    prompts = _prompts((5, 7), seed=3)
    _, base = _run(p, c, prompts, backend="msgemm_pallas")
    eng, sharded = _run(p, c, prompts, backend="msgemm_pallas",
                        mesh=_mesh())
    assert sharded == base
    assert any(pl.backend == "msgemm_pallas" and pl.shard is not None
               for pl in eng.exec_plans.values())


@needs_mesh
def test_sharded_reduce_scatter_token_identity():
    p, c = _model(CFG, "msgemm")
    prompts = _prompts((5, 9, 3), seed=4)
    _, base = _run(p, c, prompts)
    eng, sharded = _run(p, c, prompts, mesh=_mesh(),
                        shard_collective="reduce_scatter")
    assert sharded == base
    assert any(pl.shard is not None
               and pl.shard.collective == "reduce_scatter"
               for pl in eng.exec_plans.values())


@needs_mesh
def test_sharded_engine_preemption_token_identity():
    """Mid-stream preemption (pool too small for all admitted seqs) is
    host-side scheduling — the sharded step must replay evicted
    sequences to the same tokens."""
    p, c = _model(CFG, "msgemm")
    # pool too small for both sequences' final length (16 tokens = 4
    # blocks each, only 6 usable): the later one is evicted mid-decode
    # and re-prefilled — same recipe as test_serving's exhaustion test
    prompts = _prompts((6, 6), seed=5)
    kw = dict(max_slots=2, block_size=4, prefill_chunk=8, num_blocks=7,
              max_model_len=16)
    eng0, base = _run(p, c, prompts, new=10, **kw)
    eng1, sharded = _run(p, c, prompts, new=10, mesh=_mesh(), **kw)
    assert eng0.scheduler.num_preemptions > 0  # scenario really preempts
    assert eng1.scheduler.num_preemptions == eng0.scheduler.num_preemptions
    assert sharded == base


@needs_mesh
def test_sharded_plans_resolved_at_build_and_keyed_by_mesh():
    p, c = _model(CFG, "msgemm")
    eng = Engine(p, c, max_slots=4, block_size=4, prefill_chunk=4,
                 max_model_len=32, mesh=_mesh())
    assert eng.exec_plans, "mesh build must resolve plans up front"
    # every key carries the mesh tag (sharded or not) — a 1-device cache
    # entry can never satisfy these lookups
    assert all("|shdata2.model4" in key for key in eng.exec_plans)
    assert any("/m=model" in key for key in eng.exec_plans)
    assert any("/k=model" in key for key in eng.exec_plans)
    sharded = [pl for pl in eng.exec_plans.values() if pl.shard is not None]
    assert sharded and all(pl.shard.is_sharded for pl in sharded)


@needs_mesh
def test_sharded_autotune_cache_roundtrip(tmp_path):
    """Acceptance: the autotune cache round-trips keyed by mesh shape —
    a second engine build over the same cache file re-times zero
    candidates and reproduces the plans exactly."""
    p, c = _model(CFG, "msgemm")
    cache = tmp_path / "plans.json"

    def build():
        return Engine(p, c, max_slots=4, block_size=4, prefill_chunk=4,
                      max_model_len=32, mesh=_mesh(), autotune=True,
                      autotune_cache=cache)

    at.num_timed_candidates = 0
    eng1 = build()
    assert at.num_timed_candidates > 0 and cache.exists()
    assert any("|shdata2.model4" in key for key in eng1.exec_plans)

    at.num_timed_candidates = 0
    eng2 = build()  # autotune_cache= resets the in-memory view -> disk
    assert at.num_timed_candidates == 0, "warm rebuild re-timed candidates"
    assert eng1.exec_plans == eng2.exec_plans


@needs_mesh
def test_single_device_cache_never_replayed_sharded(tmp_path):
    """A plan tuned off-mesh and a plan tuned under the mesh coexist in
    one cache file under different keys (the 'vice versa' half of the
    migration guarantee)."""
    cache = tmp_path / "plans.json"
    dispatch.set_cache_path(cache)
    p1 = at.autotune(SPEC, 16, 32, 8, "msgemm_jnp", reps=1)
    from repro.distributed import sharding as shd

    with shd.use(_mesh(), "serve"):
        pol = dispatch.ExecPolicy(autotune=True)
        p2 = dispatch.plan(SPEC, 16, 32, 8, policy=pol,
                           shard_axes=("mlp", "embed"), lead_batch=8)
    assert p2.shard is not None
    keys = list(dispatch.cache()._plans)
    assert any(k.endswith("|sh-") for k in keys)
    assert any("|shdata2.model4" in k for k in keys)
    assert p1.shard is None


# --------------------------------------- pipelined collectives (on-mesh)
@needs_mesh
def test_ring_collectives_match_xla():
    """The explicit ppermute ring reductions are numerically identical
    to the XLA natives they replace (same block->device layout for the
    scatter, same totals for the psum — including the non-divisible
    naive-ring fallback)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.distributed import collectives as coll

    mesh = make_mesh((4,), ("model",))
    sm = functools.partial(jax.shard_map, check_vma=False)
    x = jnp.arange(4 * 8 * 16, dtype=jnp.float32).reshape(4, 8, 16)

    def pair(fn, ref, arr):
        a = jax.jit(sm(fn, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model")))(arr)
        b = jax.jit(sm(ref, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model")))(arr)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    pair(lambda y: coll.ring_reduce_scatter(y, "model", dim=-1),
         lambda y: jax.lax.psum_scatter(y, "model",
                                        scatter_dimension=y.ndim - 1,
                                        tiled=True), x)
    pair(lambda y: coll.ring_psum(y, "model"),
         lambda y: jax.lax.psum(y, "model"), x)
    pair(lambda y: coll.ring_all_gather(
             coll.ring_reduce_scatter(y, "model", dim=-1), "model", dim=-1),
         lambda y: jax.lax.psum(y, "model"), x)
    # last dim 9 doesn't divide the axis -> the naive shift-and-add ring
    x_odd = jnp.arange(4 * 2 * 9, dtype=jnp.float32).reshape(4, 2, 9)
    pair(lambda y: coll.ring_psum(y, "model"),
         lambda y: jax.lax.psum(y, "model"), x_odd)


@needs_mesh
@pytest.mark.parametrize("collective,pc",
                         [("psum", 2), ("reduce_scatter", 2), ("psum", 3)])
def test_pipelined_token_identity(collective, pc):
    """Acceptance: pipelined plans (chunked contraction + ring
    collective) generate exactly the single-device engine's tokens —
    both collectives, including an odd chunk request that clamps
    per-linear (pc=3 -> 2 on the down-proj, 1 on the attn out-proj)."""
    p, c = _model(CFG, "msgemm")
    prompts = _prompts((5, 9, 3), seed=6)
    _, base = _run(p, c, prompts)
    eng, piped = _run(p, c, prompts, mesh=_mesh(),
                      shard_collective=collective,
                      shard_pipeline=pc, shard_impl="ring")
    assert piped == base
    shards = [pl.shard for pl in eng.exec_plans.values()
              if pl.shard is not None]
    assert any(s.is_pipelined for s in shards)
    if pc == 3:  # the clamp is per-linear, never a rejection
        assert {s.pipeline_chunks for s in shards if s.k is not None} \
            <= {1, 2}


@needs_mesh
def test_pipelined_preemption_token_identity():
    """Mid-stream preemption under the pipelined path: eviction +
    re-prefill replays to the same tokens (host scheduling is oblivious
    to how the contraction is chunked)."""
    p, c = _model(CFG, "msgemm")
    prompts = _prompts((6, 6), seed=5)
    kw = dict(max_slots=2, block_size=4, prefill_chunk=8, num_blocks=7,
              max_model_len=16)
    eng0, base = _run(p, c, prompts, new=10, **kw)
    eng1, piped = _run(p, c, prompts, new=10, mesh=_mesh(),
                       shard_pipeline=2, shard_impl="ring", **kw)
    assert eng0.scheduler.num_preemptions > 0
    assert eng1.scheduler.num_preemptions == eng0.scheduler.num_preemptions
    assert piped == base


@needs_mesh
def test_shard_variant_autotune_roundtrip(tmp_path):
    """shard_pipeline=0: the autotuner times the variant grid once,
    persists winners to the additive shard_variants table, and a warm
    rebuild replays them with zero re-timing and identical plans."""
    import json

    p, c = _model(CFG, "msgemm")
    cache = tmp_path / "plans.json"

    def build():
        return Engine(p, c, max_slots=4, block_size=4, prefill_chunk=4,
                      max_model_len=32, mesh=_mesh(), autotune=True,
                      shard_pipeline=0, autotune_cache=cache)

    at.num_timed_candidates = 0
    eng1 = build()
    assert cache.exists()
    doc = json.loads(cache.read_text())
    assert doc.get("shard_variants"), "no variant winners persisted"
    for v in doc["shard_variants"].values():
        assert {"pipeline_chunks", "collective_impl", "rows"} <= set(v)
        assert any(r.get("winner") for r in v["rows"])

    at.num_timed_candidates = 0
    eng2 = build()
    assert at.num_timed_candidates == 0, "warm rebuild re-timed candidates"
    assert eng1.exec_plans == eng2.exec_plans


# ------------------------------------------------------------ subprocess
def test_sharded_suite_subprocess():
    """Run the whole mesh test set under 8 forced host devices from the
    plain (1-device) tier-1 session."""
    if HAVE8:
        pytest.skip("already running under a forced multi-device host")
    env = {**os.environ, "PYTHONPATH": SRC,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.abspath(__file__), "-k", "not subprocess"],
        capture_output=True, text=True, timeout=1800, env=env)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]

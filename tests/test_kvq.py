"""Quantized paged KV cache (repro.kvq): quantize/dequantize round
trips, pack/unpack bit-exactness, Pallas-vs-jnp paged-attention parity,
engine token identity at kv_bits=8, kv_bits=4 quality tolerance,
codebook checkpoint round-trip, the kv_blocks int32/double-free fixes,
and sharded serving with a quantized pool."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kvq
from repro.kvq import attention as kvq_attn
from repro.kvq.pool import init_kv_pool
from repro.kvq.quantize import (kv_dequantize, kv_quantize, pack_codes,
                                unpack_codes)
from repro.kvq.spec import KVQuantSpec
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import Engine, Request
from repro.serving.kv_blocks import BlockPool, view_slots, write_slots

CFG = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  d_ff=128, vocab_size=211, max_seq_len=128)

HAVE8 = jax.device_count() >= 8
needs_mesh = pytest.mark.skipif(
    not HAVE8, reason="needs >= 8 host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def _codebook(seed=0):
    rng = np.random.default_rng(seed)
    return tuple([0.0] + sorted(rng.normal(size=15).tolist()))


# ------------------------------------------------------------ spec
def test_spec_validation():
    assert KVQuantSpec(8).qmax == 127
    assert KVQuantSpec(4).qmax == 7
    assert KVQuantSpec(4).packed_dim(32) == 16
    assert KVQuantSpec(4).packed_dim(33) == 17  # odd head dims pad
    assert KVQuantSpec(8).packed_dim(32) == 32
    assert KVQuantSpec(4, codebook=_codebook()).codebook_kind == "learned"
    with pytest.raises(ValueError):
        KVQuantSpec(16)  # full precision is kv_quant=None, not a spec
    with pytest.raises(ValueError):
        KVQuantSpec(8, codebook=_codebook())  # codebooks are 4-bit only
    with pytest.raises(ValueError):
        KVQuantSpec(4, codebook=(0.0,) * 15)  # wrong length
    with pytest.raises(ValueError):
        KVQuantSpec(4, codebook=(0.5,) + (0.0,) * 15)  # entry 0 pinned


def test_spec_is_hashable_and_jit_static():
    # the spec rides ModelConfig into jit closures — must hash
    a = KVQuantSpec(4, codebook=_codebook())
    b = KVQuantSpec(4, codebook=_codebook())
    assert hash(a) == hash(b) and a == b
    assert a.with_codebook(np.asarray(a.codebook)).codebook == a.codebook


# ------------------------------------------------- round trip / packing
@pytest.mark.parametrize("bits", [8, 4])
def test_pack_unpack_bit_exact(bits):
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 16 if bits == 4 else 256,
                         size=(3, 5, 2, 6)).astype(np.uint8)
    packed = pack_codes(jnp.asarray(codes), bits)
    assert packed.dtype == jnp.uint8
    back = unpack_codes(packed, bits, codes.shape[-1])
    np.testing.assert_array_equal(np.asarray(back), codes)


@pytest.mark.parametrize("bits", [8, 4])
def test_round_trip_exact_on_representable(bits):
    """Inputs of the form grid_value * 2^-k survive quantize->dequantize
    bit-exactly (power-of-two scales avoid float rounding in amax/qmax)."""
    spec = KVQuantSpec(bits)
    rng = np.random.default_rng(1)
    g = rng.integers(-spec.qmax, spec.qmax + 1, size=(4, 6, 2, 8))
    g[..., 0] = spec.qmax  # pin every row's amax so scale = 0.5 exactly
    x = jnp.asarray(g * 0.5, jnp.float32)
    codes, scales = kv_quantize(x, spec)
    back = kv_dequantize(codes, scales, spec, x.shape[-1])
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_round_trip_error_bounded():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 6, 2, 8)), jnp.float32)
    for spec in (KVQuantSpec(8), KVQuantSpec(4)):
        codes, scales = kv_quantize(x, spec)
        back = kv_dequantize(codes, scales, spec, 8)
        # half a grid step at the largest per-row scale bounds the error
        bound = 0.5 * float(jnp.max(scales)) + 1e-6
        assert float(jnp.max(jnp.abs(back - x))) <= bound


def test_codebook_dequant_matches_table():
    # max-abs entry is exactly qmax=7, so a row containing it quantizes
    # with scale == s exactly and every on-codebook value round-trips
    # bit-exactly through argmin assignment
    cb = (0.0, 7.0, -7.0, 1.0, -1.0, 2.0, -2.0, 3.0, -3.0, 4.0, -4.0,
          5.0, -5.0, 6.0, -6.0, 0.5)
    spec = KVQuantSpec(4, codebook=cb)
    s = 0.5
    idx = np.array([[1, 5, 9, 0], [2, 15, 7, 1]])  # each row holds +/-7
    vals = np.asarray(cb)[idx] * s
    x = jnp.asarray(vals[None], jnp.float32)  # (1, 2, 4)
    codes, scales = kv_quantize(x, spec)
    np.testing.assert_array_equal(np.asarray(scales), s)
    back = kv_dequantize(codes, scales, spec, 4)
    np.testing.assert_array_equal(np.asarray(back), vals[None])


def test_zero_rows_round_trip():
    for spec in (KVQuantSpec(8), KVQuantSpec(4),
                 KVQuantSpec(4, codebook=_codebook())):
        x = jnp.zeros((2, 3, 4), jnp.float32)
        codes, scales = kv_quantize(x, spec)
        assert np.all(np.asarray(scales) == 1.0)  # all-zero rows: scale 1
        back = kv_dequantize(codes, scales, spec, 4)
        assert np.all(np.asarray(back) == 0.0)


# ------------------------------------------------------- pool / capacity
def test_pool_layout_and_bytes():
    spec = KVQuantSpec(4)
    pool = init_kv_pool(spec, num_blocks=5, block_size=8, num_kv_heads=2,
                        head_dim=16)
    assert pool["k"].shape == (5, 8, 2, 8) and pool["k"].dtype == jnp.uint8
    assert pool["k_scale"].shape == (5, 8, 2)
    assert pool["k_scale"].dtype == jnp.float32
    full = kvq.bytes_per_token(CFG, None)
    kv8 = kvq.bytes_per_token(CFG, KVQuantSpec(8))
    kv4 = kvq.bytes_per_token(CFG, KVQuantSpec(4))
    assert full > kv8 > kv4
    assert full / kv4 >= 2.0  # the capacity headline must be reachable
    # blocks_for_bytes: same budget buys proportionally more blocks
    budget = 20 * 8 * full
    assert kvq.blocks_for_bytes(CFG, budget, 8, KVQuantSpec(4)) \
        >= 2 * kvq.blocks_for_bytes(CFG, budget, 8, None)
    assert kvq.blocks_for_bytes(CFG, 1, 8, None) == 2  # floor: never < 2


# ------------------------------------------------ kernel parity (pallas)
@pytest.mark.parametrize("bits,codebook", [(8, False), (4, False),
                                           (4, True)])
@pytest.mark.parametrize("softcap,window", [(0.0, 0), (5.0, 0), (0.0, 7)])
def test_pallas_matches_jnp_reference(bits, codebook, softcap, window):
    """The gate CI's kernel-parity step runs: the in-VMEM-dequant Pallas
    kernel (interpret mode off-TPU) against the jnp gather+dequant
    reference, elementwise."""
    spec = KVQuantSpec(bits, codebook=_codebook(4) if codebook else None)
    B, C, H, hk, dh, bs, nseq = 2, 4, 4, 2, 16, 8, 3
    nb = 1 + B * nseq
    rng = np.random.default_rng(5)

    class Cfg:
        num_heads, num_kv_heads, head_dim = H, hk, dh
        attn_logit_softcap = softcap

    kc, ks = kv_quantize(jnp.asarray(rng.normal(size=(nb, bs, hk, dh)),
                                     jnp.float32), spec)
    vc, vs = kv_quantize(jnp.asarray(rng.normal(size=(nb, bs, hk, dh)),
                                     jnp.float32), spec)
    pool = {"k": kc, "k_scale": ks, "v": vc, "v_scale": vs}
    q = jnp.asarray(rng.normal(size=(B, C, H, dh)), jnp.float32)
    blocks = np.arange(1, nb).reshape(B, nseq)
    vslots = jnp.asarray(
        (blocks[:, :, None] * bs + np.arange(bs)).reshape(B, -1), jnp.int32)
    positions = jnp.asarray(rng.integers(0, nseq * bs, size=(B, C)),
                            jnp.int32)
    ref = kvq_attn.run_jnp(spec, Cfg, q, pool, vslots, positions,
                           window=window)
    got = kvq_attn.run_pallas(spec, Cfg, q, pool, vslots, positions,
                              window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_backend_selection():
    # auto-selection off-TPU prefers the jnp reference (50 > 40)
    assert kvq_attn.select(KVQuantSpec(8)) == "paged_attn_jnp"
    assert kvq_attn.select(
        KVQuantSpec(8, backend="paged_attn_pallas")) == "paged_attn_pallas"
    with pytest.raises(ValueError):
        kvq_attn.select(KVQuantSpec(8, backend="msgemm_pallas"))
    # the acceptance counter: pallas materializes NO dequantized HBM copy
    assert kvq_attn.dequant_hbm_bytes(
        KVQuantSpec(8, backend="paged_attn_pallas"), CFG, 4, 64) == 0
    assert kvq_attn.dequant_hbm_bytes(
        KVQuantSpec(8, backend="paged_attn_jnp"), CFG, 4, 64) > 0


# -------------------------------------------------------- engine parity
def _generate(params, cfg, prompts, new=6, **eng_kw):
    eng_kw.setdefault("max_slots", 3)
    eng_kw.setdefault("block_size", 4)
    eng_kw.setdefault("prefill_chunk", 4)
    eng_kw.setdefault("max_model_len", 64)
    eng = Engine(params, cfg, **eng_kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new)
            for i, p in enumerate(prompts)]
    res = eng.run(reqs)
    return {rid: tuple(s.generated) for rid, s in res.items()}, eng


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [tuple(int(t) for t in rng.integers(0, CFG.vocab_size, size=L))
            for L in lens]


def test_engine_kv8_token_identical(params):
    """Acceptance: kv_bits=8 serving is token-identical to the bf16-KV
    engine on the test model (int8 KV error never flips a greedy argmax
    at these scales)."""
    prompts = _prompts((5, 11, 3, 8), seed=1)
    base, _ = _generate(params, CFG, prompts)
    q8, eng = _generate(params, CFG, prompts, kv_quant=KVQuantSpec(8))
    assert base == q8
    assert eng.cfg.kv_quant == KVQuantSpec(8)
    assert eng.metrics()["max_resident_seqs"] >= 1


def test_engine_pallas_vs_jnp_token_identical(params):
    prompts = _prompts((7, 12), seed=2)
    for bits in (8, 4):
        jn, _ = _generate(params, CFG, prompts,
                          kv_quant=KVQuantSpec(bits,
                                               backend="paged_attn_jnp"))
        pl, _ = _generate(params, CFG, prompts,
                          kv_quant=KVQuantSpec(bits,
                                               backend="paged_attn_pallas"))
        assert jn == pl, f"bits={bits}"


def test_engine_kv4_quality_tolerance(params):
    """kv_bits=4 (learned codebook) through the paged path stays within
    the documented quality budget vs the dense bf16-KV forward: tight
    logit MSE and high top-1 agreement on teacher-forced positions."""
    from repro.calib.quality import evaluate_kv

    rng = np.random.default_rng(3)
    tokens = rng.integers(0, CFG.vocab_size, size=(2, 24))
    data = [{"tokens": tokens, "labels": tokens}]
    cb = kvq.fit_kv_codebook(params, CFG, [{"tokens": tokens}])
    m = evaluate_kv(params, CFG, KVQuantSpec(4, codebook=cb), data,
                    steps=1)
    # random-init logits are nearly flat, so argmax flips cheaply — the
    # bench model (trained) holds much tighter; measured here: top1
    # 0.875, logit_mse 5.6e-3
    assert m["top1_agree"] >= 0.8
    assert m["logit_mse"] <= 2e-2
    # the harness itself is clean: full-precision paged == dense
    m16 = evaluate_kv(params, CFG, None, data, steps=1)
    assert m16["logit_mse"] <= 1e-9 and m16["top1_agree"] == 1.0
    # and kv4 stays within the documented perplexity budget (README:
    # KV4_PPL_BUDGET = 1.25) even on this untrained model
    assert m["perplexity"] <= 1.25 * m16["perplexity"]


def test_kv_pool_bytes_budget(params):
    """kv_pool_bytes sizes the pool by real storage cost: the same
    budget admits >= 2x the blocks at kv4 vs full precision."""
    budget = 16 * 4 * kvq.bytes_per_token(CFG, None)
    _, e16 = _generate(params, CFG, _prompts((5,)), kv_pool_bytes=budget)
    _, e4 = _generate(params, CFG, _prompts((5,)),
                      kv_quant=KVQuantSpec(4), kv_pool_bytes=budget)
    assert e4.pool.num_blocks >= 2 * e16.pool.num_blocks


# -------------------------------------------- codebook checkpoint cycle
def test_codebook_checkpoint_round_trip(params, tmp_path):
    """A fitted KV codebook survives a CheckpointManager save/restore
    and reproduces identical serving tokens."""
    from repro.checkpoint import CheckpointManager

    cb = kvq.fit_kv_codebook(params, CFG)
    spec = KVQuantSpec(4, codebook=cb)
    prompts = _prompts((6, 9), seed=4)
    before, _ = _generate(params, CFG, prompts, kv_quant=spec)

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    tree = {"kv_codebook": np.asarray(cb, np.float32)}
    mgr.save(0, tree)
    restored = mgr.restore(0, jax.tree.map(np.zeros_like, tree))
    spec2 = KVQuantSpec(4).with_codebook(
        np.asarray(restored["kv_codebook"]))
    assert spec2 == spec
    after, _ = _generate(params, CFG, prompts, kv_quant=spec2)
    assert before == after


# ------------------------------------------------- kv_blocks regressions
def test_write_slots_int32_throughout():
    ws = write_slots([3, 1, 7], start=5, count=6, pad_to=8, block_size=4)
    assert ws.dtype == np.int32
    # position 5 lives in block index 1 (=block id 1), offset 1
    assert ws[0] == 1 * 4 + 1
    vs = view_slots([3, 1], 4, 4)
    assert vs.dtype == np.int32


def test_block_pool_double_free_raises():
    pool = BlockPool(num_blocks=6, block_size=4)
    blocks = pool.alloc(3)
    pool.free(blocks[:2])
    with pytest.raises(ValueError, match="double free"):
        pool.free([blocks[0]])
    with pytest.raises(ValueError, match="scratch"):
        pool.free([0])
    with pytest.raises(ValueError, match="outside pool"):
        pool.free([99])
    # a legal free still works after the failed ones
    pool.free([blocks[2]])
    assert pool.free_blocks == pool.capacity


def test_block_pool_alloc_free_cycle_consistent():
    pool = BlockPool(num_blocks=8, block_size=4)
    a = pool.alloc(4)
    b = pool.alloc(3)
    assert pool.alloc(1) is None  # exhausted (7 allocatable)
    pool.free(a)
    c = pool.alloc(4)
    assert set(c) == set(a)  # recycled, no duplicates vs b
    assert not set(c) & set(b)


# ------------------------------------------------------ sharded serving
@needs_mesh
def test_sharded_engine_quantized_pool(params):
    """8-host-device mesh serving with a quantized pool: tokens match
    the single-device quantized engine (the jnp backend lowers through
    GSPMD with the constrain'd pool layouts)."""
    mesh = make_mesh((2, 4), ("data", "model"))
    prompts = _prompts((5, 9), seed=6)
    base, _ = _generate(params, CFG, prompts, kv_quant=KVQuantSpec(8),
                        max_slots=4)
    sharded, eng = _generate(params, CFG, prompts,
                             kv_quant=KVQuantSpec(8), max_slots=4,
                             mesh=mesh)
    assert base == sharded
    assert eng.kv  # quantized pool leaves exist and are device-placed
    leaf = jax.tree.leaves(eng.kv)[0]
    assert len(leaf.sharding.device_set) == 8


def test_sharded_quantized_pool_subprocess(params):
    """Run the mesh test under forced host devices when this process
    couldn't (mirrors CI's dedicated sharded step)."""
    if HAVE8:
        pytest.skip("in-process mesh test already ran")
    import subprocess
    import sys

    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env = {**os.environ, "PYTHONPATH": src,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.abspath(__file__),
         "-k", "test_sharded_engine_quantized_pool"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        proc.stdout[-4000:] + proc.stderr[-2000:]

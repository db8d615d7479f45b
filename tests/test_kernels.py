"""Pallas kernel validation: interpret-mode allclose vs pure-jnp oracles,
swept over shapes, d, scale blocks, tile sizes, and dtypes.

Bit-exactness strategy: on *exactly representable* inputs (integer-valued
activations, power-of-two scales) every sum/product in the kernels is
exact, so the reordered-grid kernel, the legacy kernel, the tile-replay
oracle, AND the plain consume oracle must agree bit for bit — any logic
error (wrong scale block, index, or tile edge) still changes the integer
result, while FMA/fusion codegen ulps (which differ legitimately between
separately compiled XLA programs) vanish.  Generic float inputs are
checked with few-ulp tolerances on top.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import dispatch
from repro.core import linear, packing, scales as scales_mod
from repro.core.epilogue import Epilogue
from repro.core.spec import QuantSpec
from repro.kernels import ops, ref
from repro.kernels.msgemm import msgemm_pallas
from repro.kernels.int4_matmul import int4_matmul_pallas


def _mk(rng, m, k, b, scale_block):
    codes = jnp.asarray(rng.integers(0, 16, size=(m, k)), jnp.uint8)
    x = jnp.asarray(rng.standard_normal((k, b)), jnp.float32)
    sc = jnp.asarray(
        np.abs(rng.standard_normal((m, -(-k // scale_block)))) + 0.1,
        jnp.float32)
    return codes, x, sc


def _mk_exact(rng, m, k, b, scale_block):
    """Inputs on which all kernel arithmetic is exact (see module doc)."""
    codes = jnp.asarray(rng.integers(0, 16, size=(m, k)), jnp.uint8)
    x = jnp.asarray(rng.integers(-4, 5, size=(k, b)), jnp.float32)
    sc = jnp.asarray(2.0 ** rng.integers(-2, 3,
                                         size=(m, -(-k // scale_block))),
                     jnp.float32)
    return codes, x, sc


# ------------------------------------------------------------- msgemm kernel
@pytest.mark.parametrize("d", [1, 2, 3])
@pytest.mark.parametrize("m,k,b", [(8, 12, 4), (16, 36, 8), (32, 72, 16),
                                   (128, 144, 128)])
def test_msgemm_kernel_vs_ref(d, m, k, b):
    scale_block = 6 * d  # multiple of every d in the sweep
    if k % scale_block:
        k = -(-k // scale_block) * scale_block
    rng = np.random.default_rng(d * 1000 + m + k + b)
    codes, x, sc = _mk(rng, m, k, b, scale_block)
    got = ops.msgemm(codes, x, d, scales=sc, scale_block=scale_block)
    idx = packing.pack_indices(codes, d)
    want = ref.msgemm_ref(idx, x, sc, d=d, scale_block=scale_block)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=5e-4)


@pytest.mark.parametrize("tm,tj,tb", [(8, 2, 8), (16, 4, 16), (8, 8, 32)])
def test_msgemm_kernel_tiling_invariance(tm, tj, tb):
    d, scale_block = 2, 4
    m, kc, b = 16, 8, 32
    rng = np.random.default_rng(42)
    codes, x, sc = _mk(rng, m, kc * d, b, scale_block)
    idx = packing.pack_indices(codes, d)
    got = msgemm_pallas(idx, x, sc, d=d, scale_block=scale_block,
                        tm=tm, tj=tj, tb=tb, interpret=True)
    want = ref.msgemm_ref(idx, x, sc, d=d, scale_block=scale_block)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_msgemm_kernel_unpadded_shapes():
    """Wrapper pads ragged (m, k, b) transparently."""
    d, scale_block = 3, 6
    rng = np.random.default_rng(7)
    codes, x, sc = _mk(rng, 13, 30, 5, scale_block)
    got = ops.msgemm(codes, x, d, scales=sc, scale_block=scale_block)
    idx = packing.pack_indices(codes, d)
    want = ref.msgemm_ref(idx, x, sc, d=d, scale_block=scale_block)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_msgemm_kernel_matches_quantized_dense():
    """End-to-end: quantize real weights, kernel == dequant @ x."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((32, 72)), jnp.float32)
    qt = scales_mod.quantize_int4(w, block=12)
    x = jnp.asarray(rng.standard_normal((72, 16)), jnp.float32)
    got = ops.msgemm(qt.codes, x, 3, scales=qt.scales, scale_block=12)
    want = scales_mod.dequantize(qt) @ x
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_msgemm_kernel_vector_x():
    rng = np.random.default_rng(1)
    codes, x, sc = _mk(rng, 8, 12, 1, 6)
    got = ops.msgemm(codes, x[:, 0], 3, scales=sc, scale_block=6)
    assert got.shape == (8,)
    want = ref.msgemm_ref(packing.pack_indices(codes, 3), x, sc,
                          d=3, scale_block=6)[:, 0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ------------------------------------------ reordered grid / VMEM acc stripe
# (d, scale_block, m, k, b): sweeps LUT depth, scale-block sizes, ragged
# everything, non-power-of-two kc (k/d = 43, 35), and b=1 decode shapes.
BITEXACT_SHAPES = [
    (1, 6, 13, 30, 5),
    (2, 4, 16, 24, 8),
    (2, 8, 40, 104, 3),     # kc = 52
    (3, 6, 32, 90, 16),     # kc = 30
    (3, 12, 64, 258, 1),    # kc = 86 (non-pow2), b = 1 decode
    (3, 9, 7, 129, 2),      # kc = 43 (prime), ragged m
    (4, 8, 24, 140, 4),     # d = 4, kc = 35
]


@pytest.mark.parametrize("d,scale_block,m,k,b", BITEXACT_SHAPES)
def test_msgemm_bitexact_sweep(d, scale_block, m, k, b):
    """Reordered-grid + scratch-accumulator kernel is bit-identical to the
    legacy kernel, to the tile-replay oracle, and to kernels/ref.py's
    consume oracle on exactly representable inputs."""
    rng = np.random.default_rng(d * 101 + m + k + b)
    codes, x, sc = _mk_exact(rng, m, k, b, scale_block)
    tm, tj, tb = ops.msgemm_tiles(m, -(-k // d), b, d, scale_block)
    new = np.asarray(ops.msgemm(codes, x, d, scales=sc,
                                scale_block=scale_block))
    old = np.asarray(ops.msgemm(codes, x, d, scales=sc,
                                scale_block=scale_block, acc_in_vmem=False))
    tiled = np.asarray(ref.msgemm_tiled_ref(
        codes, x, sc, d=d, scale_block=scale_block, tm=tm, tj=tj, tb=tb))
    plain = np.asarray(ref.msgemm_ref(packing.pack_indices(codes, d), x, sc,
                                      d=d, scale_block=scale_block))
    np.testing.assert_array_equal(new, old)
    np.testing.assert_array_equal(new, tiled)
    np.testing.assert_array_equal(new, plain)


@pytest.mark.parametrize("d,scale_block,m,k,b", BITEXACT_SHAPES[:5])
def test_msgemm_new_vs_legacy_float(d, scale_block, m, k, b):
    """Generic floats: reordered kernel vs legacy within a few ulps (the
    two are the same op order; residual diffs are XLA codegen FMA
    contraction, not algorithm)."""
    rng = np.random.default_rng(d * 77 + m + k + b)
    codes, x, sc = _mk(rng, m, k, b, scale_block)
    new = ops.msgemm(codes, x, d, scales=sc, scale_block=scale_block)
    old = ops.msgemm(codes, x, d, scales=sc, scale_block=scale_block,
                     acc_in_vmem=False)
    np.testing.assert_allclose(new, old, rtol=3e-6, atol=3e-5)


EPILOGUES = [
    Epilogue(),
    Epilogue(act="relu"),
    Epilogue(act="gelu"),
    Epilogue(act="silu"),
    Epilogue(bias=True),
    Epilogue(act="relu", bias=True),
    Epilogue(residual=True),
    Epilogue(act="gelu", bias=True, residual=True),
    Epilogue(act="silu", residual=True, out_dtype="bfloat16"),
    Epilogue(out_dtype="bfloat16"),
]


@pytest.mark.parametrize("ep", EPILOGUES, ids=lambda e: (
    f"{e.act}{'+b' if e.bias else ''}{'+r' if e.residual else ''}"
    f"{'+' + e.out_dtype if e.out_dtype else ''}"))
def test_msgemm_epilogue_variants(ep):
    """Every epilogue variant: fused output equals the tile-replay oracle
    bit for bit on exact inputs (identity/relu/bias/residual/cast are
    exact ops there; gelu/silu get few-ulp tolerance), and fused equals
    the legacy-kernel + unfused-epilogue composition."""
    d, scale_block, m, k, b = 3, 6, 32, 90, 5
    rng = np.random.default_rng(EPILOGUES.index(ep))  # reproducible seed
    codes, x, sc = _mk_exact(rng, m, k, b, scale_block)
    bias = (jnp.asarray(rng.integers(-3, 4, size=m), jnp.float32)
            if ep.bias else None)
    res = (jnp.asarray(rng.integers(-3, 4, size=(m, b)), jnp.float32)
           if ep.residual else None)
    tm, tj, tb = ops.msgemm_tiles(m, -(-k // d), b, d, scale_block)
    fused = ops.msgemm(codes, x, d, scales=sc, scale_block=scale_block,
                       epilogue=ep, bias=bias, residual=res)
    tiled = ref.msgemm_tiled_ref(codes, x, sc, d=d, scale_block=scale_block,
                                 tm=tm, tj=tj, tb=tb, epilogue=ep,
                                 bias=bias, residual=res)
    unfused = ops.msgemm(codes, x, d, scales=sc, scale_block=scale_block,
                         acc_in_vmem=False, epilogue=ep, bias=bias,
                         residual=res)
    want_dtype = jnp.dtype(ep.out_dtype) if ep.out_dtype else jnp.float32
    assert fused.dtype == want_dtype and unfused.dtype == want_dtype
    f32 = lambda a: np.asarray(a, np.float32)
    if ep.act in ("none", "relu"):  # exact ops end to end
        np.testing.assert_array_equal(f32(fused), f32(tiled))
        np.testing.assert_array_equal(f32(fused), f32(unfused))
    else:  # transcendental activations: same math, codegen-ulp tolerance
        np.testing.assert_allclose(f32(fused), f32(tiled),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(f32(fused), f32(unfused),
                                   rtol=1e-5, atol=1e-5)


def test_msgemm_identity_epilogue_is_noop():
    """Epilogue() must change nothing vs a no-epilogue call (bitwise,
    generic floats — same compiled program modulo the epilogue arg)."""
    rng = np.random.default_rng(11)
    codes, x, sc = _mk(rng, 16, 36, 8, 6)
    plain = ops.msgemm(codes, x, 3, scales=sc, scale_block=6)
    with_ep = ops.msgemm(codes, x, 3, scales=sc, scale_block=6,
                         epilogue=Epilogue())
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(with_ep))


def test_int4_bitexact_and_epilogue():
    """int4 kernel: fused-acc path vs legacy bitwise on exact inputs;
    fused epilogue equals unfused composition."""
    m, k, b, scale_block = 24, 64, 6, 8
    rng = np.random.default_rng(3)
    codes, x, sc = _mk_exact(rng, m, k, b, scale_block)
    u8 = packing.pack_storage(codes)
    new = np.asarray(ops.int4_matmul(u8, sc, x, scale_block=scale_block))
    old = np.asarray(ops.int4_matmul(u8, sc, x, scale_block=scale_block,
                                     acc_in_vmem=False))
    np.testing.assert_array_equal(new, old)
    want = np.asarray(ref.int4_matmul_ref(u8, sc, x,
                                          scale_block=scale_block))
    np.testing.assert_array_equal(new, want)
    ep = Epilogue(act="relu", bias=True, residual=True)
    bias = jnp.asarray(rng.integers(-3, 4, size=m), jnp.float32)
    res = jnp.asarray(rng.integers(-3, 4, size=(m, b)), jnp.float32)
    fused = ops.int4_matmul(u8, sc, x, scale_block=scale_block, epilogue=ep,
                            bias=bias, residual=res)
    unfused = ops.int4_matmul(u8, sc, x, scale_block=scale_block,
                              acc_in_vmem=False, epilogue=ep, bias=bias,
                              residual=res)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def test_msgemm_large_m_stripe_fallback(monkeypatch):
    """When the VMEM acc+out stripe cannot fit even at the tb floor
    (vocab-sized lm-head m), the wrapper falls back to the legacy
    accumulation instead of allocating an unbuildable scratch — and the
    planner plans it that way up front."""
    from repro import dispatch

    assert ops.acc_stripe_fits(2048, 256, 8)
    assert not ops.acc_stripe_fits(2_000_000, 512, 8)
    # a fused residual keeps its own (mp, tb) block resident — counted
    assert ops.acc_stripe_fits(8192, 256, 128)
    assert not ops.acc_stripe_fits(8192, 256, 128, residual=True)
    spec = __import__("repro.core.spec", fromlist=["QuantSpec"]).QuantSpec(
        mode="msgemm", d=3, scale_block=12)
    hp = dispatch.heuristic_plan(spec, 3, 2_000_000, 768, 4,
                                 "msgemm_pallas", dispatch.ExecPolicy())
    assert hp.acc_in_vmem is False
    # shrink the budget so a small shape exercises the wrapper fallback
    monkeypatch.setattr(ops, "ACC_BUDGET", 64)
    rng = np.random.default_rng(9)
    codes, x, sc = _mk_exact(rng, 32, 36, 4, 6)
    got = np.asarray(ops.msgemm(codes, x, 3, scales=sc, scale_block=6))
    want = np.asarray(ref.msgemm_ref(packing.pack_indices(codes, 3), x, sc,
                                     d=3, scale_block=6))
    np.testing.assert_array_equal(got, want)


def test_msgemm_explicit_tiles_skip_heuristic(monkeypatch):
    """An ExecPlan that names all three tiles must not pay the heuristic
    (the old wrapper recomputed it on every traced call)."""
    called = []
    orig = ops._pick_tiles
    monkeypatch.setattr(ops, "_pick_tiles",
                        lambda *a, **kw: called.append(a) or orig(*a, **kw))
    rng = np.random.default_rng(21)
    codes, x, sc = _mk(rng, 16, 24, 8, 4)
    ops.msgemm(codes, x, 2, scales=sc, scale_block=4, tm=8, tj=4, tb=8)
    assert called == []
    ops.msgemm(codes, x, 2, scales=sc, scale_block=4, tm=8, tj=4)  # tb missing
    assert len(called) == 1
    i4 = []
    orig4 = ops.int4_tiles
    monkeypatch.setattr(ops, "int4_tiles",
                        lambda *a: i4.append(a) or orig4(*a))
    u8 = packing.pack_storage(codes)
    ops.int4_matmul(u8, sc, x, scale_block=4, tm=8, tk=8, tb=8)
    assert i4 == []


# ----------------------------------------------------------- tile heuristic
@pytest.mark.parametrize("d,scale_block", [(1, 6), (2, 4), (3, 12)])
@pytest.mark.parametrize("kc", [7, 13, 29, 43, 86, 129, 255])
def test_pick_tiles_odd_kc_no_overshoot(d, scale_block, kc):
    """The tj-growth loop must never overshoot a non-power-of-two kc:
    tj stays <= kc (no dead padded chunk columns beyond one tile), stays
    a multiple of scale_block//d (§3.3 factored scales), and the LUT
    tile fits the VMEM budget whenever growth ran at all."""
    cpb = scale_block // d
    tm, tj, tb = ops.msgemm_tiles(64, kc, 16, d, scale_block)
    assert tj % cpb == 0
    assert tj <= max(kc, cpb), (tj, kc)  # never grown past kc
    if tj > cpb:  # growth only happens inside the budget...
        assert 16**d * tj * tb * 4 <= ops.VMEM_BUDGET
        assert kc % tj == 0  # ...and only into exact divisors of kc
    # the padded chunk count never exceeds one tile of slack
    assert -(-kc // tj) * tj - kc < tj


def test_pick_tiles_power_of_two_unchanged():
    """Power-of-two kc keeps the old growth behavior: doubling from
    cpb=4 until the d=3 LUT tile hits the VMEM budget at tj=32."""
    tm, tj, tb = ops.msgemm_tiles(64, 64, 16, 3, 12)
    assert (tj, tb) == (32, 16) and 64 % tj == 0


def test_pick_tiles_decode_presets():
    """Decode shapes (small b, large m): tb is the actual batch rounded
    to 8 — never padded to 128 — and the freed LUT budget grows tj
    further than the 128-wide batch tile would allow."""
    m, kc = 4096, 1024
    for b in (1, 4, 8):
        tm, tj, tb = ops.msgemm_tiles(m, kc, b, 3, 12)
        assert tb == 8, (b, tb)
        assert tm == 512  # decode branch: taller m tiles
    _, tj_decode, _ = ops.msgemm_tiles(m, kc, 4, 3, 12)
    _, tj_wide, _ = ops.msgemm_tiles(m, kc, 512, 3, 12)
    assert tj_decode > tj_wide  # narrow stripe -> bigger LUT tile
    # vocab-sized m: no tb can hold the stripe -> the shape will run the
    # legacy kernel (no stripe), so tb stays batch-wide instead of being
    # pointlessly shrunk to the floor
    tm, tj, tb = ops.msgemm_tiles(200_000, 256, 512, 2, 4)
    assert tb == 128 and not ops.acc_stripe_fits(200_000, tm, 8)
    # large-but-holdable m shrinks tb until the stripe fits
    tm, tj, tb = ops.msgemm_tiles(16384, 256, 512, 2, 4)
    assert tb < 128 and ops.acc_stripe_fits(16384, tm, tb)
    # moderate m keeps a comfortable stripe without shrinking
    tm, tj, tb = ops.msgemm_tiles(2048, 256, 512, 2, 4)
    assert tb == 128 and 2048 * tb * 8 <= ops.ACC_BUDGET


def test_msgemm_explicit_tiles_match_heuristic():
    """ExecPlan-provided tiles produce the same result as the heuristic."""
    d, scale_block = 2, 4
    rng = np.random.default_rng(21)
    codes, x, sc = _mk(rng, 16, 24, 8, scale_block)
    want = ops.msgemm(codes, x, d, scales=sc, scale_block=scale_block)
    got = ops.msgemm(codes, x, d, scales=sc, scale_block=scale_block,
                     tm=8, tj=4, tb=8)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- int4_matmul kernel
@pytest.mark.parametrize("m,k,b", [(8, 32, 4), (16, 64, 8), (64, 128, 128),
                                   (13, 40, 5)])
def test_int4_matmul_vs_ref(m, k, b):
    scale_block = 8
    rng = np.random.default_rng(m * 7 + k + b)
    codes, x, sc = _mk(rng, m, k, b, scale_block)
    u8 = packing.pack_storage(codes)
    got = ops.int4_matmul(u8, sc, x, scale_block=scale_block)
    want = ref.int4_matmul_ref(u8, sc, x, scale_block=scale_block)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_int4_vs_msgemm_same_result():
    """Both kernels compute the same quantized GeMM (different algorithms)."""
    rng = np.random.default_rng(5)
    scale_block = 12
    codes, x, sc = _mk(rng, 24, 48, 8, scale_block)
    y1 = ops.msgemm(codes, x, 3, scales=sc, scale_block=scale_block)
    y2 = ops.int4_matmul(packing.pack_storage(codes), sc, x,
                         scale_block=scale_block)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_activation_dtypes(dtype):
    rng = np.random.default_rng(3)
    codes, x, sc = _mk(rng, 16, 24, 8, 12)
    got = ops.msgemm(codes, x.astype(dtype), 3, scales=sc, scale_block=12)
    want = ref.msgemm_ref(packing.pack_indices(codes, 3),
                          x.astype(dtype).astype(jnp.float32), sc,
                          d=3, scale_block=12)
    tol = 1e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


# ------------------------------------------------------ msgemm on the MXU
MXU_SPEC = QuantSpec(mode="msgemm", d=3, scale_block=36)

# (rows, m, k, activation dtype, epilogue); k = 100 is no multiple of 3
# or 36, m = 200 and 130 no multiple of 128, k = 4700 spans two k tiles
MXU_CASES = [
    (1, 200, 100, jnp.float32, Epilogue()),
    (8, 200, 100, jnp.float32, Epilogue()),
    (16, 200, 100, jnp.float32, Epilogue()),
    (8, 130, 4700, jnp.float32, Epilogue()),
    (16, 130, 4700, jnp.bfloat16, Epilogue()),
    (8, 256, 72, jnp.bfloat16, Epilogue()),
    (8, 256, 4700, jnp.float32, Epilogue()),  # idx read column-major
] + [(8, 200, 100, jnp.float32, ep) for ep in EPILOGUES[1:]] + [
    (16, 200, 100, jnp.bfloat16,
     Epilogue(act="gelu", residual=True, out_dtype="bfloat16")),
]


@pytest.mark.parametrize("rows,m,k,dtype,ep", MXU_CASES, ids=lambda v: (
    v.__name__ if isinstance(v, type) else
    f"{v.act}{'+b' if v.bias else ''}{'+r' if v.residual else ''}"
    f"{'+' + v.out_dtype if v.out_dtype else ''}"
    if isinstance(v, Epilogue) else str(v)))
def test_msgemm_mxu_matches_lut_and_dense(rows, m, k, dtype, ep):
    """msgemm_mxu through dispatch.execute equals msgemm_pallas (same
    sums, f32 order aside) and the dequantized dense product, for every
    row count, ragged m and k, activation dtype and epilogue."""
    rng = np.random.default_rng(rows * 1000 + m + k)
    p = linear.from_dense(
        jnp.asarray(rng.standard_normal((m, k)), jnp.float32), MXU_SPEC)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    bias = (jnp.asarray(rng.standard_normal(m), jnp.float32)
            if ep.bias else None)
    res = (jnp.asarray(rng.standard_normal((rows, m)), dtype)
           if ep.residual else None)

    def run(name, x):
        return dispatch.execute(
            p, x, MXU_SPEC, in_dim=k, epilogue=ep, bias=bias, residual=res,
            plan_override=dispatch.ExecPlan(backend=name))

    got = run("msgemm_mxu", x.astype(dtype))
    lut = run("msgemm_pallas", x.astype(dtype))
    dense = run("dense_fallback", x.astype(dtype).astype(jnp.float32))
    assert got.shape == (rows, m) and got.dtype == lut.dtype
    f32 = lambda a: np.asarray(a, np.float32)
    scale = float(np.max(np.abs(f32(dense))))
    tol = 2e-6 if got.dtype == jnp.float32 else 2**-7
    np.testing.assert_allclose(f32(got), f32(lut), rtol=tol,
                               atol=tol * scale)
    np.testing.assert_allclose(f32(got), f32(dense), rtol=10 * tol,
                               atol=10 * tol * scale)


@pytest.mark.parametrize("d,scale_block,m,k,b", BITEXACT_SHAPES + [
    (3, 36, 130, 4700, 9),  # two k tiles, the second ragged
    (3, 36, 256, 4700, 9),  # the same, idx read column-major
])
def test_msgemm_mxu_bitexact_sweep(d, scale_block, m, k, b):
    """On exactly representable inputs the MXU kernel equals the consume
    oracle bit for bit: a wrong code plane, scale block or tile edge
    changes the integer result."""
    rng = np.random.default_rng(d * 103 + m + k + b)
    codes, x, sc = _mk_exact(rng, m, k, b, scale_block)
    idx = packing.pack_indices(codes, d)
    got = ops.msgemm_mxu(idx, sc, x.T, d, scale_block=scale_block)
    want = ref.msgemm_ref(idx, x, sc, d=d, scale_block=scale_block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want).T)


@pytest.mark.parametrize("cap", [1, 2])
def test_msgemm_mxu_splits_m_into_groups(monkeypatch, cap):
    """Where the VMEM output stripe would outgrow ``STRIPE_BUDGET`` (a
    vocab-sized head prefilled at 128 rows), m is split into groups of
    ``cap`` tiles, the last of which may repeat a tile past m: still bit
    for bit the oracle plus the fused bias and residual."""
    from repro.kernels import msgemm_mxu as mx

    rows, m, k = 20, 1100 + cap, 100  # three m tiles of 512; own shapes
    stripe = 4 + 2 * 4 + 2 * 4        # f32 acc, out and residual blocks
    monkeypatch.setattr(mx, "STRIPE_BUDGET", 24 * 512 * stripe * cap)
    assert mx.m_groups(3, 512, 24, stripe) == ((3, 1), (2, 2))[cap - 1]
    rng = np.random.default_rng(cap)
    codes, x, sc = _mk_exact(rng, m, k, rows, 36)
    bias = jnp.asarray(rng.integers(-4, 5, size=m), jnp.float32)
    res = jnp.asarray(rng.integers(-4, 5, size=(rows, m)), jnp.float32)
    idx = packing.pack_indices(codes, 3)
    got = ops.msgemm_mxu(idx, sc, x.T, 3, scale_block=36, bias=bias,
                         residual=res,
                         epilogue=Epilogue(bias=True, residual=True))
    want = ref.msgemm_ref(idx, x, sc, d=3, scale_block=36).T + bias + res
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,idx_t", [(200, 600, False), (200, 100, True)])
def test_msgemm_mxu_reads_stored_weights_untouched(m, k, idx_t):
    """The linear's idx and scales reach the kernel as stored: in the
    traced step nothing but the kernel consumes them (no unpack, repack
    or pad of a weight-sized array), at most through a transpose where
    the chip stores the array column-major, which is a bitcast there
    (tests/test_chip_compile.py checks the compiled step)."""
    from repro.kernels.msgemm_mxu import stored_transposed

    assert stored_transposed(m, -(-k // 3)) == idx_t
    p = linear.from_dense(jnp.ones((m, k)), MXU_SPEC)
    pol = dispatch.ExecPolicy(backend="msgemm_mxu")
    closed = jax.make_jaxpr(lambda p, x: dispatch.execute(
        p, x, MXU_SPEC, in_dim=k, policy=pol))(p, jnp.ones((8, k)))

    def consumers(jaxpr, var):
        out = []
        for eqn in jaxpr.eqns:
            for i, v in enumerate(eqn.invars):
                if v is not var:
                    continue
                if eqn.primitive.name == "jit":  # follow into the call
                    inner = eqn.params["jaxpr"].jaxpr
                    out += consumers(inner, inner.invars[i])
                elif eqn.primitive.name == "transpose":
                    out += ["transpose>" + c for c in
                            consumers(jaxpr, eqn.outvars[0])]
                else:
                    out.append(eqn.primitive.name)
        return out

    idx_var, scales_var, _ = closed.jaxpr.invars  # sorted dict leaves, x
    assert consumers(closed.jaxpr, idx_var) == [
        "transpose>pallas_call" if idx_t else "pallas_call"]
    assert consumers(closed.jaxpr, scales_var) == ["transpose>pallas_call"]


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize("Sq,Skv,H,Hk,dh", [(32, 32, 4, 4, 16),
                                            (48, 48, 4, 2, 16),
                                            (40, 40, 2, 1, 8)])
@pytest.mark.parametrize("kwargs", [dict(causal=True),
                                    dict(causal=True, window=16),
                                    dict(causal=True, softcap=30.0)])
def test_flash_attention_vs_ref(Sq, Skv, H, Hk, dh, kwargs):
    B = 2
    key = jax.random.PRNGKey(Sq + H)
    q = jax.random.normal(key, (B, Sq, H, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Skv, Hk, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Skv, Hk, dh))
    got = ops.flash_attention(q, k, v, **kwargs)
    kr, vr = (jnp.repeat(t, H // Hk, axis=2) for t in (k, v))
    flat = lambda t: jnp.moveaxis(t, 2, 1).reshape(B * H, t.shape[1], dh)
    want = ref.flash_attention_ref(flat(q), flat(kr), flat(vr), **kwargs)
    want = jnp.moveaxis(want.reshape(B, H, Sq, dh), 1, 2)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_gqa_native_layout():
    """The kernel consumes k/v in their native (B, Hk, Skv, dh) layout —
    no H//Hk-fold jnp.repeat materialization — and still matches the
    broadcast reference for every group size including MQA."""
    from repro.kernels.flash_attention import flash_attention_pallas

    B, Sq, dh = 2, 32, 16
    for H, Hk in [(4, 4), (4, 2), (4, 1), (6, 3)]:
        q = jax.random.normal(jax.random.PRNGKey(H), (B, H, Sq, dh))
        k = jax.random.normal(jax.random.PRNGKey(H + 1), (B, Hk, Sq, dh))
        v = jax.random.normal(jax.random.PRNGKey(H + 2), (B, Hk, Sq, dh))
        got = flash_attention_pallas(q, k, v, causal=True, tq=16, tk=16,
                                     interpret=True)
        kr = jnp.repeat(k, H // Hk, axis=1)
        vr = jnp.repeat(v, H // Hk, axis=1)
        want = ref.flash_attention_ref(
            q.reshape(B * H, Sq, dh), kr.reshape(B * H, Sq, dh),
            vr.reshape(B * H, Sq, dh), causal=True).reshape(B, H, Sq, dh)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_sdpa():
    """Kernel agrees with the model's jnp attention path end to end."""
    from repro.models import layers
    from repro.models.config import ModelConfig

    cfg = ModelConfig(num_layers=1, d_model=32, num_heads=4, num_kv_heads=2,
                      d_ff=64, vocab_size=97)
    B, S, dh = 2, 24, cfg.head_dim
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, 4, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, 2, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, 2, dh))
    want = layers._sdpa(cfg, q, k, v, layers.causal_mask(S, S))
    got = ops.flash_attention(q, k, v, causal=True).reshape(B, S, -1)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)

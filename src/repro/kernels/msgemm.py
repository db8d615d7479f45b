"""Fused msGeMM Pallas TPU kernel — amortized produce, VMEM-resident
accumulation, and a fused epilogue.

TPU adaptation of the paper's proposed "LUT-add unit" (paper §4-§6,
DESIGN.md §2.B).  The performance-first formulation keeps both halves of
the paper's argument true on the actual grid:

* **produce is amortized over m** (§6): grid = (b_tiles, j_tiles,
  m_tiles) with **m innermost**.  The LUT tile for a (b, j) cell is built
  by one small MXU dot ``basis (16^d, d) · x_chunk (d, TJ·TB)`` into a
  VMEM scratch buffer on the *first* m-step only — every other m-tile
  gathers from the already-resident scratch.  Produce cost per output
  column drops by the number of m-tiles (the per-shape amortization
  factor reported by benchmarks/kernel_microbench.py).
* **consume never leaves fast memory** (§4): the output accumulates in a
  VMEM scratch stripe ``(mp, TB)`` that stays resident across the whole
  j-reduction; HBM sees exactly one writeback per (b-stripe, m-tile), on
  the last j-step — not one read-modify-write per j-step.
* **the epilogue rides the final writeback**: bias add, activation
  (relu/gelu/silu), residual add, and the output-dtype cast execute on
  the VMEM accumulator just before the single store, so callers stop
  issuing separate element-wise HBM passes after the GeMM
  (core/epilogue.Epilogue; EmuGEMM's fusion argument in PAPERS.md).

Shared scales (§3.3) are applied in the *factored* form: one multiply per
scale block after the block's chunks are summed, requiring TJ·d ≡ 0
(mod scale_block) — enforced by ops.py.

VMEM budget per step ≈ 16^d·TJ·TB·4 bytes for the LUT tile plus
(mp·TB·4) for the f32 accumulator stripe and (mp·TB·out_bytes) for the
resident output block; ops.py sizes TB/TJ to keep the LUT within
~8 MB and the stripes within ~4 MB (see README §Kernel performance).

The pre-overhaul formulation (j innermost, ``y_ref +=`` per step,
produce re-run on every m-tile) is kept behind ``acc_in_vmem=False`` as
the comparison baseline for the microbench and as an autotuner
candidate; with the identity epilogue the two paths are bit-identical
(same op order per output element — asserted in tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lut as lut_mod
from repro.core.epilogue import Epilogue
from repro.kernels.mode import resolve_interpret


LANES = 128  # TPU vreg lane width: the unit of the in-kernel gather


def lut_width(d: int) -> int:
    """LUT columns per chunk, padded to whole 128-lane groups (16^d for
    d >= 2; d = 1's 16 entries sit in one zero-padded group)."""
    return max(LANES, 16**d)


def _produce(x_ref, basis_ref, lut_ref, *, tj: int, d: int, acc_dtype):
    """LUT tile for every chunk of this j-tile (paper Eq. 3):
    ``lut[jj, t, n] = sum_r x[jj, t, r] * basis[r, n]`` — d broadcast
    multiply-adds on the vector unit, exact for integer-valued bases."""
    def body(jj, carry):
        xj = x_ref[jj].astype(acc_dtype)  # (tb, d)
        lut = xj[:, 0:1] * basis_ref[0:1, :].astype(acc_dtype)
        for r in range(1, d):
            lut = lut + xj[:, r:r + 1] * basis_ref[r:r + 1, :].astype(
                acc_dtype)
        lut_ref[jj] = lut
        return carry

    jax.lax.fori_loop(0, tj, body, 0)


def _gather(lut_ref, jj, idx_row, *, groups: int):
    """``lut[jj, t, idx[l]]`` for every batch row t and output lane l.

    The TPU gathers within one 128-lane vreg only, so the 4·d-bit index
    splits into a lane offset (low 7 bits, one dynamic lane gather per
    128-entry group of the table) and a group number (high bits, a
    binary tree of selects over the gathered groups)."""
    tb = lut_ref.shape[1]
    s = idx_row.shape[-1]
    idx = jnp.broadcast_to(idx_row, (tb, s))
    lo = idx & (LANES - 1)
    hi = idx >> 7
    if s < LANES:  # sub-128 m tiles (tiny layers): gather at full width
        lo = jnp.pad(lo, ((0, 0), (0, LANES - s)))
    vals = []
    for g in range(groups):
        v = jnp.take_along_axis(
            lut_ref[jj, :, g * LANES:(g + 1) * LANES], lo, axis=1)
        vals.append(v[:, :s] if s < LANES else v)
    bit = 0
    while len(vals) > 1:
        odd = ((hi >> bit) & 1) == 1
        vals = [jnp.where(odd, vals[i + 1], vals[i])
                for i in range(0, len(vals), 2)]
        bit += 1
    return vals[0]


def _consume(lut_ref, idx_ref, scale_ref, emit, *, d: int, tj: int,
             scale_block: int, tm: int, acc_dtype):
    """Gather-add one (TJ, TM) index tile against the resident LUT tile
    with §3.3 factored scales — shared by the fused and legacy kernels so
    the two paths stay bit-identical per j-step.  Walks the m tile in
    s-lane sub-blocks (s = min(TM, 128)) and hands each (TB, s) partial
    to ``emit(lane_offset, partial)``.  Chunks are unrolled: Mosaic
    loads index rows only at static sublane offsets.  A sub-128 m tile
    is one sub-block at the static offset 0: Mosaic proves a dynamic
    lane offset only as a multiple of 128."""
    cpb = scale_block // d  # chunks per scale block
    groups = lut_ref.shape[-1] // LANES
    tb = lut_ref.shape[1]
    s = min(tm, LANES)

    def sub_block(off):
        lanes = pl.ds(off, s)
        acc = jnp.zeros((tb, s), acc_dtype)
        for blk in range(tj // cpb):
            part = jnp.zeros((tb, s), acc_dtype)
            for t in range(cpb):
                jj = blk * cpb + t
                part = part + _gather(lut_ref, jj, idx_ref[jj:jj + 1, lanes],
                                      groups=groups)
            # §3.3 factored scale: one multiply per bounding box
            acc = acc + part * scale_ref[blk:blk + 1, lanes].astype(acc_dtype)
        emit(off, acc)

    if tm == s:
        sub_block(0)
    else:
        jax.lax.fori_loop(
            0, tm // s,
            lambda c, carry: (sub_block(pl.multiple_of(c * s, s)), carry)[1],
            0)


def _kernel_fused(idx_ref, x_ref, basis_ref, scale_ref, *rest, d: int,
                  tm: int, tj: int, scale_block: int, acc_dtype, nj: int,
                  epilogue: Epilogue, has_bias: bool, has_res: bool):
    """One (b_tile, j_tile, m_tile) grid step — m innermost.  Rows are
    batch, lanes are output features: the (TB, M) stripe is resident."""
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    res_ref = refs.pop(0) if has_res else None
    y_ref, lut_ref, acc_ref = refs
    ij, im = pl.program_id(1), pl.program_id(2)

    # ---- produce: once per (b, j), amortized over every m-tile ----------
    @pl.when(im == 0)
    def _produce_tile():
        _produce(x_ref, basis_ref, lut_ref, tj=tj, d=d, acc_dtype=acc_dtype)

    # ---- consume: gather-add from the resident LUT (paper Eq. 5), ------
    # ---- accumulated in the VMEM stripe; HBM sees only the final store --
    # one m tile (any sub-128 m): column offsets stay static
    one_tile = acc_ref.shape[1] == tm
    base = 0 if one_tile else pl.multiple_of(im * tm, tm)

    def emit(off, part):
        cols = pl.ds(off if one_tile
                     else pl.multiple_of(base + off, min(tm, LANES)),
                     min(tm, LANES))

        @pl.when(ij == 0)
        def _init():
            acc_ref[:, cols] = part

        @pl.when(ij > 0)
        def _accum():
            acc_ref[:, cols] += part

    _consume(lut_ref, idx_ref, scale_ref, emit, d=d, tj=tj,
             scale_block=scale_block, tm=tm, acc_dtype=acc_dtype)

    @pl.when(ij == nj - 1)
    def _writeback():
        cols = pl.ds(base, tm)
        total = acc_ref[:, cols]
        if has_bias:
            total = total + bias_ref[:, cols].astype(acc_dtype)
        total = epilogue.act_fn()(total)
        if has_res:
            total = total + res_ref[:, cols].astype(acc_dtype)
        y_ref[:, cols] = total.astype(y_ref.dtype)


def _kernel_legacy(idx_ref, x_ref, basis_ref, scale_ref, y_ref, lut_ref, *,
                   d: int, tm: int, tj: int, scale_block: int, acc_dtype):
    """Pre-overhaul step — grid (b, m, j) with j innermost: the produce
    re-runs on every (b, m, j) step and the output block accumulates
    via ``y_ref +=``.  Kept as the microbench baseline, as an autotuner
    candidate (ExecPlan.acc_in_vmem=False), and for m too wide for a
    VMEM stripe."""
    jstep = pl.program_id(2)

    @pl.when(jstep == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    _produce(x_ref, basis_ref, lut_ref, tj=tj, d=d, acc_dtype=acc_dtype)

    def emit(off, part):
        cols = pl.ds(off, min(tm, LANES))
        y_ref[:, cols] += part.astype(y_ref.dtype)

    _consume(lut_ref, idx_ref, scale_ref, emit, d=d, tj=tj,
             scale_block=scale_block, tm=tm, acc_dtype=acc_dtype)


def _vmem_limit(nbytes: int) -> int:
    """Scoped-VMEM request for a kernel whose buffers total ``nbytes``:
    headroom for Mosaic's internal scratch, never below the default
    16 MiB, capped under the v5e's 128 MiB."""
    return int(min(max(16 << 20, 2 * nbytes), 100 << 20))


@functools.partial(
    jax.jit,
    static_argnames=("d", "scale_block", "tm", "tj", "tb", "interpret",
                     "acc_dtype", "acc_in_vmem", "epilogue"),
)
def msgemm_pallas(
    idx: jnp.ndarray,      # (m, kc) int32 packed LUT indices
    x: jnp.ndarray,        # (k_pad = kc*d, b)
    scales: jnp.ndarray,   # (m, kc*d // scale_block)
    codebook: jnp.ndarray | None = None,  # optional (16,) value table
    bias: jnp.ndarray | None = None,      # (m, 1) when epilogue.bias
    residual: jnp.ndarray | None = None,  # (m, b) when epilogue.residual
    *,
    d: int,
    scale_block: int,
    tm: int = 256,
    tj: int | None = None,
    tb: int = 128,
    interpret: bool | None = None,
    acc_dtype=jnp.float32,
    acc_in_vmem: bool = True,
    epilogue: Epilogue | None = None,
) -> jnp.ndarray:
    """y (m, b) = epilogue(dequant(codes) @ x) via the fused kernel.

    ``codebook`` swaps the uniform int4 tuple basis for a learned 16-entry
    one (repro.calib) — the basis matrix is already an operand, so
    non-uniform codebooks are zero extra kernel cost.  ``codebook[0]``
    must be 0 (padding rows/chunks use index 0).

    ``acc_in_vmem=True`` (default) runs the reordered grid — m innermost,
    LUT produced once per (b, j) into VMEM scratch, output accumulated in
    a VMEM stripe with one HBM writeback on the last j-step.  ``False``
    selects the legacy j-innermost formulation (baseline; no fused
    epilogue — callers apply it unfused).

    ``epilogue`` (a hashable core.epilogue.Epilogue) executes inside the
    final writeback: ``y = act(acc + bias) + residual`` cast to
    ``epilogue.out_dtype``.  With the identity epilogue the output is
    bit-identical to the legacy path.

    ``interpret=None`` auto-detects: compiled on TPU, interpreter
    elsewhere (CPU/GPU have no Mosaic lowering for this kernel);
    interpret=True on a TPU raises (kernels.mode).

    Caller (ops.py) guarantees: m % tm == 0, kc % tj == 0, b % tb == 0,
    tj*d % scale_block == 0, bias (m, 1) / residual (m, b) pre-padded.
    Compiled on TPU, tm must also be a multiple of 128 (or all of m) and
    tb a multiple of 8 (or all of b): the kernel puts batch rows on
    sublanes and output features on lanes.
    """
    interpret = resolve_interpret(interpret)
    ep = epilogue or Epilogue()
    m, kc = idx.shape
    k, b = x.shape
    assert k == kc * d, (k, kc, d)
    if tj is None:
        tj = scale_block // d
    assert (tj * d) % scale_block == 0, "factored-scale tiling (§3.3)"
    assert m % tm == 0 and kc % tj == 0 and b % tb == 0, (m, kc, b, tm, tj, tb)
    assert tm <= LANES or tm % LANES == 0, tm
    nj = kc // tj
    sj = tj * d // scale_block
    acc = jnp.dtype(acc_dtype)
    nlut = lut_width(d)
    basis = lut_mod.tuple_basis(d, dtype=acc_dtype, codebook=codebook).T
    basis = jnp.pad(basis, ((0, 0), (0, nlut - basis.shape[1])))  # (d, N)
    out_dtype = jnp.dtype(ep.out_dtype) if ep.out_dtype else acc
    # kernel layout: batch on sublanes, output features on lanes, and the
    # chunk axis leading so every per-chunk access is a dynamic
    # leading/sublane index (the tiled minor dims stay static)
    idx3 = idx.T.reshape(nj, tj, m)
    sc3 = scales.T.reshape(nj, sj, m)
    x3 = x.reshape(kc, d, b).transpose(0, 2, 1)  # (kc, b, d)
    lut_scratch = pltpu.VMEM((tj, tb, nlut), acc)
    # double-buffered input blocks (x's d lanes pad to a full vreg row)
    blocks = 2 * (tj * tm * 4 + 8 * tm * 4 + tj * tb * LANES * 4
                  + 8 * nlut * 4)
    lut_bytes = tj * tb * nlut * acc.itemsize

    if not acc_in_vmem:
        assert ep.is_identity, \
            "the legacy path has no fused epilogue (ops.py applies it unfused)"
        grid = (b // tb, m // tm, nj)
        kern = functools.partial(
            _kernel_legacy, d=d, tm=tm, tj=tj, scale_block=scale_block,
            acc_dtype=acc)
        return pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, tj, tm), lambda ib, im, ij: (ij, 0, im)),
                pl.BlockSpec((tj, tb, d), lambda ib, im, ij: (ij, ib, 0)),
                pl.BlockSpec((d, nlut), lambda ib, im, ij: (0, 0)),
                pl.BlockSpec((None, sj, tm), lambda ib, im, ij: (ij, 0, im)),
            ],
            out_specs=pl.BlockSpec((tb, tm), lambda ib, im, ij: (ib, im)),
            out_shape=jax.ShapeDtypeStruct((b, m), acc),
            scratch_shapes=[lut_scratch],
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
                blocks + lut_bytes + 2 * tb * tm * 4)),
            interpret=interpret,
        )(idx3, x3, basis, sc3).T

    has_bias, has_res = ep.bias, ep.residual
    grid = (b // tb, nj, m // tm)
    # the y stripe and the epilogue operands ignore ij/im in their index
    # maps -> the blocks stay VMEM-resident for a whole b-stripe and are
    # fetched/written exactly once per (b-stripe)
    in_specs = [
        pl.BlockSpec((None, tj, tm), lambda ib, ij, im: (ij, 0, im)),  # idx
        pl.BlockSpec((tj, tb, d), lambda ib, ij, im: (ij, ib, 0)),     # x
        pl.BlockSpec((d, nlut), lambda ib, ij, im: (0, 0)),            # basis
        pl.BlockSpec((None, sj, tm), lambda ib, ij, im: (ij, 0, im)),  # scales
    ]
    operands = [idx3, x3, basis, sc3]
    stripes = tb * m * (acc.itemsize + 2 * out_dtype.itemsize)
    if has_bias:
        assert bias is not None and bias.shape == (m, 1), (m, bias)
        in_specs.append(pl.BlockSpec((1, m), lambda ib, ij, im: (0, 0)))
        operands.append(bias.T)
        stripes += 2 * 8 * m * 4
    if has_res:
        assert residual is not None and residual.shape == (m, b), \
            (m, b, residual)
        in_specs.append(pl.BlockSpec((tb, m), lambda ib, ij, im: (ib, 0)))
        operands.append(residual.T)
        stripes += 2 * tb * m * residual.dtype.itemsize
    kern = functools.partial(
        _kernel_fused, d=d, tm=tm, tj=tj, scale_block=scale_block,
        acc_dtype=acc, nj=nj, epilogue=ep, has_bias=has_bias,
        has_res=has_res)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, m), lambda ib, ij, im: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m), out_dtype),
        scratch_shapes=[
            lut_scratch,                      # LUT tile
            pltpu.VMEM((tb, m), acc),         # acc stripe
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(blocks + lut_bytes + stripes)),
        interpret=interpret,
    )(*operands).T

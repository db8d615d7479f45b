"""msGeMM-mode linears on the MXU: the stored 4-bit codes contracted
against the activations, with the §3.3 scales kept factored per block.

The LUT kernel (kernels/msgemm.py) gathers from a 16^d-entry table on the
vector unit; the v5e gathers within one 128-lane vreg only, so at d=3
every chunk costs 32 lane gathers and a tree of selects.  This kernel
computes the same sum on the matrix unit instead::

    y[t, n] = sum_q s[n, q] * sum_{k in block q} x[t, k] * b(c[n, k])

* **codes as stored**: ``idx`` (m, kc) packs the codes of inputs
  ``d*j .. d*j+d-1`` into one int32, big-endian nibbles
  (core/packing.py).  A shift left and an arithmetic shift right give
  the signed value b(c) of each nibble (§3.1) as d planes, which meet
  the d de-interleaved planes of x.  Every product is exact: b(c) is a
  small integer, exact in bf16.
* **scales factored**: the x side is expanded block-diagonally,
  ``E[(q, t), j] = x[t, j] * [chunk j in block q]``, so one MXU dot per
  128 chunks gives every block's partial sum ``P[(q, t), n]`` in f32.
  Each is multiplied by its f32 scale and accumulated in f32 on the
  vector unit: no scale is rounded into a bf16 weight.  E depends only
  on (batch tile, k tile), so it is built once, on the first m step,
  and reused over every m tile.
* **no weight-sized work outside**: ``idx`` and ``scales`` are read
  as they are stored.  The k grid steps over ``KT = 128 * cpb`` chunks
  (cpb chunks per scale block), which is one 128-lane tile of scale
  blocks; the last k and m tiles may run past the arrays' ends, and
  what they read there is masked (scales) or meets zero activations
  (codes, which decode to finite values whatever the bits).

Grid = (batch tiles, m groups, k tiles, m tiles), m innermost; the
output stripe (tb, one m group) stays in VMEM across the k reduction and
is written once.  A group is all of m unless the stripe would outgrow
``STRIPE_BUDGET`` (a vocab-sized head prefilled at 128 rows): then m is
split into groups that fit, and E is rebuilt once per group.
bf16 activations go to the MXU as they are; any other dtype runs the
dot in f32 at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import Epilogue
from repro.kernels.mode import resolve_interpret

LANES = 128  # chunks per MXU contraction step, and scale blocks per k tile
STRIPE_BUDGET = 32 << 20  # VMEM for one group's acc, out and residual


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _sub_blocks(cpb: int) -> list[list[tuple[int, int, int]]]:
    """For each 128-chunk sub-tile of a k tile, its scale blocks as
    (block within the k tile, first lane, end lane)."""
    out = []
    for s in range(cpb):
        lo_c, hi_c = s * LANES, (s + 1) * LANES
        blocks = []
        for q in range(lo_c // cpb, (hi_c - 1) // cpb + 1):
            blocks.append((q, max(q * cpb, lo_c) - lo_c,
                           min((q + 1) * cpb, hi_c) - lo_c))
        out.append(blocks)
    return out


def _kernel(idx_ref, x_ref, s_ref, *rest, d: int, cpb: int, nq: int,
            tb: int, tm: int, nk: int, nblk: int, kc: int, idx_t: bool,
            cdt, precision, epilogue: Epilogue):
    refs = list(rest)
    bias_ref = refs.pop(0) if epilogue.bias else None
    res_ref = refs.pop(0) if epilogue.residual else None
    y_ref, e_ref, acc_ref = refs
    ik, im = pl.program_id(2), pl.program_id(3)
    subs = _sub_blocks(cpb)
    last = kc - (nk - 1) * cpb * LANES  # chunks in the last k tile
    # sub-tiles that hold codes in some k tile (all but the tail of a
    # lone k tile)
    live = [s for s in range(cpb) if nk > 1 or s * LANES < last]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    # ---- E for every sub-tile of this k tile: once per m group ----------
    @pl.when(im == 0)
    def _expand():
        for s in live:
            blocks = subs[s]
            planes = []
            for r in range(d):
                xs = x_ref[r, :, s * LANES:(s + 1) * LANES].astype(
                    jnp.float32)                              # (tb, 128)
                rows = [jnp.where((lane >= lo) & (lane < hi), xs, 0.0)
                        for _, lo, hi in blocks]
                rows += [jnp.zeros_like(xs)] * (nq - len(rows))
                planes.append(jnp.concatenate(rows, axis=0))  # (nq*tb, 128)
            e_ref[s] = jnp.concatenate(planes, axis=1).astype(cdt)

    # scales of this (k, m) tile, one block per sublane; the k tile may
    # run past the last block, whose rows hold whatever the buffer held
    row = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0)
    st = jnp.where(row < nblk - ik * LANES, s_ref[...], 0.0)  # (128, tm)
    cols = pl.ds(pl.multiple_of(im * tm, tm), tm)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:, cols] = jnp.zeros((tb, tm), jnp.float32)

    def sub_tile(s, blocks):
        chunks = slice(s * LANES, (s + 1) * LANES)
        # b(c) of each nibble (§3.1): shift it to the top, sign-extend
        codes = idx_ref[chunks, :] if idx_t else idx_ref[:, chunks]
        v = jnp.concatenate(
            [(codes << (32 - 4 * (d - r))) >> 28 for r in range(d)],
            axis=0 if idx_t else 1).astype(jnp.float32).astype(cdt)
        p = jax.lax.dot_general(
            e_ref[s], v, (((1,), (0 if idx_t else 1,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)                 # (nq*tb, tm)
        part = acc_ref[:, cols]
        for i, (q, _, _) in enumerate(blocks):
            part = part + p[i * tb:(i + 1) * tb] * st[q:q + 1]
        acc_ref[:, cols] = part

    for s in live:
        if s * LANES < last:
            sub_tile(s, subs[s])
        else:  # past the codes in the last k tile: skip the dead work
            pl.when(ik < nk - 1)(functools.partial(sub_tile, s, subs[s]))

    # the epilogue on the f32 accumulator, then the one rounding to the
    # output dtype (core.epilogue's op order)
    @pl.when(ik == nk - 1)
    def _writeback():
        total = acc_ref[:, cols]
        if bias_ref is not None:
            total = total + bias_ref[:, cols]
        total = epilogue.act_fn()(total)
        if res_ref is not None:
            total = total + res_ref[:, cols].astype(jnp.float32)
        y_ref[:, cols] = total.astype(y_ref.dtype)


def m_groups(nm: int, tm: int, tb: int, stripe_bytes: int) -> tuple:
    """(groups, m tiles per group): the fewest groups of the ``nm``
    output tiles whose VMEM stripe, ``stripe_bytes`` per (row, output
    feature), stays within ``STRIPE_BUDGET``.  Only the last group may
    hold tiles past ``nm``; they repeat the last tile."""
    cap = max(1, STRIPE_BUDGET // (tb * tm * stripe_bytes))
    ng = -(-nm // cap)
    return ng, -(-nm // ng)


def stored_transposed(rows: int, cols: int) -> bool:
    """Whether a TPU keeps a 2-D 32-bit array of this shape column-major:
    its default layout puts the dimension that pads less to the (8, 128)
    tile on the lanes.  A transpose of such an array is a free bitcast
    there (and in the scanned layer stack the per-layer slice comes out
    the same way), so the kernel reads ``idx`` in whichever orientation
    the chip stores it, and the scales (whose block count rarely fills
    128 lanes) as blocks by rows."""
    return (_round_up(cols, 8) * _round_up(rows, LANES)
            < _round_up(rows, 8) * _round_up(cols, LANES))


@functools.partial(
    jax.jit,
    static_argnames=("d", "scale_block", "interpret", "epilogue"))
def msgemm_mxu(idx: jnp.ndarray, scales: jnp.ndarray, x: jnp.ndarray,
               bias: jnp.ndarray | None = None,
               residual: jnp.ndarray | None = None, *,
               d: int, scale_block: int, interpret: bool | None = None,
               epilogue: Epilogue | None = None) -> jnp.ndarray:
    """y (b, m) = epilogue(x (b, k) @ dequant(idx, scales).T), in
    ``epilogue.out_dtype`` or else x's dtype.

    ``idx`` (m, ceil(k/d)) int32 and ``scales`` (m, ceil(k/scale_block))
    are the stored arrays; they reach the kernel as they are, or
    transposed where the chip stores them column-major
    (:func:`stored_transposed`), which costs nothing there.
    ``scale_block`` must be a multiple of d so no scale block splits a
    chunk.  Only x (activation sized) is re-laid out here: its d planes,
    zero-padded to whole k tiles.  ``epilogue`` runs on the f32
    accumulator before the one store: ``act(acc + bias) + residual``
    with ``bias`` (m,) and ``residual`` (b, m)."""
    interpret = resolve_interpret(interpret)
    ep = epilogue or Epilogue()
    if scale_block % d:
        raise ValueError(f"msgemm_mxu needs scale_block % d == 0 "
                         f"(got scale_block={scale_block}, d={d})")
    m, kc = idx.shape
    nblk = scales.shape[1]
    b, k = x.shape
    assert kc == -(-k // d) and nblk == -(-k // scale_block), \
        (idx.shape, scales.shape, x.shape)
    cpb = scale_block // d
    kt = LANES * cpb  # chunks per k step: one 128-row tile of scales
    tm = min(512, _round_up(m, LANES))  # output features per m step
    tb = min(128, _round_up(b, 8))      # batch rows per stripe
    nk, nm = -(-kc // kt), -(-m // tm)
    bp = _round_up(b, tb)
    cdt = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    precision = (None if cdt == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    out_dtype = jnp.dtype(ep.out_dtype) if ep.out_dtype else x.dtype
    # the stripe: f32 acc, the double-buffered out block and residual
    stripe = 4 + 2 * out_dtype.itemsize
    if ep.residual:
        stripe += 2 * residual.dtype.itemsize
    ng, nmi = m_groups(nm, tm, tb, stripe)
    mg = nmi * tm           # output features per group
    mp = ng * mg

    def tile(ig, im):  # the m tile of group ig's step im, within the array
        return jnp.minimum(ig * nmi + im, nm - 1)

    # x planes: xp[r, t, j] = x[t, d*j + r], zero past k
    xp = jnp.pad(x.astype(cdt), ((0, bp - b), (0, nk * kt * d - k)))
    xp = xp.reshape(bp, nk * kt, d).transpose(2, 0, 1)
    nq = max(len(blocks) for blocks in _sub_blocks(cpb))
    nq += nq % 2  # whole bf16 sublane tiles of E
    idx_t = stored_transposed(m, kc)
    kern = functools.partial(
        _kernel, d=d, cpb=cpb, nq=nq, tb=tb, tm=tm, nk=nk, nblk=nblk,
        kc=kc, idx_t=idx_t, cdt=cdt, precision=precision, epilogue=ep)
    isz = jnp.dtype(cdt).itemsize
    vmem = (2 * tm * kt * 4 + 2 * tm * LANES * 4 + 2 * d * tb * kt * isz
            + cpb * nq * tb * d * LANES * isz + tb * mg * stripe
            + 4 * tm * LANES * d * 4)  # decoded planes in flight
    idx_spec = (
        pl.BlockSpec((kt, tm), lambda ib, ig, ik, im: (ik, tile(ig, im)))
        if idx_t else
        pl.BlockSpec((tm, kt), lambda ib, ig, ik, im: (tile(ig, im), ik)))
    in_specs = [
        idx_spec,
        pl.BlockSpec((d, tb, kt), lambda ib, ig, ik, im: (0, ib, ik)),  # x
        pl.BlockSpec((LANES, tm),                                # scales
                     lambda ib, ig, ik, im: (ik, tile(ig, im))),
    ]
    operands = [idx.T if idx_t else idx, xp, scales.T]
    # the epilogue's operands stay resident for a whole group stripe
    if ep.bias:
        assert bias is not None and bias.shape == (m,), (m, bias)
        in_specs.append(
            pl.BlockSpec((1, mg), lambda ib, ig, ik, im: (0, ig)))
        operands.append(jnp.pad(bias.astype(jnp.float32),
                                (0, mp - m))[None])
    if ep.residual:
        assert residual is not None and residual.shape == (b, m), \
            (b, m, residual)
        in_specs.append(
            pl.BlockSpec((tb, mg), lambda ib, ig, ik, im: (ib, ig)))
        operands.append(jnp.pad(residual, ((0, bp - b), (0, mp - m))))
    y = pl.pallas_call(
        kern,
        grid=(bp // tb, ng, nk, nmi),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tb, mg), lambda ib, ig, ik, im: (ib, ig)),
        out_shape=jax.ShapeDtypeStruct((bp, mp), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((cpb, nq * tb, d * LANES), cdt),  # E per sub-tile
            pltpu.VMEM((tb, mg), jnp.float32),           # acc stripe
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=int(min(max(32 << 20, 2 * vmem), 100 << 20))),
        interpret=interpret,
    )(*operands)
    return y[:b, :m]

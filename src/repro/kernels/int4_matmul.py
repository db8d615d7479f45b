"""Blocked int4 dequantize-then-matmul Pallas kernel — the *practical
current-TPU* baseline the paper's proposal competes against (DESIGN.md §2.C).

Weights are stored truly packed (2 codes/byte).  Per grid step the kernel
unpacks a (TM, TK) weight tile in VMEM with bit ops, applies the §3.3
row-block scales, and feeds the MXU with a dense (TM, TK)·(TK, TB) dot.
This is the standard int4 weight-only-quant GeMM shape used in production
TPU serving stacks.

Grid = (b_tiles, m_tiles, k_tiles), k innermost.  The default path
(``acc_in_vmem=True``) accumulates over k in a VMEM scratch buffer and
stores to HBM exactly once per output block, executing the fused epilogue
(bias/act/residual/cast — core.epilogue.Epilogue) on the accumulator just
before that single store.  ``acc_in_vmem=False`` keeps the pre-overhaul
``y_ref +=`` formulation as the microbench baseline.  Requires
tk % scale_block == 0 so each k tile covers whole scale blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import Epilogue
from repro.kernels.mode import resolve_interpret


def _dequant_dot(u8_ref, scale_ref, xe_ref, xo_ref, *, scale_block: int,
                 acc_dtype):
    """Unpack + §3.3 scales + MXU dots for the current (TM, TK) tile —
    shared by the fused and legacy kernels (bit-identical per k-step).

    Mosaic keeps lanes whole, so the nibble pairs are never interleaved
    back into one k axis: the high nibbles (even k) meet the even rows of
    x and the low nibbles (odd k) the odd rows, in two dots.  The per-block
    scales (SK, TM) expand to per-column scales by an exact 0/1 matmul."""
    packed = u8_ref[...].astype(jnp.int32)  # (TM, TK//2), two codes/byte
    tk2 = packed.shape[1]
    q = scale_ref[...].astype(acc_dtype)  # (TK // scale_block, TM)
    blk = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], tk2), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], tk2), 1)
    out = None
    for shift, odd, x_ref in ((4, 0, xe_ref), (0, 1, xo_ref)):
        c = (packed >> shift) & 0xF
        vals = jnp.where(c <= 7, c, c - 16).astype(acc_dtype)  # b(), §3.1
        kk = 2 * col + odd  # k index of this nibble within the tile
        sel = ((kk >= blk * scale_block)
               & (kk < (blk + 1) * scale_block)).astype(acc_dtype)
        s = jax.lax.dot_general(
            q, sel, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=acc_dtype)  # (TM, TK//2)
        part = jax.lax.dot_general(
            x_ref[...].astype(acc_dtype), vals * s, (((1,), (1,)), ((), ())),
            preferred_element_type=acc_dtype)  # (TB, TM)
        out = part if out is None else out + part
    return out


def _kernel_fused(u8_ref, scale_ref, xe_ref, xo_ref, *rest, scale_block: int,
                  acc_dtype, nk: int, epilogue: Epilogue, has_bias: bool,
                  has_res: bool):
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    res_ref = refs.pop(0) if has_res else None
    y_ref, acc_ref = refs
    kstep = pl.program_id(2)
    part = _dequant_dot(u8_ref, scale_ref, xe_ref, xo_ref,
                        scale_block=scale_block, acc_dtype=acc_dtype)

    @pl.when(kstep == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(kstep > 0)
    def _accum():
        acc_ref[...] += part

    @pl.when(kstep == nk - 1)
    def _writeback():
        total = acc_ref[...]
        if has_bias:
            total = total + bias_ref[...].astype(acc_dtype)
        total = epilogue.act_fn()(total)
        if has_res:
            total = total + res_ref[...].astype(acc_dtype)
        y_ref[...] = total.astype(y_ref.dtype)


def _kernel_legacy(u8_ref, scale_ref, xe_ref, xo_ref, y_ref, *,
                   scale_block: int, acc_dtype):
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    part = _dequant_dot(u8_ref, scale_ref, xe_ref, xo_ref,
                        scale_block=scale_block, acc_dtype=acc_dtype)
    y_ref[...] += part.astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale_block", "tm", "tk", "tb", "interpret",
                     "acc_dtype", "acc_in_vmem", "epilogue"),
)
def int4_matmul_pallas(
    u8: jnp.ndarray,       # (m, k//2) packed codes
    scales: jnp.ndarray,   # (m, k // scale_block)
    x: jnp.ndarray,        # (k, b)
    bias: jnp.ndarray | None = None,      # (m, 1) when epilogue.bias
    residual: jnp.ndarray | None = None,  # (m, b) when epilogue.residual
    *,
    scale_block: int,
    tm: int = 256,
    tk: int | None = None,
    tb: int = 128,
    interpret: bool | None = None,
    acc_dtype=jnp.float32,
    acc_in_vmem: bool = True,
    epilogue: Epilogue | None = None,
) -> jnp.ndarray:
    """y (m, b) = epilogue(dequant(u8) @ x).

    Compiled on TPU the tiles must follow the (8, 128) tiling: tm a
    multiple of 128 (or all of m), tk // 2 a multiple of 128 (or all of
    k // 2), tk // scale_block a multiple of 8 (or all of the scale
    blocks), and tb a multiple of 8 (or all of b) — ops.int4_tiles picks
    such tiles.  The kernel puts batch rows on sublanes and output
    features on lanes; the layout changes happen here, outside it."""
    interpret = resolve_interpret(interpret)
    ep = epilogue or Epilogue()
    m, k2 = u8.shape
    k, b = x.shape
    assert k == k2 * 2
    if tk is None:
        tk = scale_block * max(1, 256 // scale_block)
    assert tk % scale_block == 0 and tk % 2 == 0
    assert m % tm == 0 and k % tk == 0 and b % tb == 0, (m, k, b, tm, tk, tb)
    sk, tk2 = tk // scale_block, tk // 2
    acc = jnp.dtype(acc_dtype)
    out_dtype = jnp.dtype(ep.out_dtype) if ep.out_dtype else acc
    xr = x.T.reshape(b, k2, 2)
    operands = [u8, scales.T, xr[..., 0], xr[..., 1]]  # x's even/odd k rows
    in_specs = [
        pl.BlockSpec((tm, tk2), lambda ib, im, ik: (im, ik)),
        pl.BlockSpec((sk, tm), lambda ib, im, ik: (ik, im)),
        pl.BlockSpec((tb, tk2), lambda ib, im, ik: (ib, ik)),
        pl.BlockSpec((tb, tk2), lambda ib, im, ik: (ib, ik)),
    ]
    out_spec = pl.BlockSpec((tb, tm), lambda ib, im, ik: (ib, im))
    grid = (b // tb, m // tm, k // tk)
    # the unpacked f32 tiles (codes, scales, weights per nibble half)
    # dominate VMEM; ask for room beyond the default scoped limit
    params = pltpu.CompilerParams(vmem_limit_bytes=int(min(
        100 << 20, max(32 << 20, 16 * tm * tk2 * 4))))
    if not acc_in_vmem:
        assert ep.is_identity, \
            "the legacy path has no fused epilogue (ops.py applies it unfused)"
        kern = functools.partial(
            _kernel_legacy, scale_block=scale_block, acc_dtype=acc)
        return pl.pallas_call(
            kern,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((b, m), acc),
            compiler_params=params,
            interpret=interpret,
        )(*operands).T

    has_bias, has_res = ep.bias, ep.residual
    if has_bias:
        assert bias is not None and bias.shape == (m, 1), (m, bias)
        in_specs.append(pl.BlockSpec((1, tm), lambda ib, im, ik: (0, im)))
        operands.append(bias.T)
    if has_res:
        assert residual is not None and residual.shape == (m, b), \
            (m, b, residual)
        in_specs.append(pl.BlockSpec((tb, tm), lambda ib, im, ik: (ib, im)))
        operands.append(residual.T)
    kern = functools.partial(
        _kernel_fused, scale_block=scale_block, acc_dtype=acc,
        nk=k // tk, epilogue=ep, has_bias=has_bias, has_res=has_res)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, m), out_dtype),
        scratch_shapes=[pltpu.VMEM((tb, tm), acc)],
        compiler_params=params,
        interpret=interpret,
    )(*operands).T

"""jit'd public wrappers around the Pallas kernels.

Handles tile-size selection (VMEM budgeting), padding to tile multiples,
backend detection (interpret=True off-TPU), epilogue padding/layout, and
the quantized-param plumbing used by the dispatch backends.

VMEM budget math (README §Kernel performance): the fused msgemm kernel
holds, per core,

* the LUT tile           16^d · TJ · TB · 4 B   (≤ ``VMEM_BUDGET``)
* the f32 acc stripe     mp · TB · 4 B          (≤ ``ACC_BUDGET`` together
* the resident out block mp · TB · out_bytes     with the out stripe)

plus the small idx/x/scale blocks.  ``_pick_tiles`` first sizes TB to the
batch (decode: TB == round_up(b, 8), *not* padded to 128 — small-batch
decode shapes get narrow stripes and the freed LUT budget lets TJ grow),
shrinks TB if the acc stripe would blow ``ACC_BUDGET``, then grows TJ
while the LUT tile stays within ``VMEM_BUDGET``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.epilogue import Epilogue
from repro.kernels import int4_matmul as _i4
from repro.kernels import msgemm as _ms
from repro.kernels import msgemm_mxu as _mx
from repro.kernels.mode import resolve_interpret

VMEM_BUDGET = 8 * 1024 * 1024  # conservative per-step LUT budget (bytes)
ACC_BUDGET = 4 * 1024 * 1024   # acc + out stripe budget (bytes)
DECODE_BATCH = 32  # b <= this is treated as a decode shape (tall-skinny)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _pick_tiles(m: int, kc: int, b: int, d: int, scale_block: int,
                out_bytes: int = 4, residual: bool = False):
    """Pick (tm, tj, tb) fitting the LUT tile in the VMEM budget.

    tj must stay a multiple of scale_block // d (factored-scale tiling,
    §3.3).  Growth doubles tj only while the doubled tile still divides
    kc evenly AND fits within kc: the old ``kc % (tj*2) == 0 or
    kc > tj*2`` condition let non-power-of-two kc overshoot into a
    non-divisor tile, silently padding dead columns the kernel then
    gathered for nothing (e.g. kc=86, cpb=12 grew tj to 96 -> 10 dead
    chunk columns per row).

    The kernel puts output features on lanes, so tm is a multiple of 128
    (or all of a sub-128 m).  tb is sized to the actual batch (decode:
    round_up(b, 8), never padded to 128) and shrunk while the fused
    kernel's VMEM acc+out stripe (mp·tb·8 B) exceeds ACC_BUDGET, or
    while even the narrowest LUT tile (one scale block of chunks) would
    not fit VMEM_BUDGET.  Decode shapes (b <= DECODE_BATCH) take tm up
    to 512: more rows per gather step against the same resident LUT
    tile.
    """
    n = _ms.lut_width(d)
    cpb = scale_block // d
    tb = min(128, _round_up(b, 8))
    tm_cap = 512 if b <= DECODE_BATCH else 256
    tm = (min(tm_cap, _round_up(m, 128)) if m > _ms.LANES
          else _round_up(m, 8))
    # acc stripe (f32 acc + f32 out ~ 8 B/elem) must stay within budget —
    # but only shrink tb when some tb can actually satisfy it; if even the
    # tb floor cannot (vocab-sized m), the shape runs the legacy kernel
    # (no stripe) and a batch-wide tb is the right choice there
    # out_bytes/residual let ops.msgemm shrink for the stripes the fused
    # call will actually keep resident (the planner, which cannot know
    # the per-call epilogue, budgets the plain acc+out stripes)
    if acc_stripe_fits(m, tm, 8, out_bytes, residual):
        while tb > 8 and not acc_stripe_fits(m, tm, tb, out_bytes, residual):
            tb = max(8, _round_up(tb // 2, 8))
    while tb > 8 and n * cpb * tb * 4 > VMEM_BUDGET:
        tb = max(8, _round_up(tb // 2, 8))
    tj = cpb
    # grow tj while the LUT tile (n * tj * tb * 4B) stays in budget and
    # the doubled tile still tiles kc exactly (tj <= kc, kc % tj == 0)
    while (n * tj * 2 * tb * 4 <= VMEM_BUDGET
           and tj * 2 <= kc and kc % (tj * 2) == 0):
        tj *= 2
    return tm, tj, tb


def acc_stripe_fits(m: int, tm: int, tb: int, out_bytes: int = 4,
                    residual: bool = False) -> bool:
    """Can the fused kernel's VMEM-resident stripes for this shape stay
    within (2x of) ACC_BUDGET?  Counts the f32 acc scratch, the resident
    out block, and — when a residual is fused — the residual operand's
    resident (mp, tb) block.  Beyond that — e.g. a vocab-sized lm-head m
    at the tb floor — ops.msgemm falls back to the legacy j-innermost
    accumulation (no stripes) rather than asking Mosaic for an
    unbuildable allocation."""
    mp = _round_up(m, tm)
    per_elem = 4 + out_bytes + (4 if residual else 0)
    return mp * tb * per_elem <= 2 * ACC_BUDGET


def msgemm_tiles(m: int, kc: int, b: int, d: int, scale_block: int):
    """Public heuristic tile choice for the fused msgemm kernel —
    (tm, tj, tb) for (m rows, kc packed chunks, b batch cols).  The
    dispatch planner records these into ExecPlans; the autotuner seeds
    its candidate grid from them."""
    return _pick_tiles(m, kc, b, d, scale_block)


def int4_tiles(m: int, k: int, b: int, scale_block: int):
    """Heuristic (tm, tk, tb) for the blocked int4 dequant kernel, on the
    TPU tiling: tk // 2 packed bytes fill whole 128-lane rows and the
    tk // scale_block scale rows whole 8-row sublane groups — unless one
    tile covers all of k, which any block may."""
    tk = min(math.lcm(256, 8 * scale_block),
             _round_up(k, math.lcm(2, scale_block)))
    tm = (min(256, _round_up(m, 128)) if m > _ms.LANES
          else _round_up(m, 8))
    tb = min(128, _round_up(b, 8))
    return tm, tk, tb


def _epilogue_cols(y: jnp.ndarray, ep: Epilogue | None,
                   bias: jnp.ndarray | None,
                   residual: jnp.ndarray | None) -> jnp.ndarray:
    """Unfused epilogue in the kernels' (m, b) column layout — the exact
    op order of the fused writeback, for acc_in_vmem=False / jnp paths."""
    if ep is None or ep.is_identity:
        return y
    if ep.bias:
        y = y + bias[:, None].astype(y.dtype)
    y = ep.act_fn()(y)
    if ep.residual:
        y = y + residual.astype(y.dtype)
    if ep.out_dtype is not None:
        y = y.astype(ep.out_dtype)
    return y


def msgemm(codes: jnp.ndarray, x: jnp.ndarray, d: int, *,
           scales: jnp.ndarray | None = None, scale_block: int = 36,
           codebook: jnp.ndarray | None = None,
           interpret: bool | None = None,
           tm: int | None = None, tj: int | None = None,
           tb: int | None = None,
           acc_dtype=jnp.float32, acc_in_vmem: bool = True,
           epilogue: Epilogue | None = None,
           bias: jnp.ndarray | None = None,
           residual: jnp.ndarray | None = None) -> jnp.ndarray:
    """y (m, b) = epilogue(dequant(codes (m,k)) @ x (k, b)) via the kernel.

    Pads every dim to tile multiples; zero code rows/cols contribute 0
    (codebooks pin value 0 at code 0, so this holds for learned tables
    too).  ``codebook``: optional (16,) non-uniform value table.

    ``tm/tj/tb``: explicit tile sizes from a dispatch ExecPlan (the
    autotuner's winners); None falls back to the heuristic, which is only
    computed when at least one tile is missing (an ExecPlan that names
    all three skips the pick entirely).  tj must be a multiple of
    scale_block // d (§3.3 factored-scale tiling).

    ``epilogue``: a core.epilogue.Epilogue fused into the kernel's final
    VMEM writeback (``acc_in_vmem=True``); the legacy path
    (``acc_in_vmem=False``) applies it unfused after the kernel, same op
    order.  ``bias`` is (m,), ``residual`` is (m, b) column layout.
    """
    ep = epilogue or Epilogue()
    m, k = codes.shape
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
        if residual is not None and residual.ndim == 1:
            residual = residual[:, None]
    b = x.shape[1]
    if scales is None:
        scales = jnp.ones((m, -(-k // scale_block)), jnp.float32)
    idx = packing.pack_indices(codes, d)
    kc = idx.shape[1]

    out_bytes = jnp.dtype(ep.out_dtype or jnp.float32).itemsize
    if tm is None or tj is None or tb is None:
        htm, htj, htb = _pick_tiles(
            m, kc, b, d, scale_block, out_bytes,
            residual=acc_in_vmem and ep.residual)
        tm, tj, tb = tm or htm, tj or htj, tb or htb
    if acc_in_vmem and not acc_stripe_fits(
            m, tm, tb, out_bytes, residual=ep.residual):
        acc_in_vmem = False  # stripes would blow VMEM — legacy accumulation
    mp, kcp, bp = _round_up(m, tm), _round_up(kc, tj), _round_up(b, tb)
    sj = kcp * d // scale_block
    idx_p = jnp.pad(idx, ((0, mp - m), (0, kcp - kc)))
    x_p = jnp.pad(x.astype(jnp.float32),
                  ((0, kcp * d - x.shape[0]), (0, bp - b)))
    sc_p = jnp.pad(scales.astype(jnp.float32),
                   ((0, mp - m), (0, sj - scales.shape[1])))
    interpret = resolve_interpret(interpret)

    fuse = acc_in_vmem and not ep.is_identity
    bias_p = res_p = None
    if fuse:
        if ep.bias:
            bias_p = jnp.pad(bias.astype(jnp.float32)[:, None],
                             ((0, mp - m), (0, 0)))
        if ep.residual:
            res_p = jnp.pad(residual.astype(jnp.float32),
                            ((0, mp - m), (0, bp - b)))
    y = _ms.msgemm_pallas(
        idx_p, x_p, sc_p, codebook, bias_p, res_p, d=d,
        scale_block=scale_block, tm=tm, tj=tj, tb=tb, interpret=interpret,
        acc_dtype=acc_dtype, acc_in_vmem=acc_in_vmem,
        epilogue=ep if fuse else None)
    y = y[:m, :b]
    if not fuse:
        y = _epilogue_cols(y, ep, bias, residual)
    return y[:, 0] if squeeze else y


def msgemm_mxu(idx: jnp.ndarray, scales: jnp.ndarray, x: jnp.ndarray,
               d: int, *, scale_block: int, interpret: bool | None = None,
               epilogue: Epilogue | None = None,
               bias: jnp.ndarray | None = None,
               residual: jnp.ndarray | None = None) -> jnp.ndarray:
    """y (b, m) = epilogue(x (b, k) @ dequant(idx (m, k/d), scales).T) on
    the MXU (kernels/msgemm_mxu.py), in row layout: ``bias`` (m,),
    ``residual`` (b, m).  ``idx`` and ``scales`` are the stored arrays
    and reach the kernel untouched; the kernel sizes its own tiles."""
    return _mx.msgemm_mxu(idx, scales, x, bias, residual, d=d,
                          scale_block=scale_block, interpret=interpret,
                          epilogue=epilogue)


def int4_matmul(u8: jnp.ndarray, scales: jnp.ndarray, x: jnp.ndarray, *,
                scale_block: int = 32, interpret: bool | None = None,
                tm: int | None = None, tk: int | None = None,
                tb: int | None = None,
                acc_dtype=jnp.float32, acc_in_vmem: bool = True,
                epilogue: Epilogue | None = None,
                bias: jnp.ndarray | None = None,
                residual: jnp.ndarray | None = None) -> jnp.ndarray:
    """y = epilogue(dequant(packed u8 (m, k/2)) @ x (k, b)) via the kernel.

    ``tm/tk/tb``: explicit tiles from a dispatch ExecPlan; the heuristic
    only runs when one is missing (tk must be even and % scale_block ==
    0).  Epilogue semantics match :func:`msgemm`."""
    ep = epilogue or Epilogue()
    m = u8.shape[0]
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
        if residual is not None and residual.ndim == 1:
            residual = residual[:, None]
    k, b = x.shape
    if tm is None or tk is None or tb is None:
        htm, htk, htb = int4_tiles(m, k, b, scale_block)
        tm, tk, tb = tm or htm, tk or htk, tb or htb
    mp, kp, bp = _round_up(m, tm), _round_up(k, tk), _round_up(b, tb)
    u8_p = jnp.pad(u8, ((0, mp - m), (0, kp // 2 - u8.shape[1])))
    sc_p = jnp.pad(scales.astype(jnp.float32),
                   ((0, mp - m), (0, kp // scale_block - scales.shape[1])))
    x_p = jnp.pad(x.astype(jnp.float32), ((0, kp - k), (0, bp - b)))
    interpret = resolve_interpret(interpret)

    fuse = acc_in_vmem and not ep.is_identity
    bias_p = res_p = None
    if fuse:
        if ep.bias:
            bias_p = jnp.pad(bias.astype(jnp.float32)[:, None],
                             ((0, mp - m), (0, 0)))
        if ep.residual:
            res_p = jnp.pad(residual.astype(jnp.float32),
                            ((0, mp - m), (0, bp - b)))
    y = _i4.int4_matmul_pallas(
        u8_p, sc_p, x_p, bias_p, res_p, scale_block=scale_block,
        tm=tm, tk=tk, tb=tb, interpret=interpret, acc_dtype=acc_dtype,
        acc_in_vmem=acc_in_vmem, epilogue=ep if fuse else None)
    y = y[:m, :b]
    if not fuse:
        y = _epilogue_cols(y, ep, bias, residual)
    return y[:, 0] if squeeze else y


def k_chunk_params(params: dict, *, k: int, chunks: int, d: int = 1,
                   scale_block: int = 1) -> list[dict]:
    """Split a quantized linear's packed params into ``chunks``
    contraction slices — the chunked-consume entry point for pipelined
    sharded execution (dispatch.shard).

    Every packed leaf stores the contraction dim in columns at a
    leaf-specific density: ``w`` (dense) has k columns, ``idx`` k/d
    packed tuples, ``u8`` k/2 nibble pairs, ``scales`` k/scale_block
    blocks.  Chunk c of leaf L is columns [c*w_L, (c+1)*w_L) where
    ``w_L = cols_L // chunks``; ``codebook`` (and any unrecognized leaf)
    is the 16-entry value table — replicated into every chunk.  Feeding
    chunk c's slice dict plus the matching k-slice of x back through the
    same backend reproduces that chunk's partial product exactly: the
    LUT produce runs per chunk against 1/chunks of the consume columns,
    which is the granularity the collective ring overlaps.

    Requires k to be chunk-aligned at every density (the dispatch layer
    guarantees this by construction: shard_spec_for only admits
    pipeline_chunks where k_chunk stays scale_block/d/nibble aligned).
    """
    chunks = max(int(chunks), 1)
    if chunks == 1:
        return [dict(params)]
    cols = {"w": k, "idx": k // max(int(d), 1), "u8": k // 2,
            "scales": k // max(int(scale_block), 1)}
    out = []
    for c in range(chunks):
        sl = {}
        for name, leaf in params.items():
            width = cols.get(name)
            if width is None:  # codebook etc.: no contraction dim
                sl[name] = leaf
                continue
            if width % chunks:
                raise ValueError(
                    f"k_chunk_params: leaf {name!r} has {width} "
                    f"contraction columns, not divisible by {chunks}")
            w = width // chunks
            sl[name] = jax.lax.slice_in_dim(leaf, c * w, (c + 1) * w,
                                            axis=1)
        out.append(sl)
    return out


def profile_gemm(kind: str, m: int, k: int, b: int, *, d: int = 3,
                 scale_block: int | None = None, reps: int = 3,
                 interpret: bool | None = None, seed: int = 0) -> dict:
    """Time one kernel invocation on synthetic data and annotate it with
    the analytic cost model (obs.costs): per-shape wall time, the
    produce-vs-consume op split, bytes moved, and the achieved-vs-
    roofline fraction for this process's device.

    ``kind``: 'msgemm' | 'int4'.  Times best-of-``reps`` of one jitted
    call (compile excluded), records the measurement into the
    ``kernel_profile_s`` registry histogram, and returns the annotated
    row — what kernel_microbench embeds in BENCH_kernels.json.
    """
    import time as _time

    import numpy as np

    from repro import obs

    sb = scale_block if scale_block is not None else 12 * d
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((k, b)), jnp.float32)
    sc = jnp.asarray(np.abs(rng.standard_normal((m, -(-k // sb)))) + 0.1,
                     jnp.float32)
    if kind == "msgemm":
        codes = jnp.asarray(rng.integers(0, 16, size=(m, k)), jnp.uint8)
        fn = jax.jit(lambda: msgemm(codes, x, d, scales=sc, scale_block=sb,
                                    interpret=interpret))
        quant = "msgemm"
    elif kind == "int4":
        u8 = jnp.asarray(
            packing.pack_storage(rng.integers(0, 16, size=(m, k))
                                 .astype(np.uint8)))
        fn = jax.jit(lambda: int4_matmul(u8, sc, x, scale_block=sb,
                                         interpret=interpret))
        quant = "int4_dequant"
    else:
        raise ValueError(f"kind={kind!r} must be 'msgemm' or 'int4'")

    jax.block_until_ready(fn())  # compile + warm
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, _time.perf_counter() - t0)

    row = obs.costs.annotate(best, m, k, b, quant=quant, d=d)
    row["kind"] = kind
    obs.registry().histogram(
        "kernel_profile_s", help="profiled kernel wall time",
        kind=kind, m=m, k=k, b=b).observe(best)
    return row


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    interpret=None):
    """Multi-head attention via the flash kernel.

    q (B, Sq, H, dh), k/v (B, Skv, Hk, dh) with H % Hk == 0.  GQA kv
    heads are NOT materialized: the kernel's k/v index maps divide the
    query-head grid index by the group size, so each kv head's (Skv, dh)
    block is fetched from HBM once per group instead of being expanded
    H//Hk-fold by ``jnp.repeat`` first.  Pads sequence dims to tile
    multiples (masked out)."""
    from repro.kernels import flash_attention as _fa

    B, Sq, H, dh = q.shape
    Skv, Hk = k.shape[1], k.shape[2]
    assert H % Hk == 0, (H, Hk)
    tq = min(128, _round_up(Sq, 8))
    tk = min(128, _round_up(Skv, 8))
    sqp, skp = _round_up(Sq, tq), _round_up(Skv, tk)
    qt = jnp.moveaxis(jnp.pad(q, ((0, 0), (0, sqp - Sq), (0, 0), (0, 0))),
                      2, 1)  # (B, H, Sqp, dh)
    kt = jnp.moveaxis(jnp.pad(k, ((0, 0), (0, skp - Skv), (0, 0), (0, 0))),
                      2, 1)  # (B, Hk, Skp, dh)
    vt = jnp.moveaxis(jnp.pad(v, ((0, 0), (0, skp - Skv), (0, 0), (0, 0))),
                      2, 1)
    # padded keys must never win the softmax: causal masking handles the
    # q-pad rows; padded kpos > any real qpos under causal; for
    # non-causal callers we require Skv % tk == 0 (asserted).
    if not causal:
        assert skp == Skv, "non-causal flash requires Skv % tile == 0"
    o = _fa.flash_attention_pallas(
        qt, kt, vt, causal=causal, window=window, softcap=softcap,
        tq=tq, tk=tk,
        interpret=resolve_interpret(interpret))
    return jnp.moveaxis(o, 1, 2)[:, :Sq]

"""Paged attention over the quantized KV block pool — dequantize in VMEM.

The serving-side analogue of kernels/msgemm.py's produce-once/consume-many
structure: the pool stores low-bit codes + scales in HBM (repro.kvq),
and the *kernel* reconstructs K/V values from the 16-entry table / int
grid inside VMEM right before the dot — the HBM-resident dequantized
copies that ``models/layers.attn_paged``'s jnp reference path
materializes via ``jnp.take`` never exist here.  Per step the kernel
reads the quantized bytes once; the f32 K/V blocks live only as
(block_size, Dh) VMEM tiles.

Block tables ride scalar prefetch (pltpu.PrefetchScalarGridSpec): the
grid is (B, H, blocks-per-view) and the kv-side index maps dereference
``block_tables[b, i]`` to DMA exactly the block each step consumes —
gather-by-block-table at the BlockSpec level, no flat-slot gather op.

Softmax is the standard flash online recurrence (kernels/
flash_attention.py) carried in VMEM scratch across the innermost grid
dim; masking is position-based (layers.view_mask semantics): view index
w holds logical position w, so kvpos = i*block_size + offset and a row
attends iff kvpos <= qpos (+ sliding window).  Scratch-padded blocks sit
at view positions > every qpos, so they mask to probability exactly 0;
view position 0 is always valid (every query attends to it), so the
running max is grounded before any fully-masked block is folded in.

Validated against the jnp gather+dequant reference in interpret mode
(tests/test_kvq.py); ``interpret=None`` auto-detects like the other
kernels (compiled on TPU, interpreter elsewhere).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret

NEG_INF = -1e30


def _decode_block(codes, scales, *, bits: int, codebook):
    """(bs, Dhp) u8 codes + (bs, 1) scales -> f32 values, all in VMEM.
    8-bit codes give (bs, Dh).  4-bit codes give (bs, 2*Dhp) in the
    kernel's nibble-major order — every high nibble (even head dims)
    first, then every low nibble (odd dims) — so no lane interleave is
    needed; the wrapper puts q and the output in the same order.  The
    codebook path reconstructs via a 16-way select chain on scalar
    constants — no in-kernel gather, no captured array consts."""
    c = codes.astype(jnp.int32)
    if bits == 8:
        vals = jnp.where(c < 128, c, c - 256).astype(jnp.float32)
    else:
        cc = jnp.concatenate([(c >> 4) & 0xF, c & 0xF], axis=1)
        if codebook is None:
            vals = jnp.where(cc <= 7, cc, cc - 16).astype(jnp.float32)
        else:
            # 16-way select chain over scalar constants: pallas_call
            # rejects captured *array* constants, and a chain of selects
            # on the code tile is VPU-trivial next to the dot
            vals = jnp.zeros(cc.shape, jnp.float32)
            for j, entry in enumerate(codebook):
                if entry:
                    vals = jnp.where(cc == j, jnp.float32(entry), vals)
    return vals * scales


def _head_scales(tile, kv_head):
    """Column ``kv_head`` of a (bs, Hk) scale tile, as (bs, 1) — a masked
    lane sum, since the head index is only known at run time."""
    if tile.shape[1] == 1:
        return tile.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(lane == kv_head, tile, 0.0).astype(jnp.float32),
                   axis=1, keepdims=True)


def _kernel(bt_ref, q_ref, pos_ref, kc_ref, ks_ref, vc_ref, vs_ref, o_ref,
            m_scr, l_scr, acc_scr, *, bits: int, codebook,
            block_size: int, nseq: int, group: int, window: int,
            softcap: float, scale: float):
    kv_head = pl.program_id(1) // group
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    qb = q_ref[...].astype(jnp.float32) * scale  # (C, W)
    k = _decode_block(kc_ref[...], _head_scales(ks_ref[...], kv_head),
                      bits=bits, codebook=codebook)
    v = _decode_block(vc_ref[...], _head_scales(vs_ref[...], kv_head),
                      bits=bits, codebook=codebook)
    s = jax.lax.dot_general(qb, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C, bs)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = pos_ref[...]  # (C, 1)
    kvpos = i * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    ok = kvpos <= qpos
    if window:
        ok &= kvpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m, l = m_scr[...], l_scr[...]  # (C, 1)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = corr * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = corr * acc_scr[...] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(i == nseq - 1)
    def _writeback():
        o_ref[...] = (acc_new / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bits", "codebook", "block_size", "window",
                              "softcap", "interpret"))
def paged_attention_pallas(q, k_codes, k_scales, v_codes, v_scales,
                           block_tables, positions, *, bits: int,
                           codebook=None, block_size: int,
                           window: int = 0, softcap: float = 0.0,
                           interpret: bool | None = None):
    """q (B, C, H, Dh); codes (nb, bs, Hk, Dhp) u8 + scales (nb, bs, Hk)
    f32 (the repro.kvq pool layout); block_tables (B, nseq) int32 block
    ids covering each row's view positions [0, nseq*bs); positions (B, C)
    int32 logical query positions.  Returns (B, C, H, Dh) in q.dtype.

    ``codebook`` is the spec's static 16-float tuple (None: int grid) —
    embedded as a compile-time constant, consumed from VMEM per block.

    The kernel sees one (query head, KV block) pair per step: q and the
    output as (C, Dh) tiles of a (B, H, C, Dh) transpose, the codes as
    (bs, Dhp) lane slices of the (nb, bs, Hk*Dhp) pool — compiled on TPU
    that needs Dhp % 128 == 0 unless Hk == 1 — and the scales as whole
    (bs, Hk) rows."""
    interpret = resolve_interpret(interpret)
    B, C, H, dh = q.shape
    nb, bs, hk, dhp = k_codes.shape
    assert bs == block_size, (bs, block_size)
    assert H % hk == 0, (H, hk)
    g = H // hk
    nseq = block_tables.shape[1]
    qt = jnp.swapaxes(q, 1, 2)  # (B, H, C, Dh)
    if bits == 4:  # nibble-major head dims: evens, then odds (padded)
        odd = qt[..., 1::2]
        qt = jnp.concatenate(
            [qt[..., 0::2], jnp.pad(odd, ((0, 0),) * 3
                                    + ((0, dhp - odd.shape[-1]),))], -1)
    width = qt.shape[-1]
    kern = functools.partial(
        _kernel, bits=bits, codebook=codebook, block_size=block_size,
        nseq=nseq, group=g, window=window, softcap=softcap, scale=dh**-0.5)
    code_spec = pl.BlockSpec((None, bs, dhp),
                             lambda b, h, i, bt: (bt[b, i], 0, h // g))
    scale_spec = pl.BlockSpec((None, bs, hk),
                              lambda b, h, i, bt: (bt[b, i], 0, 0))
    head_spec = pl.BlockSpec((None, None, C, width),
                             lambda b, h, i, bt: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nseq),
        in_specs=[
            head_spec,
            pl.BlockSpec((None, C, 1), lambda b, h, i, bt: (b, 0, 0)),
            code_spec, scale_spec, code_spec, scale_spec,
        ],
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((C, 1), jnp.float32),      # running max m
            pltpu.VMEM((C, 1), jnp.float32),      # running denom l
            pltpu.VMEM((C, width), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C, width), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), qt,
      jnp.asarray(positions, jnp.int32)[..., None],
      k_codes.reshape(nb, bs, hk * dhp), k_scales,
      v_codes.reshape(nb, bs, hk * dhp), v_scales)
    if bits == 4:  # back to interleaved head dims
        out = jnp.stack([out[..., :dhp], out[..., dhp:]], -1)
        out = out.reshape(B, H, C, 2 * dhp)[..., :dh]
    return jnp.swapaxes(out, 1, 2)

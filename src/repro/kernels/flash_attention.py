"""Row-blocked online-softmax attention Pallas kernel (flash-style).

The prefill-side hot spot: msGeMM covers the weight GeMMs, attention
covers the O(S²) sequence mixing — at prefill_32k the (S, S) logits must
never materialize (the jnp path q-chunks via lax.scan; this kernel is the
TPU-native tile version with the online-softmax rescaling, so the working
set is one (TQ, TK) tile + the (TQ, dh) accumulator in VMEM).

Grid: (batch, q heads, q blocks); the kernel loops over k blocks with a
fori_loop carrying (m, l, acc) — the standard flash recurrence:

    m' = max(m, rowmax(s));  p = exp(s - m');  c = exp(m - m')
    l' = c·l + rowsum(p);    acc' = c·acc + p @ v

GQA/MQA is resolved *in the index maps*: k/v keep their native
(B, Hk, Skv, dh) layout and the kv block index is ``h // (H // Hk)`` —
each kv head streams from HBM once per query-head group instead of being
expanded H//Hk-fold into a materialized ``jnp.repeat`` copy first (the
old wrapper's behavior, which multiplied both HBM footprint and
bandwidth by the group size).

Supports causal masking, sliding windows (gemma2 'local'), and logit
soft-capping.  Validated against ref.flash_attention_ref in interpret
mode (tests/test_kernels.py)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, tq: int, tk: int, causal: bool,
            window: int, softcap: float, scale: float):
    iq = pl.program_id(2)
    qb = q_ref[...][0, 0].astype(jnp.float32) * scale  # (TQ, dh)
    kfull = k_ref[...][0, 0]  # (Skv, dh) — this kv head's whole block
    vfull = v_ref[...][0, 0]
    S = kfull.shape[0]
    qpos = iq * tq + jax.lax.iota(jnp.int32, tq)

    def body(j, carry):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(kfull, j * tk, tk, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(vfull, j * tk, tk, axis=0)
        s = qb @ kb.astype(jnp.float32).T  # (TQ, TK)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * tk + jax.lax.iota(jnp.int32, tk)
        ok = jnp.ones((tq, tk), bool)
        if causal:
            ok &= kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = corr[:, None] * acc + p @ vb.astype(jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((tq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((tq,), jnp.float32)
    a0 = jnp.zeros((tq, q_ref.shape[-1]), jnp.float32)
    # causal: blocks beyond the diagonal contribute nothing; bound the loop
    nk = S // tk
    if causal:
        nk_eff = jnp.minimum(((iq + 1) * tq + tk - 1) // tk, nk)
    else:
        nk_eff = nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(
        o_ref.dtype)[None, None]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "tq", "tk",
                              "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, tq: int = 128,
                           tk: int = 128, interpret: bool | None = None):
    """q (B, H, Sq, dh), k/v (B, Hk, Skv, dh) -> (B, H, Sq, dh).

    H % Hk == 0; kv heads are shared across each group of H//Hk query
    heads through the index maps (no repeat/materialization).

    ``interpret=None`` auto-detects (compiled on TPU, interpreter off-TPU).
    Caller pads Sq % tq == 0 and Skv % tk == 0 (ops.py wrapper)."""
    interpret = resolve_interpret(interpret)
    B, H, Sq, dh = q.shape
    Hk, Skv = k.shape[1], k.shape[2]
    assert H % Hk == 0, (H, Hk)
    g = H // Hk  # query heads per kv head
    assert Sq % tq == 0 and Skv % tk == 0, (Sq, Skv, tq, tk)
    scale = dh**-0.5
    kern = functools.partial(_kernel, tq=tq, tk=tk, causal=causal,
                             window=window, softcap=softcap, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(B, H, Sq // tq),
        in_specs=[
            pl.BlockSpec((1, 1, tq, dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Skv, dh), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, Skv, dh), lambda b, h, i: (b, h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tq, dh), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        interpret=interpret,
    )(q, k, v)

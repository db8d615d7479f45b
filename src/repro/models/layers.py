"""Attention (GQA/MQA, RoPE, sliding-window, soft-cap, cross-attn) with
full-sequence and single-step-decode paths."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import common


# ---------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float
               ) -> jnp.ndarray:
    """x (B, S, H, Dh), positions (B, S) -> rotated x."""
    freqs = rope_freqs(x.shape[-1], theta)  # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, Dh/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention
def attn_init(key, cfg, *, cross: bool = False) -> dict:
    d, h, hk, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": common.linear_init(ks[0], d, h * dh, cfg, cfg.quant),
        "wk": common.linear_init(ks[1], d, hk * dh, cfg, cfg.quant),
        "wv": common.linear_init(ks[2], d, hk * dh, cfg, cfg.quant),
        "wo": common.linear_init(ks[3], h * dh, d, cfg, cfg.quant),
    }
    if cfg.qk_norm:
        p["q_norm"] = common.norm_init(dh, "rmsnorm")
        p["k_norm"] = common.norm_init(dh, "rmsnorm")
    return p


def _qkv(p, cfg, xq, xkv, positions_q, positions_kv, *, rope=True):
    B = xq.shape[0]
    h, hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = common.linear_apply(p["wq"], xq, cfg.quant, in_dim=cfg.d_model,
                            tag="wq")
    k = common.linear_apply(p["wk"], xkv, cfg.quant, in_dim=cfg.d_model,
                            tag="wk")
    v = common.linear_apply(p["wv"], xkv, cfg.quant, in_dim=cfg.d_model,
                            tag="wv")
    q = q.reshape(B, -1, h, dh)
    k = k.reshape(B, -1, hk, dh)
    v = v.reshape(B, -1, hk, dh)
    if cfg.qk_norm:
        q = common.norm_apply(p["q_norm"], q, "rmsnorm")
        k = common.norm_apply(p["k_norm"], k, "rmsnorm")
    if rope and cfg.use_rope:
        q = apply_rope(q, positions_q, cfg.rope_theta)
        k = apply_rope(k, positions_kv, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kvheads", "head_dim")
    v = constrain(v, "batch", "seq", "kvheads", "head_dim")
    return q, k, v


def _sdpa(cfg, q, k, v, mask) -> jnp.ndarray:
    """q (B,Sq,H,Dh), k/v (B,Skv,Hk,Dh), mask (B,1,Sq,Skv) bool or None."""
    B, Sq, h, dh = q.shape
    hk = k.shape[2]
    g = h // hk
    qg = q.reshape(B, Sq, hk, g, dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * dh**-0.5
    logits = common.softcap(logits, cfg.attn_logit_softcap)
    if mask is not None:
        logits = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask,
                           logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    out = out.reshape(B, Sq, h * dh).astype(q.dtype)
    return out


def causal_mask(Sq: int, Skv: int, *, window: int = 0, offset: int = 0
                ) -> jnp.ndarray:
    """(1, 1, Sq, Skv) bool; offset = start position of the query block."""
    qpos = offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m[None, None]


def attn_apply(p, cfg, x, positions, *, window: int = 0,
               mask: jnp.ndarray | None = None, causal: bool = True,
               return_kv: bool = False, residual=None):
    """Full-sequence self-attention (train / prefill).

    ``residual`` (the block input) is folded into the output
    projection's epilogue — one fused writeback instead of a separate
    elementwise add over (B, S, d) after every attention block.

    Above cfg.attn_chunk the query dim is processed in chunks via
    lax.scan (flash-style row blocking, exact math): the (Sq, Skv) logits
    block never exceeds (chunk, Skv) — this is what makes prefill_32k
    lowerable without an O(S^2) footprint.
    """
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x, x, positions, positions)
    pm = mask[:, None, None, :] if mask is not None else None
    C = cfg.attn_chunk
    if C and S > C and S % C == 0:
        nc = S // C
        qs = jnp.moveaxis(q.reshape(B, nc, C, *q.shape[2:]), 1, 0)
        offs = jnp.arange(nc) * C

        def body(_, xs):
            qc, off = xs
            m = _chunk_mask(C, S, window, off) if causal else None
            if pm is not None:
                m = pm if m is None else (m & pm)
            return None, _sdpa(cfg, qc, k, v, m)

        _, outs = jax.lax.scan(body, None, (qs, offs))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, -1)
    else:
        m = causal_mask(S, S, window=window) if causal else None
        if pm is not None:
            m = pm if m is None else (m & pm)
        out = _sdpa(cfg, q, k, v, m)
    out = common.linear_apply(p["wo"], out, cfg.quant,
                              in_dim=cfg.num_heads * cfg.head_dim, tag="wo",
                              residual=residual)
    out = constrain(out, "batch", "seq", "embed")
    return (out, k, v) if return_kv else out


def _chunk_mask(C: int, Skv: int, window: int, offset) -> jnp.ndarray:
    """Traced-offset causal (+sliding window) mask for one q chunk."""
    qpos = offset + jnp.arange(C)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m[None, None]


def view_mask(Skv: int, positions, *, window: int = 0) -> jnp.ndarray:
    """Causal (+sliding-window) mask over a logically-ordered KV view.

    positions (B, C) are the query tokens' logical positions; view index w
    holds the KV of logical position w (true for both the dense cache and
    a block-table-expanded paged view).  Returns (B, C, Skv) bool — shared
    by the static decode and paged serving paths.
    """
    kpos = jnp.arange(Skv)[None, None, :]
    qpos = positions[:, :, None]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m


def attn_decode(p, cfg, x, cache_k, cache_v, pos, *, window: int = 0,
                residual=None):
    """Single-token decode. x (B, 1, d); cache (B, Skv, Hk, Dh); pos (B,).

    Returns (out, new_k, new_v).  The KV cache is logically
    ('batch','kv_seq','kvheads','head_dim') — on meshes where kv-heads
    cannot shard, kv_seq takes the model axis (DESIGN.md §4).
    """
    q, k, v = _qkv(p, cfg, x, x, pos[:, None], pos[:, None])
    B, Skv = cache_k.shape[0], cache_k.shape[1]
    # where-based write: no arithmetic on the cache dtype, so quantized
    # (f8) caches lower cleanly
    mask = (jnp.arange(Skv)[None, :] == pos[:, None])[..., None, None]
    new_k = jnp.where(mask, k.astype(cache_k.dtype), cache_k)
    new_v = jnp.where(mask, v.astype(cache_v.dtype), cache_v)
    new_k = constrain(new_k, "batch", "kv_seq", "kvheads", "head_dim")
    new_v = constrain(new_v, "batch", "kv_seq", "kvheads", "head_dim")
    m = view_mask(Skv, pos[:, None], window=window)[:, 0]
    out = _sdpa(cfg, q, new_k, new_v, m[:, None, None, :])
    out = common.linear_apply(p["wo"], out, cfg.quant,
                              in_dim=cfg.num_heads * cfg.head_dim, tag="wo",
                              residual=residual)
    return out, new_k, new_v


def attn_paged(p, cfg, x, cache, positions, write_slots, view_slots,
               *, window: int = 0, residual=None):
    """Self-attention over a paged (block-pooled) KV cache — one step of
    chunked prefill (C > 1) or batched decode (C == 1); the two share this
    code and its compiled form.

    x (B, C, d) normed hidden; ``cache`` is the layer's shared block pool:
    {"k", "v"} of (num_blocks, bs, Hk, Dh) at full precision, or the
    quantized {"k", "k_scale", "v", "v_scale"} layout of repro.kvq.pool
    when ``cfg.kv_quant`` is set; positions (B, C) logical token
    positions; write_slots (B, C) flat pool slots (block*bs + offset)
    where this step's K/V are scattered — padding rows point into the
    reserved scratch block; view_slots (B, W) flat pool slots such that
    view index w holds sequence b's logical position w (block tables
    expanded by the host scheduler, padded with scratch).  Masked
    (future / scratch) view entries get probability exactly 0, so
    outputs match the dense-cache path bit-for-bit.

    Returns (out, new_cache).
    """
    q, k, v = _qkv(p, cfg, x, x, positions, positions)
    if cfg.kv_quant is not None:
        out, new_cache = _attn_paged_quantized(
            cfg, q, k, v, cache, positions, write_slots, view_slots,
            window=window)
    else:
        k_pool, v_pool = cache["k"], cache["v"]
        nb, bs, hk, dh = k_pool.shape
        kp = k_pool.reshape(nb * bs, hk, dh)
        vp = v_pool.reshape(nb * bs, hk, dh)
        ws = write_slots.reshape(-1)
        with jax.named_scope("attn.kv_write"):
            kp = kp.at[ws].set(k.reshape(-1, hk, dh).astype(kp.dtype))
            vp = vp.at[ws].set(v.reshape(-1, hk, dh).astype(vp.dtype))
        # mesh-aware pool layout: slots replicated (every data shard must
        # resolve any sequence's blocks), kvheads on the model axis when
        # divisible — matching runtime.serve.init_paged_cache's placement
        # so the scatter/gather pair stays local to each model shard
        kp = constrain(kp, "none", "kvheads", "head_dim")
        vp = constrain(vp, "none", "kvheads", "head_dim")
        with jax.named_scope("attn.core"):
            k_view = jnp.take(kp, view_slots, axis=0)  # (B, W, Hk, Dh)
            v_view = jnp.take(vp, view_slots, axis=0)
            k_view = constrain(k_view, "batch", "kv_seq", "kvheads",
                               "head_dim")
            v_view = constrain(v_view, "batch", "kv_seq", "kvheads",
                               "head_dim")
            m = view_mask(view_slots.shape[1], positions, window=window)
            out = _sdpa(cfg, q, k_view, v_view, m[:, None])
        new_cache = dict(cache,
                         k=kp.reshape(nb, bs, hk, dh),
                         v=vp.reshape(nb, bs, hk, dh))
    out = common.linear_apply(p["wo"], out, cfg.quant,
                              in_dim=cfg.num_heads * cfg.head_dim, tag="wo",
                              residual=residual)
    return out, new_cache


def _attn_paged_quantized(cfg, q, k, v, cache, positions, write_slots,
                          view_slots, *, window: int = 0):
    """Quantize-on-write into the codes+scales pool, then dispatch the
    attention math through the registered paged-attention backend
    (repro.kvq.attention: jnp gather+dequant reference, or the Pallas
    kernel that dequantizes in VMEM)."""
    from repro import kvq
    from repro.kvq import attention as kvq_attn

    spec = cfg.kv_quant
    B, C, H, dh = q.shape
    nb, bs, hk, dhp = cache["k"].shape
    ws = write_slots.reshape(-1)
    kq, ks = kvq.kv_quantize(k, spec)  # codes (B, C, Hk, Dhp), scales f32
    vq, vs = kvq.kv_quantize(v, spec)
    new_cache = {}
    for name, codes, scales in (("k", kq, ks), ("v", vq, vs)):
        cp = cache[name].reshape(nb * bs, hk, dhp)
        sp = cache[f"{name}_scale"].reshape(nb * bs, hk)
        with jax.named_scope("attn.kv_write"):
            cp = cp.at[ws].set(codes.reshape(-1, hk, dhp))
            sp = sp.at[ws].set(scales.reshape(-1, hk))
        cp = constrain(cp, "none", "kvheads", "none")
        sp = constrain(sp, "none", "kvheads")
        new_cache[name] = cp.reshape(nb, bs, hk, dhp)
        new_cache[f"{name}_scale"] = sp.reshape(nb, bs, hk)
    with jax.named_scope("attn.core"):
        out = kvq_attn.run(spec, cfg, q, new_cache, view_slots, positions,
                           window=window)
    return out, new_cache


def cross_attn_apply(p, cfg, x, enc_k, enc_v, positions, *, residual=None):
    """Decoder cross-attention against precomputed encoder K/V."""
    B = x.shape[0]
    h, dh = cfg.num_heads, cfg.head_dim
    q = common.linear_apply(p["wq"], x, cfg.quant, in_dim=cfg.d_model,
                            tag="wq")
    q = q.reshape(B, -1, h, dh)
    out = _sdpa(cfg, q, enc_k, enc_v, None)
    out = common.linear_apply(p["wo"], out, cfg.quant,
                              in_dim=cfg.num_heads * cfg.head_dim, tag="wo",
                              residual=residual)
    return out


def cross_kv(p, cfg, enc_out):
    """Project encoder output once; cached for all decode steps."""
    B = enc_out.shape[0]
    hk, dh = cfg.num_kv_heads, cfg.head_dim
    k = common.linear_apply(p["wk"], enc_out, cfg.quant, in_dim=cfg.d_model,
                            tag="wk")
    v = common.linear_apply(p["wv"], enc_out, cfg.quant, in_dim=cfg.d_model,
                            tag="wv")
    return k.reshape(B, -1, hk, dh), v.reshape(B, -1, hk, dh)

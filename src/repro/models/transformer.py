"""Model assembly: embeddings, heterogeneous block stacks (scan-over-layers),
KV/SSM caches, decoder-only + encoder-decoder forward/prefill/decode.

Layers are grouped by the repeating ``cfg.block_pattern``; parameters are
stacked (G, ...) along a leading scan axis so the HLO contains each distinct
block body once regardless of depth — essential for 512-way SPMD compile
times and for XLA's collective overlap scheduling (DESIGN.md §4).
"""

from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.distributed.sharding import constrain_params as \
    sharding_constrain_params
from repro.models import common, layers, mamba, moe, xlstm
from repro.models.config import ModelConfig


# ----------------------------------------------------------------- blocks
def block_init(key, cfg: ModelConfig, kind: str, *, cross: bool = False) -> dict:
    ks = jax.random.split(key, 8)
    if kind in ("attn", "local"):
        p = {"ln1": common.norm_init(cfg.d_model, cfg.norm),
             "attn": layers.attn_init(ks[0], cfg),
             "ln2": common.norm_init(cfg.d_model, cfg.norm),
             "mlp": common.mlp_init(ks[1], cfg, cfg.d_ff)}
    elif kind == "moe":
        p = {"ln1": common.norm_init(cfg.d_model, cfg.norm),
             "attn": layers.attn_init(ks[0], cfg),
             "ln2": common.norm_init(cfg.d_model, cfg.norm),
             "moe": moe.moe_init(ks[1], cfg)}
    elif kind == "mamba":
        p = {"ln1": common.norm_init(cfg.d_model, cfg.norm),
             "mamba": mamba.mamba_init(ks[0], cfg),
             "ln2": common.norm_init(cfg.d_model, cfg.norm),
             "mlp": common.mlp_init(ks[1], cfg, cfg.d_ff)}
    elif kind == "mamba_moe":
        p = {"ln1": common.norm_init(cfg.d_model, cfg.norm),
             "mamba": mamba.mamba_init(ks[0], cfg),
             "ln2": common.norm_init(cfg.d_model, cfg.norm),
             "moe": moe.moe_init(ks[1], cfg)}
    elif kind == "mlstm":
        p = xlstm.mlstm_init(ks[0], cfg)
    elif kind == "slstm":
        p = xlstm.slstm_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    if cross and kind in ("attn", "local", "moe"):
        p["ln_cross"] = common.norm_init(cfg.d_model, cfg.norm)
        p["cross"] = layers.attn_init(ks[2], cfg, cross=True)
    return p


def block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                dtype) -> dict:
    """`dtype` applies to the (large, read-only-per-step) KV tensors — it
    may be a storage dtype like f8.  Recurrent states participate in
    arithmetic every step and stay in the activation dtype."""
    hk, dh = cfg.num_kv_heads, cfg.head_dim
    if kind in ("attn", "local", "moe"):
        c = {"k": jnp.zeros((batch, max_len, hk, dh), dtype),
             "v": jnp.zeros((batch, max_len, hk, dh), dtype)}
        if cfg.is_encdec:
            src = cfg.max_source_len or max_len
            c["cross_k"] = jnp.zeros((batch, src, hk, dh), dtype)
            c["cross_v"] = jnp.zeros((batch, src, hk, dh), dtype)
        return c
    state_dt = jnp.dtype(cfg.dtype)
    if kind in ("mamba", "mamba_moe"):
        return mamba.init_state(cfg, batch, state_dt)
    if kind == "mlstm":
        return xlstm.mlstm_state(cfg, batch, state_dt)
    if kind == "slstm":
        return xlstm.slstm_state(cfg, batch, state_dt)
    raise ValueError(kind)


def _ffn(p, cfg, x, aux):
    if "mlp" in p:
        h = common.norm_apply(p["ln2"], x, cfg.norm, rms_offset=cfg.rms_offset)
        # residual rides the down projection's fused epilogue
        return common.mlp_apply(p["mlp"], h, cfg, residual=x), aux
    h = common.norm_apply(p["ln2"], x, cfg.norm, rms_offset=cfg.rms_offset)
    y, a = moe.moe_apply(p["moe"], h, cfg)
    for k, v in a.items():
        aux[k] = aux.get(k, 0.0) + v
    return x + y, aux


def block_apply(p, cfg: ModelConfig, kind: str, x, positions, *,
                mode: str = "train", cache: dict | None = None,
                pos=None, enc_out=None, paged=None):
    """Dispatch one block.  Returns (x, new_cache, aux).

    mode 'paged' runs the serving path over a block-pooled KV cache:
    ``paged`` carries (write_slots (B, C), view_slots (B, W)) and ``cache``
    holds this group's pool tensors (num_blocks, bs, Hk, Dh).
    """
    aux: dict = {}
    window = cfg.sliding_window if kind == "local" else 0
    if mode == "paged" and kind not in ("attn", "local", "moe"):
        raise NotImplementedError(
            f"paged serving supports attention block kinds only, got {kind!r}")
    if kind in ("attn", "local", "moe"):
        h = common.norm_apply(p["ln1"], x, cfg.norm, rms_offset=cfg.rms_offset)
        new_cache = dict(cache) if cache is not None else None
        # the block-input residual rides each attention out-projection's
        # fused epilogue (no separate x + y elementwise pass)
        if mode == "paged":
            write_slots, view_slots = paged
            y, paged_cache = layers.attn_paged(
                p["attn"], cfg, h, cache, positions,
                write_slots, view_slots, window=window, residual=x)
            new_cache.update(paged_cache)
        elif mode == "decode":
            y, nk, nv = layers.attn_decode(
                p["attn"], cfg, h, cache["k"], cache["v"], pos, window=window,
                residual=x)
            new_cache["k"], new_cache["v"] = nk, nv
        else:
            causal = not (cfg.is_encdec and mode == "encode")
            if cache is not None:  # prefill: also write the prompt's K/V
                y, k, v = layers.attn_apply(p["attn"], cfg, h, positions,
                                            window=window, causal=causal,
                                            return_kv=True, residual=x)
                new_cache["k"] = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
                new_cache["v"] = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
            else:
                y = layers.attn_apply(p["attn"], cfg, h, positions,
                                      window=window, causal=causal,
                                      residual=x)
        x = y
        if cfg.is_encdec and mode != "encode" and "cross" in p:
            hc = common.norm_apply(p["ln_cross"], x, cfg.norm,
                                   rms_offset=cfg.rms_offset)
            if cache is not None and mode == "decode":
                ck, cv = cache["cross_k"], cache["cross_v"]
            elif cache is not None:  # prefill computes + stores cross K/V
                ck, cv = layers.cross_kv(p["cross"], cfg, enc_out)
                new_cache["cross_k"] = ck.astype(cache["cross_k"].dtype)
                new_cache["cross_v"] = cv.astype(cache["cross_v"].dtype)
            else:
                ck, cv = layers.cross_kv(p["cross"], cfg, enc_out)
            x = layers.cross_attn_apply(p["cross"], cfg, hc, ck, cv,
                                        positions, residual=x)
        x, aux = _ffn(p, cfg, x, aux)
        return x, new_cache, aux
    if kind in ("mamba", "mamba_moe"):
        h = common.norm_apply(p["ln1"], x, cfg.norm, rms_offset=cfg.rms_offset)
        y, new_state = mamba.mamba_apply(p["mamba"], cfg, h, state=cache)
        x = x + y
        x, aux = _ffn(p, cfg, x, aux)
        return x, new_state if cache is not None else None, aux
    if kind == "mlstm":
        x, new_state = xlstm.mlstm_block_apply(p, cfg, x, state=cache)
        return x, new_state if cache is not None else None, aux
    if kind == "slstm":
        x, new_state = xlstm.slstm_block_apply(p, cfg, x, state=cache)
        return x, new_state if cache is not None else None, aux
    raise ValueError(kind)


# ----------------------------------------------------------------- stacks
def _stack_init(key, cfg: ModelConfig, pattern, groups: int, *,
                cross: bool = False) -> dict:
    out = {}
    for i, kind in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(key, i), groups)
        # one group at a time: a quantized config then holds one group's
        # dense weights, not the whole stack's, while it quantizes them
        out[f"{i}:{kind}"] = jax.lax.map(
            lambda k: block_init(k, cfg, kind, cross=cross), keys)
    return out


def _stack_apply(blocks: dict, cfg: ModelConfig, pattern, x, positions, *,
                 mode="train", cache=None, pos=None, enc_out=None,
                 paged=None):
    """Scan the block-pattern groups.  cache leaves are stacked (G, ...)."""
    has_cache = cache is not None

    def group_fn(x, xs):
        params_g, cache_g = xs
        params_g = sharding_constrain_params(
            params_g,
            int8_gather=cfg.fsdp_int8_gather and mode == "train")
        if cfg.save_gathered_weights and mode == "train":
            params_g = jax.tree.map(
                lambda p: checkpoint_name(p, "gathered"),
                params_g)
        new_cache_g = {}
        auxs = {"load_balance": jnp.zeros((), jnp.float32),
                "dropped_frac": jnp.zeros((), jnp.float32)}
        for i, kind in enumerate(pattern):
            key = f"{i}:{kind}"
            c = cache_g.get(key) if has_cache else None
            x, nc, aux = block_apply(
                params_g[key], cfg, kind, x, positions,
                mode=mode, cache=c, pos=pos, enc_out=enc_out, paged=paged)
            if has_cache:
                new_cache_g[key] = nc
            for k, v in aux.items():
                auxs[k] = auxs[k] + v
        return x, (new_cache_g, auxs)

    policy = (jax.checkpoint_policies.save_only_these_names("gathered")
              if cfg.save_gathered_weights else None)
    if cfg.remat_policy == "dots" and policy is None:
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    fn = (jax.checkpoint(group_fn, policy=policy)
          if (cfg.remat and mode == "train") else group_fn)

    if cfg.scan_layers:
        xs = (blocks, cache if has_cache else {})
        x, (new_cache, auxs) = jax.lax.scan(fn, x, xs)
        aux = {k: jnp.sum(v) for k, v in auxs.items()}
        return x, (new_cache if has_cache else None), aux
    # unscanned fallback (debugging / perf comparison)
    new_cache = cache
    total_aux = {"load_balance": 0.0, "dropped_frac": 0.0}
    for g in range(_stack_len(blocks)):
        params_g = jax.tree.map(lambda a: a[g], blocks)
        cache_g = (jax.tree.map(lambda a: a[g], cache) if has_cache else {})
        x, (ncg, auxs) = fn(x, (params_g, cache_g))
        if has_cache:
            new_cache = jax.tree.map(lambda full, one: full.at[g].set(one),
                                     new_cache, ncg)
        for k, v in auxs.items():
            total_aux[k] = total_aux[k] + v
    return x, (new_cache if has_cache else None), total_aux


def _stack_len(blocks: dict) -> int:
    return jax.tree.leaves(blocks)[0].shape[0]


# ----------------------------------------------------------------- model
def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    params = {
        "embedding": common.truncated_normal(ks[0], (cfg.vocab_size, d), 1.0),
        "final_norm": common.norm_init(d, cfg.norm),
        "blocks": _stack_init(ks[1], cfg, cfg.block_pattern, cfg.num_groups,
                              cross=cfg.is_encdec),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = common.linear_init(ks[2], d, cfg.vocab_size, cfg,
                                               cfg.quant)
    if cfg.is_encdec:
        params["encoder"] = {
            "blocks": _stack_init(ks[3], cfg, ("attn",), cfg.encoder_layers),
            "final_norm": common.norm_init(d, cfg.norm),
        }
        params["pos_embedding"] = common.truncated_normal(
            ks[4], (cfg.max_seq_len, d), 0.02)
    return params


def _sinusoidal(S: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(S)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, d, 2) * (-jnp.log(10000.0) / (d // 2 - 1)))
    pe = jnp.zeros((S, d))
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def embed_inputs(params, cfg: ModelConfig, tokens, *, patch_embeds=None):
    """tokens (B, S_text); vlm: patch embeds are prepended (stub frontend)."""
    x = jnp.take(params["embedding"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * cfg.d_model**0.5
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    return constrain(x.astype(jnp.dtype(cfg.dtype)), "batch", "seq", "embed")


def encode(params, cfg: ModelConfig, frames) -> jnp.ndarray:
    """Encoder for enc-dec models; frames (B, S_src, d) from the stub
    frontend, sinusoidal positions (length-safe at 32k)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = x + _sinusoidal(x.shape[1], cfg.d_model).astype(x.dtype)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    enc = params["encoder"]
    x, _, _ = _stack_apply(enc["blocks"], cfg, ("attn",), x, positions,
                           mode="encode")
    return common.norm_apply(enc["final_norm"], x, cfg.norm,
                             rms_offset=cfg.rms_offset)


def logits_from_hidden(params, cfg: ModelConfig, x) -> jnp.ndarray:
    x = common.norm_apply(params["final_norm"], x, cfg.norm,
                          rms_offset=cfg.rms_offset)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                            params["embedding"].astype(jnp.float32))
    else:
        logits = common.linear_apply(params["lm_head"], x, cfg.quant,
                                     in_dim=cfg.d_model, tag="lm_head").astype(jnp.float32)
    logits = common.softcap(logits, cfg.final_logit_softcap)
    return constrain(logits, "batch", "seq", "vocab")


def forward(params, cfg: ModelConfig, batch: dict, *, mode="train"):
    """Full-sequence forward.  batch: tokens (+frames / +patch_embeds).

    Returns (logits, aux)."""
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(params, cfg, batch["frames"])
    x = embed_inputs(params, cfg, batch["tokens"],
                     patch_embeds=batch.get("patch_embeds"))
    if cfg.is_encdec:
        S = x.shape[1]
        x = x + params["pos_embedding"][:S].astype(x.dtype)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _, aux = _stack_apply(params["blocks"], cfg, cfg.block_pattern, x,
                             positions, mode=mode, enc_out=enc_out)
    return logits_from_hidden(params, cfg, x), aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.float32) -> dict:
    """Stacked (G, ...) cache pytree for decode."""
    out = {}
    for i, kind in enumerate(cfg.block_pattern):
        one = block_cache(cfg, kind, batch, max_len, dtype)
        out[f"{i}:{kind}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_groups, *a.shape)).copy(),
            one)
    return out


def prefill(params, cfg: ModelConfig, batch: dict, cache: dict):
    """Run the prompt through the model, filling the cache.

    Returns (logits_last (B, V), cache)."""
    enc_out = encode(params, cfg, batch["frames"]) if cfg.is_encdec else None
    x = embed_inputs(params, cfg, batch["tokens"],
                     patch_embeds=batch.get("patch_embeds"))
    if cfg.is_encdec:
        x = x + params["pos_embedding"][: x.shape[1]].astype(x.dtype)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, cache, _ = _stack_apply(params["blocks"], cfg, cfg.block_pattern, x,
                               positions, mode="prefill", cache=cache,
                               enc_out=enc_out)
    logits = logits_from_hidden(params, cfg, x[:, -1:, :])
    return logits[:, 0], cache


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=jnp.float32, *, kv_spec=None) -> dict:
    """Stacked (G, num_blocks, bs, Hk, Dh) KV block pool for paged serving.

    One shared pool per layer group: sequences own disjoint block subsets
    via host-side block tables (serving/kv_blocks.py), so the (batch,
    max_len) dense cache footprint becomes (blocks actually in use).
    ``kv_spec`` (default ``cfg.kv_quant``) switches the pool tensors to
    the quantized codes+scales layout of repro.kvq.pool — same block/slot
    indexing, 2–4x+ fewer bytes per token.  Attention-free (recurrent)
    block kinds, enc-dec, and modality frontends are not paged — the
    continuous engine rejects them.
    """
    if cfg.is_encdec or cfg.frontend:
        raise NotImplementedError(
            "paged serving supports plain decoder-only models")
    if kv_spec is None:
        kv_spec = cfg.kv_quant
    hk, dh = cfg.num_kv_heads, cfg.head_dim
    out = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind not in ("attn", "local", "moe"):
            raise NotImplementedError(
                f"paged KV cache for block kind {kind!r}")
        if kv_spec is not None:
            from repro import kvq
            one = kvq.init_kv_pool(kv_spec, num_blocks, block_size, hk, dh)
        else:
            one = {"k": jnp.zeros((num_blocks, block_size, hk, dh), dtype),
                   "v": jnp.zeros((num_blocks, block_size, hk, dh), dtype)}
        out[f"{i}:{kind}"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_groups, *a.shape)).copy(),
            one)
    return out


def forward_paged(params, cfg: ModelConfig, tokens, pool: dict, positions,
                  write_slots, view_slots):
    """One paged serving step — chunked prefill (C > 1) and batched decode
    (C == 1) both lower through this single function, so the two phases
    share all model code with each other and with the dense-cache path.

    tokens/positions/write_slots (B, C); view_slots (B, W) flat pool slots
    covering each row's logical positions 0..W-1 (see layers.attn_paged).

    Returns (logits (B, C, V), new_pool).
    """
    x = embed_inputs(params, cfg, tokens)
    x, pool, _ = _stack_apply(params["blocks"], cfg, cfg.block_pattern, x,
                              positions, mode="paged", cache=pool,
                              paged=(write_slots, view_slots))
    return logits_from_hidden(params, cfg, x), pool


def decode_step(params, cfg: ModelConfig, token, cache: dict, pos):
    """One decode step.  token (B,), pos (B,) current position.

    Returns (logits (B, V), new_cache)."""
    x = embed_inputs(params, cfg, token[:, None])
    if cfg.is_encdec:
        x = x + jnp.take(params["pos_embedding"], pos, axis=0)[:, None].astype(
            x.dtype)
    positions = pos[:, None]
    x, cache, _ = _stack_apply(params["blocks"], cfg, cfg.block_pattern, x,
                               positions, mode="decode", cache=cache, pos=pos)
    logits = logits_from_hidden(params, cfg, x)
    return logits[:, 0], cache

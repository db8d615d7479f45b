"""Paged-attention backends over the quantized KV pool.

Two peers register in the dispatch capability/priority registry under
mode ``"paged_attn"`` (duck-typed spec — Backend.supports only reads
``mode`` / ``storage`` / ``codebook``):

* ``paged_attn_jnp``     gather codes+scales by view_slots with
                         ``jnp.take`` and dequantize in HBM, then the
                         exact ``models.layers._sdpa`` math — the
                         reference/fallback, runs anywhere;
* ``paged_attn_pallas``  kernels/paged_attention.py — block tables via
                         scalar prefetch, dequantize in VMEM, flash
                         online softmax; outranks jnp on real TPU.

Selection (:func:`select`) honors ``KVQuantSpec.backend`` as a forced
override, and pins the jnp path whenever a mesh is active: the Pallas
kernel is a single-device program and we don't shard_map it yet, while
the jnp gather lowers through GSPMD with the existing ``constrain``
pool layouts (slots replicated, kvheads on the model axis).

The dequantized HBM footprint is the observable difference: the jnp
path materializes 2 * B * W * Hk * Dh f32 view bytes per layer-step
(engine gauge ``kv_dequant_hbm_bytes``); the Pallas path reports 0 —
the acceptance check that no HBM-resident dequantized K/V copy exists.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.dispatch import registry
from repro.distributed.sharding import active_mesh, constrain
from repro.kvq.quantize import kv_dequantize
from repro.kvq.spec import KVQuantSpec

KV_STORAGE = "kv_u8"


class _AttnQuery(NamedTuple):
    """Duck-typed stand-in for QuantSpec in registry capability checks."""
    mode: str
    storage: str
    codebook: str


def run_jnp(spec: KVQuantSpec, cfg, q, pool, view_slots, positions, *,
            window: int = 0):
    """Reference: gather + dequantize the view in HBM, dense sdpa.

    q (B, C, H, Dh); pool the layer's quantized leaves (nb, bs, Hk, ...);
    view_slots (B, W) flat slots; positions (B, C).  Returns (B, C, H*Dh).
    """
    from repro.models import layers  # lazy: layers imports kvq

    nb, bs, hk, dhp = pool["k"].shape
    dh = q.shape[-1]
    kc = pool["k"].reshape(nb * bs, hk, dhp)
    vc = pool["v"].reshape(nb * bs, hk, dhp)
    ks = pool["k_scale"].reshape(nb * bs, hk)
    vs = pool["v_scale"].reshape(nb * bs, hk)
    k_view = kv_dequantize(jnp.take(kc, view_slots, axis=0),
                           jnp.take(ks, view_slots, axis=0), spec, dh)
    v_view = kv_dequantize(jnp.take(vc, view_slots, axis=0),
                           jnp.take(vs, view_slots, axis=0), spec, dh)
    k_view = constrain(k_view, "batch", "kv_seq", "kvheads", "head_dim")
    v_view = constrain(v_view, "batch", "kv_seq", "kvheads", "head_dim")
    m = layers.view_mask(view_slots.shape[1], positions, window=window)
    return layers._sdpa(cfg, q, k_view, v_view, m[:, None])


def run_pallas(spec: KVQuantSpec, cfg, q, pool, view_slots, positions, *,
               window: int = 0):
    """In-kernel dequant: derive block tables from the slot view (view
    position w*bs starts block w's slots, slot // bs = block id — exact
    because the scheduler builds views from whole blocks) and hand the
    quantized leaves straight to the kernel."""
    from repro.kernels.paged_attention import paged_attention_pallas

    bs = pool["k"].shape[1]
    block_tables = view_slots[:, ::bs] // bs
    B, C, H, dh = q.shape
    out = paged_attention_pallas(
        q, pool["k"], pool["k_scale"], pool["v"], pool["v_scale"],
        block_tables, positions, bits=spec.bits, codebook=spec.codebook,
        block_size=bs, window=window,
        softcap=float(cfg.attn_logit_softcap or 0.0))
    return out.reshape(B, C, H * dh)


registry.register_backend(
    "paged_attn_jnp", modes=("paged_attn",), run=run_jnp, priority=50,
    storages=(KV_STORAGE,), codebooks=("none", "learned"),
    description="gather+dequantize in HBM, dense sdpa (reference)",
    overwrite=True)
registry.register_backend(
    "paged_attn_pallas", modes=("paged_attn",), run=run_pallas,
    priority=lambda dev: 60 if dev == "tpu" else 40,
    storages=(KV_STORAGE,), codebooks=("none", "learned"),
    description="Pallas paged attention, dequantize in VMEM",
    overwrite=True)


def select(spec: KVQuantSpec) -> str:
    """Resolve the backend name serving this spec right now (forced
    override > mesh pin > registry priority)."""
    if spec.backend is not None:
        be = registry.get_backend(spec.backend)
        if "paged_attn" not in be.modes:
            raise ValueError(
                f"backend {spec.backend!r} is not a paged-attention "
                f"backend (modes={be.modes})")
        return spec.backend
    if active_mesh() is not None:
        return "paged_attn_jnp"
    query = _AttnQuery("paged_attn", KV_STORAGE, spec.codebook_kind)
    return registry.select_backend(query, 1).name


def run(spec: KVQuantSpec, cfg, q, pool, view_slots, positions, *,
        window: int = 0):
    """Dispatch one paged-attention step through the selected backend."""
    be = registry.get_backend(select(spec))
    return be.run(spec, cfg, q, pool, view_slots, positions, window=window)


def dequant_hbm_bytes(spec: KVQuantSpec, cfg, max_slots: int,
                      view_width: int) -> int:
    """Per-layer-step HBM bytes of dequantized K/V the selected backend
    materializes (engine gauge ``kv_dequant_hbm_bytes``; 0 for Pallas —
    the kernel's f32 K/V tiles live only in VMEM)."""
    if select(spec) == "paged_attn_pallas":
        return 0
    return 2 * max_slots * view_width * cfg.num_kv_heads * cfg.head_dim * 4

"""Shared building blocks: norms, activations, initializers, linear glue."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import linear as qlinear
from repro.core.epilogue import Epilogue
from repro.distributed import sharding as shd_rules
from repro.distributed.sharding import constrain


def truncated_normal(key, shape, scale, dtype=jnp.float32):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) \
        .astype(dtype) * scale


# ---------------------------------------------------------------- norms
def norm_init(d: int, kind: str) -> dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(p: dict, x: jnp.ndarray, kind: str, *, rms_offset: bool = False,
               eps: float = 1e-6) -> jnp.ndarray:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        if kind == "rmsnorm":
            var = jnp.mean(xf * xf, axis=-1, keepdims=True)
            y = xf * jax.lax.rsqrt(var + eps)
            w = (1.0 + p["scale"]) if rms_offset else p["scale"]
            return (y * w).astype(x.dtype)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------- linears
def linear_init(key, in_dim, out_dim, cfg, quant=qlinear.DENSE, *, scale=None):
    """A QuantizedLinear leaf (dict with 'w' or quantized params)."""
    return qlinear.init(key, in_dim, out_dim, quant,
                        dtype=jnp.dtype(cfg.param_dtype), init_scale=scale)


def linear_apply(p, x, quant=qlinear.DENSE, *, in_dim=None, tag=None,
                 act="none", bias=None, residual=None, out_dtype=None,
                 shard_axes=None):
    """``tag`` names the linear for calibration's activation-statistics
    observer (repro.calib.stats); it never changes the computation —
    but it *does* name the weight's logical axes: under an active mesh
    (distributed.sharding.use) the LINEAR_AXES entry for the tag rides
    to the dispatch layer as ``shard_axes``, which plans local-shard
    tiles and runs the quantized GeMM inside a shard_map (tensor
    parallelism with per-shard LUT produce).  Tags without an entry
    (e.g. the vmapped MoE expert linears) stay under plain GSPMD.

    ``act``/``bias``/``residual``/``out_dtype`` describe the element-wise
    tail ``y = act(Wx + bias) + residual`` (cast to ``out_dtype``): they
    become a core.epilogue.Epilogue that fuses into the Pallas kernels'
    final VMEM writeback and falls back to the same unfused op sequence
    on every other backend (identical at f32 activations) — so model
    code stops issuing separate element-wise HBM passes after its
    quantized matmuls.  Under a contraction-sharded (row-parallel) plan
    the tail instead runs exactly once after the psum/reduce-scatter.

    A tagged linear's ops sit under the ``linear.<tag>`` scope (op
    metadata only), so a profiler trace names each role's device time."""
    ep = None
    if act != "none" or bias is not None or residual is not None \
            or out_dtype is not None:
        ep = Epilogue(act=act, bias=bias is not None,
                      residual=residual is not None, out_dtype=out_dtype)
    if tag is None:
        return qlinear.apply(p, x, quant, in_dim=in_dim, epilogue=ep,
                             bias=bias, residual=residual,
                             shard_axes=shard_axes)
    if shard_axes is None:
        shard_axes = shd_rules.LINEAR_AXES.get(tag)
    with jax.named_scope(f"linear.{tag}"):
        return qlinear.apply(p, x, quant, in_dim=in_dim, tag=tag,
                             epilogue=ep, bias=bias, residual=residual,
                             shard_axes=shard_axes)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    """gemma2 logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap) if cap else x


def chunked_scan(step, carry, xs, *, chunk: int, remat: bool = True):
    """Two-level lax.scan: outer over chunks (carry checkpointed per
    chunk), inner rematerialized.  Backward memory for a T-step recurrence
    drops from O(T x state) to O(T/chunk x state) at the cost of one
    recomputed forward — the standard sqrt-T checkpointing for the
    mLSTM/sLSTM sequence scans (xlstm train at 4k stores 274 GB/device of
    per-step matrix-memory states without this).

    xs leaves must have leading dim T with T % chunk == 0 (caller pads).
    """
    T = jax.tree.leaves(xs)[0].shape[0]
    if chunk >= T:
        return jax.lax.scan(step, carry, xs)
    assert T % chunk == 0, (T, chunk)
    xs_c = jax.tree.map(
        lambda a: a.reshape(T // chunk, chunk, *a.shape[1:]), xs)

    def outer(c, xc):
        return jax.lax.scan(step, c, xc)

    if remat:
        outer = jax.checkpoint(outer)
    carry, ys_c = jax.lax.scan(outer, carry, xs_c)
    ys = jax.tree.map(lambda a: a.reshape(T, *a.shape[2:]), ys_c)
    return carry, ys


def activation(name: str):
    return {"gelu": jax.nn.gelu, "silu": jax.nn.silu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------- MLP
def mlp_init(key, cfg, d_ff: int, quant=None) -> dict:
    q = quant if quant is not None else cfg.quant
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    gated = cfg.mlp_activation in ("swiglu", "geglu")
    p = {"up": linear_init(ks[0], d, d_ff, cfg, q),
         "down": linear_init(ks[1], d_ff, d, cfg, q)}
    if gated:
        p["gate"] = linear_init(ks[2], d, d_ff, cfg, q)
    return p


def mlp_apply(p: dict, x: jnp.ndarray, cfg, quant=None, *,
              residual=None) -> jnp.ndarray:
    """MLP with the element-wise tail folded into the linears' epilogues:
    the non-gated activation fuses into the up projection's writeback and
    ``residual`` (the block input) into the down projection's, so the
    quantized hot path issues no separate activation/residual HBM passes
    (gated variants still need the gate×up product — only the gate's
    activation fuses)."""
    q = quant if quant is not None else cfg.quant
    act_name = {"swiglu": "silu", "geglu": "gelu",
                "gelu": "gelu"}[cfg.mlp_activation]
    if "gate" in p:
        up = linear_apply(p["up"], x, q, in_dim=cfg.d_model, tag="up")
        gate = linear_apply(p["gate"], x, q, in_dim=cfg.d_model, tag="gate",
                            act=act_name)
        h = gate * up
    else:
        h = linear_apply(p["up"], x, q, in_dim=cfg.d_model, tag="up",
                         act=act_name)
    h = constrain(h, *(("batch",) + ("seq",) * (h.ndim - 2) + ("mlp",)))
    return linear_apply(p["down"], h, q, in_dim=h.shape[-1], tag="down",
                        residual=residual)

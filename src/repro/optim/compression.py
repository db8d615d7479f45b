"""int8 gradient compression with error feedback — the cross-pod (DCN)
all-reduce optimization of DESIGN.md §4.

Cross-pod gradient reduction moves bytes over the slow DCN ('pod') axis;
quantizing to int8 (+ one f32 scale shared via a scalar pmax) cuts DCN
bytes 4x vs f32.  Summation happens in int32 — exact given the shared
scale — so the only loss is the quantization itself, which error feedback
folds into the next step.

``compressed_psum`` is used inside the train step's partial-auto
shard_map over 'pod' (runtime/train.py): the data/model axes stay under
GSPMD while the pod axis collective is explicit and compressed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x: jnp.ndarray):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """psum over `axis_name` with an int8 wire format (callable inside
    shard_map/pmap where `axis_name` is manual)."""
    xf = x.astype(jnp.float32)
    scale = jax.lax.pmax(jnp.max(jnp.abs(xf)), axis_name) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    s = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return s.astype(jnp.float32) * scale


def compressed_pmean_tree(grads, axis_name: str, residual=None):
    """Error-feedback compressed mean of a gradient pytree over `axis_name`.

    Returns (mean_grads, new_residual).  Must run where `axis_name` is a
    manual (shard_map) axis.
    """
    n = jax.lax.axis_size(axis_name)
    if residual is None:
        residual = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                                grads)

    def one(g, r):
        gf = g.astype(jnp.float32) + r
        scale = jax.lax.pmax(jnp.max(jnp.abs(gf)), axis_name) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
        red = (jax.lax.psum(q.astype(jnp.int32), axis_name)
               .astype(jnp.float32) * scale / n)
        # error feedback: carry THIS shard's quantization error only
        return red.astype(g.dtype), gf - q.astype(jnp.float32) * scale

    pairs = jax.tree.map(lambda g, r: one(g, r), grads, residual)
    mean = jax.tree.map(lambda p: p[0], pairs,
                        is_leaf=lambda p: isinstance(p, tuple))
    res = jax.tree.map(lambda p: p[1], pairs,
                       is_leaf=lambda p: isinstance(p, tuple))
    return mean, res

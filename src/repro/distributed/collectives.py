"""Explicit collective helpers.

``int8_all_gather`` — quantized FSDP weight gather: the parameter shard is
quantized to int8 (one symmetric scale per leaf, agreed via a scalar
pmax), all-gathered over the data axis in the int8 wire format (halving
the dominant 400B-train collective, EXPERIMENTS.md §Perf A), and
dequantized locally.  Backward is the exact FSDP transpose — a full-
precision reduce-scatter of the gradient (straight-through w.r.t. the
quantization, standard for compressed weight gathers).

``ring_psum`` / ``ring_reduce_scatter`` / ``ring_all_gather`` — explicit
``lax.ppermute`` rings for use *inside* a fully-manual shard_map body.
XLA's fused ``psum``/``psum_scatter`` are opaque single ops: nothing can
be scheduled between their internal steps, so the contraction collective
of a row-parallel linear serializes behind the whole GeMM.  The ring
spellings decompose the same reduction into N-1 point-to-point hops,
each a separate HLO the scheduler may interleave with independent
compute — which is what lets ``dispatch.shard`` overlap the collective
for contraction-chunk *i* with the msGeMM consume of chunk *i+1*.
``collective_cost`` is the matching analytic (hops, bytes) model used by
``obs.perfmodel`` to rank pipelined plan variants without measuring
every chunk count.

Implemented with fully-manual ``jax.shard_map``: the
gather axis carries the collectives, the model/tensor axes are pure
per-shard layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _ring_perm(n: int):
    """Shift-by-one permutation over an axis of size n."""
    return [(i, (i + 1) % n) for i in range(n)]


def ring_reduce_scatter(y, axis: str, *, axis_size: int | None = None,
                        dim: int = -1):
    """Block ring reduce-scatter of ``y`` over named axis ``axis``.

    Must be called inside a fully-manual shard_map.  ``y.shape[dim]``
    must be divisible by the axis size N; device p ends with block p of
    the cross-device sum — the same block→device assignment as
    ``lax.psum_scatter(..., tiled=True)``.  N-1 hops, each carrying one
    1/N-size block, every hop a separate ppermute the scheduler can
    slide under unrelated compute.
    """
    n = axis_size if axis_size is not None else jax.lax.axis_size(axis)
    if n == 1:
        return y
    dim = dim % y.ndim
    if y.shape[dim] % n:
        raise ValueError(
            f"ring_reduce_scatter: dim {dim} of {y.shape} not divisible "
            f"by axis {axis!r} size {n}")
    sz = y.shape[dim] // n
    p = jax.lax.axis_index(axis)
    perm = _ring_perm(n)

    def blk(i):
        # i is traced and may exceed n; reduce mod n (always >= 0 here).
        return jax.lax.dynamic_slice_in_dim(y, (i % n) * sz, sz, axis=dim)

    # Device p seeds the ring with block (p-1); after hop t it holds the
    # running sum of block (p-t-2 mod n) over devices p-t..p, so after
    # n-1 hops it ends with block p fully reduced.
    acc = blk(p + n - 1)
    for t in range(n - 1):
        acc = jax.lax.ppermute(acc, axis, perm)
        acc = acc + blk(p + 2 * n - t - 2)
    return acc


def ring_all_gather(y, axis: str, *, axis_size: int | None = None,
                    dim: int = -1):
    """Ring all-gather over named axis ``axis`` (inverse of the scatter).

    Device p contributes block p; output concatenates all N blocks along
    ``dim`` in axis order.  N-1 single-block hops.
    """
    n = axis_size if axis_size is not None else jax.lax.axis_size(axis)
    if n == 1:
        return y
    dim = dim % y.ndim
    sz = y.shape[dim]
    p = jax.lax.axis_index(axis)
    perm = _ring_perm(n)
    shape = y.shape[:dim] + (n * sz,) + y.shape[dim + 1:]
    out = jnp.zeros(shape, y.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, y, p * sz, axis=dim)
    cur = y
    for t in range(n - 1):
        cur = jax.lax.ppermute(cur, axis, perm)
        # hop t delivers the block of device (p - t - 1) mod n
        out = jax.lax.dynamic_update_slice_in_dim(
            out, cur, ((p + 2 * n - t - 1) % n) * sz, axis=dim)
    return out


def ring_psum(y, axis: str, *, axis_size: int | None = None):
    """Ring all-reduce of ``y`` over named axis ``axis``.

    When the last dim divides the axis size, runs the bandwidth-optimal
    reduce-scatter + all-gather ring (2(N-1) hops of 1/N-size blocks).
    Otherwise falls back to the naive full-buffer ring (N-1 hops, each
    carrying the whole partial).  Either way every hop is an independent
    ppermute that can overlap unrelated compute.
    """
    n = axis_size if axis_size is not None else jax.lax.axis_size(axis)
    if n == 1:
        return y
    if y.shape[-1] % n == 0:
        sc = ring_reduce_scatter(y, axis, axis_size=n, dim=-1)
        return ring_all_gather(sc, axis, axis_size=n, dim=-1)
    perm = _ring_perm(n)
    acc = y
    cur = y
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis, perm)
        acc = acc + cur
    return acc


def collective_cost(*, impl: str, collective: str, axis_size: int,
                    elems: int, dtype_bytes: int = 4,
                    pipeline_chunks: int = 1):
    """Analytic (hops, bytes) one device moves to resolve a k-sharded
    contraction whose full (unscattered) partial output has ``elems``
    elements, split into ``pipeline_chunks`` k-chunks.

    Returns ``(hops_total, bytes_total)`` summed over all chunks.  The
    ring impls count their actual ppermute hops; the opaque XLA ops are
    modeled as one logical hop per chunk moving the standard-algorithm
    byte volume (ring-equivalent: (N-1)/N of the buffer for a
    reduce-scatter, twice that for an all-reduce).  This is the single
    source of truth for ``obs.perfmodel.collective_features``.
    """
    n = int(axis_size)
    pc = max(int(pipeline_chunks), 1)
    if n <= 1:
        return 0, 0.0
    chunk_bytes = elems / pc * dtype_bytes
    if impl == "ring":
        if collective == "reduce_scatter":
            hops_c = n - 1
            bytes_c = (n - 1) * chunk_bytes / n
        elif chunk_bytes and elems % (pc * n) == 0:
            # rs+ag ring: 2(N-1) hops of 1/N-size blocks
            hops_c = 2 * (n - 1)
            bytes_c = 2 * (n - 1) * chunk_bytes / n
        else:
            # naive full-buffer ring
            hops_c = n - 1
            bytes_c = (n - 1) * chunk_bytes
    else:  # opaque xla psum / psum_scatter
        hops_c = 1
        scale = 1 if collective == "reduce_scatter" else 2
        bytes_c = scale * (n - 1) * chunk_bytes / n
    return hops_c * pc, bytes_c * pc


def _gather_spec(spec: P, axis: str):
    """Locate `axis` in a PartitionSpec; return (dim, spec-without-axis)."""
    entries = list(spec) + [None] * 8
    for i, e in enumerate(entries):
        names = e if isinstance(e, tuple) else (e,)
        if axis in names:
            rest = tuple(n for n in names if n != axis)
            new = list(spec)
            new[i] = rest if len(rest) > 1 else (rest[0] if rest else None)
            return i, P(*new)
    return None, spec


def int8_all_gather(x: jnp.ndarray, mesh, spec: P, *, axis: str = "data"):
    """Gather the `axis`-sharded dim of x in int8; exact-gradient RS bwd."""
    dim, out_spec = _gather_spec(spec, axis)
    if dim is None or axis not in mesh.shape or mesh.shape[axis] == 1:
        return x
    # fully-manual shard_map over the leaf's own storage spec: the only
    # collectives inside are over `axis`; the model/tensor axes are pure
    # layout (each shard just carries its slice through).
    gather = functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec,), out_specs=out_spec,
        check_vma=False)
    scatter = functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(out_spec,), out_specs=spec,
        check_vma=False)

    @jax.custom_vjp
    def f(xs):
        @gather
        def run(s):
            amax = jax.lax.pmax(jnp.max(jnp.abs(s)).astype(jnp.float32),
                                axis)
            scale = jnp.where(amax > 0, amax / 127.0, 1.0)
            q = jnp.clip(jnp.round(s.astype(jnp.float32) / scale),
                         -127, 127).astype(jnp.int8)
            g = jax.lax.all_gather(q, axis, axis=dim, tiled=True)
            return (g.astype(jnp.float32) * scale).astype(s.dtype)

        return run(xs)

    def fwd(xs):
        return f(xs), None

    def bwd(_, ct):
        # The gathered output is replicated over `axis`, so its cotangent
        # arrives reduced+replicated; the exact transpose is the local
        # slice.  XLA's reduce-scatter-creator pass fuses the upstream
        # all-reduce with this partition-indexed slice into a
        # reduce-scatter where profitable.
        @scatter
        def run(c):
            size = c.shape[dim] // mesh.shape[axis]
            start = jax.lax.axis_index(axis) * size
            return jax.lax.dynamic_slice_in_dim(c, start, size, axis=dim)

        return (run(ct),)

    f.defvjp(fwd, bwd)
    return f(x)

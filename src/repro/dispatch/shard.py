"""Sharded execution of one quantized linear on a device mesh.

The paper's produce/consume split interacts with tensor parallelism in a
specific way (§6): the LUT produce cost is amortized over the output
rows m, so sharding m (column parallelism) keeps the amortization
*per shard* — every device produces the LUT for its own activation
shard once and consumes it over its m rows — instead of replicating the
whole GeMM.  Sharding the contraction dim k (row parallelism, the
Megatron down-proj/wo pattern) makes every device produce a LUT over
its k-slice of the activations, and the partial sums meet in exactly
one collective, after which the epilogue (bias/residual — which must
NOT be applied per shard) runs once.

This module carries that story end to end:

* :class:`ShardSpec` — the frozen, hashable ``ExecPlan.shard`` field:
  which mesh axis shards m / k / the activation batch, which collective
  resolves the contraction (``psum`` keeps the output replicated over
  the k axis, ``reduce_scatter`` leaves it m-sharded), and the mesh
  shape it was derived against (part of the plan-cache key).
* :func:`shard_spec_for` — derives a ShardSpec for one linear from its
  *logical* weight axes (the same ``distributed.sharding.LINEAR_AXES``
  names the param-placement rules use), with divisibility and
  quantization-alignment guards: a dim only shards when every packed
  storage view (idx / u8 / scales) splits cleanly on the shard
  boundary.  Anything that cannot shard safely (adaptive d, expert
  stacks under vmap, misaligned dims) returns None and stays under
  GSPMD exactly as before.
* :func:`run_sharded` — wraps a registered backend's ``run`` in a
  fully-manual ``shard_map``: per-shard LUT produce, per-shard VMEM
  accumulation, the epilogue fused into the kernel writeback when no
  contraction collective separates them, and applied exactly once
  *after* the collective when one does.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.epilogue import apply_epilogue
from repro.distributed import collectives as coll
from repro.distributed import sharding as shd
from repro.kernels import ops as kops

COLLECTIVES = ("psum", "reduce_scatter")
COLLECTIVE_IMPLS = ("xla", "ring")


@dataclass(frozen=True)
class ShardSpec:
    """How one linear's GeMM is laid out on the mesh (ExecPlan.shard).

    mesh_axes : ordered ((axis_name, size), ...) snapshot of the mesh the
        spec was derived against — makes the spec self-describing (cache
        keys, warm()) without holding a live Mesh object.
    m / k / batch : mesh axis name sharding the weight's output rows,
        the contraction dim, and the activations' leading (batch) dim;
        None leaves that dim whole on every device.  m and k are
        mutually exclusive (one TP axis per linear).
    collective : how k-sharded partial sums meet: ``psum`` (output
        replicated over the k axis) or ``reduce_scatter`` (output rows
        scattered over the k axis — the next layer's column-parallel
        input sharding).  Ignored when k is None.
    pipeline_chunks : number of contraction slices the k-sharded GeMM is
        split into so chunk i's collective overlaps chunk i+1's consume;
        1 is the classic one-collective-per-linear plan.  Only
        meaningful with k sharded.
    collective_impl : ``xla`` (fused psum/psum_scatter ops) or ``ring``
        (explicit ppermute hops from distributed.collectives, each hop
        schedulable under compute).  Only meaningful with k sharded.
    """

    mesh_axes: tuple[tuple[str, int], ...] = ()
    m: str | None = None
    k: str | None = None
    batch: str | None = None
    collective: str = "psum"
    pipeline_chunks: int = 1
    collective_impl: str = "xla"

    def __post_init__(self):
        if self.collective not in COLLECTIVES:
            raise ValueError(f"collective={self.collective!r} must be one "
                             f"of {COLLECTIVES}")
        if self.collective_impl not in COLLECTIVE_IMPLS:
            raise ValueError(
                f"collective_impl={self.collective_impl!r} must be one of "
                f"{COLLECTIVE_IMPLS}")
        if self.m is not None and self.k is not None:
            raise ValueError("m and k cannot both be sharded by one linear "
                             f"(m={self.m!r}, k={self.k!r})")
        if self.pipeline_chunks < 1:
            raise ValueError(
                f"pipeline_chunks={self.pipeline_chunks} must be >= 1")
        if self.k is None and (self.pipeline_chunks != 1
                               or self.collective_impl != "xla"):
            raise ValueError(
                "pipeline_chunks/collective_impl apply only to k-sharded "
                "(row-parallel) linears — there is no contraction "
                "collective to pipeline otherwise")

    # ------------------------------------------------------------ sizes
    def axis_size(self, axis: str | None) -> int:
        if axis is None:
            return 1
        return dict(self.mesh_axes)[axis]

    @property
    def is_sharded(self) -> bool:
        return any(a is not None and self.axis_size(a) > 1
                   for a in (self.m, self.k, self.batch))

    @property
    def is_pipelined(self) -> bool:
        return self.pipeline_chunks > 1 or self.collective_impl != "xla"

    def local_mkb(self, m: int, k: int, batch: int) -> tuple[int, int, int]:
        """Per-device (m, k, batch-rows) under this spec."""
        return (m // self.axis_size(self.m), k // self.axis_size(self.k),
                batch // self.axis_size(self.batch))

    def exec_mkb(self, m: int, k: int, batch: int) -> tuple[int, int, int]:
        """Per-kernel-invocation (m, k, batch-rows) — what tile
        heuristics and the autotuner must plan/time under this spec.
        Same as :meth:`local_mkb` except the contraction dim shrinks by
        ``pipeline_chunks``: a pipelined plan invokes the kernel once
        per k-chunk."""
        lm, lk, lb = self.local_mkb(m, k, batch)
        return lm, lk // self.pipeline_chunks, lb

    # ------------------------------------------------------------- keys
    def tag(self) -> str:
        """Cache-key fragment: mesh shape + the shard choice.

        The pipeline suffix (``/pc{n}.{impl}``) is appended only when it
        differs from the classic one-shot layout, so every key a v3
        cache file recorded before pipelining existed is byte-identical
        to the key the same plan derives today (additive-key
        discipline)."""
        mesh = ".".join(f"{a}{s}" for a, s in self.mesh_axes)
        base = (f"{mesh}/m={self.m or '-'}/k={self.k or '-'}"
                f"/b={self.batch or '-'}/{self.collective}")
        if self.is_pipelined:
            base += f"/pc{self.pipeline_chunks}.{self.collective_impl}"
        return base


def mesh_tag(mesh) -> str:
    """Cache-key fragment for the ambient mesh alone ('-' off-mesh).
    Distinguishes plans measured on N devices from single-device plans
    even when the linear itself ends up unsharded."""
    if mesh is None:
        return "-"
    return ".".join(f"{a}{s}" for a, s in mesh.shape.items())


def plan_shard_tag(shard: "ShardSpec | None", mesh) -> str:
    return shard.tag() if shard is not None else mesh_tag(mesh)


# ------------------------------------------------------------ derivation
def _quant_aligned(spec, k_local: int) -> bool:
    """Can the packed weight storage split at a k_local boundary?  Every
    per-shard view must be whole: scale blocks (scales columns), d-chunks
    (packed_idx columns) and code pairs (packed_u8 columns)."""
    if spec.mode == "bf16":
        return True
    if k_local % spec.scale_block:
        return False
    if k_local % int(spec.d):
        return False
    if spec.storage == "packed_u8" and k_local % 2:
        return False
    return True


def _collective_fallback(kind: str, **labels):
    """Count a downgraded collective layout (satellite of ISSUE 10: the
    reduce_scatter->psum fallback used to be silent)."""
    obs.registry().counter(
        "dispatch_shard_collective_fallback_total",
        help="shard derivations that downgraded the requested collective "
             "layout (reduce_scatter->psum, pipeline-chunk clamping)",
        kind=kind, **labels).inc()


def shard_spec_for(spec, axes, m: int, k: int, batch: int, mesh, *,
                   lead_batch: int | None = None,
                   collective: str = "psum",
                   rules: str = "default",
                   pipeline_chunks: int = 1,
                   collective_impl: str = "xla") -> ShardSpec | None:
    """Derive the ShardSpec for one linear, or None to stay under GSPMD.

    ``axes``: the weight's logical (out, in) axis names — the
    ``distributed.sharding.LINEAR_AXES`` entry for this linear's tag.
    Candidate mesh axes come from the activation table of the selected
    ``rules`` set (the TP table: heads / kvheads / mlp / vocab / ... ->
    'model'), the batch axis from its 'batch' rule ('pod' x 'data' —
    empty under 'serve_tp', which therefore never batch-shards); a
    candidate is taken only when the dim divides and (for k) the packed
    storage stays shard-aligned.

    ``pipeline_chunks``/``collective_impl`` request the pipelined
    contraction (ISSUE 10): the request is *clamped*, never rejected —
    the chunk count drops to the largest value that both divides the
    local k slice and keeps every packed-storage view (scales / idx /
    u8) whole per chunk, and both knobs normalize to the one-shot
    defaults for anything that is not k-sharded.  Every downgrade
    (including the pre-existing reduce_scatter->psum fallback when m
    does not divide the k axis) bumps
    ``dispatch_shard_collective_fallback_total``.

    Adaptive-d specs never shard: ``resolve_d`` keys off the *global*
    (in, out) dims the weights were quantized with, and a local-shape
    resolve could silently reinterpret the packed codes.
    """
    if mesh is None or axes is None or len(axes) != 2:
        return None
    if spec.mode != "bf16" and spec.d == "adaptive":
        return None
    out_ax, in_ax = axes
    act_rules = shd.RULE_SETS[rules][0]
    mesh_axes = tuple(mesh.shape.items())
    used: set[str] = set()

    def pick(logical, dim, *, need_alignment: bool):
        for cand in act_rules.get(logical, ()):
            size = mesh.shape.get(cand, 1)
            if size == 1 or cand in used or dim % size:
                continue
            if need_alignment and not _quant_aligned(spec, dim // size):
                continue
            used.add(cand)
            return cand
        return None

    m_axis = pick(out_ax, m, need_alignment=False)
    k_axis = None
    if m_axis is None:
        k_axis = pick(in_ax, k, need_alignment=True)
    if k_axis is not None and collective == "reduce_scatter" \
            and m % mesh.shape[k_axis]:
        collective = "psum"  # cannot scatter the output rows: fall back
        _collective_fallback("reduce_scatter_to_psum", axis=k_axis)
    pc, impl = 1, "xla"
    if k_axis is not None:
        impl = collective_impl if collective_impl in COLLECTIVE_IMPLS \
            else "xla"
        want = max(int(pipeline_chunks), 1)
        pc = want
        k_local = k // mesh.shape[k_axis]
        while pc > 1 and (k_local % pc
                          or not _quant_aligned(spec, k_local // pc)):
            pc -= 1
        if pc != want:
            _collective_fallback("pipeline_chunks_clamped", axis=k_axis,
                                 requested=want, clamped=pc)
    lead = batch if lead_batch is None else lead_batch
    b_axis = None
    for cand in act_rules.get("batch", ()):
        size = mesh.shape.get(cand, 1)
        if size == 1 or cand in used:
            continue
        if lead % size == 0 and batch % size == 0:
            b_axis = cand
            break
    if m_axis is None and k_axis is None and b_axis is None:
        return None
    return ShardSpec(mesh_axes=mesh_axes, m=m_axis, k=k_axis, batch=b_axis,
                     collective=collective, pipeline_chunks=pc,
                     collective_impl=impl)


# -------------------------------------------------------------- execution
def _param_specs(spec, params: dict, s: ShardSpec) -> dict:
    """Per-leaf PartitionSpecs for a linear's param dict.  All weight
    views share (m, k) orientation — their packed second dims split
    cleanly because shard_spec_for guarded the alignment; the codebook
    (16,) value table is replicated."""
    out = {}
    for name, leaf in params.items():
        if name == "codebook":
            out[name] = P(*([None] * leaf.ndim))
        else:
            out[name] = P(s.m, s.k)
    return out


def run_sharded(backend, spec, plan, params: dict, x, *, k: int, mesh,
                precision=None, epilogue=None, bias=None, residual=None,
                fuse: bool = False):
    """Run one planned linear under shard_map on ``mesh``.

    The inner call sees *local* shapes — exactly the shapes
    ``dispatch.plan`` planned tiles for — so per-shard LUT produce and
    per-shard VMEM accumulation follow from the unmodified kernels.
    With a k-sharded (row-parallel) linear the epilogue runs once after
    the contraction collective; otherwise it fuses into the kernel
    writeback per shard (disjoint m rows) whenever the backend can.

    Pipelined plans (``shard.pipeline_chunks > 1`` and/or
    ``collective_impl == 'ring'``) split the local contraction into
    k-chunks: the collective for chunk i (a ppermute ring under the ring
    impl, so each hop is an independently schedulable HLO) carries no
    data dependency on chunk i+1's produce/consume, letting the compiler
    slide the communication under the next chunk's compute.  Partials
    are double-buffered — the chunk whose collective is in flight
    (``pending``) is only folded into the accumulator after the *next*
    chunk's compute has been issued.

    Column-parallel (m-sharded) outputs are never gathered here:
    ``out_specs`` leaves them m-sharded, so the all-gather a consumer
    might need is deferred into that consumer's own produce phase (and
    vanishes entirely when the next linear is row-parallel — its k
    sharding *is* this layer's m sharding, the up-proj -> down-proj
    pattern).
    """
    s = plan.shard
    size = dict(s.mesh_axes)
    if any(mesh.shape.get(a) != n for a, n in s.mesh_axes) \
            or len(mesh.shape) != len(s.mesh_axes):
        raise ValueError(
            f"plan was sharded for mesh {dict(s.mesh_axes)} but the active "
            f"mesh is {dict(mesh.shape)}; re-plan under the current mesh")
    k_local = k // size.get(s.k, 1) if s.k else k
    pc = s.pipeline_chunks if s.k else 1
    k_chunk = k_local // pc
    inner_plan = dataclasses.replace(plan, shard=None)
    rank = x.ndim
    mid = (None,) * (rank - 2)
    # the m dim of y / bias / residual: m-sharded linears keep their own
    # axis; reduce_scatter hands the k axis over; psum replicates.
    out_m = s.m if s.k is None else (
        s.k if s.collective == "reduce_scatter" else None)

    operands = {"params": params, "x": x}
    in_specs = {"params": _param_specs(spec, params, s),
                "x": P(*((s.batch,) + mid + (s.k,)))}
    if bias is not None:
        operands["bias"] = bias
        in_specs["bias"] = P(out_m)
    if residual is not None:
        operands["residual"] = residual
        in_specs["residual"] = P(*((s.batch,) + mid + (out_m,)))
    out_specs = P(*((s.batch,) + mid + (out_m,)))

    # trace attribution: compute vs contraction collective, named by the
    # shard layout so a mesh trace splits step time between them.  The
    # marks are keyed to the *output* of each stage (data dependency, no
    # ordered side channel — safe under shard_map), and fire once per
    # device shard (per chunk when pipelined — the span overlap between
    # the two families is the measured comms/compute overlap).
    tagname = s.tag()
    mk_compute = f"shard.compute.{tagname}.k{k_chunk}"
    mk_coll = f"shard.collective.{s.collective}.{tagname}"

    def contract(y):
        """Resolve k-sharded partials with the planned collective."""
        n = size[s.k]
        if s.collective == "reduce_scatter":
            if s.collective_impl == "ring":
                return coll.ring_reduce_scatter(y, s.k, axis_size=n,
                                                dim=y.ndim - 1)
            return jax.lax.psum_scatter(y, s.k,
                                        scatter_dimension=y.ndim - 1,
                                        tiled=True)
        if s.collective_impl == "ring":
            return coll.ring_psum(y, s.k, axis_size=n)
        return jax.lax.psum(y, s.k)

    def compute_chunk(p_c, x_c):
        x_c = obs.jit_begin(x_c, mk_compute)
        y = backend.run(spec, inner_plan, p_c, x_c, k=k_chunk,
                        precision=precision)
        return obs.jit_end(y, mk_compute, cat="shard",
                           hist="shard_compute_s",
                           hist_labels={"tag": tagname})

    def collect_chunk(y):
        y = obs.jit_begin(y, mk_coll)
        y = contract(y)
        return obs.jit_end(y, mk_coll, cat="shard",
                           hist="shard_collective_s",
                           hist_labels={"collective": s.collective,
                                        "axis": s.k,
                                        "impl": s.collective_impl})

    def local(ops):
        b_l, r_l = ops.get("bias"), ops.get("residual")
        if s.k is None:
            x_l = obs.jit_begin(ops["x"], mk_compute)
            if fuse:
                y = backend.run(spec, inner_plan, ops["params"], x_l,
                                k=k_local, precision=precision,
                                epilogue=epilogue, bias=b_l, residual=r_l)
                return obs.jit_end(y, mk_compute, cat="shard",
                                   hist="shard_compute_s",
                                   hist_labels={"tag": tagname})
            y = backend.run(spec, inner_plan, ops["params"], x_l,
                            k=k_local, precision=precision)
            y = obs.jit_end(y, mk_compute, cat="shard",
                            hist="shard_compute_s",
                            hist_labels={"tag": tagname})
            return apply_epilogue(y, epilogue, bias=b_l, residual=r_l)
        # row-parallel: partial sums over the local k slice; the epilogue
        # must see the *resolved* sum, never the per-shard partials
        if pc == 1:
            y = compute_chunk(ops["params"], ops["x"])
            y = collect_chunk(y)
            return apply_epilogue(y, epilogue, bias=b_l, residual=r_l)
        d_pack = 1 if spec.mode == "bf16" else int(spec.d)
        sb_pack = 1 if spec.mode == "bf16" else int(spec.scale_block)
        p_chunks = kops.k_chunk_params(ops["params"], k=k_local, chunks=pc,
                                       d=d_pack, scale_block=sb_pack)
        x_chunks = jnp.split(ops["x"], pc, axis=-1)
        out = None      # partials whose collective has been retired
        pending = None  # the chunk whose collective is in flight
        for ci in range(pc):
            y_c = compute_chunk(p_chunks[ci], x_chunks[ci])
            if pending is not None:
                # retire the previous chunk only after this chunk's
                # compute is issued — the in-flight ring and the compute
                # above share no dataflow, so the scheduler overlaps them
                out = pending if out is None else out + pending
            pending = collect_chunk(y_c)
        y = pending if out is None else out + pending
        return apply_epilogue(y, epilogue, bias=b_l, residual=r_l)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(in_specs,),
                       out_specs=out_specs, check_vma=False)
    return fn(operands)

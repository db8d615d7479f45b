"""ExecPlan / ExecPolicy — the physical half of a quantized linear.

``plan(spec, m, k, batch) -> ExecPlan`` answers "how should THIS shape
run on THIS device": which registered backend, which VMEM tiles, which
consume chunking.  Plans come from three sources, in precedence order:

1. an explicit ``ExecPolicy.plan`` override (tests, power users);
2. the persistent autotune cache (shape-keyed winners measured by
   ``repro.dispatch.autotune`` and stored as JSON, so warm serving
   restarts skip retuning);
3. the shape heuristic (``kernels.ops`` tile picker) — exactly what the
   pre-registry code did, keeping default numerics identical.

Plans are frozen and hashable: they ride through ``jax.jit`` as static
closure state, and a (spec, plan) pair fully determines the lowered
kernel.  Plan resolution happens at trace time with concrete static
shapes — the serving engine pre-collects and warms every (shape, batch)
it will ever step so tracing only ever hits the cache.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, field, replace
from typing import NamedTuple

from repro import obs
from repro.core.spec import QuantSpec
from repro.dispatch import registry
from repro.dispatch.shard import (
    COLLECTIVE_IMPLS, COLLECTIVES, ShardSpec, plan_shard_tag,
    shard_spec_for,
)


ACC_DTYPES = ("float32", "bfloat16", "float16", "float64")


@dataclass(frozen=True)
class ExecPlan:
    """A frozen, hashable physical execution choice.

    backend : registered backend name (``repro.dispatch.registry``).
    tm, tj, tb : kernel tiles for Pallas backends — output rows, k-axis
        inner tile (j-chunks for msgemm, k elements for int4), batch
        columns.  None -> the kernel wrapper's heuristic.
    consume_chunk : j-chunks per consume scan step (jnp msgemm backend).
    acc_in_vmem : Pallas kernels accumulate in VMEM scratch with a single
        HBM writeback (the reordered produce-amortized msgemm grid);
        False selects the legacy per-step ``y_ref +=`` formulation (kept
        as a baseline and autotuner candidate).
    acc_dtype : accumulation dtype name for the Pallas kernels; part of
        the autotune cache key (a plan measured at one precision never
        serves another).
    epilogue : allow fusing a requested core.epilogue.Epilogue into the
        kernel's final writeback when the backend's capability predicate
        accepts it; False forces the unfused fallback (execute applies
        the same ops after the GeMM).
    interpret : Pallas execution mode; None auto-detects (compiled on
        TPU, interpreter elsewhere).
    shard : dispatch.shard.ShardSpec laying the GeMM out on the active
        mesh (m / k / batch mesh axes + contraction collective); None
        runs unsharded (or under plain GSPMD).  Like ``interpret`` it is
        a runtime overlay — derived from the ambient mesh at plan time,
        never persisted to the plan cache (the cache key carries the
        mesh/shard tag instead, and tm/tj/tb are planned and timed on
        the *local-shard* shapes).
    source : provenance tag — 'heuristic' | 'autotuned' | 'explicit';
        metadata only, excluded from equality/hash.
    """

    backend: str
    tm: int | None = None
    tj: int | None = None
    tb: int | None = None
    consume_chunk: int = 1
    acc_in_vmem: bool = True
    acc_dtype: str = "float32"
    epilogue: bool = True
    interpret: bool | None = None
    shard: ShardSpec | None = None
    source: str = field(default="heuristic", compare=False)

    def __post_init__(self):
        if self.consume_chunk < 1:
            raise ValueError(f"consume_chunk={self.consume_chunk} must be >= 1")
        if self.acc_dtype not in ACC_DTYPES:
            raise ValueError(f"acc_dtype={self.acc_dtype!r} must be one of "
                             f"{ACC_DTYPES}")


@dataclass(frozen=True)
class ExecPolicy:
    """Preferences that *steer* planning without naming exact tiles.

    backend : force a registered backend by name (None -> registry
        auto-selection by capability + priority).
    interpret / consume_chunk / acc_dtype : forwarded into heuristic
        plans (acc_dtype also keys the autotune cache).
    autotune : measure candidate tile configs for unseen shape keys and
        persist winners to the plan cache.  ``True`` uses the analytic
        perf model to prune the candidate sweep when a matching
        calibration exists (falling back to the full sweep otherwise);
        ``'full'`` always measures every candidate, ``'model'`` requires
        the model-guided path.  ``False`` disables tuning.
    shard_collective : how k-sharded (row-parallel) linears resolve
        their partial sums under a mesh: 'psum' | 'reduce_scatter'
        (see dispatch.shard.ShardSpec).
    shard_pipeline : contraction pipeline chunks for k-sharded linears.
        1 (default) is the classic one-collective-per-linear plan; N>1
        splits the local k slice into N chunks whose collectives overlap
        the next chunk's consume; 0 means *auto* — the autotuner times
        pipelined variants against the one-shot plan per linear and the
        measured winner (persisted in the plan cache's shard_variants
        table) is replayed on warm restarts.
    shard_impl : collective implementation for k-sharded linears:
        'xla' (fused psum/psum_scatter) | 'ring' (explicit ppermute
        hops, independently schedulable under compute).  Ignored when
        shard_pipeline == 0 (auto picks the impl too).
    plan : a fully explicit ExecPlan override (skips planning entirely).
    """

    backend: str | None = None
    interpret: bool | None = None
    consume_chunk: int = 1
    acc_dtype: str = "float32"
    autotune: bool | str = False
    shard_collective: str = "psum"
    shard_pipeline: int = 1
    shard_impl: str = "xla"
    plan: ExecPlan | None = None

    def __post_init__(self):
        if self.consume_chunk < 1:
            raise ValueError(f"consume_chunk={self.consume_chunk} must be >= 1")
        if self.acc_dtype not in ACC_DTYPES:
            raise ValueError(f"acc_dtype={self.acc_dtype!r} must be one of "
                             f"{ACC_DTYPES}")
        if self.autotune not in (False, True, "model", "full"):
            raise ValueError(f"autotune={self.autotune!r} must be one of "
                             f"False, True, 'model', 'full'")
        if self.shard_collective not in COLLECTIVES:
            raise ValueError(f"shard_collective={self.shard_collective!r} "
                             f"must be one of {COLLECTIVES}")
        if self.shard_pipeline < 0:
            raise ValueError(f"shard_pipeline={self.shard_pipeline} must "
                             f"be >= 0 (0 = autotuned)")
        if self.shard_impl not in COLLECTIVE_IMPLS:
            raise ValueError(f"shard_impl={self.shard_impl!r} must be one "
                             f"of {COLLECTIVE_IMPLS}")


DEFAULT_POLICY = ExecPolicy()
_default_policy: ExecPolicy = DEFAULT_POLICY


def set_default_policy(policy: ExecPolicy | None) -> None:
    """Install the process-wide default ExecPolicy (None resets).  CLI
    flags (``launch/serve --backend/--autotune``) land here so the choice
    reaches every linear without threading a new argument through the
    model stack."""
    global _default_policy
    _default_policy = policy or DEFAULT_POLICY


def get_default_policy() -> ExecPolicy:
    return _default_policy


@contextlib.contextmanager
def using_policy(policy: ExecPolicy | None):
    """Scoped default policy (the serving engine wraps its jitted step
    calls so the policy is active exactly while tracing)."""
    if policy is None:
        yield
        return
    prev = _default_policy
    set_default_policy(policy)
    try:
        yield
    finally:
        set_default_policy(prev)


# ------------------------------------------------------- plan collection
class PlanRequest(NamedTuple):
    """One collected plan() call: GLOBAL shapes + the derived shard.
    ``warm`` recomputes the local-shard shapes and cache key from these,
    so a collected request resolves to exactly the plan the later trace
    will ask for."""

    spec: QuantSpec
    m: int
    k: int
    batch: int
    backend: str
    shard: "ShardSpec | None" = None
    tag: str = "-"


_collector: list | None = None


@contextlib.contextmanager
def collecting():
    """Record every plan request made while active (autotuning is
    suppressed).  The engine runs an abstract ``jax.eval_shape`` of its
    step under this to enumerate the exact (spec, m, k, batch) keys it
    will trace, then warms them concretely — plans resolved once at
    engine build, never mid-step."""
    global _collector
    prev, _collector = _collector, []
    try:
        yield _collector
    finally:
        _collector = prev


def _tracing_active() -> bool:
    """True while inside a jax trace (jit/eval_shape/...).  Autotuning is
    impossible there: omnistaging stages every jnp op into the ambient
    trace, so 'timing' a candidate would just grow the traced graph (and
    crash converting tracers to numpy).  plan() falls back to the
    heuristic; callers that want tuned plans pre-warm the cache outside
    the trace (collecting() + warm(), as the engine and serve CLI do)."""
    import jax
    import jax.numpy as jnp

    # a throwaway op is staged into the ambient trace iff one is active
    return isinstance(jnp.zeros(()), jax.core.Tracer)


# ---------------------------------------------------------------- keys
def plan_d(spec: QuantSpec, m: int, k: int) -> int:
    """The depth that keys plans/capabilities for this (spec, shape):
    the resolved LUT depth for msgemm, the (irrelevant but stable)
    declared d otherwise, 0 for adaptive non-msgemm."""
    if spec.mode == "msgemm":
        return spec.resolve_d(k, m)
    return int(spec.d) if isinstance(spec.d, int) else 0


def plan_key(backend: str, spec: QuantSpec, d: int, m: int, k: int,
             batch: int, device: str, acc_dtype: str = "float32",
             shard: str = "-") -> str:
    """Shape key for the persistent autotune cache.  ``acc_dtype`` is
    part of the key: a winner measured at one accumulation precision is
    never served to a caller asking for another.  ``shard`` is the
    mesh/shard tag (dispatch.shard.plan_shard_tag) and m/k/batch are the
    *local-shard* shapes: a plan measured on one device is never
    replayed as a sharded plan on a mesh, nor vice versa — different
    mesh shapes key (and time) independently."""
    return (f"{device}|{backend}|{spec.mode}|d{d}|sb{spec.scale_block}|"
            f"{spec.storage}|cb{spec.codebook}|m{m}|k{k}|b{batch}|"
            f"acc{acc_dtype}|sh{shard}")


# ------------------------------------------------------------ heuristics
def heuristic_plan(spec: QuantSpec, d: int, m: int, k: int, batch: int,
                   backend: str, policy: ExecPolicy) -> ExecPlan:
    """The shape-heuristic tile/chunk choices, as an explicit plan.

    Small-batch (decode) shapes get their presets through
    ``ops.msgemm_tiles``: tb is sized to the actual batch (round_up(b, 8),
    never padded to 128) and the LUT budget freed by the narrow stripe
    lets tj — and for decode shapes tm — grow, which is where the
    produce-amortized kernel wins hardest (large-m, small-b)."""
    from repro.kernels import ops

    if backend == "msgemm_pallas":
        kc = math.ceil(k / d)
        tm, tj, tb = ops.msgemm_tiles(m, kc, batch, d, spec.scale_block)
        return ExecPlan(backend=backend, tm=tm, tj=tj, tb=tb,
                        # vocab-sized m can't hold a VMEM stripe: plan the
                        # legacy accumulation up front (the ops wrapper
                        # guards the same condition as a backstop)
                        acc_in_vmem=ops.acc_stripe_fits(m, tm, tb),
                        acc_dtype=policy.acc_dtype,
                        interpret=policy.interpret)
    if backend == "int4_pallas":
        tm, tk, tb = ops.int4_tiles(m, k, batch, spec.scale_block)
        return ExecPlan(backend=backend, tm=tm, tj=tk, tb=tb,
                        acc_dtype=policy.acc_dtype,
                        interpret=policy.interpret)
    if backend == "msgemm_jnp":
        return ExecPlan(backend=backend, consume_chunk=policy.consume_chunk)
    return ExecPlan(backend=backend)


# ------------------------------------------------------------------ plan
def plan(spec: QuantSpec, m: int, k: int, batch: int = 1, *,
         device: str | None = None, policy: ExecPolicy | None = None,
         shard_axes: tuple | None = None, lead_batch: int | None = None
         ) -> ExecPlan:
    """Resolve the physical execution for one (spec, shape) cell.

    m/k are the linear's GLOBAL (out, in) dims; ``batch`` the flattened
    activation row count.  All static Python ints — safe at trace time.

    ``shard_axes``: the weight's logical (out, in) axis names (the
    ``distributed.sharding.LINEAR_AXES`` entry for this linear's tag).
    With an active mesh (``distributed.sharding.use``) they derive the
    plan's ShardSpec, and tile heuristics / cache lookups / autotuning
    all run on the **local-shard** shapes — what one device actually
    executes under TP.  ``lead_batch``: the activations' leading dim
    (what the batch mesh axis shards); defaults to ``batch``.
    """
    policy = policy or get_default_policy()
    if policy.plan is not None:
        return policy.plan
    device = device or registry.device_kind()
    d = plan_d(spec, m, k)

    from repro.distributed.sharding import active_mesh, active_rules

    mesh = active_mesh()
    # shard_pipeline == 0 (auto) derives the one-shot base layout first;
    # the tuned (chunks, impl) winner — if the cache has one — replaces
    # it below, once the backend (part of the variant key) is known.
    shard = shard_spec_for(spec, shard_axes, m, k, batch, mesh,
                           lead_batch=lead_batch,
                           collective=policy.shard_collective,
                           rules=active_rules(),
                           pipeline_chunks=max(policy.shard_pipeline, 1),
                           collective_impl=(
                               policy.shard_impl
                               if policy.shard_pipeline != 0 else "xla"))
    if shard is not None and not shard.is_sharded:
        shard = None
    tag = plan_shard_tag(shard, mesh)
    lm, lk, lb = shard.exec_mkb(m, k, batch) if shard else (m, k, batch)

    be = None
    if policy.backend is not None:
        forced = registry.get_backend(policy.backend)
        # a forced backend applies only to specs it can execute; other
        # linears fall back to auto-selection.  This mirrors the shim's
        # impl= semantics (it only ever forced msgemm-mode linears) and
        # keeps model-wide --backend flags working on models that mix
        # modes per layer (MoE experts run int4_dequant inside an
        # msgemm model).
        # a quarantined forced backend degrades to auto-selection —
        # same ladder the NaN guard / watchdog escalation rely on
        if forced.supports(spec, d) and not registry.is_quarantined(
                forced.name):
            be = forced
    if be is None:
        be = registry.select_backend(spec, d, device)

    if _collector is not None:
        # collection is an abstract dry run — its plan() calls are not
        # real resolutions, so they stay out of the telemetry
        _collector.append(PlanRequest(spec, m, k, batch, be.name, shard, tag))
        return replace(heuristic_plan(spec, d, lm, lk, lb, be.name, policy),
                       shard=shard)

    reg = obs.registry()
    reg.counter("dispatch_backend_selected_total",
                help="plan resolutions per backend",
                backend=be.name).inc()

    import repro.dispatch.autotune as at

    if policy.shard_pipeline == 0 and shard is not None \
            and shard.k is not None:
        var = at.cache().shard_variant(
            plan_key(be.name, spec, d, lm, lk, lb, device,
                     policy.acc_dtype, tag))
        if var is not None:
            shard = shard_spec_for(
                spec, shard_axes, m, k, batch, mesh,
                lead_batch=lead_batch,
                collective=policy.shard_collective,
                rules=active_rules(),
                pipeline_chunks=int(var["pipeline_chunks"]),
                collective_impl=str(var["collective_impl"]))
            tag = plan_shard_tag(shard, mesh)
            lm, lk, lb = (shard.exec_mkb(m, k, batch) if shard
                          else (m, k, batch))

    cached = at.cache().get(plan_key(be.name, spec, d, lm, lk, lb, device,
                                     policy.acc_dtype, tag))
    reg.counter("dispatch_plan_cache_total",
                help="persistent plan-cache lookups",
                result="hit" if cached is not None else "miss").inc()
    if cached is not None:
        # interpret and shard are runtime/policy choices, not tunables:
        # the current policy/mesh always wins over whatever the plan was
        # measured under (None -> per-backend auto-detect), so an
        # interpret-mode tuning run can never pin the interpreter onto
        # later compiled runs, and a plan tuned on the local-shard
        # shapes re-attaches to the live mesh on every hit.
        return replace(cached, interpret=policy.interpret, shard=shard)

    if policy.autotune and be.tunable and not _tracing_active():
        search = (policy.autotune
                  if policy.autotune in ("model", "full") else "auto")
        return replace(
            at.autotune(spec, lm, lk, lb, be.name, device=device,
                        interpret=policy.interpret,
                        acc_dtype=policy.acc_dtype, tag=tag,
                        search=search),
            shard=shard)
    return replace(heuristic_plan(spec, d, lm, lk, lb, be.name, policy),
                   shard=shard)

"""repro.dispatch — pluggable GeMM execution behind a stable front-end.

The three-layer split (see core/spec.py):

* ``QuantSpec`` (core.spec) says *what the weights are*;
* this package's **registry** holds the physical execution paths
  (dense MXU, jnp produce/consume msGeMM, fused Pallas msGeMM, int4
  dequant jnp + Pallas) as capability-scoped peers;
* ``plan()`` maps (spec, m, k, batch, device) to a frozen **ExecPlan**
  via heuristic or the persistent **autotuner**; ``execute()`` runs one
  linear through its plan.

``core.linear.apply`` is a thin wrapper over :func:`execute`; every
model linear in every architecture routes through here.
"""

from __future__ import annotations

import dataclasses
import math

from repro import obs
from repro.core.epilogue import Epilogue, apply_epilogue  # noqa: F401
from repro.core.spec import QuantSpec, as_spec
from repro.dispatch.registry import (  # noqa: F401
    Backend, available_backends, backend_names, clear_quarantine,
    device_kind, get_backend, is_quarantined, quarantine_backend,
    quarantined, register_backend, select_backend, unregister_backend,
)
from repro.dispatch.plan import (  # noqa: F401
    DEFAULT_POLICY, ExecPlan, ExecPolicy, PlanRequest, collecting,
    get_default_policy, heuristic_plan, plan, plan_d, plan_key,
    set_default_policy, using_policy,
)
from repro.dispatch.shard import (  # noqa: F401
    ShardSpec, mesh_tag, plan_shard_tag, shard_spec_for,
)
from repro.dispatch import shard as _shard
from repro.dispatch import backends as _backends  # noqa: F401  (registers)
# NOTE: the tuner *function* lives at dispatch.autotune.autotune — the
# bare name is not re-exported so the ``autotune`` submodule stays
# addressable as dispatch.autotune.
from repro.dispatch.autotune import (  # noqa: F401
    PlanCache, cache, default_cache_path, set_cache_path, warm,
)


def split(cfg) -> tuple[QuantSpec, ExecPolicy | None]:
    """(spec, policy) from a QuantSpec (no policy) or a deprecated
    QuantConfig shim (which carries one)."""
    if isinstance(cfg, QuantSpec):
        return cfg, None
    spec = getattr(cfg, "spec", None)
    pol = getattr(cfg, "policy", None)
    if isinstance(spec, QuantSpec):
        return spec, pol
    raise TypeError(f"expected QuantSpec or QuantConfig, got {type(cfg)!r}")


def execute(params: dict, x, cfg, *, in_dim: int | None = None,
            precision=None, plan_override: ExecPlan | None = None,
            policy: ExecPolicy | None = None, epilogue: Epilogue | None = None,
            bias=None, residual=None, shard_axes: tuple | None = None):
    """Run one linear ``x (..., k) -> y (..., m)`` through the registry.

    Precedence for execution choices: explicit ``plan_override`` >
    ``policy`` argument > policy embedded in a QuantConfig shim >
    process default policy (``set_default_policy`` / CLI flags).

    ``epilogue`` (core.epilogue.Epilogue) describes the element-wise tail
    ``y = act(y + bias) + residual`` (then cast).  When the plan allows
    fusion (``plan.epilogue``) and the backend's capability predicate
    accepts the spec, the tail executes inside the kernel's final VMEM
    writeback — zero extra HBM passes; otherwise the same op sequence
    runs unfused after ``run`` (apply_epilogue, computed at f32-or-better
    like the fused accumulator).  For f32 activations the two routes are
    the same function; at lower activation precision they can differ by
    final-rounding ulps (the unfused route sees the GeMM output after
    its activation-dtype cast).  ``bias`` is (m,); ``residual`` matches
    the output shape (..., m) — both row-major model layout.

    ``shard_axes`` (the weight's logical (out, in) axis names) makes the
    linear mesh-aware: under an active mesh the resolved plan carries a
    ShardSpec and the backend runs inside a shard_map — per-shard LUT
    produce / VMEM accumulation, one contraction collective, the
    epilogue applied after it (dispatch.shard.run_sharded).
    """
    from repro.core import linear as _linear

    spec, cfg_policy = split(cfg)
    policy = policy or cfg_policy or get_default_policy()
    k = in_dim if in_dim is not None else _linear._infer_k(params, spec)
    m = (params["w"].shape[0] if spec.mode == "bf16"
         else params["scales"].shape[0])
    batch = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    p = plan_override
    if p is None:
        lead = x.shape[0] if x.ndim > 1 else 1
        p = plan(spec, m, k, batch, policy=policy,
                 shard_axes=shard_axes if x.ndim > 1 else None,
                 lead_batch=lead)
    be = get_backend(p.backend)
    d = plan_d(spec, m, k)
    # full capability check — matters for explicit plans (plan_override /
    # ExecPolicy.plan), which bypass plan()'s selection: e.g. int4_pallas
    # would silently dequantize a learned codebook with the uniform grid
    if not be.supports(spec, d):
        raise ValueError(
            f"plan backend {be.name!r} cannot execute mode={spec.mode!r} "
            f"d={d} storage={spec.storage!r} codebook={spec.codebook!r} "
            f"(modes={be.modes}, d_range={be.d_range}, "
            f"storages={be.storages}, codebooks={be.codebooks})")
    # a bias/residual array without a matching Epilogue flag would be
    # silently ignored by both the fused and unfused paths — reject it
    # (the inverse mismatch, flag without array, already raises)
    if bias is not None and (epilogue is None or not epilogue.bias):
        raise ValueError(
            "bias array given but the epilogue does not declare bias=True "
            "(pass epilogue=Epilogue(bias=True, ...) — or use "
            "common.linear_apply, which builds it for you)")
    if residual is not None and (epilogue is None or not epilogue.residual):
        raise ValueError(
            "residual array given but the epilogue does not declare "
            "residual=True (pass epilogue=Epilogue(residual=True, ...) — "
            "or use common.linear_apply, which builds it for you)")
    fuse = (epilogue is not None and not epilogue.is_identity
            and p.epilogue and be.epilogue_ok(epilogue))
    if epilogue is not None and not epilogue.is_identity:
        # fusion *rate* = fused / (fused + unfused); counted per traced
        # call site, which is once per (shape, phase) executable
        obs.registry().counter(
            "dispatch_epilogue_total",
            help="non-identity epilogues by fused/unfused execution",
            fused="true" if fuse else "false").inc()
    if spec.mode == "msgemm":
        obs.registry().counter(
            "dispatch_backend_total",
            help="msgemm-mode linears by the backend that runs them",
            backend=be.name).inc()
    sharded = p.shard is not None and p.shard.is_sharded
    if sharded or not be.partitionable:
        from repro.distributed.sharding import active_mesh

        mesh = active_mesh()
        if mesh is not None:
            if not sharded:  # a kernel XLA cannot partition: replicate it
                p = dataclasses.replace(p, shard=_shard.ShardSpec(
                    mesh_axes=tuple(mesh.shape.items())))
            return _shard.run_sharded(
                be, spec, p, params, x, k=k, mesh=mesh, precision=precision,
                epilogue=epilogue, bias=bias, residual=residual, fuse=fuse)
        # a sharded plan without a live mesh (explicit override outside
        # sharding.use): fall through and run unsharded on local math
    if fuse:
        return be.run(spec, p, params, x, k=k, precision=precision,
                      epilogue=epilogue, bias=bias, residual=residual)
    y = be.run(spec, p, params, x, k=k, precision=precision)
    return apply_epilogue(y, epilogue, bias=bias, residual=residual)

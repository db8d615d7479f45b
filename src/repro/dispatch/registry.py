"""Pluggable execution-backend registry.

One logical GeMM admits many physical executions (paper Eq. 15: the best
choice depends on shape and hardware).  Each execution path registers
here as a peer with capability predicates; selection is deterministic —
highest priority among the available backends that can run the spec,
ties broken by name.

A backend's ``run`` callable has the uniform signature::

    run(spec, plan, params, x, *, k, precision=None) -> y

with ``x (..., k)`` row-major activations and ``y (..., m)`` — the
convention of ``core.linear.apply``.  New backends (CPU/GPU Pallas
variants, XLA int8, ...) plug in via :func:`register_backend` without
touching ``core.linear`` or any model code.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import jax

from repro.core.spec import QuantSpec


def _always(device_kind: str) -> bool:
    return True


def _no_epilogue(epilogue) -> bool:
    """Default epilogue capability: fuse nothing (dispatch.execute applies
    the epilogue unfused after ``run`` — core.epilogue.apply_epilogue)."""
    return False


@dataclass(frozen=True)
class Backend:
    """A registered execution path with its capability envelope."""

    name: str
    modes: tuple[str, ...]            # quant modes it can execute
    run: Callable                      # run(spec, plan, params, x, *, k, ...)
    is_available: Callable[[str], bool] = _always  # device kind -> bool
    # higher wins in auto-selection; an int, or a callable(device_kind)
    # for device-dependent ranking (msgemm_pallas outranks the jnp scan
    # on real TPU but not in CPU interpret mode)
    priority: int | Callable[[str], int] = 0
    d_range: tuple[int, int] = (1, 4)  # inclusive LUT-depth envelope
    storages: tuple[str, ...] = ("packed_idx", "packed_u8")
    codebooks: tuple[str, ...] = ("none", "learned")
    tunable: tuple[str, ...] = ()      # ExecPlan fields the autotuner explores
    # epilogue capability predicate: can this backend execute the given
    # core.epilogue.Epilogue *inside* its kernel (fused into the final
    # writeback)?  False -> dispatch.execute applies it unfused after run.
    epilogue_ok: Callable = _no_epilogue
    # can XLA partition ``run``'s ops under GSPMD?  A compiled Pallas
    # kernel is an opaque Mosaic custom call that it cannot partition, so
    # under a mesh dispatch.execute runs it inside shard_map, replicated
    # when the plan itself is unsharded.
    partitionable: bool = True
    description: str = ""

    def priority_for(self, device_kind: str) -> int:
        return self.priority(device_kind) if callable(self.priority) \
            else self.priority

    def supports(self, spec: QuantSpec, d: int) -> bool:
        """Can this backend execute weights described by ``spec`` at depth d?"""
        if spec.mode not in self.modes:
            return False
        if spec.storage not in self.storages:
            return False
        if spec.codebook not in self.codebooks:
            return False
        if spec.mode == "msgemm" and not self.d_range[0] <= d <= self.d_range[1]:
            return False
        return True


_REGISTRY: dict[str, Backend] = {}

# Runtime quarantine: backend name -> reason.  A quarantined backend is
# skipped by auto-selection and by forced-policy resolution so a
# misbehaving execution path (NaN logits, watchdog hang) degrades to the
# next backend on the ladder instead of crashing the server.  Quarantine
# is process-local, never persisted, and cleared by clear_quarantine().
_QUARANTINED: dict[str, str] = {}


def quarantine_backend(name: str, reason: str = "") -> None:
    """Mark a backend suspect; selection skips it until cleared.  The
    dense fallback ladder guarantees a safe backend always remains, but
    if quarantine would leave a spec with zero candidates, selection
    ignores the quarantine rather than fail (see available_backends)."""
    get_backend(name)  # raise on unknown names
    _QUARANTINED[name] = reason or "quarantined"
    from repro import obs
    obs.registry().counter(
        "dispatch_backend_quarantined_total", backend=name).inc()
    obs.registry().gauge("dispatch_backends_quarantined").set(
        len(_QUARANTINED))


def clear_quarantine(name: str | None = None) -> None:
    """Lift quarantine for one backend, or all when name is None."""
    if name is None:
        _QUARANTINED.clear()
    else:
        _QUARANTINED.pop(name, None)
    from repro import obs
    obs.registry().gauge("dispatch_backends_quarantined").set(
        len(_QUARANTINED))


def is_quarantined(name: str) -> bool:
    return name in _QUARANTINED


def quarantined() -> dict[str, str]:
    """Snapshot of the current quarantine list (name -> reason)."""
    return dict(_QUARANTINED)


def register_backend(name: str, *, modes, run, is_available=_always,
                     priority: int = 0, d_range=(1, 4),
                     storages=("packed_idx", "packed_u8"),
                     codebooks=("none", "learned"), tunable=(),
                     epilogue_ok=_no_epilogue, partitionable: bool = True,
                     description: str = "", overwrite: bool = False) -> Backend:
    """Register an execution backend.  Raises on duplicate names unless
    ``overwrite`` (tests use overwrite to shadow a backend temporarily)."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered; "
                         "pass overwrite=True to replace it")
    be = Backend(name=name, modes=tuple(modes), run=run,
                 is_available=is_available, priority=priority,
                 d_range=tuple(d_range), storages=tuple(storages),
                 codebooks=tuple(codebooks), tunable=tuple(tunable),
                 epilogue_ok=epilogue_ok, partitionable=partitionable,
                 description=description)
    _REGISTRY[name] = be
    return be


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def backend_names() -> list[str]:
    return sorted(_REGISTRY)


def device_kind() -> str:
    """The platform string auto-selection keys on ('cpu'|'gpu'|'tpu')."""
    return jax.default_backend()


def available_backends(spec: QuantSpec, d: int, device: str | None = None
                       ) -> list[Backend]:
    """Backends that can run ``spec`` on ``device``, best-first
    (priority desc, then name — fully deterministic)."""
    dev = device or device_kind()
    cands = [b for b in _REGISTRY.values()
             if b.supports(spec, d) and b.is_available(dev)]
    if _QUARANTINED:
        healthy = [b for b in cands if b.name not in _QUARANTINED]
        # never quarantine into an empty candidate set — serving a
        # suspect backend beats serving nothing
        cands = healthy or cands
    return sorted(cands, key=lambda b: (-b.priority_for(dev), b.name))


def select_backend(spec: QuantSpec, d: int, device: str | None = None
                   ) -> Backend:
    """Deterministic auto-selection: highest-priority capable backend."""
    cands = available_backends(spec, d, device)
    if not cands:
        raise ValueError(
            f"no backend can execute mode={spec.mode!r} d={d} "
            f"storage={spec.storage!r} codebook={spec.codebook!r} on "
            f"{device or device_kind()!r}; registered: {backend_names()}")
    return cands[0]

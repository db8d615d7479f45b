"""Observability layer: metrics registry, span tracer, cost model.

One import surface for the rest of the repo::

    from repro import obs

    obs.registry().counter("dispatch_plan_cache_total",
                           result="hit").inc()
    with obs.tracer().span("engine.fetch"):
        ...

Counters are attribute bumps; a span is a profiler annotation plus one
ring append (``obs.trace``), always on, and nothing is ever staged into
jitted code.  Importing this package registers the listener that counts
backend compiles into ``jax_compiles_total``.
"""

from repro.obs import costs  # noqa: F401  (re-export module)
from repro.obs import perfmodel  # noqa: F401  (re-export module)


def __getattr__(name):
    # lazy: obs.artifacts imports repro.obs back for the registry, so a
    # top-level import here would be circular
    if name == "artifacts":
        import importlib
        return importlib.import_module("repro.obs.artifacts")
    raise AttributeError(name)
from repro.obs.metrics import (  # noqa: F401
    Registry,
    SNAPSHOT_SCHEMA_VERSION,
    registry,
    serve_prometheus,
    validate_snapshot,
    validate_snapshot_file,
)
from repro.obs.trace import (  # noqa: F401
    RING_SPANS,
    Span,
    Tracer,
    compiles,
    tracer,
)

__all__ = [
    "Registry", "registry", "serve_prometheus",
    "validate_snapshot", "validate_snapshot_file",
    "SNAPSHOT_SCHEMA_VERSION",
    "Span", "Tracer", "tracer", "compiles", "RING_SPANS",
    "costs", "perfmodel",
]

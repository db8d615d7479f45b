"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; tests and benches must keep seeing 1 device.
"""

from __future__ import annotations

import os

import jax


def force_host_devices(n: int) -> None:
    """Fake ``n`` host CPU devices via XLA_FLAGS.  Only effective before
    jax first touches the backend — the CLI entry points call this from
    argument handling, ahead of any device use."""
    if n:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; 'pod' is the DCN
    axis (data-parallel only), 'model' stays intra-pod ICI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (elastic re-scale, tests on host devices).

    Unlike ``jax.make_mesh`` this accepts a shape smaller than the
    visible device count (a 2x2 sweep entry on an 8-device host uses the
    first 4 devices) — what the serve/bench ``--mesh`` sweeps need.

    Every axis is ``Auto``: the model code lays arrays out with
    ``with_sharding_constraint`` under GSPMD, which ``jax.make_mesh``'s
    default ``Explicit`` axes turn into layout assertions."""
    import numpy as np
    from jax.sharding import AxisType

    shape, axes = tuple(shape), tuple(axes)
    n = int(np.prod(shape))
    auto = (AxisType.Auto,) * len(axes)
    devs = jax.devices()
    if n < len(devs):
        from jax.sharding import Mesh

        return Mesh(np.asarray(devs[:n]).reshape(shape), axes,
                    axis_types=auto)
    return jax.make_mesh(shape, axes, axis_types=auto)


def mesh_devices(mesh) -> int:
    import numpy as np

    return int(np.prod(list(mesh.shape.values())))

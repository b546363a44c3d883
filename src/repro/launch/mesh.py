"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required for the dry-run's
forced-host-device trick and for tests that expect 1 CPU device.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: jax defaults to
    ``Explicit`` axes, under which sharded steps refuse implicit
    resharding."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model_axis: int | None = None):
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    m = model_axis or 1
    assert n % m == 0
    return _make_mesh((n // m, m), ("data", "model"))


def elastic_mesh_shape(n_devices: int, model_axis: int = 16):
    """Largest (pod, data, model) grid on surviving devices (fault path).

    Keeps the model axis intact (resharding TP state is the expensive
    direction); shrinks data parallelism to what survives.
    """
    while model_axis > 1 and n_devices % model_axis:
        model_axis //= 2
    data = max(n_devices // model_axis, 1)
    return (data, model_axis), ("data", "model")

"""Production mesh builders.

Functions (not module-level constants) so that importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    # Auto axes: shardings propagate through the SPMD partitioner from the
    # jit in/out shardings (jax.make_mesh defaults to Explicit axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """A (data, model) mesh over the first data * model local devices.
    Raises when fewer devices exist — a mesh is never silently shrunk."""
    n = len(jax.devices())
    if data < 1 or model < 1 or data * model > n:
        raise ValueError(
            f"mesh data={data} x model={model} needs {data * model} "
            f"devices; {n} present")
    return _make_mesh((data, model), ("data", "model"))

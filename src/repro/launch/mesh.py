"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization, and smoke
tests/benches must keep seeing 1 CPU device.

Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods = 512 chips
as (pod=2, data=16, model=16); the pod axis is the outer data-parallel/FSDP
axis (gradient all-reduce crosses the pod interconnect; see sharding/rules).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    """Mesh whose axes are all ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to explicit axis types, under which the
    compiler refuses the slab code's slices of sharded dims and the rule
    tables' repeated axis names.  Every mesh in the repo is built here.
    """
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes), devices=devices
    )


def make_host_mesh(model_parallel: Optional[int] = None):
    """Mesh over whatever devices exist locally (tests / CPU examples)."""
    n = len(jax.devices())
    mp = model_parallel or 1
    assert n % mp == 0
    return make_mesh((n // mp, mp), ("data", "model"))


def data_axes(mesh) -> Tuple[str, ...]:
    """All data-parallel axes of a mesh (pod is outer DP when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)

"""Shared (rows, 128) plane tiling for the VPU-aligned Pallas kernel suites.

Every elementwise kernel in :mod:`repro.kernels` (fcube, scube, rfft)
flattens arbitrary-rank tensors into ``(rows, LANES)`` float planes with
``rows`` padded to a block multiple, and reassembles afterwards.  The
padding contract lives HERE, once: data pads with zeros (never a violation
under a positive bound), pointwise bounds pad with ``+inf`` (padded lanes
never clip or count), and weight planes pad with zeros (padded lanes never
count).  ``is_cpu`` is the shared interpret-mode default probe.  The
``count_*`` helpers give every kernel's per-grid-step violation count the
same TPU-legal output tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: VPU lane width shared by every (rows, 128) kernel tile.
LANES = 128

#: Per-grid-step tile that holds one int32 count.  The TPU compiler refuses a
#: rank-1 ``(1,)`` block; (8, 128) is the smallest tile its layout accepts.
COUNT_TILE = (8, LANES)


def is_cpu() -> bool:
    """Default interpret-mode probe: emulate kernels off-TPU."""
    return jax.default_backend() == "cpu"


def count_spec() -> pl.BlockSpec:
    """Output BlockSpec of the per-step count: grid step ``i`` owns tile ``i``."""
    return pl.BlockSpec(COUNT_TILE, lambda i: (i, 0))


def count_shape(n_steps: int) -> jax.ShapeDtypeStruct:
    """Output shape of the per-step counts over a 1-D grid of ``n_steps``."""
    return jax.ShapeDtypeStruct((n_steps * COUNT_TILE[0], LANES), jnp.int32)


def store_count(ref, count) -> None:
    """Write ``count`` at [0, 0] of the step's tile and zeros elsewhere, so
    the sum over the whole output is the total count."""
    row = jax.lax.broadcasted_iota(jnp.int32, COUNT_TILE, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, COUNT_TILE, 1)
    ref[...] = jnp.where((row == 0) & (lane == 0), count, 0)


def tile(x: jnp.ndarray, block_rows: int):
    """Flatten to (rows, 128) with rows % block_rows == 0; returns (tiled, pad)."""
    flat = x.reshape(-1)
    chunk = block_rows * LANES
    pad = (-flat.size) % chunk
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), pad


def tile_bound(b: jnp.ndarray, shape, block_rows: int, pad: int):
    """Tile a pointwise bound, padding with +inf so pad lanes never clip/count."""
    t, _ = tile(jnp.broadcast_to(b, shape).astype(jnp.float32), block_rows)
    if pad:
        t = t.reshape(-1).at[-pad:].set(jnp.inf).reshape(-1, LANES)
    return t


def untile(t: jnp.ndarray, shape, pad: int):
    """Inverse of :func:`tile`: strip the pad and restore ``shape``."""
    flat = t.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape)

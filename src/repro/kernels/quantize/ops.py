"""jit'd wrapper for the QuantizeEdits kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.quantize.kernel import BLOCK_ROWS, LANES, quantize_pallas
from repro.kernels.tiling import is_cpu as _is_cpu


@functools.partial(jax.jit, static_argnames=("m", "block_rows", "interpret"))
def quantize_edits(
    values: jnp.ndarray,
    bound,
    m: int = 16,
    block_rows: int = BLOCK_ROWS,
    interpret: bool | None = None,
):
    """Quantize an edit tensor on the 2^m cube grid; returns (codes, flags)."""
    if interpret is None:
        interpret = _is_cpu()
    shape = values.shape
    flat = values.astype(jnp.float32).reshape(-1)
    chunk = block_rows * LANES
    pad = (-flat.size) % chunk
    flat = jnp.pad(flat, (0, pad))
    tiled = flat.reshape(-1, LANES)
    b_arr = jnp.asarray(bound, dtype=jnp.float32)
    pointwise = b_arr.ndim > 0
    if pointwise:
        bf = jnp.pad(jnp.broadcast_to(b_arr, shape).astype(jnp.float32).reshape(-1), (0, pad))
        b_in = bf.reshape(-1, LANES)
    else:
        b_in = b_arr.reshape(1, 1)
    codes, flags = quantize_pallas(
        tiled, b_in, m=m, pointwise=pointwise, interpret=interpret, block_rows=block_rows
    )

    def untile(t):
        f = t.reshape(-1)
        if pad:
            f = f[:-pad]
        return f.reshape(shape)

    return untile(codes), untile(flags)

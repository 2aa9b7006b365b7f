"""jit'd wrapper for the blockwise transform+quantize kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.block_transform.kernel import BLOCK_ROWS, block_transform_pallas
from repro.kernels.tiling import is_cpu as _is_cpu


@functools.partial(jax.jit, static_argnames=("q", "block_rows", "interpret"))
def block_transform_quantize(
    blocks: jnp.ndarray,
    matrix: jnp.ndarray,
    q: float,
    block_rows: int = BLOCK_ROWS,
    interpret: bool | None = None,
):
    """(nb, B) blocks -> int32 codes via one fused GEMM+quantize kernel."""
    if interpret is None:
        interpret = _is_cpu()
    nb, B = blocks.shape
    pad = (-nb) % block_rows
    x = jnp.pad(blocks.astype(jnp.float32), ((0, pad), (0, 0)))
    codes = block_transform_pallas(
        x, matrix.astype(jnp.float32), q=float(q), interpret=interpret, block_rows=block_rows
    )
    return codes[:nb]

"""jit'd wrapper for the flash-attention kernel: padding + backend dispatch."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.tiling import is_cpu as _is_cpu


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Causal GQA flash attention; shapes (b,hq,sq,d) / (b,hkv,sk,d).

    Pads sq/sk up to block multiples (padded kv columns are masked by the
    causal test for suffix queries; for non-causal use, padded columns are
    masked explicitly with a -inf additive K-row marker).
    """
    if interpret is None:
        interpret = _is_cpu()
    b, hq, sq, d = q.shape
    sk = k.shape[2]
    if causal and sq > sk:
        raise ValueError("suffix-causal attention requires sq <= sk")
    if not causal and (sk % min(block_k, _round_up(sk)) != 0):
        raise NotImplementedError("non-causal padding requires explicit kv mask")
    block_q = min(block_q, _round_up(sq))
    block_k = min(block_k, _round_up(sk))
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k

    # Back-pad both streams; the kernel receives the REAL kv offset, so real
    # queries (rows < sq) keep exact causal semantics, padded query rows
    # compute discarded garbage, and padded kv columns (cols >= sk) sit
    # strictly in the causal future of every real query.
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = flash_attention_pallas(
        q, k, v,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, kv_offset=sk - sq,
    )
    return out[:, :, :sq, :]


def _round_up(n: int, mult: int = 128) -> int:
    return max(mult, ((n + mult - 1) // mult) * mult)

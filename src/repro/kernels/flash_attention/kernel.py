"""Causal GQA flash-attention forward Pallas TPU kernel.

TPU-native adaptation of FlashAttention: grid (batch, q_head, q_blocks,
kv_blocks) with the kv dimension innermost ("arbitrary" semantics), online
softmax state (running max m, normalizer l, accumulator acc) held in VMEM
scratch that persists across the kv sweep.  The MXU sees two GEMMs per step:
(bq, d) x (d, bk) for scores and (bq, bk) x (bk, d) for the value gather.
GQA is expressed in the K/V BlockSpec index maps (q head h reads kv head
h // group) — no repeat/materialization of K/V per q head.

m and l are carried lane-replicated as (bq, 128) tiles (TPU VREG layout needs
the trailing-128 lane dim; column 0 is authoritative).

Causality supports the decode/suffix convention: queries are the last ``sq``
positions of the ``sk``-long kv stream (offset = sk - sq), which serves both
full prefill (sq == sk) and chunked decode (sq << sk).  Fully-masked kv
blocks are skipped via pl.when on the block-level causal test — the classic
flash skip, which halves prefill FLOPs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
_NEG_INF = -1e30
_LANES = 128


def _flash_kernel(
    q_ref,  # (1, 1, bq, d)
    k_ref,  # (1, 1, bk, d)
    v_ref,  # (1, 1, bk, d)
    o_ref,  # (1, 1, bq, d)
    m_scr,  # (bq, 128)
    l_scr,  # (bq, 128)
    acc_scr,  # (bq, d)
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_offset: int,
):
    i_q = pl.program_id(2)
    i_k = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(i_k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level causal skip: kv block strictly after the last query row.
    q_last_row = (i_q + 1) * block_q - 1 + kv_offset
    should_run = (i_k * block_k <= q_last_row) if causal else jnp.bool_(True)

    @pl.when(should_run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i_q * block_q + kv_offset
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + i_k * block_k
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_prev = m_scr[...][:, :1]  # (bq, 1)
        l_prev = l_scr[...][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        l_corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * l_corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * l_corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(i_k == n_k - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[0, 0, :, :] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret", "kv_offset"),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # (b, hq, sq, d)
    k: jnp.ndarray,  # (b, hkv, sk, d)
    v: jnp.ndarray,  # (b, hkv, sk, d)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    kv_offset: int | None = None,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, "GQA requires hq % hkv == 0"
    group = hq // hkv
    assert sq % block_q == 0 and sk % block_k == 0, "ops.py pads to block multiples"
    if scale is None:
        scale = float(1.0 / (d**0.5))
    if kv_offset is None:
        kv_offset = sk - sq  # suffix convention (row i is kv position offset+i)

    grid = (b, hq, sq // block_q, sk // block_k)
    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)
    )
    out_spec = pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0))

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_offset=kv_offset,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)

"""Fused f-cube projection Pallas TPU kernel.

Fuses the paper's two GPU kernels — CheckConvergence and ProjectOntoFCube
(§IV-D) — into one VMEM pass: the A100 implementation reads the frequency
error vector twice (once to test convergence, once to clip); on TPU we clip,
accumulate the edit displacement, and reduce the violation count in a single
(rows, 128)-tiled sweep, halving HBM traffic for the projection stage.

Complex data is carried as separate Re/Im planes (TPU has no complex VREGs).
``Delta`` comes in two flavours selected statically by ``pointwise``:
scalar (a (1,1) block re-read by every grid step) or a full per-component
array tiled like the data (Observation 4's pointwise bounds).

The rFFT fast path feeds *half-spectrum* Re/Im tiles plus a pair-weight
plane (``weighted=True``): each component's violation indicator is scaled by
its conjugate-pair multiplicity (1 on the self-conjugate planes, 2
elsewhere), so the fused CheckConvergence reduction over the half-spectrum
reports full-spectrum violation counts.  Padded lanes carry weight 0 and
never count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANES, count_shape, count_spec, store_count

# VPU-aligned tile: (rows, 128) float32.  9 live buffers per grid step
# (re/im in, delta, weight, re/im out, edit re/im, viol) * 256*128*4B ~ 1.1 MiB << VMEM.
BLOCK_ROWS = 256


def _fcube_kernel(
    dr_ref, di_ref, dlt_ref, w_ref, slk_ref, cr_ref, ci_ref, er_ref, ei_ref, viol_ref,
    *, check_tol: float
):
    re = dr_ref[...]
    im = di_ref[...]
    d = dlt_ref[...]  # (rows,128) pointwise or (1,1) scalar — broadcasts
    w = w_ref[...]  # (rows,128) pair weights or (1,1) scalar 1 — broadcasts
    cre = jnp.clip(re, -d, d)
    cim = jnp.clip(im, -d, d)
    cr_ref[...] = cre
    ci_ref[...] = cim
    er_ref[...] = cre - re
    ei_ref[...] = cim - im
    # fused CheckConvergence with a float32-resolution tolerance (see
    # core.pocs: violations below ~1e-5 relative oscillate at fp32 FFT
    # round-off; the float64 polish owns the last digits) plus the caller's
    # absolute slack for near-floor pointwise Delta_k
    dt = d * (1.0 + check_tol) + slk_ref[...]
    viol = ((jnp.abs(re) > dt) | (jnp.abs(im) > dt)).astype(jnp.int32) * w
    # dtype pinned: under jax_enable_x64 a bare sum promotes to int64 and
    # the store into the int32 out ref fails at trace time
    store_count(viol_ref, jnp.sum(viol, dtype=jnp.int32))


@functools.partial(
    jax.jit, static_argnames=("pointwise", "weighted", "interpret", "block_rows", "check_tol")
)
def fcube_pallas(
    delta_re: jnp.ndarray,
    delta_im: jnp.ndarray,
    Delta: jnp.ndarray,
    weight: jnp.ndarray,
    check_slack: jnp.ndarray = None,
    *,
    pointwise: bool,
    weighted: bool = False,
    interpret: bool = False,
    block_rows: int = BLOCK_ROWS,
    check_tol: float = 0.0,
):
    """Tiled inputs: (R, 128) planes, R a multiple of ``block_rows``.

    ``weight`` is an int32 pair-weight plane tiled like the data
    (``weighted=True``) or a (1, 1) scalar 1 (plain per-component counting).
    ``check_slack`` is a (1, 1) absolute convergence allowance added on top
    of the relative ``check_tol`` (defaults to 0).

    Returns (clipped_re, clipped_im, edit_re, edit_im, viol) where ``viol``
    holds one count tile per grid step (see ``tiling.count_spec``); its sum
    is the violation count.
    """
    rows = delta_re.shape[0]
    assert delta_re.shape[1] == LANES and rows % block_rows == 0
    if check_slack is None:
        check_slack = jnp.zeros((1, 1), dtype=delta_re.dtype)
    grid = (rows // block_rows,)
    data_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    delta_spec = data_spec if pointwise else scalar_spec
    weight_spec = data_spec if weighted else scalar_spec
    out_specs = [data_spec] * 4 + [count_spec()]
    out_shapes = [jax.ShapeDtypeStruct((rows, LANES), delta_re.dtype) for _ in range(4)] + [
        count_shape(grid[0])
    ]
    return pl.pallas_call(
        functools.partial(_fcube_kernel, check_tol=check_tol),
        grid=grid,
        in_specs=[data_spec, data_spec, delta_spec, weight_spec, scalar_spec],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(delta_re, delta_im, Delta, weight, check_slack)

"""Fused rFFT-epilogue Pallas TPU kernels for the POCS hot loop.

The loop's per-iteration transform+clip sequence (paper §IV-D, our Alg. 1
body) is ``rfftn -> f-cube clip -> irfftn -> s-cube clip``.  XLA's FFTs are
custom calls, so the clips around them are separate HBM passes.  These
kernels close that gap by fusing every elementwise stage *between* the FFT
custom calls into single VMEM sweeps:

``_rfft_fwd_epilogue_kernel``
    One (rows, 128)-tiled pass over the forward half-spectrum that performs
    the f-cube clip, accumulates the edit displacement, reduces the
    pair-weighted violation count (the fused CheckConvergence of
    :mod:`repro.kernels.fcube`), AND applies the inverse pack-trick twiddle
    (``Z = E + iO`` with ``E = (X + conj(X~))/2``, ``O = w_inv (X -
    conj(X~))/2`` — see :mod:`repro.kernels.rfft.ops`) so the output feeds a
    half-length complex ``ifftn`` directly.  The mirrored spectrum arrives as
    a separate *unclipped* operand plus its mirrored bound: ``clip`` commutes
    with the Hermitian mirror when the bound is mirrored too, so the kernel
    clips both views locally instead of waiting on its own output.

``_unpack_sclip_kernel``
    The inverse epilogue: the pack-trick inverse ends with a complex
    half-length ``ifftn`` whose real/imag planes are the even/odd spatial
    samples.  The s-cube clip is elementwise and therefore commutes with the
    de-interleave, so one pass clips both planes and emits the clipped
    samples plus the spatial edit displacement, still in packed layout; the
    ops wrapper interleaves.

Complex data is carried as separate Re/Im planes (TPU has no complex VREGs).
Bounds come scalar ((1, 1) blocks) or pointwise (tiled like the data),
selected statically.  Padded lanes carry zero data, +inf pointwise bounds and
zero pair weights, so they never clip, never count, and produce zero Z.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANES, count_shape, count_spec, store_count

# VPU-aligned tile, shared with the fcube/scube kernels.  The forward
# epilogue holds 17 live (rows, 128) float32 planes per grid step:
# 256*128*4B * 17 ~ 2.2 MiB << VMEM.
BLOCK_ROWS = 256


def _rfft_fwd_epilogue_kernel(
    xr_ref, xi_ref, mr_ref, mi_ref, dlt_ref, dltm_ref, wr_ref, wi_ref, pw_ref, slk_ref,
    cr_ref, ci_ref, er_ref, ei_ref, zr_ref, zi_ref, viol_ref,
    *, check_tol: float
):
    xr = xr_ref[...]
    xi = xi_ref[...]
    d = dlt_ref[...]  # (rows,128) pointwise or (1,1) scalar — broadcasts
    dm = dltm_ref[...]  # mirrored bound (same flavour as d)
    # f-cube projection + edit displacement (ProjectOntoFCube)
    cr = jnp.clip(xr, -d, d)
    ci = jnp.clip(xi, -d, d)
    cr_ref[...] = cr
    ci_ref[...] = ci
    er_ref[...] = cr - xr
    ei_ref[...] = ci - xi
    # the clipped Hermitian mirror, from the unclipped mirror operand:
    # clip(mirror(X), mirror(D)) == mirror(clip(X, D)) elementwise
    cmr = jnp.clip(mr_ref[...], -dm, dm)
    cmi = jnp.clip(mi_ref[...], -dm, dm)
    # inverse pack-trick twiddle: Z = E + iO with conj(mirror) = (cmr, -cmi)
    Er = 0.5 * (cr + cmr)
    Ei = 0.5 * (ci - cmi)
    tr = cr - cmr
    ti = ci + cmi
    wr = wr_ref[...]
    wi = wi_ref[...]
    Or = 0.5 * (wr * tr - wi * ti)
    Oi = 0.5 * (wr * ti + wi * tr)
    zr_ref[...] = Er - Oi
    zi_ref[...] = Ei + Or
    # fused CheckConvergence (see kernels/fcube): float32-resolution relative
    # tolerance + the caller's absolute slack, pair-weighted so the
    # half-spectrum count keeps full-spectrum semantics
    dt = d * (1.0 + check_tol) + slk_ref[...]
    viol = ((jnp.abs(xr) > dt) | (jnp.abs(xi) > dt)).astype(jnp.int32) * pw_ref[...]
    # dtype pinned: under jax_enable_x64 a bare sum promotes to int64 and
    # the store into the int32 out ref fails at trace time
    store_count(viol_ref, jnp.sum(viol, dtype=jnp.int32))


@functools.partial(
    jax.jit, static_argnames=("pointwise", "interpret", "block_rows", "check_tol")
)
def rfft_fwd_epilogue_pallas(
    delta_re: jnp.ndarray,
    delta_im: jnp.ndarray,
    mirror_re: jnp.ndarray,
    mirror_im: jnp.ndarray,
    Delta: jnp.ndarray,
    Delta_m: jnp.ndarray,
    w_re: jnp.ndarray,
    w_im: jnp.ndarray,
    weight: jnp.ndarray,
    check_slack: jnp.ndarray,
    *,
    pointwise: bool,
    interpret: bool = False,
    block_rows: int = BLOCK_ROWS,
    check_tol: float = 0.0,
):
    """Tiled forward epilogue: (R, 128) planes, R a multiple of ``block_rows``.

    ``mirror_re/im`` are the UNCLIPPED Hermitian-mirrored spectrum planes and
    ``Delta_m`` the mirrored bound (scalar bounds pass the same (1, 1) block
    twice).  ``w_re/im`` are the inverse pack twiddle planes (always tiled),
    ``weight`` the int32 pair-weight plane, ``check_slack`` a (1, 1) absolute
    convergence allowance.

    Returns ``(clip_re, clip_im, edit_re, edit_im, z_re, z_im, viol)`` where
    ``viol`` holds one count tile per grid step (see ``tiling.count_spec``);
    its sum is the violation count.
    """
    rows = delta_re.shape[0]
    assert delta_re.shape[1] == LANES and rows % block_rows == 0
    grid = (rows // block_rows,)
    data_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    delta_spec = data_spec if pointwise else scalar_spec
    out_specs = [data_spec] * 6 + [count_spec()]
    out_shapes = [jax.ShapeDtypeStruct((rows, LANES), delta_re.dtype) for _ in range(6)] + [
        count_shape(grid[0])
    ]
    return pl.pallas_call(
        functools.partial(_rfft_fwd_epilogue_kernel, check_tol=check_tol),
        grid=grid,
        in_specs=[
            data_spec, data_spec, data_spec, data_spec,  # X, mirror(X)
            delta_spec, delta_spec,  # Delta, mirror(Delta)
            data_spec, data_spec,  # inverse twiddle planes
            data_spec,  # pair weights
            scalar_spec,  # check slack
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(
        delta_re, delta_im, mirror_re, mirror_im, Delta, Delta_m, w_re, w_im,
        weight, check_slack,
    )


def _unpack_sclip_kernel(zr_ref, zi_ref, ee_ref, eo_ref, ce_ref, co_ref, de_ref, do_ref):
    zr = zr_ref[...]  # even spatial samples (Re of the half-length ifftn)
    zi = zi_ref[...]  # odd spatial samples (Im)
    ee = ee_ref[...]
    eo = eo_ref[...]
    ce = jnp.clip(zr, -ee, ee)
    co = jnp.clip(zi, -eo, eo)
    ce_ref[...] = ce
    co_ref[...] = co
    de_ref[...] = ce - zr
    do_ref[...] = co - zi


@functools.partial(jax.jit, static_argnames=("pointwise", "interpret", "block_rows"))
def unpack_sclip_pallas(
    z_re: jnp.ndarray,
    z_im: jnp.ndarray,
    E_even: jnp.ndarray,
    E_odd: jnp.ndarray,
    *,
    pointwise: bool,
    interpret: bool = False,
    block_rows: int = BLOCK_ROWS,
):
    """Tiled inverse epilogue: s-cube clip on packed even/odd sample planes.

    ``E_even``/``E_odd`` are the de-interleaved pointwise bounds (or the same
    (1, 1) scalar block twice).  Returns ``(clip_even, clip_odd, edit_even,
    edit_odd)`` in packed layout; the caller interleaves.
    """
    rows = z_re.shape[0]
    assert z_re.shape[1] == LANES and rows % block_rows == 0
    grid = (rows // block_rows,)
    data_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    e_spec = data_spec if pointwise else pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        _unpack_sclip_kernel,
        grid=grid,
        in_specs=[data_spec, data_spec, e_spec, e_spec],
        out_specs=[data_spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), z_re.dtype)] * 4,
        interpret=interpret,
    )(z_re, z_im, E_even, E_odd)

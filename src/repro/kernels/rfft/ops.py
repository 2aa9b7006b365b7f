"""Pack-trick C2R/R2C transforms + fused projection epilogues (POCS hot loop).

Why this exists: the POCS loop is transform-bound, and on every measured
backend the *C2R inverse* is the slow half — XLA's ``irfftn`` custom call
costs ~2.1x the R2C forward on the CI CPU (the forward DUCC r2c already
implements the pack trick internally).  The pack trick computes an N-point
real transform via an N/2-point *complex* transform plus O(N) twiddle work:

  forward (R2C):  pack ``z[n] = x[2n] + i x[2n+1]``, take the complex FFT
    ``Z`` over ALL axes, and recombine ``X[k] = E[k] + w_fwd[k] O[k]`` with
    ``E = (Z + conj(Z~))/2``, ``O = (Z - conj(Z~))/(2i)``, where ``Z~`` is
    the Hermitian mirror ``Z[-k0, .., Nh-k]`` (leading axes negated mod N_a,
    last axis reflected; the even/odd sample fields are real, so their
    spectra are Hermitian) and ``w_fwd[k] = exp(-2 pi i k / N)``.
  inverse (C2R):  ``E = (X + conj(X~))/2``, ``O = w_inv (X - conj(X~))/2``
    with ``w_inv[k] = exp(+2 pi i k / N)``, then ``z = ifftn(E + iO)`` over
    all axes at half the last-axis length, and de-interleave
    ``x[2n] = Re z[n]``, ``x[2n+1] = Im z[n]``.

Both run on any rank with only jnp primitives (vmap-safe, so the pencil
backends lift them for free).  Twiddles come from a cached plan registry
(:func:`twiddle_plan`, keyed by last-axis length + dtype) so repeated shapes
never rebuild them.

Measured on the CI container CPU (512^2 / 128x128x64 POCS loop, the
committed ``BENCH_pocs.json`` record): swapping ONLY the inverse for
:func:`packed_irfftn` is 1.20x / 1.16x per iteration — the forward keeps
``jnp.fft.rfftn`` because DUCC's r2c is already packed and beats
:func:`packed_rfftn` (which is still provided: it is the fallback-free R2C
for backends without a native r2c, and the oracle the tests pin).

The ``pallas`` variant (:func:`fwd_epilogue_fused`,
:func:`unpack_sclip_fused`) goes further: the forward epilogue fuses the
f-cube clip + pair-weighted violation count + the inverse pack twiddle into
one VMEM pass over the spectrum, and the inverse epilogue fuses the s-cube
clip into the de-interleave — one pass over the data instead of
FFT-then-clip, eliminating the two per-iteration HBM round-trips the
unfused loop pays (kernels in :mod:`repro.kernels.rfft.kernel`; interpret
mode on CPU, Mosaic on TPU).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rfft.kernel import (
    BLOCK_ROWS,
    rfft_fwd_epilogue_pallas,
    unpack_sclip_pallas,
)
from repro.kernels.tiling import is_cpu as _is_cpu
from repro.kernels.tiling import tile as _tile
from repro.kernels.tiling import tile_bound as _tile_bound
from repro.kernels.tiling import untile as _untile


# ---------------------------------------------------------------------------
# twiddle-plan registry


@functools.lru_cache(maxsize=None)
def twiddle_plan(n: int, dtype_name: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """Cached pack-trick twiddles for an even last-axis length ``n``.

    Returns ``(w_fwd, w_inv)``, each of shape ``(n // 2 + 1,)``:
    ``w_fwd[k] = exp(-2 pi i k / n)`` (forward recombination) and its
    conjugate (inverse).  Keyed by ``(n, dtype)`` — the twiddles are the only
    shape-dependent precompute the packed transforms need, so caching here
    makes every same-shape trace reuse one host constant (embedded once per
    compiled program).  Built in float64 and rounded once to the working
    precision.
    """
    if n % 2:
        raise ValueError(f"pack-trick transforms need an even last axis, got {n}")
    k = np.arange(n // 2 + 1)
    w = np.exp((-2j * np.pi / n) * k)
    cdtype = np.complex64 if dtype_name == "float32" else np.complex128
    return w.astype(cdtype), np.conj(w).astype(cdtype)


def supports_packed(shape: Tuple[int, ...]) -> bool:
    """True when the pack trick applies: even last axis of at least 2."""
    return len(shape) >= 1 and shape[-1] >= 2 and shape[-1] % 2 == 0


def mirror_half_spectrum(a: jnp.ndarray) -> jnp.ndarray:
    """Hermitian mirror index map ``a[k0, .., k] -> a[-k0, .., Nh-k]``.

    Leading axes are negated modulo their extent (flip + roll); the last
    (half-spectrum, ``Nh + 1``-long) axis is reflected in place.  Combined
    with a ``conj`` this maps each stored half-spectrum component to its
    conjugate partner's stored image — the gather both pack-trick
    recombinations share.
    """
    for ax in range(a.ndim - 1):
        a = jnp.roll(jnp.flip(a, axis=ax), 1, axis=ax)
    return a[..., ::-1]


def _interleave_last(even: jnp.ndarray, odd: jnp.ndarray) -> jnp.ndarray:
    """Riffle two (..., Nh) planes into (..., 2*Nh): out[2n]=even, out[2n+1]=odd."""
    out = jnp.stack([even, odd], axis=-1)
    return out.reshape(*even.shape[:-1], even.shape[-1] * 2)


# ---------------------------------------------------------------------------
# pure-XLA packed transforms (fft_impl="packed")


def packed_rfftn(x: jnp.ndarray) -> jnp.ndarray:
    """``jnp.fft.rfftn`` via the pack trick (complex FFT at half the last axis).

    Matches ``jnp.fft.rfftn`` to float-rounding level on any rank with an
    even last axis.  Provided as the R2C half of the suite (and the oracle
    the kernel tests pin); the POCS loop's ``"packed"`` path keeps XLA's
    forward — DUCC's r2c is already packed internally — and only swaps the
    inverse, where the measured gap is.
    """
    n = x.shape[-1]
    w_fwd, _ = twiddle_plan(n, x.dtype.name)
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2])
    Z = jnp.fft.fftn(z)
    Zf = jnp.concatenate([Z, Z[..., :1]], axis=-1)  # periodic extension to k=Nh
    Zm = jnp.conj(mirror_half_spectrum(Zf))
    E = 0.5 * (Zf + Zm)
    O = -0.5j * (Zf - Zm)
    return E + jnp.asarray(w_fwd) * O


def packed_irfftn(X: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """``jnp.fft.irfftn(X, s=shape)`` via the pack trick — the C2R fast path.

    One Hermitian-mirror gather, one elementwise twiddle recombination, one
    complex ``ifftn`` at half the last-axis length, one de-interleave.  On
    the CI CPU this replaces XLA's C2R custom call at ~1.55x; inside the
    POCS loop the swap is worth 1.2-1.3x per iteration (see module
    docstring).  ``shape`` is the true spatial shape (even last axis).
    """
    n = shape[-1]
    _, w_inv = twiddle_plan(n, "float32" if X.dtype == jnp.complex64 else "float64")
    # slice to the Nh-wide packed domain BEFORE the twiddle recombination:
    # Z only needs k = 0..Nh-1, so the k = Nh column never enters the math
    Xm = jnp.conj(mirror_half_spectrum(X))[..., : n // 2]
    Xs = X[..., : n // 2]
    w = jnp.asarray(w_inv)[: n // 2]
    Z = 0.5 * ((Xs + Xm) + 1j * (w * (Xs - Xm)))
    z = jnp.fft.ifftn(Z)
    return _interleave_last(z.real, z.imag)


def packed_irfft(X: jnp.ndarray, n: int) -> jnp.ndarray:
    """Last-axis-only pack-trick C2R: ``jnp.fft.irfft(X, n, axis=-1)``.

    Each last-axis line must be the half-spectrum of a real line (true after
    the leading c2c axes have been inverse-transformed), so the Hermitian
    mirror reduces to the in-line reflection.  This is the form the
    distributed pencil transform composes: :func:`...dist_fft.irfftn_local`
    swaps exactly its final local last-axis pass for this one.
    """
    _, w_inv = twiddle_plan(n, "float32" if X.dtype == jnp.complex64 else "float64")
    Xm = jnp.conj(X[..., ::-1])[..., : n // 2]
    Xs = X[..., : n // 2]
    w = jnp.asarray(w_inv)[: n // 2]
    Z = 0.5 * ((Xs + Xm) + 1j * (w * (Xs - Xm)))
    z = jnp.fft.ifft(Z, axis=-1)
    return _interleave_last(z.real, z.imag)


# ---------------------------------------------------------------------------
# per-axis transforms (fft_impl="xla" where the backend's C2R is inaccurate)


def c2r_is_accurate() -> bool:
    """Whether XLA's C2R transform (``jnp.fft.irfft``/``irfftn``) is accurate.

    False on TPU: on a v5e (JAX 0.9, libtpu 0.0.34) it returned outputs off
    by up to ~30% of their largest value at lengths 256 and 512, while the
    R2C and C2C transforms stayed at float32 accuracy.  Where it is False,
    the whole-field transforms (:func:`field_rfftn`, :func:`field_irfftn`)
    and the slab-sharded ones in :mod:`repro.sharding.dist_fft` run the same
    per-axis passes, ending in the pack-trick C2R pass of :func:`c2r_last`.
    """
    return jax.default_backend() != "tpu"


def c2r_last(X: jnp.ndarray, n: int, packed: bool = False) -> jnp.ndarray:
    """The last-axis C2R pass of a per-axis inverse (``irfft`` to length ``n``).

    The pack trick (:func:`packed_irfft`) where ``packed`` asks for it or
    where XLA's C2R is inaccurate; ``jnp.fft.irfft`` otherwise and for odd
    ``n``, which has no pack trick.  On a backend with an inaccurate C2R the
    pass runs between ``optimization_barrier`` s: on a v5e, the pack-trick
    inverse fused with its neighbours in the POCS loop body drifted by ~E
    per run at 512^3, and isolated it did not.
    """
    if n % 2 or n < 2 or (not packed and c2r_is_accurate()):
        return jnp.fft.irfft(X, n=n, axis=-1)
    if c2r_is_accurate():
        return packed_irfft(X, n)
    barrier = jax.lax.optimization_barrier
    return barrier(packed_irfft(barrier(X), n))


def rfftn_passes(x: jnp.ndarray) -> jnp.ndarray:
    """``rfftn`` as separate passes: r2c along the last axis, then c2c along
    axis 0, 1, ... — the pass order of the slab-sharded transform
    (:func:`repro.sharding.dist_fft.rfftn_local`)."""
    r = jnp.fft.rfft(x, axis=-1)
    for ax in range(x.ndim - 1):
        r = jnp.fft.fft(r, axis=ax)
    return r


def irfftn_passes(X: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Inverse of :func:`rfftn_passes`: c2c along axis 0, 1, ..., then
    :func:`c2r_last` (the slab-sharded inverse's order)."""
    for ax in range(len(shape) - 1):
        X = jnp.fft.ifft(X, axis=ax)
    return c2r_last(X, shape[-1])


def field_rfftn(x: jnp.ndarray) -> jnp.ndarray:
    """The whole-field forward of ``fft_impl="xla"`` and of PLAN:
    ``jnp.fft.rfftn``, or :func:`rfftn_passes` where XLA's C2R is inaccurate,
    so that a single-device field runs the passes of a slab-sharded one."""
    return jnp.fft.rfftn(x) if c2r_is_accurate() else rfftn_passes(x)


def field_irfftn(X: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """The whole-field inverse of ``fft_impl="xla"``: ``jnp.fft.irfftn``, or
    :func:`irfftn_passes` where XLA's C2R is inaccurate."""
    return jnp.fft.irfftn(X, s=shape) if c2r_is_accurate() else irfftn_passes(X, shape)


# ---------------------------------------------------------------------------
# fused Pallas epilogues (fft_impl="pallas"); plane tiling + padding contract
# shared with the fcube/scube suites via repro.kernels.tiling


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret", "check_tol"))
def fwd_epilogue_fused(
    delta: jnp.ndarray,
    Delta,
    Delta_m=None,
    weight=None,
    block_rows: int = BLOCK_ROWS,
    interpret: bool | None = None,
    check_tol: float = 0.0,
    check_slack=0.0,
):
    """Fused forward epilogue: f-clip + pair-weighted count + inverse twiddle.

    One kernel pass over the half-spectrum ``delta`` replaces the loop's
    ``project_fcube`` + ``fcube_violations`` + the inverse pack-twiddle
    prologue.  ``Delta_m`` is the Hermitian-mirrored pointwise bound
    (loop-invariant — mirror it once outside the while body; ``None`` for
    scalar bounds).  ``weight`` is the conjugate-pair multiplicity plane
    (None counts each component once).

    Returns ``(clipped, displacement, Z, violations)`` where ``Z`` is the
    full-grid packed spectrum — slice ``Z[..., :N//2]`` and ``ifftn`` it to
    finish the inverse.
    """
    if interpret is None:
        interpret = _is_cpu()
    shape = delta.shape
    n = 2 * (shape[-1] - 1)  # true last-axis length (even by construction)
    _, w_inv = twiddle_plan(n, "float32" if delta.dtype == jnp.complex64 else "float64")
    w_grid = jnp.broadcast_to(jnp.asarray(w_inv), shape)
    mirrored = mirror_half_spectrum(delta)

    re, pad = _tile(delta.real.astype(jnp.float32), block_rows)
    im, _ = _tile(delta.imag.astype(jnp.float32), block_rows)
    mr, _ = _tile(mirrored.real.astype(jnp.float32), block_rows)
    mi, _ = _tile(mirrored.imag.astype(jnp.float32), block_rows)
    wr, _ = _tile(w_grid.real.astype(jnp.float32), block_rows)
    wi, _ = _tile(w_grid.imag.astype(jnp.float32), block_rows)
    Delta_arr = jnp.asarray(Delta, dtype=jnp.float32)
    pointwise = Delta_arr.ndim > 0
    if pointwise:
        if Delta_m is None:
            Delta_m = mirror_half_spectrum(jnp.broadcast_to(Delta_arr, shape))
        dt = _tile_bound(Delta_arr, shape, block_rows, pad)
        dtm = _tile_bound(jnp.asarray(Delta_m, dtype=jnp.float32), shape, block_rows, pad)
    else:
        dt = dtm = Delta_arr.reshape(1, 1)
    if weight is not None:
        # zero-pad: padded lanes carry weight 0 and never count
        wt, _ = _tile(jnp.broadcast_to(jnp.asarray(weight, dtype=jnp.int32), shape), block_rows)
    else:
        wt, _ = _tile(jnp.ones(shape, dtype=jnp.int32), block_rows)
    slk = jnp.asarray(check_slack, dtype=jnp.float32).reshape(1, 1)

    cr, ci, er, ei, zr, zi, viol = rfft_fwd_epilogue_pallas(
        re, im, mr, mi, dt, dtm, wr, wi, wt, slk,
        pointwise=pointwise, interpret=interpret, block_rows=block_rows,
        check_tol=check_tol,
    )
    clipped = (_untile(cr, shape, pad) + 1j * _untile(ci, shape, pad)).astype(delta.dtype)
    edits = (_untile(er, shape, pad) + 1j * _untile(ei, shape, pad)).astype(delta.dtype)
    Z = (_untile(zr, shape, pad) + 1j * _untile(zi, shape, pad)).astype(delta.dtype)
    # dtype pinned so the loop carry stays int32 under jax_enable_x64
    return clipped, edits, Z, jnp.sum(viol, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("shape", "block_rows", "interpret"))
def unpack_sclip_fused(
    z: jnp.ndarray,
    E,
    shape: Tuple[int, ...],
    block_rows: int = BLOCK_ROWS,
    interpret: bool | None = None,
):
    """Fused inverse epilogue: s-cube clip on packed planes + de-interleave.

    ``z`` is the half-length complex ``ifftn`` output (its Re/Im planes are
    the even/odd spatial samples of the true ``shape``-sized field); the
    elementwise s-clip commutes with the de-interleave, so one kernel pass
    clips both planes and emits the displacement before the riffle.

    Returns ``(eps_clipped, displacement)``, both real with ``shape``.
    """
    if interpret is None:
        interpret = _is_cpu()
    zr, pad = _tile(z.real.astype(jnp.float32), block_rows)
    zi, _ = _tile(z.imag.astype(jnp.float32), block_rows)
    E_arr = jnp.asarray(E, dtype=jnp.float32)
    pointwise = E_arr.ndim > 0
    if pointwise:
        Eb = jnp.broadcast_to(E_arr, shape)
        ee = _tile_bound(Eb[..., 0::2], z.shape, block_rows, pad)
        eo = _tile_bound(Eb[..., 1::2], z.shape, block_rows, pad)
    else:
        ee = eo = E_arr.reshape(1, 1)
    ce, co, de, do = unpack_sclip_pallas(
        zr, zi, ee, eo, pointwise=pointwise, interpret=interpret, block_rows=block_rows
    )
    eps = _interleave_last(_untile(ce, z.shape, pad), _untile(co, z.shape, pad))
    disp = _interleave_last(_untile(de, z.shape, pad), _untile(do, z.shape, pad))
    return eps.reshape(shape), disp.reshape(shape)

"""Plain float64 reference of what FFCz promises, independent of the program.

Given the original data and a request's relative bounds, the reference
resolves the absolute bounds the blob must carry, and measures the decoded
output against them:

    E     = E_rel * (max(x) - min(x))
    Delta = max(Delta_rel * max_k |X_k|, 16 u32 ||x||_2)

where ``X`` is the real DFT over all axes (whole field) or over each
``block``-length pencil of the flattened data (pencil requests; the max and
the norm are then taken over pencils), and the second term is the float32
representability floor the bound discipline documents (``u32`` = float32
machine epsilon).  The decoded output must then hold

    max |x_hat - x| <= E    and    max(|Re|, |Im|) of DFT(x_hat - x) <= Delta,

computed in float64 (the spectrum of the difference, not a difference of
spectra).  Nothing here imports the program.

``precision="bfloat16"`` is the control: the same resolution with the data,
each intermediate and each result rounded to bfloat16, the nearest precision
below the float32 the configurations state.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
from scipy import fft as sfft

U32 = float(np.finfo(np.float32).eps)
PRECISIONS = ("float64", "bfloat16")


def _rounder(precision: str):
    if precision == "float64":
        return lambda v: float(v)
    if precision == "bfloat16":
        return lambda v: float(np.asarray(v, dtype=np.float64).astype(ml_dtypes.bfloat16))
    raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")


def _data(x: np.ndarray, precision: str) -> np.ndarray:
    x = np.asarray(x)
    if precision == "bfloat16":
        return x.astype(ml_dtypes.bfloat16).astype(np.float64)
    return x.astype(np.float64)


def tiles(x: np.ndarray, block: int) -> np.ndarray:
    """Row-major flattening, zero-padded into ``block``-length pencils."""
    flat = np.asarray(x, dtype=np.float64).reshape(-1)
    return np.pad(flat, (0, (-flat.size) % block)).reshape(-1, block)


def bounds(x: np.ndarray, E_rel: float, Delta_rel: float, block: int = 0,
           precision: str = "float64") -> tuple:
    """``(E, Delta)`` a blob of ``x`` must carry; ``block`` > 0 for pencils."""
    r = _rounder(precision)
    d = _data(x, precision)
    E = r(E_rel * r(r(d.max()) - r(d.min())))
    if block:
        t = tiles(d, block)
        peak = r(np.abs(sfft.rfft(t, axis=-1, workers=-1)).max())
        norm = r(np.sqrt((t * t).sum(axis=-1)).max())
    else:
        peak = r(np.abs(sfft.rfftn(d, workers=-1)).max())
        norm = r(np.sqrt(np.sum(d * d)))
    return E, r(max(r(Delta_rel * peak), r(16.0 * U32 * norm)))


def errors(x: np.ndarray, x_hat: np.ndarray, block: int = 0) -> tuple:
    """``(max |x_hat - x|, max(|Re|, |Im|) of the DFT of x_hat - x)`` in
    float64; over each pencil when ``block`` > 0, else over all axes."""
    x_hat = np.asarray(x_hat)
    if x_hat.shape != np.shape(x):
        raise ValueError(f"decoded shape {x_hat.shape} is not the input's {np.shape(x)}")
    err = x_hat.astype(np.float64) - np.asarray(x, dtype=np.float64)
    spec = sfft.rfft(tiles(err, block), axis=-1, workers=-1) if block else sfft.rfftn(
        err, workers=-1)
    return float(np.max(np.abs(err))), float(np.max(np.maximum(np.abs(spec.real),
                                                               np.abs(spec.imag))))


def compare(stored: tuple, ref: tuple, errs: tuple) -> dict:
    """The compared numbers for one response.

    ``stored`` is the ``(E, Delta)`` the blob carries (or, for the control,
    what the bfloat16 resolution gives in its place), ``ref`` the float64
    reference's, ``errs`` what :func:`errors` measured on the decoded
    output.  ``E_gap`` / ``Delta_gap``: relative distance of the stored
    bounds from the reference's.  ``spatial`` / ``spectral``: the largest
    error over the stored bound (the guarantee is <= 1).
    """
    (E, D), (E_ref, D_ref), (e, s) = stored, ref, errs
    return {
        "E_gap": abs(E - E_ref) / E_ref,
        "Delta_gap": abs(D - D_ref) / D_ref,
        "spatial": e / E,
        "spectral": s / D,
    }

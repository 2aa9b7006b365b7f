"""Nyx-like density cube from a seed (the ``nyx`` configuration's data).

A lognormal transform of a power-law Gaussian random field, as the
program's own ``data/fields`` makes its ``nyx-like`` field, with scipy's
FFT on every host core so that a 256^3 or 512^3 cube takes seconds.

Every field is the same multiset of values in another arrangement: the
Gaussian field's ranks are mapped onto the standard normal's quantiles at
``(i + 0.5) / n`` before the lognormal transform.  So the range (which sets
E), the sum (the spectrum's peak, its DC term, which sets Delta) and the
distribution of values are the same for every seed and index, and only the
spatial structure changes with the seed: two seeds offer the same work, as
two seeds of ``perfbench/loadgen.py`` offer the same gaps and sizes.
"""

from __future__ import annotations

import functools

import numpy as np
from scipy import fft as sfft
from scipy import special


def _kgrid(shape) -> np.ndarray:
    axes = [np.fft.fftfreq(n) * n for n in shape]
    grids = np.meshgrid(*axes, indexing="ij", sparse=True)
    return np.sqrt(sum(g.astype(np.float64) ** 2 for g in grids))


@functools.lru_cache(maxsize=2)
def _values(n: int, sigma: float) -> np.ndarray:
    """The ``n`` values of every field, ascending: ``exp(sigma * q_i)`` at
    the standard normal's quantiles ``q_i``."""
    q = special.ndtri((np.arange(n, dtype=np.float64) + 0.5) / n)
    return np.exp(sigma * q).astype(np.float32)


def make(cfg: dict, size: str, index: int, seed: int) -> np.ndarray:
    """Field ``index`` of the run with ``seed``: ``edge``^3 float32."""
    shape = (int(cfg["edge"]),) * 3
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), int(index)]))
    k = _kgrid(shape)
    with np.errstate(divide="ignore"):
        amp = np.where(k > 0, k ** (-float(cfg["alpha"]) / 2.0), 0.0)
    del k
    noise = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    noise *= amp
    del amp
    g = sfft.ifftn(noise, workers=-1, overwrite_x=True).real
    del noise
    out = np.empty(g.size, np.float32)
    out[np.argsort(g, axis=None)] = _values(g.size, float(cfg["sigma"]))
    return out.reshape(shape)

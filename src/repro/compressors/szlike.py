"""SZ3-style error-bounded compressor: multi-level interpolation prediction.

Implements the algorithmic core of SZ3's interpolation mode [13], [2]:
coarse-to-fine grid refinement where each new point is predicted by linear
interpolation of already-*reconstructed* neighbors along one axis, and the
residual is quantized with a uniform quantizer of step ``2E`` (error <= E,
codes entropy-coded).  Prediction from reconstructed values keeps the bound
non-compounding, exactly as in SZ.

The paper's characterization (§V-B, Obs. 1) — prediction-based, local
neighbors, weak at preserving global frequency content — applies verbatim to
this implementation, which is what makes it the interesting base for FFCz.

Each (level, axis) pass runs vectorized on strided views of the field; encode
and decode share the same deterministic pass schedule, so the code stream
needs no per-point metadata.
"""

from __future__ import annotations

import struct
from typing import Iterator, Tuple

import numpy as np

from repro.coding.lossless import lossless_compress, lossless_decompress


def _pass_schedule(shape: Tuple[int, ...]) -> Iterator[Tuple[int, int]]:
    """Yield (stride, axis) passes from coarsest to finest level."""
    n_max = max(shape)
    s = 1
    while s * 2 < n_max:
        s *= 2
    while s >= 1:
        for axis in range(len(shape)):
            yield s, axis
        s //= 2


def _coarse_stride(shape: Tuple[int, ...]) -> int:
    n_max = max(shape)
    s = 1
    while s * 2 < n_max:
        s *= 2
    return 2 * s  # the grid known *before* the first (s, axis=0) pass


def _pass_slices(shape: Tuple[int, ...], stride: int, axis: int):
    """Basic slices (strided views) for one interpolation pass.

    Targets: coordinates ``stride (mod 2*stride)`` along ``axis``; axes before
    ``axis`` already refined to ``stride``; axes after still at ``2*stride``.
    Each target is predicted from its neighbours ``tgt - stride`` (left) and
    ``tgt + stride`` (right); a last target whose right neighbour falls off
    the end takes its left one twice.  Returns ``(tgt, pairs)`` or None if
    the pass is empty: ``pairs`` lists ``(out, left, right)``, where ``out``
    indexes the pass's code block and the other two index the field, one
    entry for the targets with both neighbours and one for that last plane.
    """
    n_a = shape[axis]
    n_t = len(range(stride, n_a, 2 * stride))
    if n_t == 0:
        return None
    n_full = len(range(stride, n_a - stride, 2 * stride))  # right neighbour inside
    step = 2 * stride

    def at(ax_slice):
        full = [slice(0, n, stride if a < axis else step) for a, n in enumerate(shape)]
        full[axis] = ax_slice
        return tuple(full)

    def out(ax_slice):
        return (slice(None),) * axis + (ax_slice,)

    pairs = []
    if n_full:
        left, right = slice(0, step * n_full, step), slice(step, step * n_full + 1, step)
        pairs.append((out(slice(0, n_full)), at(left), at(right)))
    if n_full < n_t:
        last = at(slice(step * n_full, step * n_full + 1))
        pairs.append((out(slice(n_full, n_t)), last, last))
    return at(slice(stride, n_a, step)), pairs


def _predict(r: np.ndarray, tgt, pairs) -> np.ndarray:
    """``0.5 * (r[left] + r[right])`` over one pass's targets, in float64."""
    pred = np.empty(r[tgt].shape, dtype=np.float64)
    for o, left, right in pairs:
        np.add(r[left], r[right], out=pred[o])
    pred *= 0.5
    return pred


def _coarse(shape: Tuple[int, ...]):
    s0 = _coarse_stride(shape)
    return tuple(slice(0, n, s0) for n in shape)


class SZLikeCompressor:
    """Interpolation-predictor error-bounded compressor (SZ3-like)."""

    name = "szlike"

    def __init__(self, codec: str = "zlib"):
        self.codec = codec

    def compress(self, x: np.ndarray, E: float) -> bytes:
        return self.compress_with_recon(x, E)[0]

    def compress_with_recon(self, x: np.ndarray, E: float) -> Tuple[bytes, np.ndarray]:
        """``(blob, r)``: the blob and the float64 reconstruction the codes
        were quantised against.  ``float32(r)`` is what :meth:`decompress`
        returns for ``blob``, bit for bit: both build ``r`` by the same
        arithmetic from the same codes."""
        x = np.asarray(x, dtype=np.float32)
        E = float(E)
        if E <= 0:
            raise ValueError("E must be positive")
        shape = x.shape
        step = 2.0 * E
        r = np.zeros(shape, dtype=np.float64)
        coarse = _coarse(shape)
        coarse_vals = x[coarse].astype(np.float32)
        r[coarse] = coarse_vals  # coarsest anchors stored losslessly

        codes_flat = np.empty(x.size - coarse_vals.size, dtype=np.int64)
        pos = 0
        for stride, axis in _pass_schedule(shape):
            idx = _pass_slices(shape, stride, axis)
            if idx is None:
                continue
            tgt, pairs = idx
            pred = _predict(r, tgt, pairs)
            codes = x[tgt].astype(np.float64)
            codes -= pred
            codes /= step
            np.rint(codes, out=codes)
            np.add(pred, codes * step, out=r[tgt])
            codes_flat[pos : pos + codes.size] = codes.ravel()
            pos += codes.size

        payload = lossless_compress(codes_flat, codec=self.codec)
        header = struct.pack("<dB", E, x.ndim) + struct.pack(f"<{x.ndim}Q", *shape)
        header += struct.pack("<I", coarse_vals.size) + coarse_vals.tobytes()
        return header + payload, r

    def decompress(self, blob: bytes) -> np.ndarray:
        E, ndim = struct.unpack_from("<dB", blob, 0)
        off = struct.calcsize("<dB")
        shape = struct.unpack_from(f"<{ndim}Q", blob, off)
        off += 8 * ndim
        (n_coarse,) = struct.unpack_from("<I", blob, off)
        off += 4
        coarse_vals = np.frombuffer(blob, dtype=np.float32, count=n_coarse, offset=off)
        off += 4 * n_coarse
        codes_flat = lossless_decompress(blob[off:])

        step = 2.0 * E
        r = np.zeros(shape, dtype=np.float64)
        coarse = _coarse(shape)
        r[coarse] = coarse_vals.reshape(r[coarse].shape)

        pos = 0
        for stride, axis in _pass_schedule(shape):
            idx = _pass_slices(shape, stride, axis)
            if idx is None:
                continue
            tgt, pairs = idx
            pred = _predict(r, tgt, pairs)
            n = pred.size
            codes = codes_flat[pos : pos + n].reshape(pred.shape)
            pos += n
            np.add(pred, codes * step, out=r[tgt])
        return r.astype(np.float32)

"""Base compressors: the pointwise L-inf contract, all dims and dtypes."""

import struct

import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or skip-stubs (requirements-dev.txt)

from repro.coding import lossless
from repro.compressors import base_residual, get_compressor, keeps_recon

NAMES = ["szlike", "zfplike", "sperrlike", "identity"]


def _field(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(shape).astype(np.float32).cumsum(axis=0)


class TestBoundContract:
    @pytest.mark.parametrize("name", NAMES)
    @pytest.mark.parametrize("shape", [(257,), (33, 21), (17, 12, 9)])
    @pytest.mark.parametrize("E", [1e-1, 1e-3])
    def test_linf_bound(self, name, shape, E):
        x = _field(shape)
        c = get_compressor(name)
        xh = c.decompress(c.compress(x, E))
        assert xh.shape == x.shape
        assert np.abs(xh - x).max() <= E * (1 + 1e-5), name

    @pytest.mark.parametrize("name", NAMES)
    def test_compresses(self, name):
        """Smooth data must compress below raw float32 size."""
        x = _field((64, 64))
        blob = get_compressor(name).compress(x, 1e-2)
        if name != "identity":
            assert len(blob) < x.nbytes / 2, (name, len(blob))

    @pytest.mark.parametrize("name", ["szlike", "zfplike", "sperrlike"])
    def test_rejects_nonpositive_bound(self, name):
        with pytest.raises(ValueError):
            get_compressor(name).compress(_field((8, 8)), 0.0)

    def test_unknown_name(self):
        with pytest.raises(ValueError):
            get_compressor("nope")

    @pytest.mark.parametrize("name", ["szlike", "zfplike"])
    @given(st.integers(1, 3), st.integers(0, 1000))
    @settings(max_examples=20, deadline=None)
    def test_bound_property(self, name, ndim, seed):
        rng = np.random.default_rng(seed)
        shape = tuple(int(rng.integers(3, 24)) for _ in range(ndim))
        x = (rng.standard_normal(shape) * rng.uniform(0.1, 10)).astype(np.float32)
        E = float(rng.uniform(1e-4, 1e-1)) * (np.ptp(x) + 1e-6)
        c = get_compressor(name)
        xh = c.decompress(c.compress(x, E))
        assert np.abs(xh - x).max() <= E * (1 + 1e-5)


class TestBaseResidual:
    @pytest.mark.parametrize("name", ["szlike", "zfplike", "identity"])
    def test_same_blob_and_residual_as_the_round_trip(self, name):
        x = _field((33, 21), seed=4)
        E = 1e-3 * float(np.ptp(x))
        c = get_compressor(name)
        blob, eps0 = base_residual(c, x, E)
        ref_blob = c.compress(x, E)
        ref_eps0 = np.asarray(c.decompress(ref_blob), dtype=np.float32) - x
        assert blob == ref_blob
        assert eps0.dtype == np.float32
        assert np.array_equal(eps0.view(np.uint32), ref_eps0.view(np.uint32))


class TestRatioOrdering:
    def test_smoothness_helps(self):
        """zfplike should beat identity/zlib on smooth fields (decorrelation)."""
        from repro.data.fields import make_field

        x = make_field("s3d-like")
        z = get_compressor("zfplike").compress(x, 1e-3 * np.ptp(x))
        i = get_compressor("identity").compress(x, 1e-3)
        assert len(z) < len(i)


class TestChunkedBaseCodec:
    """A 128^3 nyx-like field's int16 code stream (~4 MiB) spans several
    deflate chunks, so szlike's payload is deflated on the pool."""

    def test_szlike_decodes_as_the_serial_blob(self, monkeypatch):
        from repro.data.fields import make_field

        x = make_field("nyx-like-128")
        E = 1e-3 * float(np.ptp(x))
        c = get_compressor("szlike")
        blob = c.compress(x, E)
        monkeypatch.setattr(lossless, "DEFLATE_CHUNK_BYTES", 1 << 40)  # one chunk: serial
        serial = c.compress(x, E)
        assert blob != serial
        assert np.array_equal(c.decompress(blob), c.decompress(serial))
        assert abs(len(blob) - len(serial)) <= 0.002 * len(serial)


# -- the strided interpolation passes against the np.ix_ index grids --------


def _ix_pass_indices(shape, stride, axis):
    """One pass's ``np.ix_`` grids (target, left, right), as the passes were
    indexed before they ran on strided views."""
    n_a = shape[axis]
    tgt = np.arange(stride, n_a, 2 * stride)
    if tgt.size == 0:
        return None
    left = tgt - stride
    right = np.where(tgt + stride < n_a, tgt + stride, tgt - stride)
    others = [np.arange(0, n, stride if a < axis else 2 * stride) for a, n in enumerate(shape) if a != axis]

    def with_axis(ax_idx):
        return np.ix_(*others[:axis], ax_idx, *others[axis:])

    return with_axis(tgt), with_axis(left), with_axis(right)


def _ix_reference(codec, x, E):
    """szlike's blob and decode by ``np.ix_`` gathers and scatters."""
    from repro.coding.lossless import lossless_compress
    from repro.compressors.szlike import _coarse_stride, _pass_schedule

    shape, step = x.shape, 2.0 * E
    r = np.zeros(shape, dtype=np.float64)
    coarse_ix = np.ix_(*[np.arange(0, n, _coarse_stride(shape)) for n in shape])
    coarse_vals = x[coarse_ix].astype(np.float32)
    r[coarse_ix] = coarse_vals
    codes_all = []
    for stride, axis in _pass_schedule(shape):
        idx = _ix_pass_indices(shape, stride, axis)
        if idx is None:
            continue
        tgt, left, right = idx
        pred = 0.5 * (r[left] + r[right])
        codes = np.rint((x[tgt].astype(np.float64) - pred) / step)
        r[tgt] = pred + codes * step
        codes_all.append(codes.astype(np.int64).ravel())
    codes_flat = np.concatenate(codes_all) if codes_all else np.zeros(0, dtype=np.int64)
    header = struct.pack("<dB", E, x.ndim) + struct.pack(f"<{x.ndim}Q", *shape)
    header += struct.pack("<I", coarse_vals.size) + coarse_vals.tobytes()
    return header + lossless_compress(codes_flat, codec=codec), r.astype(np.float32)


STRIDED_SHAPES = [
    (1,), (2,), (257,), (256,),
    (1, 7), (2, 2), (33, 21), (32, 64), (2, 5),
    (17, 12, 9), (16, 16, 16), (1, 2, 9), (12, 1, 31), (9, 2, 1),
]


class TestStridedPasses:
    @pytest.mark.parametrize("shape", STRIDED_SHAPES)
    @pytest.mark.parametrize("codec", ["zlib", "huffman+zlib"])
    def test_blob_and_decode_match_the_ix_passes(self, shape, codec):
        x = _field(shape, seed=len(shape) + shape[-1])
        E = 1e-3 * float(np.ptp(x)) + 1e-6
        ref_blob, ref_dec = _ix_reference(codec, x, E)
        c = get_compressor("szlike", codec=codec)
        blob, recon = c.compress_with_recon(x, E)
        assert blob == ref_blob == c.compress(x, E)
        dec = c.decompress(blob)
        assert dec.dtype == np.float32 and dec.shape == x.shape
        assert np.array_equal(dec.view(np.uint32), ref_dec.view(np.uint32))
        # the kept reconstruction is what the decoder rebuilds, bit for bit
        assert np.array_equal(recon.astype(np.float32).view(np.uint32), dec.view(np.uint32))


class _DecodeOnly:
    """szlike's blob behind a codec that keeps no reconstruction."""

    def __init__(self):
        self._inner = get_compressor("szlike")

    def compress(self, x, E):
        return self._inner.compress(x, E)

    def decompress(self, blob):
        return self._inner.decompress(blob)


class TestKeptReconstruction:
    @pytest.mark.parametrize("shape", [(257,), (33, 21), (17, 12, 9)])
    def test_kept_eps0_is_the_decoded_eps0(self, shape):
        x = _field(shape, seed=9)
        E = 1e-3 * float(np.ptp(x))
        kept_blob, kept = base_residual(get_compressor("szlike"), x, E)
        dec_blob, decoded = base_residual(_DecodeOnly(), x, E)
        assert kept_blob == dec_blob
        assert kept.dtype == decoded.dtype == np.float32
        assert np.array_equal(kept.view(np.uint32), decoded.view(np.uint32))

    @pytest.mark.parametrize("name,kept", [("szlike", True), ("zfplike", False), ("sperrlike", False), ("identity", False)])
    def test_which_codecs_keep_it(self, name, kept, monkeypatch):
        c = get_compressor(name)
        assert keeps_recon(c) is kept
        calls = []
        real = c.decompress
        monkeypatch.setattr(c, "decompress", lambda blob: calls.append(1) or real(blob))
        base_residual(c, _field((33, 21)), 1e-2)
        assert len(calls) == (0 if kept else 1)

"""Base compressors: the pointwise L-inf contract, all dims and dtypes."""

import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or skip-stubs (requirements-dev.txt)

from repro.coding import lossless
from repro.compressors import base_residual, get_compressor

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

"""End-to-end FFCz codec: dual-domain guarantees, serialization, edits."""

import dataclasses

import numpy as np
import pytest

from repro.compressors import get_compressor
from repro.core.edits import decode_edits, encode_edits
from repro.core.ffcz import FFCz, FFCzBlob, FFCzConfig
from repro.core.temporal import TemporalCodec, TemporalConfig, TemporalStream
from repro.data.fields import make_field
from repro.serving.ffcz_service import FFCzService, ServiceConfig


@pytest.fixture(scope="module")
def nyx():
    return make_field("nyx-like")[:32, :32, :32]


BASES = ["szlike", "zfplike", "sperrlike"]


class TestDualDomainGuarantee:
    @pytest.mark.parametrize("base", BASES)
    def test_scalar_bounds_hold(self, base, nyx):
        c = FFCz(get_compressor(base), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=1000))
        _, blob = c.roundtrip(nyx)
        st = blob.stats
        assert st.spatial_margin >= 0, st
        assert st.frequency_margin >= 0, st

    def test_pspec_bounds_hold(self, nyx):
        cfg = FFCzConfig(E_rel=1e-3, Delta_rel=None, pspec_rel=1e-3, max_iters=1500)
        c = FFCz(get_compressor("szlike"), cfg)
        xh, blob = c.roundtrip(nyx)
        assert blob.stats.spatial_margin >= 0
        assert blob.stats.frequency_margin >= 0
        # the actual guarantee of Observation 4: relative power-spectrum error
        from repro.core.spectrum import power_spectrum_relative_error

        _, rel = power_spectrum_relative_error(xh, nyx)
        assert np.abs(rel[1:]).max() <= 1e-3 * 1.05

    @pytest.mark.parametrize("dims", [(2048,), (64, 48)])
    def test_other_ranks(self, dims):
        rng = np.random.default_rng(3)
        x = rng.standard_normal(dims).astype(np.float32).cumsum(axis=0)
        c = FFCz(get_compressor("zfplike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=500))
        _, blob = c.roundtrip(x)
        assert blob.stats.spatial_margin >= 0 and blob.stats.frequency_margin >= 0


class TestSerialization:
    def test_blob_roundtrip(self, nyx):
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        xh, blob = c.roundtrip(nyx)
        blob2 = FFCzBlob.from_bytes(blob.to_bytes())
        xh2 = c.decompress(blob2)
        assert np.array_equal(xh, xh2)

    def test_edits_roundtrip_sparse(self, rng):
        edits = np.zeros(10_000)
        idx = rng.integers(0, 10_000, 50)
        edits[idx] = rng.standard_normal(50) * 0.01
        enc = encode_edits(edits, 0.05, m=16)
        back = decode_edits(enc, 0.05)
        assert np.abs(back - edits).max() <= 0.05 * 2.0**-16 * (1 + 1e-9)
        assert enc.n_active <= 50

    def test_edits_roundtrip_complex(self, rng):
        edits = (rng.standard_normal(500) + 1j * rng.standard_normal(500)) * 0.01
        enc = encode_edits(edits, 0.2, m=16)
        back = decode_edits(enc, 0.2)
        assert np.abs(back - edits).max() <= 0.2 * 2.0**-16 * np.sqrt(2) * (1 + 1e-9)


def _dense_encode_edits(edits, bound, m, codec="huffman+zlib", half_spectrum=False):
    """``encode_edits`` by quantizing every component, zero or not."""
    import zlib

    from repro.coding.bitpack import pack_bits
    from repro.coding.lossless import lossless_compress
    from repro.coding.quantize import quantize_uniform
    from repro.core.edits import EncodedEdits

    flat = np.asarray(edits).ravel()
    bound = np.asarray(bound, dtype=np.float64)
    bound = bound.ravel() if bound.ndim else bound
    if np.iscomplexobj(flat):
        codes = np.stack([quantize_uniform(flat.real, bound, m), quantize_uniform(flat.imag, bound, m)], -1)
        active = np.flatnonzero(codes.any(axis=-1))
        compact = codes[active].ravel()
    else:
        codes = quantize_uniform(flat, bound, m)
        active = np.flatnonzero(codes)
        compact = codes[active]
    flags = np.zeros(flat.size, dtype=bool)
    flags[active] = True
    return EncodedEdits(
        shape=tuple(np.shape(edits)),
        is_complex=np.iscomplexobj(flat),
        flags=zlib.compress(pack_bits(flags), 6),
        payload=lossless_compress(compact, codec=codec),
        n_active=int(active.size),
        quant_bits=m,
        half_spectrum=half_spectrum,
    )


def _sparse_stream(rng, shape, is_complex, density, scale):
    edits = np.zeros(shape, dtype=np.complex128 if is_complex else np.float64)
    k = max(1, int(density * edits.size))
    at = rng.choice(edits.size, k, replace=False)
    vals = rng.standard_normal(k) * scale
    if is_complex:
        vals = vals + 1j * rng.standard_normal(k) * scale * (rng.random(k) < 0.5)
    edits.flat[at] = vals
    return edits


# (shape, density, value scale against the bound): a scale of 1e-7 makes
# many nonzero values quantize to code 0; density 0 is an all-zero stream
EDIT_STREAMS = [
    ((4000,), 0.02, 0.3),
    ((24, 17, 9), 0.05, 0.5),
    ((24, 17, 9), 0.05, 1e-7),
    ((30, 40), 0.5, 2.0),
    ((30, 40), 0.0, 1.0),
    ((1, 1), 1.0, 0.5),
]


class TestActiveSetEncode:
    """``encode_edits`` quantizes only the nonzero components; its streams
    are the dense quantizer's, byte for byte."""

    @pytest.mark.parametrize("shape,density,scale", EDIT_STREAMS)
    @pytest.mark.parametrize("is_complex", [False, True])
    @pytest.mark.parametrize("per_component", [False, True])
    @pytest.mark.parametrize("codec", ["huffman+zlib", "zlib"])
    def test_matches_the_dense_encoder(self, shape, density, scale, is_complex, per_component, codec):
        rng = np.random.default_rng(sum(shape) + int(is_complex))
        edits = _sparse_stream(rng, shape, is_complex, density, 0.05 * scale) if density else np.zeros(
            shape, dtype=np.complex128 if is_complex else np.float64
        )
        bound = rng.uniform(0.02, 0.08, shape) if per_component else 0.05
        if per_component:
            bound.flat[:: 7] = 0.0  # zero-width bounds quantize to code 0
        enc = encode_edits(edits, bound, m=18, codec=codec, half_spectrum=is_complex)
        ref = _dense_encode_edits(edits, bound, 18, codec=codec, half_spectrum=is_complex)
        assert enc == ref
        assert enc.to_bytes() == ref.to_bytes()
        if scale == 1e-7 or not density:
            assert enc.n_active == 0
        back = decode_edits(enc, bound)
        assert np.array_equal(back, decode_edits(ref, bound))


class TestEditsAreSparse:
    def test_edit_overhead_modest(self, nyx):
        """Paper Obs. 1: edits cost a modest fraction on top of the base."""
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-2, max_iters=500))
        _, blob = c.roundtrip(nyx)
        st = blob.stats
        n = nyx.size
        assert st.n_active_spatial < n * 0.2
        # flags dominate the floor: edit bytes should be well under raw data
        assert st.edit_bytes < nyx.nbytes / 2


class TestConfigValidation:
    def test_requires_exactly_one_spatial(self):
        with pytest.raises(ValueError):
            FFCzConfig(E_abs=1.0, E_rel=1.0, Delta_rel=1e-3, Delta_abs=None)

    def test_requires_exactly_one_frequency(self):
        with pytest.raises(ValueError):
            FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, pspec_rel=1e-3)

    def test_identity_base_zero_iterations(self, nyx):
        c = FFCz(get_compressor("identity"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        _, blob = c.roundtrip(nyx)
        assert blob.stats.iterations == 1  # converges at the first check
        assert blob.stats.n_active_spatial == 0


class TestNonConvergenceSurfacing:
    def test_too_tight_bound_pair_reports_violations(self, rng):
        """A starved POCS budget on a too-tight frequency bound must not fail
        silently: stats carry converged=False plus the pair-weighted count of
        components still outside the shrunk f-cube after the polish."""
        x = rng.standard_normal((24, 24)).astype(np.float32).cumsum(axis=0)
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-7, max_iters=1))
        _, blob = c.roundtrip(x)
        st = blob.stats
        assert st.converged is False
        assert st.final_violations > 0
        # the spatial bound still holds by construction (final state is
        # inside the s-cube); only the frequency bound is violated
        assert st.spatial_margin >= 0

    def test_converged_run_reports_zero_violations(self, nyx):
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=1000))
        _, blob = c.roundtrip(nyx)
        assert blob.stats.converged is True
        assert blob.stats.final_violations == 0


class TestOnePipeline:
    def test_every_writer_mints_the_same_blob(self, nyx):
        """FFCz.compress, the service's whole-field request and frame 0 of a
        field stream run one pipeline: the same config and base give the same
        bytes from each writer."""
        cfg = FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=1000)
        x = np.ascontiguousarray(nyx)
        direct = FFCz(get_compressor("szlike"), cfg).compress(x).to_bytes()

        svc = FFCzService(get_compressor("szlike"), config=ServiceConfig(pipeline_depth=1))
        uid = svc.submit_compress(x, cfg)
        resp = svc.drain()[uid]
        svc.close()
        assert resp.ok, resp.error

        codec = TemporalCodec(get_compressor("szlike"), cfg, TemporalConfig(mode="field"))
        frame0 = TemporalStream.from_bytes(codec.compress_stream([x])).frame_payload(0)

        assert resp.payload == direct
        assert frame0 == direct

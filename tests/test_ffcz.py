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

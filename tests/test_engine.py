"""CorrectionEngine: backend parity (incl. shard_map on a multi-device CPU
mesh), engine-vs-legacy golden compression stats, plan-stage spectrum
laziness, and the versioned FFCzBlob wire format (legacy blob fixture)."""

import json
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

from repro.compressors import get_compressor
from repro.core.engine import CorrectionEngine, default_engine
from repro.core.ffcz import FFCz, FFCzBlob, FFCzConfig

_DATA = os.path.join(os.path.dirname(__file__), "data")

# ---------------------------------------------------------------------------
# sharded backend parity: >= 2 fake CPU devices, so a subprocess (XLA_FLAGS
# must be set before jax import — same pattern as tests/test_distributed.py)

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import numpy as np
from repro.core.engine import CorrectionEngine

rng = np.random.default_rng(7)
# block counts 5 + 3 + 1 = 9: odd total exercises the sharded backend's
# pad-to-axis-multiple path
tensors = [
    (rng.standard_normal(2500) * 0.02).astype(np.float32),
    (rng.standard_normal((32, 48)) * 0.01).astype(np.float32),
    (rng.standard_normal(100) * 0.01).astype(np.float32),
]
E, D = [0.03, 0.02, 0.05], [0.4, 0.5, 0.2]

eng_b = CorrectionEngine("batched")
eng_s = CorrectionEngine("sharded")
out = {"n_dev": len(jax.devices())}

cb, eb, sb = eng_b.correct(tensors, E, D, block=512, max_iters=50, return_edits=True)
cs, es, ss = eng_s.correct(tensors, E, D, block=512, max_iters=50, return_edits=True)

out["corrected_bitwise"] = all(
    np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(cb, cs)
)
out["edits_bitwise"] = all(
    np.array_equal(np.asarray(s1), np.asarray(s2)) and np.array_equal(np.asarray(f1), np.asarray(f2))
    for (s1, f1), (s2, f2) in zip(eb, es)
)
out["iters_equal"] = bool(np.array_equal(np.asarray(sb.iterations), np.asarray(ss.iterations)))
out["block_stats_equal"] = bool(
    np.array_equal(np.asarray(sb.block_iterations), np.asarray(ss.block_iterations))
    and np.array_equal(np.asarray(sb.block_converged), np.asarray(ss.block_converged))
)
out["n_blocks"] = int(np.asarray(sb.block_iterations).shape[0])
out["all_converged"] = bool(np.asarray(sb.converged).all())
print("RESULTS:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT], capture_output=True, text=True, env=env, timeout=600
    )
    if proc.returncode != 0:  # the child's whole stderr, not a tail
        pytest.fail(f"child exited {proc.returncode}:\n{proc.stderr}", pytrace=False)
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS:")][0]
    return json.loads(line[len("RESULTS:"):])


class TestShardedBackend:
    def test_runs_on_multi_device_mesh(self, sharded_results):
        assert sharded_results["n_dev"] == 2
        assert sharded_results["n_blocks"] == 9  # odd: pad path exercised

    def test_bit_identical_to_batched(self, sharded_results):
        assert sharded_results["corrected_bitwise"]
        assert sharded_results["edits_bitwise"]

    def test_stats_identical_to_batched(self, sharded_results):
        assert sharded_results["iters_equal"]
        assert sharded_results["block_stats_equal"]
        assert sharded_results["all_converged"]

    def test_sharded_requires_mesh_arg(self):
        from repro.core.blockwise import correct_batch

        with pytest.raises(ValueError, match="mesh"):
            correct_batch([np.zeros(8, np.float32)], 0.1, 0.1, backend="sharded")


class TestLocalBackendParity:
    def test_local_matches_batched(self, rng):
        tensors = [
            (rng.standard_normal(1200) * 0.02).astype(np.float32),
            (rng.standard_normal((16, 40)) * 0.01).astype(np.float32),
        ]
        E, D = [0.03, 0.02], [0.4, 0.5]
        cb, sb = CorrectionEngine("batched").correct(tensors, E, D, block=256, max_iters=50)
        cl, sl = CorrectionEngine("local").correct(tensors, E, D, block=256, max_iters=50)
        for a, b in zip(cb, cl):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(sb.iterations), np.asarray(sl.iterations))


# ---------------------------------------------------------------------------
# engine compression parity vs the pre-refactor host-numpy FFCz pipeline.
# Golden stats recorded from the pre-engine FFCz.compress on this corpus.


@pytest.fixture(scope="module")
def nyx():
    from repro.data.fields import make_field

    return make_field("nyx-like")[:32, :32, :32]


class TestGoldenCompressionParity:
    def test_delta_rel_stats_match_legacy_pipeline(self, nyx):
        """Scalar-Delta config: byte-identical to the pre-refactor pipeline
        (same bounds, same edits, same payload bytes; the only wire change
        is the 5-byte magic+version header)."""
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=1000))
        blob = c.compress(nyx)
        st = blob.stats
        assert int(st.iterations) == 2 and st.converged
        assert st.n_active_spatial == 3
        assert st.n_active_frequency == 2
        assert st.base_bytes == 25571
        assert st.edit_bytes == 229
        assert blob.E == pytest.approx(0.38016030192375183, rel=1e-12)
        assert blob.Delta_scalar == pytest.approx(116.1599349975586, rel=1e-12)
        # pre-refactor blob was 25873 bytes; +5 = magic + version byte
        assert blob.nbytes() == 25873 + 5

    def test_pspec_stats_match_legacy_pipeline(self, nyx):
        """pspec config: equal bounds and active sets vs the pre-refactor
        pipeline.  The Delta_k grid is now built from a device (float32)
        rfft rather than a host float64 one, so grid values may differ at
        float32-rounding level (~1e-7 relative) — active counts and margins
        are unchanged; payload bytes may shift by a few quantization codes."""
        cfg = FFCzConfig(E_rel=1e-3, Delta_rel=None, pspec_rel=1e-3, max_iters=1500)
        c = FFCz(get_compressor("szlike"), cfg)
        blob = c.compress(nyx)
        st = blob.stats
        assert int(st.iterations) == 2 and st.converged
        assert st.n_active_spatial == 0
        assert st.n_active_frequency == 17407
        assert st.base_bytes == 25571
        assert blob.E == pytest.approx(0.38016030192375183, rel=1e-12)
        assert st.spatial_margin == pytest.approx(0.37088, abs=1e-4)
        assert st.spatial_margin >= 0 and st.frequency_margin >= 0


# ---------------------------------------------------------------------------
# plan stage computes only the spectra it consumes (satellite: skip the
# wasted forward rfftn under Delta_abs)


class TestPlanSpectrumLaziness:
    def _count_rfftn(self, monkeypatch):
        import jax.numpy as jnp

        calls = {"n": 0}
        real = jnp.fft.rfftn

        def counting(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(jnp.fft, "rfftn", counting)
        return calls

    def test_delta_abs_plan_skips_forward_fft(self, rng, monkeypatch):
        calls = self._count_rfftn(monkeypatch)
        x = rng.standard_normal((32, 32)).astype(np.float32)
        default_engine().plan_field(x, FFCzConfig(E_rel=1e-3, Delta_rel=None, Delta_abs=0.5))
        assert calls["n"] == 0

    def test_delta_rel_plan_computes_forward_fft(self, rng, monkeypatch):
        calls = self._count_rfftn(monkeypatch)
        x = rng.standard_normal((32, 32)).astype(np.float32)
        plan = default_engine().plan_field(x, FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        assert calls["n"] == 1 and plan.Delta > 0

    def test_delta_abs_end_to_end(self, rng):
        x = rng.standard_normal((48, 32)).astype(np.float32).cumsum(axis=0)
        cfg = FFCzConfig(E_rel=1e-3, Delta_rel=None, Delta_abs=float(np.abs(np.fft.fftn(x)).max() * 1e-3))
        c = FFCz(get_compressor("zfplike"), cfg)
        _, blob = c.roundtrip(x)
        assert blob.stats.spatial_margin >= 0 and blob.stats.frequency_margin >= 0


# ---------------------------------------------------------------------------
# versioned wire format + legacy (v0, magic-less) blob fixture


class TestBlobWireFormat:
    def test_v1_magic_and_version(self, nyx):
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        _, blob = c.roundtrip(nyx)
        raw = blob.to_bytes()
        assert raw[:4] == b"FFCZ" and raw[4] == 1
        back = FFCzBlob.from_bytes(raw)
        assert back.shape == blob.shape and back.base_blob == blob.base_blob

    def test_truncated_raises(self, nyx):
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        raw = c.compress(nyx).to_bytes()
        for cut in (3, 4, 20, len(raw) - 1):
            with pytest.raises(ValueError):
                FFCzBlob.from_bytes(raw[:cut])

    def test_foreign_bytes_raise(self):
        for junk in (b"", b"junk", b"\x00" * 64, os.urandom(256)):
            with pytest.raises(ValueError):
                FFCzBlob.from_bytes(junk)

    def test_unknown_version_raises(self, nyx):
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        raw = bytearray(c.compress(nyx).to_bytes())
        raw[4] = 9
        with pytest.raises(ValueError, match="version"):
            FFCzBlob.from_bytes(bytes(raw))

    def test_golden_legacy_v0_blob_roundtrip(self):
        """A checked-in magic-less blob written by the pre-version wire
        format must decode bit-identically to its recorded reconstruction."""
        data = open(os.path.join(_DATA, "legacy_blob_v0.bin"), "rb").read()
        assert data[:4] != b"FFCZ"  # genuinely magic-less
        blob = FFCzBlob.from_bytes(data)
        x = np.load(os.path.join(_DATA, "legacy_blob_v0_input.npy"))
        expected = np.load(os.path.join(_DATA, "legacy_blob_v0_output.npy"))
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        got = c.decompress(blob)
        assert np.array_equal(got, expected)
        # and the legacy guarantee still holds against the original field
        assert np.abs(got - x).max() <= blob.E * (1 + 1e-9)

    def test_rewritten_legacy_blob_gains_magic(self):
        data = open(os.path.join(_DATA, "legacy_blob_v0.bin"), "rb").read()
        blob = FFCzBlob.from_bytes(data)
        raw = blob.to_bytes()
        assert raw[:4] == b"FFCZ" and len(raw) == len(data) + 5
        assert np.array_equal(
            struct.unpack_from("<dd", raw, 5), struct.unpack_from("<dd", data, 0)
        )

    def test_golden_uneven_v1_blob_with_pad_metadata(self):
        """A checked-in v1 blob written from an uneven (15, 14, 10)
        ShardedField on an 8-way mesh: its FFCP pad-metadata section must
        parse, survive a rewrite byte-exactly, and decode bit-identically to
        the recorded reconstruction — with both stored bounds holding."""
        data = open(os.path.join(_DATA, "uneven_v1_blob.bin"), "rb").read()
        blob = FFCzBlob.from_bytes(data)
        assert blob.pad_meta is not None
        assert blob.pad_meta.n_dev == 8
        assert blob.pad_meta.padded_shape == (16, 14, 10)
        assert blob.shape == (15, 14, 10)
        assert blob.to_bytes() == data  # decode -> re-encode is stable
        x = np.load(os.path.join(_DATA, "uneven_v1_input.npy"))
        expected = np.load(os.path.join(_DATA, "uneven_v1_output.npy"))
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        got = c.decompress(blob)
        assert np.array_equal(got, expected)
        eps = got.astype(np.float64) - x.astype(np.float64)
        assert np.abs(eps).max() <= blob.E
        d = np.fft.rfftn(eps)
        assert max(np.abs(d.real).max(), np.abs(d.imag).max()) <= blob.Delta_scalar

    def test_golden_padfree_v1_blob_still_decodes_byte_exactly(self):
        """The pad-free v1 fixture (same field, single-device writer) has no
        FFCP tail and must keep decoding byte-exactly now that the parser
        sniffs for one."""
        data = open(os.path.join(_DATA, "padfree_v1_blob.bin"), "rb").read()
        blob = FFCzBlob.from_bytes(data)
        assert blob.pad_meta is None
        assert blob.to_bytes() == data
        expected = np.load(os.path.join(_DATA, "padfree_v1_output.npy"))
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        assert np.array_equal(c.decompress(blob), expected)

    def test_pad_metadata_tail_corruption_raises(self):
        data = open(os.path.join(_DATA, "uneven_v1_blob.bin"), "rb").read()
        for junk in (data + b"x", data[:-1]):
            with pytest.raises(ValueError):
                FFCzBlob.from_bytes(junk)
        # foreign (non-FFCP) tail on a pad-free blob is corruption too
        clean = open(os.path.join(_DATA, "padfree_v1_blob.bin"), "rb").read()
        with pytest.raises(ValueError, match="pad-metadata|corrupt"):
            FFCzBlob.from_bytes(clean + b"JUNKJUNKJUNK")


class TestFloat64Polish:
    def test_stops_on_the_roundoff_floor(self, nyx, monkeypatch):
        """The polish leaves its loop once the frequency excess stops
        shrinking at float64 round-off, with the spatial bound exact and the
        excess under the floor -- far fewer round trips than its cap."""
        from repro.core import engine as engine_mod

        seen = []
        real = engine_mod.polish_pocs_float64
        monkeypatch.setattr(
            engine_mod, "polish_pocs_float64", lambda *a: seen.append(a) or real(*a)
        )
        cfg = FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, verify=False)
        FFCz(get_compressor("szlike"), cfg).compress(nyx)
        eps0, spat0, freq0, E, Delta = seen[0]

        calls = []
        rfftn = engine_mod.host_fft.rfftn
        monkeypatch.setattr(
            engine_mod.host_fft, "rfftn", lambda *a, **k: calls.append(1) or rfftn(*a, **k)
        )
        eps, _spat, _freq, settled = real(eps0, spat0, freq0, E, Delta)
        # one forward transform per check: the round trips plus the last check
        assert settled and 2 <= len(calls) < 15
        d = np.fft.rfftn(eps)
        excess = np.max(np.maximum(np.abs(d.real), np.abs(d.imag))) - Delta
        assert excess <= engine_mod.POLISH_FLOOR_REL * Delta
        assert np.abs(eps).max() <= E

"""Temporal stream codec gates (ISSUE 8).

The stream contract in test form:

  container   ``FFCS`` round trips; ``decode_frame`` (seek from the latest
              keyframe) is bitwise ``decompress_stream(data)[t]`` for every
              frame; corrupt bytes (magic, truncation, CRC, a non-keyframe
              first frame) raise :class:`BlobCorruptError`, never garbage.
  predictor   residuals are taken against the predictor evaluated on
              DECODED history, so per-frame error never accumulates along a
              long residual chain — rechecked in float64 against the bounds
              the stream header claims.
  warm start  ``warm_start=False`` (the default) is bitwise-neutral: the
              engine ignores any ``warm_freq`` and reproduces the legacy
              cold trajectory; ``warm_start=True`` still conforms.
  service     ``submit_stream`` preserves submission order through the
              FRONT/BACK pipeline at depths 1 and 2, and FFCS bytes decode
              through ``submit_decompress`` to the stacked frames.
"""

import struct
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compressors import get_compressor
from repro.core.engine import default_engine
from repro.core.errors import BlobCorruptError, FFCzError, StreamStateError
from repro.core.ffcz import FFCz, FFCzConfig
from repro.core.pocs import alternating_projection
from repro.core.temporal import TemporalCodec, TemporalConfig, TemporalStream
from repro.serving.ffcz_service import FFCzService, ServiceConfig

pytestmark = pytest.mark.timeout(180)


def _frames(n, shape=(16, 16), seed=0, drift=0.05):
    """Coherent synthetic sequence: a fixed field plus a slowly drifting
    structured mode plus small per-frame noise (what a predictor can win on)."""
    rng = np.random.default_rng(seed)
    base = (rng.standard_normal(shape) * 0.5 + 4.0).cumsum(axis=0)
    mode = np.cos(np.linspace(0, 2 * np.pi, base.size)).reshape(shape)
    out = []
    for t in range(n):
        x = base + drift * t * mode + 0.01 * rng.standard_normal(shape)
        out.append(np.ascontiguousarray(x, dtype=np.float32))
    return out


def _codec(mode="field", warm_start=True, predictor="linear", interval=4, **cfg_kw):
    cfg = dict(E_rel=1e-3, Delta_rel=1e-3, max_iters=300, warm_start=warm_start)
    cfg.update(cfg_kw)
    return TemporalCodec(
        get_compressor("szlike"),
        FFCzConfig(**cfg),
        TemporalConfig(mode=mode, predictor=predictor, keyframe_interval=interval),
    )


class TestContainer:
    @pytest.mark.parametrize("mode", ["field", "pencils"])
    def test_round_trip_and_keyframe_cadence(self, mode):
        frames = _frames(9)
        codec = _codec(mode, interval=4)
        data = codec.compress_stream(frames)
        s = TemporalStream.from_bytes(data)
        assert s.n_frames == 9 and s.shape == frames[0].shape
        assert [s.is_keyframe(t) for t in range(9)] == [t % 4 == 0 for t in range(9)]
        dec = codec.decompress_stream(data)
        assert len(dec) == 9
        for x, d in zip(frames, dec):
            assert d.shape == x.shape and d.dtype == np.float32
            assert np.abs(d.astype(np.float64) - x.astype(np.float64)).max() <= s.E

    @pytest.mark.parametrize("mode", ["field", "pencils"])
    def test_seek_matches_full_decode_bitwise(self, mode):
        """decode_frame walks from the latest keyframe only — resync means
        that chain reproduces the full sequential decode exactly."""
        frames = _frames(10, seed=3)
        codec = _codec(mode, interval=4)
        data = codec.compress_stream(frames)
        full = codec.decompress_stream(data)
        for t in range(10):
            assert np.array_equal(codec.decode_frame(data, t), full[t]), t
        with pytest.raises(IndexError):
            codec.decode_frame(data, 10)
        with pytest.raises(IndexError):
            codec.decode_frame(data, -1)

    def test_decoder_is_header_driven(self):
        """Any codec instance decodes any stream: the container header, not
        the decoder's own TemporalConfig, names mode/predictor/interval."""
        frames = _frames(6, seed=5)
        data = _codec("pencils", predictor="linear", interval=3).compress_stream(frames)
        other = _codec("field", predictor="identity", interval=8, warm_start=False)
        dec = other.decompress_stream(data)
        E = TemporalStream.from_bytes(data).E
        for x, d in zip(frames, dec):
            assert np.abs(d.astype(np.float64) - x.astype(np.float64)).max() <= E

    def test_corrupt_bytes_raise(self):
        data = _codec("field", interval=2).compress_stream(_frames(4))
        with pytest.raises(BlobCorruptError, match="magic"):
            TemporalStream.from_bytes(b"XXCS" + data[4:])
        for keep in (0, 3, 5, 12, len(data) // 2):
            with pytest.raises(BlobCorruptError):
                TemporalStream.from_bytes(data[:keep])
        # flip one bit inside the CRC-covered header region
        bad = bytearray(data)
        bad[8] ^= 0x10
        with pytest.raises(BlobCorruptError):
            TemporalStream.from_bytes(bytes(bad))

    def test_first_frame_must_be_keyframe(self):
        """A stream whose index marks frame 0 as a residual is structurally
        corrupt (there is no predecessor to predict from) — rebuild the
        header with the flag cleared and a fresh CRC to prove the parser
        rejects it rather than the CRC merely masking the case."""
        data = _codec("field", interval=2).compress_stream(_frames(4))
        s = TemporalStream.from_bytes(data)
        index_end = s.frames_base - 4
        entry = struct.calcsize("<QQB")
        first_entry = index_end - s.n_frames * entry
        bad = bytearray(data)
        bad[first_entry + 16] = 0  # clear frame 0's keyframe flag
        bad[index_end : index_end + 4] = struct.pack("<I", zlib.crc32(bytes(bad[:index_end])))
        with pytest.raises(BlobCorruptError, match="keyframe"):
            TemporalStream.from_bytes(bytes(bad))

    def test_empty_and_mismatched_frames_rejected(self):
        codec = _codec()
        with pytest.raises(ValueError, match="empty"):
            codec.compress_stream([])
        enc = codec.open_stream()
        with pytest.raises(ValueError, match="empty"):
            enc.add_frame(np.zeros((0, 4), np.float32))
        enc.add_frame(_frames(1)[0])
        with pytest.raises(ValueError, match="shape"):
            enc.add_frame(np.zeros((4, 4), np.float32))

    def test_config_validation(self):
        with pytest.raises(ValueError, match="predictor"):
            TemporalConfig(predictor="quadratic")
        with pytest.raises(ValueError, match="mode"):
            TemporalConfig(mode="blocks")
        with pytest.raises(ValueError, match="keyframe_interval"):
            TemporalConfig(keyframe_interval=0)
        with pytest.raises(ValueError, match="pspec"):
            TemporalCodec(
                get_compressor("szlike"),
                FFCzConfig(E_rel=1e-3, Delta_rel=None, pspec_rel=1e-3),
            )


class TestPredictorSelfCorrection:
    @pytest.mark.parametrize("predictor", ["identity", "linear"])
    def test_long_residual_chain_holds_bounds(self, predictor):
        """24 residual frames off one keyframe: predicting from DECODED
        history makes the chain self-correcting, so the float64-rechecked
        per-frame error stays inside the stream's claimed bounds at frame 24
        exactly as at frame 1 — no accumulation."""
        frames = _frames(25, shape=(12, 12), seed=11)
        codec = _codec("field", predictor=predictor, interval=32)
        data = codec.compress_stream(frames)
        s = TemporalStream.from_bytes(data)
        assert [s.is_keyframe(t) for t in range(25)] == [True] + [False] * 24
        dec = codec.decompress_stream(data)
        for t, (x, d) in enumerate(zip(frames, dec)):
            eps = d.astype(np.float64) - x.astype(np.float64)
            assert np.abs(eps).max() <= s.E, f"spatial bound violated at frame {t}"
            spec = np.fft.rfftn(eps)
            assert np.abs(spec.real).max() <= s.Delta, f"freq bound (Re) at frame {t}"
            assert np.abs(spec.imag).max() <= s.Delta, f"freq bound (Im) at frame {t}"

    def test_roi_frames_carry_their_bound_grid(self):
        """A field stream under an ROI mask: every frame's blob carries the
        FFCR grid its spatial edits were quantized against, so each decoded
        frame holds the stream's claimed E everywhere and the tightened
        bound inside the region."""
        frames = _frames(4, shape=(32, 32), seed=5)
        mask = np.zeros((32, 32), dtype=bool)
        mask[8:16, 8:16] = True
        codec = _codec("field", warm_start=False, interval=2, E_roi=mask, E_roi_scale=0.1)
        data = codec.compress_stream(frames)
        s = TemporalStream.from_bytes(data)
        for t, (x, d) in enumerate(zip(frames, codec.decompress_stream(data))):
            eps = np.abs(d.astype(np.float64) - x.astype(np.float64))
            assert eps.max() <= s.E, f"spatial bound violated at frame {t}"
            assert eps[mask].max() <= 0.1 * s.E, f"ROI bound violated at frame {t}"

    def test_encoder_history_matches_decoder(self):
        """The encoder's committed decoded history IS the decoder's output —
        the property the self-correction argument rests on."""
        frames = _frames(8, seed=2)  # keyframes at 0/3/6, so 6..7 is history
        codec = _codec("field", interval=3)
        enc = codec.open_stream()
        for x in frames:
            enc.add_frame(x)
        dec = codec.decompress_stream(enc.finish())
        # the last two decoded frames are exactly the encoder's history
        assert np.array_equal(enc._history[-1], dec[-1])
        assert np.array_equal(enc._history[-2], dec[-2])


class TestWarmStart:
    def test_disabled_is_bitwise_neutral(self):
        """A cold plan (warm_start=False, the default) ignores any supplied
        warm spectrum: the POCS trajectory, and hence the encoded stream
        bytes, are bit-for-bit the legacy ones."""
        rng = np.random.default_rng(7)
        x = _frames(1, seed=7)[0]
        eng = default_engine()
        plan = eng.plan_field(x, FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        assert plan.warm_start is False
        eps0 = (0.3 * plan.E * rng.standard_normal(x.shape)).astype(np.float32)
        cold = eng.execute_field(eps0, plan)
        junk = (rng.standard_normal(np.asarray(cold.freq).shape) * 1e-3).astype(np.complex64)
        again = eng.execute_field(eps0, plan, warm_freq=junk)
        assert np.array_equal(np.asarray(cold.freq), np.asarray(again.freq))
        assert int(cold.iterations) == int(again.iterations)

    def test_disabled_stream_keyframes_equal_plain_ffcz(self):
        """With warm_start off and interval 1, every frame is an independent
        cold keyframe — frame 0's payload is byte-identical to what the
        plain per-frame FFCz path produces for the same input."""
        frames = _frames(3, seed=9)
        cfg = FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=300)
        codec = TemporalCodec(
            get_compressor("szlike"), cfg, TemporalConfig(mode="field", keyframe_interval=1)
        )
        data = codec.compress_stream(frames)
        s = TemporalStream.from_bytes(data)
        plain = FFCz(get_compressor("szlike"), cfg).compress(frames[0])
        assert s.frame_payload(0) == plain.to_bytes()

    def test_warm_chain_cuts_pocs_iterations(self):
        """The iteration win itself, measured: a coherent 64^2 stream of 8
        base-error frames in the slow-convergence regime (scalar E, Delta
        pinned at the DC component only) drifting along one smooth mode.
        Each frame's POCS loop is seeded with the previous warm frame's
        converged spectrum, the codec's wiring; over frames 1..7 the mean
        iteration count must be at most the cold one over 1.2."""
        shape, E = (64, 64), 0.05
        rng = np.random.default_rng(0)
        sgn = np.where(rng.random(shape) < 0.52, 1.0, -1.0)
        eps0 = (E * sgn * (1 - 1e-4 * rng.random(shape))).astype(np.float32)
        Delta = np.full(shape, 1e9, dtype=np.float32)
        Delta[0, 0] = 0.01 * abs(eps0.astype(np.float64).sum())
        drift = np.cos(np.linspace(0, 2 * np.pi, eps0.size)).reshape(shape)
        frames = [np.clip(eps0 + 0.02 * E * t * drift, -E, E).astype(np.float32) for t in range(8)]

        def run(f, warm=None):
            r = alternating_projection(
                jnp.asarray(f), E, jnp.asarray(Delta), max_iters=200, warm_freq=warm
            )
            assert bool(r.converged)
            return r

        cold, warm_iters, warm = [], [], run(frames[0]).freq_edits
        for f in frames[1:]:
            cold.append(int(run(f).iterations))
            r = run(f, warm)
            warm_iters.append(int(r.iterations))
            warm = r.freq_edits
        assert np.mean(warm_iters) <= np.mean(cold) / 1.2, (cold, warm_iters)

    def test_enabled_still_conforms(self):
        """Warm residual frames converge and hold the same claimed bounds —
        the warm state is an initial guess, never a correctness input.  (The
        iteration win is test_warm_chain_cuts_pocs_iterations.)"""
        frames = _frames(8, seed=13, drift=0.2)
        for mode in ("field", "pencils"):
            codec = _codec(mode, warm_start=True, interval=8)
            enc = codec.open_stream()
            for x in frames:
                enc.add_frame(x)
            assert all(st["converged"] for st in enc.frame_stats), mode
            data = enc.finish()
            s = TemporalStream.from_bytes(data)
            dec = codec.decompress_stream(data)
            for t, (x, d) in enumerate(zip(frames, dec)):
                eps = d.astype(np.float64) - x.astype(np.float64)
                assert np.abs(eps).max() <= s.E, (mode, t)


class TestServiceStream:
    def _service(self, depth):
        return FFCzService(
            get_compressor("szlike"),
            config=ServiceConfig(max_batch=4, block=64, seed=1, pipeline_depth=depth),
            clock=lambda: 0.0,
            sleep=lambda s: None,
        )

    @pytest.mark.parametrize("depth", [1, 2])
    def test_stream_kind_ordering_and_decode(self, depth):
        svc = self._service(depth)
        cfg = FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=300, warm_start=True)
        frames = _frames(5, shape=(12, 12), seed=4)
        rng = np.random.default_rng(4)
        uids = [
            svc.submit_stream(frames, cfg, TemporalConfig(mode="field", keyframe_interval=2)),
            svc.submit_compress(rng.standard_normal((12, 12)).astype(np.float32),
                                FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=300)),
            svc.submit_stream(frames, cfg,
                              TemporalConfig(mode="pencils", predictor="identity")),
        ]
        res = svc.drain()
        assert list(res) == uids  # submission-ordered, streams interleaved with fields
        assert all(r.ok for r in res.values())
        ffcs = res[uids[0]].payload
        assert ffcs[:4] == b"FFCS"
        # FFCS bytes decode through the service to the stacked frames,
        # matching the library decoder exactly
        d = svc.submit_decompress(ffcs)
        out = svc.drain()[d].payload
        lib = np.stack(
            TemporalCodec(get_compressor("szlike"), cfg).decompress_stream(ffcs)
        )
        assert np.array_equal(out, lib)

    def test_stream_submit_validation(self):
        svc = self._service(1)
        with pytest.raises(ValueError, match="empty"):
            svc.submit_stream([], FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        # pspec bounds cannot back a stream claim: rejected as a response,
        # not a hang or a crash
        u = svc.submit_stream(
            _frames(2), FFCzConfig(E_rel=1e-3, Delta_rel=None, pspec_rel=1e-3)
        )
        res = svc.drain()[u]
        assert not res.ok and "pspec" in str(res.error)


class TestEncoderTerminalState:
    """``finish()`` is terminal (ISSUE 10): committed state can neither be
    mutated nor re-emitted afterwards — the session layer's finalize-vs-append
    serialization rests on this raising structurally instead of corrupting."""

    def _finished(self):
        codec = _codec("field", warm_start=False, interval=2)
        enc = codec.open_stream()
        frames = _frames(3, shape=(12, 12), seed=6)
        for x in frames:
            enc.add_frame(x)
        return codec, enc, frames, enc.finish()

    def test_add_frame_after_finish_raises(self):
        _codec_, enc, frames, _data = self._finished()
        assert enc.finished
        with pytest.raises(StreamStateError, match="finished stream"):
            enc.add_frame(frames[0])
        # structured: a service/session layer catches it as an FFCzError
        with pytest.raises(FFCzError):
            enc.add_frame(frames[0])

    def test_double_finish_raises(self):
        _codec_, enc, _frames_, data = self._finished()
        with pytest.raises(StreamStateError, match="twice"):
            enc.finish()
        # the first container stays valid — the guard protects, not poisons
        assert TemporalStream.from_bytes(data).n_frames == 3

    def test_failed_add_frame_is_retryable_not_terminal(self):
        codec, enc, frames, _data = self._finished()
        enc2 = codec.open_stream()
        enc2.add_frame(frames[0])
        with pytest.raises(ValueError, match="shape"):
            enc2.add_frame(np.zeros((4, 4), np.float32))
        # a FAILED add_frame never finishes the stream: the retry lands
        assert not enc2.finished
        enc2.add_frame(frames[1])
        assert enc2.n_frames == 2

    def test_export_restore_roundtrip_is_bitwise(self):
        frames = _frames(6, shape=(12, 12), seed=8)
        codec = _codec("field", warm_start=False, interval=2)
        ref = codec.compress_stream(frames)
        enc = codec.open_stream()
        for x in frames[:4]:
            enc.add_frame(x)
        state = enc.export_state()
        enc2 = codec.restore_stream(
            state["frames"],
            shape=state["shape"],
            block=state["block"],
            E0=state["E0"],
            Delta0=state["Delta0"],
        )
        for x in frames[4:]:
            enc2.add_frame(x)
        assert enc2.finish() == ref

    def test_restore_rejects_foreign_keyframe_cadence(self):
        frames = _frames(4, shape=(12, 12), seed=8)
        codec = _codec("field", warm_start=False, interval=2)
        enc = codec.open_stream()
        for x in frames:
            enc.add_frame(x)
        state = enc.export_state()
        other = _codec("field", warm_start=False, interval=3)
        with pytest.raises(BlobCorruptError, match="different stream config"):
            other.restore_stream(
                state["frames"],
                shape=state["shape"],
                E0=state["E0"],
                Delta0=state["Delta0"],
            )
        with pytest.raises(ValueError, match="empty"):
            codec.restore_stream(
                [], shape=state["shape"], E0=state["E0"], Delta0=state["Delta0"]
            )

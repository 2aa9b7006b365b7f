"""Hermitian rFFT fast path: oracle parity, weighted counts, batched entry,
half-spectrum serialization and legacy-blob backward compatibility."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compressors import get_compressor
from repro.core.blockwise import blockwise_correct, correct_batch
from repro.core.cubes import (
    fcube_violations,
    project_box_relaxed,
    rfft_pair_weights,
    rfft_shape,
)
from repro.core.edits import EncodedEdits, decode_edits, encode_edits
from repro.core.ffcz import FFCz, FFCzBlob, FFCzConfig
from repro.core.pocs import alternating_projection

# 1D/2D/3D, odd and even last axis — the N//2+1 edge cases
SHAPES = [(128,), (127,), (32, 32), (31, 17), (12, 10, 16), (8, 9, 15)]


def _mismatch(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


class TestRfftOracleParity:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_matches_complex_fft_scalar_delta(self, shape, rng):
        E = 0.1
        eps0 = np.clip(rng.standard_normal(shape) * 0.05, -E, E).astype(np.float32)
        Delta = 0.4 * np.abs(np.fft.fftn(eps0)).max()
        r_c = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=500, use_rfft=False)
        r_r = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=500, use_rfft=True)
        assert int(r_c.iterations) == int(r_r.iterations)
        assert bool(r_c.converged) and bool(r_r.converged)
        assert _mismatch(r_c.eps, r_r.eps) < 1e-6
        assert _mismatch(r_c.spat_edits, r_r.spat_edits) < 1e-6
        # freq edits agree after transforming back to the spatial basis
        full = np.fft.ifftn(np.asarray(r_c.freq_edits)).real
        half = np.fft.irfftn(
            np.asarray(r_r.freq_edits), s=shape, axes=tuple(range(len(shape)))
        )
        assert np.abs(full - half).max() < 1e-6
        assert np.asarray(r_r.freq_edits).shape == rfft_shape(shape)

    @pytest.mark.parametrize("shape", [(256,), (255,), (24, 18)])
    def test_matches_complex_fft_pointwise_delta(self, shape, rng):
        E = 0.1
        eps0 = np.clip(rng.standard_normal(shape) * 0.05, -E, E).astype(np.float32)
        d0 = np.abs(np.fft.fftn(eps0))
        Delta_full = np.maximum(0.5 * d0, 0.1 * d0.max()).astype(np.float32)
        Delta_half = Delta_full[..., : shape[-1] // 2 + 1]
        r_c = alternating_projection(
            jnp.asarray(eps0), E, jnp.asarray(Delta_full), max_iters=1000, use_rfft=False
        )
        # both the half grid and the auto-sliced full grid must work
        for Delta in (jnp.asarray(Delta_half), jnp.asarray(Delta_full)):
            r_r = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=1000, use_rfft=True)
            assert int(r_c.iterations) == int(r_r.iterations)
            assert _mismatch(r_c.eps, r_r.eps) < 1e-6

    @pytest.mark.parametrize("shape", SHAPES)
    def test_weighted_violation_counts_match_full_spectrum(self, shape, rng):
        eps = rng.standard_normal(shape).astype(np.float32)
        d_full = np.fft.fftn(eps)
        d_half = np.fft.rfftn(eps)
        w = rfft_pair_weights(shape)
        assert int(np.sum(np.broadcast_to(np.asarray(w), rfft_shape(shape)))) == int(
            np.prod(shape)
        )
        for Delta in (0.25, 1.0, 4.0):
            v_full = int(fcube_violations(jnp.asarray(d_full), Delta))
            v_half = int(fcube_violations(jnp.asarray(d_half), Delta, w))
            assert v_full == v_half

    def test_weighted_violations_kernel_path(self, rng):
        from repro.kernels.fcube.ops import project_fcube_fused

        shape = (24, 18)
        d_half = np.fft.rfftn(rng.standard_normal(shape)).astype(np.complex64)
        w = rfft_pair_weights(shape)
        _, _, viol = project_fcube_fused(jnp.asarray(d_half), 0.7, weight=w)
        expected = int(fcube_violations(jnp.asarray(d_half), 0.7, w))
        assert int(viol) == expected

    def test_final_violations_full_spectrum_semantics(self, rng):
        """A non-converged run reports full-spectrum violation counts."""
        eps0 = (rng.standard_normal(64) * 0.1).astype(np.float32)
        E = 1.0  # s-cube never binds -> first f-check decides
        Delta = 1e-9  # everything violates; cannot converge in 1 iter
        r_c = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=1, use_rfft=False)
        r_r = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=1, use_rfft=True)
        assert int(r_r.final_violations) == int(r_c.final_violations) > 0


class TestRelaxedProjectionClosedForm:
    def test_one_clip_matches_double_projection(self, rng):
        x = (rng.standard_normal(4096) * 2).astype(np.float32)
        for relax in (1.0, 1.3, 1.7, 1.95):
            fused = np.asarray(project_box_relaxed(jnp.asarray(x), 0.5, relax))
            first = np.clip(x, -0.5, 0.5)
            over = x + relax * (first - x)
            oracle = np.clip(over, -0.5, 0.5)
            np.testing.assert_allclose(fused, oracle, atol=1e-6)

    def test_pointwise_bound(self, rng):
        x = (rng.standard_normal(512) * 2).astype(np.float32)
        b = (0.1 + np.abs(rng.standard_normal(512))).astype(np.float32)
        fused = np.asarray(project_box_relaxed(jnp.asarray(x), jnp.asarray(b), 1.4))
        oracle = np.clip(x + 1.4 * (np.clip(x, -b, b) - x), -b, b)
        np.testing.assert_allclose(fused, oracle, atol=1e-6)


class TestCorrectBatch:
    def test_matches_per_tensor_blockwise(self, rng):
        tensors = [
            (rng.standard_normal((1000,)) * 0.01).astype(np.float32),
            (rng.standard_normal((64, 48)) * 0.02).astype(np.float32),
            (rng.standard_normal((3000,)) * 0.005).astype(np.float32),
        ]
        Es = [0.02, 0.03, 0.01]
        Ds = [0.5, 0.4, 0.3]
        outs, stats = correct_batch(
            [jnp.asarray(t) for t in tensors], Es, Ds, block=512, max_iters=50
        )
        for t, E, D, o in zip(tensors, Es, Ds, outs):
            ref = blockwise_correct(jnp.asarray(t), E, D, block=512, max_iters=50)
            assert _mismatch(ref, o) == 0.0
            assert np.asarray(o).shape == t.shape

    def test_per_instance_iteration_counts(self, rng):
        # one already-feasible tensor (1 iteration) + one needing work
        easy = (rng.standard_normal(512) * 1e-6).astype(np.float32)
        hard = (rng.standard_normal(512) * 0.05).astype(np.float32)
        Delta_hard = 0.3 * np.abs(np.fft.fft(hard)).max()
        outs, stats = correct_batch(
            [jnp.asarray(easy), jnp.asarray(hard)],
            [1.0, 0.06],
            [1e9, float(Delta_hard)],
            block=512,
            max_iters=100,
        )
        iters = np.asarray(stats.iterations)
        assert iters[0] == 1  # containment case
        assert iters[1] >= 1
        assert np.asarray(stats.converged).all()
        # the easy instance is untouched
        assert _mismatch(outs[0], easy) == 0.0

    def test_edit_streams_reconstruct(self, rng):
        t = (rng.standard_normal(1500) * 0.02).astype(np.float32)
        E, D = 0.04, 0.6
        outs, edits, stats = correct_batch(
            [jnp.asarray(t)], E, D, block=512, max_iters=50, return_edits=True
        )
        spat, freq = edits[0]
        tiles = np.pad(t, (0, 36)).reshape(-1, 512)
        recon = tiles + np.fft.irfft(np.asarray(freq), n=512, axis=-1) + np.asarray(spat)
        # the identity holds on the stored region (pad-tail values are loop
        # state the unpack discards — see correct_batch docstring)
        assert np.abs(recon.reshape(-1)[: t.size] - np.asarray(outs[0])).max() < 1e-6

    def test_empty_batch(self):
        outs, stats = correct_batch([], 0.1, 0.1)
        assert outs == [] and stats.iterations.shape == (0,)


class TestHalfSpectrumSerialization:
    def test_format_flag_roundtrips(self, rng):
        freq = (rng.standard_normal((10, 9)) + 1j * rng.standard_normal((10, 9))) * 0.01
        enc = encode_edits(freq, 0.2, m=16, half_spectrum=True)
        back = EncodedEdits.from_bytes(enc.to_bytes())
        assert back.half_spectrum and back.is_complex
        assert back.quant_bits == 16
        assert back.shape == (10, 9)
        # legacy streams (bit 7 clear) parse as full-spectrum
        enc_legacy = encode_edits(freq, 0.2, m=16)
        assert not EncodedEdits.from_bytes(enc_legacy.to_bytes()).half_spectrum

    def test_nbytes_is_exact(self, rng):
        for edits in (
            np.zeros(100),
            (rng.standard_normal(333) * 0.01).astype(np.float64),
            (rng.standard_normal((7, 11)) + 1j * rng.standard_normal((7, 11))) * 0.01,
        ):
            enc = encode_edits(edits, 0.5, m=16)
            assert enc.nbytes() == len(enc.to_bytes())

    def test_ffcz_blob_freq_stream_is_half_spectrum(self):
        from repro.data.fields import make_field

        x = make_field("nyx-like")[:16, :16, :16]
        c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
        _, blob = c.roundtrip(x)
        assert blob.freq_edits.half_spectrum
        assert blob.freq_edits.shape == rfft_shape(x.shape)
        blob2 = FFCzBlob.from_bytes(blob.to_bytes())
        assert blob2.freq_edits.half_spectrum


class TestLegacyBlobBackwardCompat:
    """Blobs written by the pre-rfft pipeline (full-spectrum freq edits, no
    format flag) must still decompress byte-identically."""

    def _legacy_blob(self, blob: FFCzBlob, shape) -> FFCzBlob:
        """Re-encode a modern blob the way the old pipeline serialized it."""
        if blob.pointwise_delta is not None:
            half_delta = np.frombuffer(blob.pointwise_delta, dtype=np.float32).reshape(
                rfft_shape(shape)
            )
            full_delta = np.zeros(shape, dtype=np.float32)
            full_delta[..., : shape[-1] // 2 + 1] = half_delta
            for k in range(1, shape[-1] // 2 + 1):
                if (shape[-1] - k) > shape[-1] // 2:
                    full_delta[..., shape[-1] - k] = half_delta[..., k]
            Delta_full = full_delta
            pw = full_delta.tobytes()
        else:
            Delta_full = blob.Delta_scalar
            pw = None
        half = decode_edits(blob.freq_edits, (
            np.frombuffer(blob.pointwise_delta, dtype=np.float32).reshape(rfft_shape(shape))
            if blob.pointwise_delta is not None else blob.Delta_scalar
        ))
        # rebuild the full Hermitian spectrum the old pipeline stored
        spatial = np.fft.irfftn(half, s=shape, axes=tuple(range(len(shape))))
        full = np.fft.fftn(spatial)
        fe = encode_edits(full, Delta_full, m=blob.freq_edits.quant_bits, half_spectrum=False)
        return dataclasses.replace(blob, freq_edits=fe, pointwise_delta=pw, stats=None)

    @pytest.mark.parametrize("pspec", [False, True])
    def test_legacy_full_spectrum_blob_decodes(self, pspec, rng):
        x = (rng.standard_normal((24, 20)).astype(np.float32)).cumsum(axis=0)
        cfg = (
            FFCzConfig(E_rel=1e-3, Delta_rel=None, pspec_rel=1e-2, max_iters=500)
            if pspec
            else FFCzConfig(E_rel=1e-3, Delta_rel=1e-3, max_iters=500)
        )
        c = FFCz(get_compressor("szlike"), cfg)
        blob = c.compress(x)
        modern = c.decompress(blob)
        legacy = self._legacy_blob(blob, x.shape)
        # through serialization: the flag byte must survive the wire
        legacy_wire = FFCzBlob.from_bytes(legacy.to_bytes())
        assert not legacy_wire.freq_edits.half_spectrum
        out = c.decompress(legacy_wire)
        # identical up to the (coarser) re-quantization of the freq stream
        E = float(blob.E)
        assert np.abs(out.astype(np.float64) - modern.astype(np.float64)).max() <= E
        # and the legacy reconstruction still honors the spatial bound
        assert np.abs(out.astype(np.float64) - x.astype(np.float64)).max() <= E * (1 + 1e-6)


class TestFftImplSelector:
    """fft_impl='packed'/'pallas' loop parity vs the XLA transforms."""

    @pytest.mark.parametrize("impl", ["packed", "pallas"])
    @pytest.mark.parametrize("shape", [(128,), (32, 32), (12, 10, 16)])
    def test_matches_xla_loop(self, impl, shape, rng):
        E = 0.1
        eps0 = np.clip(rng.standard_normal(shape) * 0.05, -E, E).astype(np.float32)
        Delta = 0.4 * np.abs(np.fft.fftn(eps0)).max()
        r_x = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=500)
        r_i = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=500, fft_impl=impl)
        # packed transforms round differently at float32 level, so the
        # trajectory may differ by rounding; the fixed point must agree
        assert bool(r_i.converged)
        assert abs(int(r_i.iterations) - int(r_x.iterations)) <= 1
        assert _mismatch(r_x.eps, r_i.eps) < 1e-5
        assert np.abs(np.asarray(r_i.eps)).max() <= E

    @pytest.mark.parametrize("impl", ["packed", "pallas"])
    def test_pointwise_delta(self, impl, rng):
        shape = (24, 18)
        E = 0.1
        eps0 = np.clip(rng.standard_normal(shape) * 0.05, -E, E).astype(np.float32)
        d0 = np.abs(np.fft.rfftn(eps0))
        Delta = np.maximum(0.5 * d0, 0.1 * d0.max()).astype(np.float32)
        r_x = alternating_projection(jnp.asarray(eps0), E, jnp.asarray(Delta), max_iters=1000)
        r_i = alternating_projection(
            jnp.asarray(eps0), E, jnp.asarray(Delta), max_iters=1000, fft_impl=impl
        )
        assert bool(r_i.converged)
        assert _mismatch(r_x.eps, r_i.eps) < 1e-5

    @pytest.mark.parametrize("impl", ["packed", "pallas"])
    def test_odd_last_axis_falls_back(self, impl, rng):
        """Odd shapes statically fall back; 'packed' becomes the exact XLA
        path, 'pallas' the XLA transforms + fused projection kernels."""
        shape = (31, 17)
        E = 0.1
        eps0 = np.clip(rng.standard_normal(shape) * 0.05, -E, E).astype(np.float32)
        Delta = 0.4 * np.abs(np.fft.fftn(eps0)).max()
        r_x = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=500)
        r_i = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=500, fft_impl=impl)
        assert int(r_i.iterations) == int(r_x.iterations)
        assert _mismatch(r_x.eps, r_i.eps) == 0.0

    def test_invalid_combinations_raise(self, rng):
        eps0 = jnp.zeros((16,), jnp.float32)
        with pytest.raises(ValueError, match="fft_impl"):
            alternating_projection(eps0, 0.1, 0.1, fft_impl="duff")
        with pytest.raises(ValueError, match="rfft"):
            alternating_projection(eps0, 0.1, 0.1, fft_impl="packed", use_rfft=False)
        with pytest.raises(ValueError, match="use_kernels"):
            alternating_projection(eps0, 0.1, 0.1, fft_impl="pallas", use_kernels=True)
        with pytest.raises(ValueError, match="relax"):
            alternating_projection(eps0, 0.1, 0.1, fft_impl="pallas", relax=1.3)
        with pytest.raises(ValueError, match="check_every"):
            alternating_projection(eps0, 0.1, 0.1, check_every=0)

    @pytest.mark.parametrize("impl", ["packed", "pallas"])
    def test_blockwise_backends_take_fft_impl(self, impl, rng):
        """The vmapped pencil program lifts the packed transforms unchanged."""
        eps = (rng.standard_normal(512) * 0.02).astype(np.float32)
        base = np.asarray(blockwise_correct(jnp.asarray(eps), 0.03, 0.05, block=128, max_iters=60))
        got = np.asarray(
            blockwise_correct(jnp.asarray(eps), 0.03, 0.05, block=128, max_iters=60, fft_impl=impl)
        )
        assert np.abs(got).max() <= 0.03
        assert np.abs(got - base).max() < 1e-6


class TestCheckEveryCadence:
    def test_cadenced_loop_converges_and_holds_bounds(self, rng):
        shape = (32, 32)
        E = 0.05
        eps0 = np.clip(rng.standard_normal(shape) * 0.03, -E, E).astype(np.float32)
        Delta = 0.3 * np.abs(np.fft.fftn(eps0)).max()
        r1 = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=500)
        for k in (2, 5):
            rk = alternating_projection(jnp.asarray(eps0), E, Delta, max_iters=500, check_every=k)
            assert bool(rk.converged)
            # convergence is declared at the first check at-or-after the true
            # iteration (extra iterations are safe no-ops)
            assert int(r1.iterations) <= int(rk.iterations) < int(r1.iterations) + k
            assert int(rk.final_violations) == 0
            assert np.abs(np.asarray(rk.eps)).max() <= E
            d = np.fft.rfftn(np.asarray(rk.eps, dtype=np.float64))
            tol = Delta * 2e-5
            assert max(np.abs(d.real).max(), np.abs(d.imag).max()) <= Delta + tol

    def test_final_iteration_always_checks(self, rng):
        """A max_iters exit reports a real violation count, never a stale one.

        Adversarial never-feasible configuration (the bench's forced-iteration
        workload): every point sits on an s-cube face with an imbalanced sign
        pattern and the f-cube pins the DC component, so the s-projection
        restores the DC violation every iteration.
        """
        E = 0.05
        sgn = np.where(rng.random(64) < 0.7, 1.0, -1.0)
        eps0 = (E * sgn).astype(np.float32)
        Delta = (1e9 * np.ones(33)).astype(np.float32)
        Delta[0] = 1e-4 * abs(float(eps0.sum()))
        r = alternating_projection(
            jnp.asarray(eps0), E, jnp.asarray(Delta), max_iters=5, check_every=4
        )
        assert not bool(r.converged)
        assert int(r.final_violations) > 0


def _one_device_dist_loop(eps0, E, Delta, fft_impl):
    """The slab-sharded ``dist`` loop on a one-device mesh (same passes as
    a slab of any mesh, with trivial transposes)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import pocs
    from repro.launch.mesh import make_mesh
    from repro.sharding import dist_fft
    from repro.sharding.shardmap import shard_map

    spec = dist_fft.DistSpec("data", eps0.shape, 1)
    fspec = dist_fft.freq_partition_spec(eps0.ndim, "data")
    out = pocs.AlternatingProjectionResult(P("data"), P("data"), fspec, P(), P(), P())
    run = shard_map(
        lambda e: pocs._alternating_projection(
            e, E, Delta, max_iters=500, dist=spec, fft_impl=fft_impl
        ),
        mesh=make_mesh((1,), ("data",), devices=jax.devices()[:1]),
        in_specs=P("data"),
        out_specs=out,
    )
    return jax.jit(run)(jnp.asarray(eps0))


class TestTpuInverse:
    @pytest.mark.parametrize("shape", [(32, 32), (12, 10, 16), (31, 17)])
    def test_xla_without_accurate_c2r_is_the_packed_loop(self, shape, rng, inaccurate_c2r):
        """Where the backend's C2R is inaccurate (TPU), ``fft_impl="xla"``
        runs per-axis passes ending in the pack-trick C2R pass: the same
        trajectory as the slab-sharded loop bit for bit, with both bounds
        held.  Odd last axes keep XLA's own C2R pass, as there is no pack
        trick for them."""
        import jax

        from repro.core import pocs

        E = 0.1
        eps0 = np.clip(rng.standard_normal(shape) * 0.05, -E, E).astype(np.float32)
        Delta = 0.4 * np.abs(np.fft.rfftn(eps0)).max()
        r = jax.jit(lambda e: pocs._alternating_projection(e, E, Delta, max_iters=500))(
            jnp.asarray(eps0)
        )
        d = _one_device_dist_loop(eps0, E, Delta, "xla")
        for field in ("eps", "spat_edits", "freq_edits"):
            assert np.array_equal(np.asarray(getattr(r, field)), np.asarray(getattr(d, field)))
        assert int(r.iterations) == int(d.iterations)
        assert bool(r.converged)
        eps = np.asarray(r.eps, np.float64)
        assert np.abs(eps).max() <= np.float32(E)
        f = np.fft.rfftn(eps)
        assert max(np.abs(f.real).max(), np.abs(f.imag).max()) <= Delta * (1 + 1e-4)

    @pytest.mark.parametrize("shape", [(64,), (31, 17), (12, 10, 16)])
    def test_per_axis_transforms_are_accurate(self, shape, rng, inaccurate_c2r):
        """The per-axis forward and inverse stay at float32 accuracy against
        float64 numpy, and the inverse inverts the forward."""
        from repro.kernels.rfft import ops as rfft_ops

        x = rng.standard_normal(shape).astype(np.float32)
        X = rfft_ops.field_rfftn(jnp.asarray(x))
        ref = np.fft.rfftn(x.astype(np.float64))
        assert np.abs(np.asarray(X) - ref).max() <= 1e-5 * np.abs(ref).max()
        y = np.asarray(rfft_ops.field_irfftn(X, shape))
        assert y.shape == shape
        assert np.abs(y - x).max() <= 1e-5 * np.abs(x).max()

    @pytest.mark.parametrize("n", [16, 15])
    def test_c2r_last_takes_the_pack_trick_only_where_needed(self, n, rng, monkeypatch):
        """Asked for (``packed``) or forced by an inaccurate C2R, an even
        line takes the pack trick; odd lines always keep XLA's C2R."""
        from repro.kernels.rfft import ops as rfft_ops

        X = jnp.asarray(np.fft.rfft(rng.standard_normal((4, n))).astype(np.complex64))
        xla = np.asarray(jnp.fft.irfft(X, n=n, axis=-1))
        packed = np.asarray(rfft_ops.packed_irfft(X, n)) if n % 2 == 0 else xla
        assert np.array_equal(np.asarray(rfft_ops.c2r_last(X, n)), xla)
        assert np.array_equal(np.asarray(rfft_ops.c2r_last(X, n, packed=True)), packed)
        monkeypatch.setattr(rfft_ops, "c2r_is_accurate", lambda: False)
        assert np.array_equal(np.asarray(rfft_ops.c2r_last(X, n)), packed)

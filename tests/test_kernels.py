"""Pallas kernel sweeps: shapes x dtypes against the pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_transform.ops import block_transform_quantize
from repro.kernels.block_transform.ref import block_transform_quantize_ref
from repro.kernels.fcube.ops import project_fcube_fused
from repro.kernels.fcube.ref import project_fcube_fused_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.quantize.ops import quantize_edits
from repro.kernels.quantize.ref import quantize_edits_ref
from repro.kernels.rfft import ops as rfft_ops
from repro.kernels.rfft import ref as rfft_ref
from repro.kernels.scube.ops import project_scube_fused
from repro.kernels.scube.ref import project_scube_fused_ref

SHAPES = [(64,), (100,), (256, 128), (33, 17, 5)]


class TestFCubeKernel:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("pointwise", [False, True])
    def test_matches_ref(self, shape, pointwise, rng):
        d = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)
        Delta = (np.abs(d.real) * 0.8 + 0.05).astype(np.float32) if pointwise else np.float32(0.7)
        c1, e1, v1 = project_fcube_fused(jnp.asarray(d), jnp.asarray(Delta))
        c2, e2, v2 = project_fcube_fused_ref(jnp.asarray(d), jnp.asarray(Delta))
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-6, atol=1e-7)
        assert int(v1) == int(v2)


class TestSCubeKernel:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_matches_ref(self, shape, dtype, rng):
        x = rng.standard_normal(shape).astype(dtype)
        c1, e1 = project_scube_fused(jnp.asarray(x), 0.4)
        c2, e2 = project_scube_fused_ref(jnp.asarray(x), 0.4)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), rtol=1e-6, atol=1e-7)

    def test_pointwise_E(self, rng):
        x = rng.standard_normal(300).astype(np.float32)
        E = (np.abs(rng.standard_normal(300)) * 0.3 + 0.05).astype(np.float32)
        c1, e1 = project_scube_fused(jnp.asarray(x), jnp.asarray(E))
        c2, e2 = project_scube_fused_ref(jnp.asarray(x), jnp.asarray(E))
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-6)


class TestQuantizeKernel:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("m", [8, 16])
    def test_matches_ref(self, shape, m, rng):
        v = rng.standard_normal(shape).astype(np.float32)
        c1, f1 = quantize_edits(jnp.asarray(v), 0.5, m=m)
        c2, f2 = quantize_edits_ref(jnp.asarray(v), 0.5, m=m)
        assert np.array_equal(np.asarray(c1), np.asarray(c2))
        assert np.array_equal(np.asarray(f1), np.asarray(f2))


class TestBlockTransformKernel:
    @pytest.mark.parametrize("nb", [1, 64, 777])
    @pytest.mark.parametrize("B", [64, 128])
    def test_matches_ref(self, nb, B, rng):
        blocks = rng.standard_normal((nb, B)).astype(np.float32)
        mat = np.linalg.qr(rng.standard_normal((B, B)))[0].astype(np.float32)
        c1 = block_transform_quantize(jnp.asarray(blocks), jnp.asarray(mat), 0.01)
        c2 = block_transform_quantize_ref(jnp.asarray(blocks), jnp.asarray(mat), 0.01)
        diff = np.abs(np.asarray(c1) - np.asarray(c2))
        assert (diff <= 1).all() and (diff > 0).mean() < 1e-3  # fp32 rint ties


class TestFlashAttentionKernel:
    @pytest.mark.parametrize(
        "b,hq,hkv,sq,sk,d",
        [
            (2, 4, 2, 128, 128, 64),
            (1, 2, 1, 256, 256, 128),
            (1, 4, 4, 1, 384, 64),  # decode
            (2, 8, 2, 100, 100, 64),  # unaligned
            (1, 2, 1, 100, 260, 64),  # suffix queries
            (1, 14, 2, 64, 512, 64),  # qwen-ish GQA
        ],
    )
    def test_matches_ref(self, b, hq, hkv, sq, sk, d, rng):
        q = rng.standard_normal((b, hq, sq, d)).astype(np.float32) * 0.5
        k = rng.standard_normal((b, hkv, sk, d)).astype(np.float32) * 0.5
        v = rng.standard_normal((b, hkv, sk, d)).astype(np.float32)
        o1 = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), block_q=128, block_k=128)
        o2 = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5)

    def test_bf16(self, rng):
        q = jnp.asarray(rng.standard_normal((1, 4, 128, 64)), dtype=jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), dtype=jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), dtype=jnp.bfloat16)
        o1 = flash_attention(q, k, v)
        o2 = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
        assert np.abs(np.asarray(o1, dtype=np.float32) - np.asarray(o2)).max() < 0.03

    def test_rejects_sq_gt_sk(self, rng):
        q = jnp.zeros((1, 2, 16, 32))
        k = jnp.zeros((1, 2, 8, 32))
        with pytest.raises(ValueError):
            flash_attention(q, k, k)


# shapes with an even last axis (the pack-trick domain); 1-D through 3-D
RFFT_SHAPES = [(64,), (100,), (16, 48), (31, 22), (12, 10, 8), (33, 17, 6)]


class TestPackedTransforms:
    @pytest.mark.parametrize("shape", RFFT_SHAPES)
    def test_packed_rfftn_matches_fft(self, shape, rng):
        x = rng.standard_normal(shape).astype(np.float32)
        X = np.asarray(rfft_ops.packed_rfftn(jnp.asarray(x)))
        want = np.fft.rfftn(x)
        scale = max(np.abs(want).max(), 1.0)
        np.testing.assert_allclose(X, want, atol=1e-5 * scale)
        # and the float64 numpy ref twin
        np.testing.assert_allclose(
            rfft_ref.packed_rfftn_ref(x.astype(np.float64)), want, atol=1e-6 * scale
        )

    @pytest.mark.parametrize("shape", RFFT_SHAPES)
    def test_packed_irfftn_matches_ifft(self, shape, rng):
        x = rng.standard_normal(shape).astype(np.float32)
        X = np.fft.rfftn(x).astype(np.complex64)
        out = np.asarray(rfft_ops.packed_irfftn(jnp.asarray(X), shape))
        np.testing.assert_allclose(out, x, atol=2e-6 * max(np.abs(x).max(), 1.0))
        np.testing.assert_allclose(rfft_ref.packed_irfftn_ref(X, shape), x, atol=1e-6)

    def test_packed_irfft_lastaxis_lines(self, rng):
        """The per-line C2R the distributed transform composes."""
        x = rng.standard_normal((7, 32)).astype(np.float32)
        X = np.fft.rfft(x, axis=-1).astype(np.complex64)
        out = np.asarray(rfft_ops.packed_irfft(jnp.asarray(X), 32))
        np.testing.assert_allclose(out, x, atol=2e-6)

    def test_twiddle_plan_registry_caches(self):
        a = rfft_ops.twiddle_plan(64, "float32")
        b = rfft_ops.twiddle_plan(64, "float32")
        assert a[0] is b[0]  # lru_cache hit: same host constant
        assert rfft_ops.twiddle_plan(64, "float64")[0] is not a[0]
        with pytest.raises(ValueError, match="even"):
            rfft_ops.twiddle_plan(33)

    def test_supports_packed(self):
        assert rfft_ops.supports_packed((16, 48))
        assert not rfft_ops.supports_packed((16, 47))
        assert not rfft_ops.supports_packed(())


class TestRfftFwdEpilogueKernel:
    @pytest.mark.parametrize("shape", [(48,), (12, 34), (6, 10, 16)])
    @pytest.mark.parametrize("pointwise", [False, True])
    def test_matches_ref(self, shape, pointwise, rng):
        from repro.core.cubes import rfft_pair_weights

        h = shape[:-1] + (shape[-1] // 2 + 1,)
        d = (rng.standard_normal(h) + 1j * rng.standard_normal(h)).astype(np.complex64)
        Delta = (np.abs(d.real) * 0.8 + 0.05).astype(np.float32) if pointwise else np.float32(0.7)
        w = np.broadcast_to(np.asarray(rfft_pair_weights(shape)), h)
        c1, e1, z1, v1 = rfft_ops.fwd_epilogue_fused(
            jnp.asarray(d), jnp.asarray(Delta), weight=jnp.asarray(w),
            check_tol=1e-5, check_slack=1e-4,
        )
        c2, e2, z2, v2 = rfft_ref.fwd_epilogue_ref(d, Delta, weight=w, check_tol=1e-5, check_slack=1e-4)
        np.testing.assert_allclose(np.asarray(c1), c2, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(e1), e2, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(z1), z2, atol=1e-6)
        assert int(v1) == int(v2)

    def test_fused_z_completes_the_inverse(self, rng):
        """ifftn of the kernel's Z slice == irfftn of the clipped spectrum."""
        shape = (16, 32)
        x = rng.standard_normal(shape).astype(np.float32) * 0.1
        d = jnp.fft.rfftn(jnp.asarray(x))
        _, _, Z, _ = rfft_ops.fwd_epilogue_fused(d, 0.05)
        z = jnp.fft.ifftn(Z[..., : shape[-1] // 2])
        got, _ = rfft_ops.unpack_sclip_fused(z, jnp.asarray(np.float32(np.inf)), shape)
        clip = jnp.clip(d.real, -0.05, 0.05) + 1j * jnp.clip(d.imag, -0.05, 0.05)
        want = np.fft.irfftn(np.asarray(clip), s=shape, axes=(0, 1))
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)


class TestUnpackSclipKernel:
    @pytest.mark.parametrize("pointwise", [False, True])
    def test_matches_ref(self, pointwise, rng):
        shape = (10, 64)
        z = (rng.standard_normal((10, 32)) + 1j * rng.standard_normal((10, 32))).astype(np.complex64)
        E = (np.abs(rng.standard_normal(shape)) * 0.5 + 0.1).astype(np.float32) if pointwise else np.float32(0.6)
        c1, d1 = rfft_ops.unpack_sclip_fused(jnp.asarray(z), jnp.asarray(E), shape)
        c2, d2 = rfft_ref.unpack_sclip_ref(z, E, shape)
        np.testing.assert_allclose(np.asarray(c1), c2, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(d1), d2, rtol=1e-6, atol=1e-7)

    def test_kernels_trace_under_x64(self, rng):
        """int32 violation sums must not promote under jax_enable_x64 (the
        store into the int32 out ref / loop carry fails at trace time)."""
        from repro.core.pocs import alternating_projection

        x = (rng.standard_normal((8, 16)) * 0.04).astype(np.float32)
        with jax.enable_x64(True):
            r = alternating_projection(jnp.asarray(x), 0.05, 0.4, max_iters=20, fft_impl="pallas")
            assert bool(r.converged)
            r = alternating_projection(jnp.asarray(x), 0.05, 0.4, max_iters=20, use_kernels=True)
            assert bool(r.converged)

    def test_vmap_lifts(self, rng):
        """The pencil backends vmap the fused epilogues; gate the batch rule."""
        z = (rng.standard_normal((3, 16)) + 1j * rng.standard_normal((3, 16))).astype(np.complex64)
        c, d = jax.vmap(lambda t: rfft_ops.unpack_sclip_fused(t, 0.4, (32,)))(jnp.asarray(z))
        for i in range(3):
            c2, d2 = rfft_ref.unpack_sclip_ref(z[i], np.float32(0.4), (32,))
            np.testing.assert_allclose(np.asarray(c)[i], c2, rtol=1e-6)

"""Distributed pencil-decomposed rFFT: generalized (uneven, padded) slab
decomposition, parity tri-state, and bitwise parity with the single-device
path on 2- and 8-device CPU meshes.

The multi-device checks run in a subprocess (XLA_FLAGS must be set before jax
imports — same pattern as tests/test_distributed.py) and report JSON; the
shape-classification checks are pure functions and run in-process.

The parity bar extends PR 2's batched-vs-sharded discipline to whole fields:
``pencil_rfftn`` must equal the fused ``jnp.fft.rfftn`` bit for bit, and
``FFCz.compress`` of a :class:`ShardedField` must emit the byte-identical
blob payload the single-device path emits, for scalar (``Delta_abs``) and
pointwise (``pspec_rel``) bounds alike — now on uneven (non-divisible) slabs
too, where axis extents classify as ``"bitwise"``.  ``"bound"``-class shapes
(non-power-of-two c2c axes) must hold both bounds without byte parity, and
divisibility is no longer an error anywhere.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.sharding.dist_fft import (
    classify_parity,
    local_freq_shape,
    padded_freq_shape,
    padded_spatial_shape,
    validate_pencil_shape,
)

_TRANSFORM_CASES = (
    "3d",
    "2d",
    "3d_uneven_pow2",
    "3d_uneven",
    "2d_uneven",
    "2d_uneven_pow2",
)

_CHILD_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
import json
import numpy as np
import jax
import jax.numpy as jnp
from repro.compressors import get_compressor
from repro.core.ffcz import FFCz, FFCzConfig, ShardedField
from repro.core.spectrum import power_spectrum
from repro.sharding.dist_fft import DistSpec, pencil_irfftn, pencil_rfftn

out = {"n_dev": len(jax.devices())}
n_dev = len(jax.devices())
rng = np.random.default_rng(7)

# --- transform parity: decomposed+distributed == fused single-device, bitwise
# (32,16,12)/(32,62): evenly divisible (the PR 3 contract); (4,16,12): uneven
# pow2 slabs (axis 0 < mesh size); (30,14,10)/(30,48): uneven AND non-pow2
cases = {
    "3d": rng.standard_normal((32, 16, 12)).astype(np.float32),
    "2d": rng.standard_normal((32, 62)).astype(np.float32),
    "3d_uneven_pow2": rng.standard_normal((4, 16, 12)).astype(np.float32),
    "3d_uneven": rng.standard_normal((30, 14, 10)).astype(np.float32),
    "2d_uneven": rng.standard_normal((30, 48)).astype(np.float32),
    "2d_uneven_pow2": rng.standard_normal((32, 48)).astype(np.float32),
}
for name, x in cases.items():
    field = ShardedField.shard(x)
    out[f"parity_class_{name}"] = field.parity
    X = pencil_rfftn(field)
    fused = jnp.fft.rfftn(jnp.asarray(x))
    out[f"fwd_bitwise_{name}"] = bool(
        np.array_equal(np.asarray(field.unpad_freq(X)), np.asarray(fused))
    )
    back = pencil_irfftn(X, x.shape, field.mesh, field.axis_name)
    ref = jnp.fft.irfftn(fused, s=x.shape).astype(jnp.float32)
    if field.parity == "bitwise":
        out[f"inv_bitwise_{name}"] = bool(np.array_equal(np.asarray(back), np.asarray(ref)))
    out[f"roundtrip_close_{name}"] = bool(
        np.allclose(np.asarray(back), x, atol=1e-5 * np.abs(x).max())
    )

# --- cross-mesh spectrum layouts: a foreign (larger) writer mesh's padded
# layout and the true-extent layout both decode on THIS mesh
x = cases["2d_uneven"]
X_true = np.fft.rfftn(x).astype(np.complex64)
X_foreign = np.pad(X_true, [(0, 0), (0, 7)])  # some other mesh's transit pad
fld = ShardedField.shard(x)
out["cross_mesh_irfftn"] = all(
    bool(
        np.allclose(
            np.asarray(pencil_irfftn(spec, x.shape, fld.mesh, fld.axis_name)),
            np.fft.irfftn(X_true, s=x.shape),
            atol=1e-5 * np.abs(x).max(),
        )
    )
    for spec in (X_true, X_foreign)
)

# --- overlapped (double-buffered) transposes are bitwise-neutral
x = cases["3d_uneven"]
X1 = pencil_rfftn(ShardedField.shard(x, overlap_chunks=1))
X2 = pencil_rfftn(ShardedField.shard(x, overlap_chunks=2))
X3 = pencil_rfftn(ShardedField.shard(x, overlap_chunks=3))
out["overlap_bitwise"] = bool(
    np.array_equal(np.asarray(X1), np.asarray(X2))
    and np.array_equal(np.asarray(X1), np.asarray(X3))
)

# --- FFCz blob parity: sharded compress == single-device compress, bytewise
f3 = (rng.standard_normal((32, 16, 12)) * 0.5 + 5.0).astype(np.float32).cumsum(axis=0)
cfgs = {
    "Delta_abs": FFCzConfig(
        E_rel=1e-3,
        Delta_rel=None,
        Delta_abs=float(np.abs(np.fft.fftn(f3)).max() * 1e-3),
    ),
    "pspec": FFCzConfig(E_rel=1e-3, Delta_rel=None, pspec_rel=1e-3, max_iters=1500),
}
for name, cfg in cfgs.items():
    c = FFCz(get_compressor("szlike"), cfg)
    blob_single = c.compress(f3)
    blob_sharded = c.compress(ShardedField.shard(f3))
    out[f"blob_bitwise_{name}"] = blob_single.to_bytes() == blob_sharded.to_bytes()
    out[f"converged_{name}"] = bool(blob_sharded.stats.converged)
    out[f"margins_ok_{name}"] = bool(
        blob_sharded.stats.spatial_margin >= 0 and blob_sharded.stats.frequency_margin >= 0
    )
    dec = c.decompress(blob_single)
    dec_sharded = c.decompress_sharded(blob_sharded)
    out[f"decompress_bitwise_{name}"] = bool(
        np.array_equal(np.asarray(dec_sharded.to_host()), dec)
    )

# uneven pow2 slabs: the blob PAYLOAD stays byte-identical; the pad-metadata
# tail records the decomposition and survives a wire round trip
f_up = (rng.standard_normal((4, 16, 12)) * 0.5 + 5.0).astype(np.float32).cumsum(axis=0)
c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
b_single = c.compress(f_up)
b_sh = c.compress(ShardedField.shard(f_up))
out["uneven_payload_bitwise"] = b_sh.payload_bytes() == b_single.to_bytes()

# pad-metadata section: (15, 14, 10) is non-divisible by every mesh size the
# matrix runs (15 %% 2 == 1, 15 %% 8 == 7), so the FFCP tail is always written
f_pm = (rng.standard_normal((15, 14, 10)) * 0.5 + 5.0).astype(np.float32).cumsum(axis=0)
f_pm_sh = ShardedField.shard(f_pm)
b_pm = c.compress(f_pm_sh)
out["uneven_pad_meta"] = (
    b_pm.pad_meta is not None
    and b_pm.pad_meta.n_dev == n_dev
    and tuple(b_pm.pad_meta.padded_shape) == f_pm_sh.padded_shape
)
from repro.core.ffcz import FFCzBlob
b_rt = FFCzBlob.from_bytes(b_pm.to_bytes())
out["uneven_pad_meta_wire"] = b_rt.pad_meta == b_pm.pad_meta and bool(
    np.array_equal(c.decompress(b_rt), c.decompress(b_pm))
)

# 2-D field through the full codec as well (half axis is the sharded one)
f2 = (rng.standard_normal((32, 62)) * 0.1).astype(np.float32).cumsum(axis=1)
c = FFCz(get_compressor("zfplike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
out["blob_bitwise_2d"] = c.compress(f2).to_bytes() == c.compress(ShardedField.shard(f2)).to_bytes()

# "bound"-class shapes (non-power-of-two c2c axes, uneven slabs): outside the
# bitwise contract but the dual bounds must hold exactly, and the blob must
# stay decodable to a mesh-resident field
f4 = (rng.standard_normal((30, 14, 10)) * 0.3 + 4.0).astype(np.float32).cumsum(axis=2)
c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=1e-3))
blob_ns = c.compress(ShardedField.shard(f4))
out["bound_class_bounds_hold"] = bool(
    blob_ns.stats.spatial_margin >= 0 and blob_ns.stats.frequency_margin >= 0
)
out["bound_class_decompress_bitwise"] = bool(
    np.array_equal(np.asarray(c.decompress_sharded(blob_ns).to_host()), c.decompress(blob_ns))
)

# acceptance shape class: non-power-of-two axes at realistic scale, tight
# pointwise-POCS-exercising Delta; compress+decompress with both bounds held
f5 = (rng.standard_normal((96, 80, 56)) * 0.5 + 5.0).astype(np.float32).cumsum(axis=0)
c = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-3, Delta_rel=2e-5, max_iters=400))
blob5 = c.compress(ShardedField.shard(f5))
out["accept_96_80_56"] = bool(
    blob5.stats.spatial_margin >= 0 and blob5.stats.frequency_margin >= 0
)
dec5 = c.decompress(blob5)
d5 = np.fft.rfftn(dec5.astype(np.float64) - f5.astype(np.float64))
out["accept_bounds_recheck"] = bool(
    np.abs(dec5.astype(np.float64) - f5).max() <= blob5.E
    and max(np.abs(d5.real).max(), np.abs(d5.imag).max()) <= blob5.Delta_scalar
)

# --- sharded power spectrum: same shells to float tolerance (metric, not bound)
for name, fld in (("", f3), ("_uneven", f4)):
    k_ref, p_ref = power_spectrum(fld)
    k_sh, p_sh = power_spectrum(ShardedField.shard(fld))
    p_ref, p_sh = np.asarray(p_ref, np.float64), np.asarray(p_sh, np.float64)
    # shell 0 is the mean-normalized DC: ~0 by construction, cancellation noise
    out[f"pspec_shells_close{name}"] = bool(
        np.array_equal(np.asarray(k_ref), np.asarray(k_sh))
        and np.allclose(p_ref[1:], p_sh[1:], rtol=1e-4)
        and abs(p_sh[0]) <= 1e-6 * p_ref[1:].max()
    )

print("RESULTS:" + json.dumps(out))
"""


@pytest.fixture(scope="module", params=[2, 8])
def dist_results(request):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_SCRIPT % request.param],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    if proc.returncode != 0:  # the child's whole stderr, not a tail
        pytest.fail(f"child exited {proc.returncode}:\n{proc.stderr}", pytrace=False)
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULTS:")][0]
    return json.loads(line[len("RESULTS:") :]), request.param


class TestPencilTransformParity:
    def test_mesh_size(self, dist_results):
        results, n_dev = dist_results
        assert results["n_dev"] == n_dev

    def test_parity_classification(self, dist_results):
        results, _ = dist_results
        assert results["parity_class_3d"] == "bitwise"
        assert results["parity_class_3d_uneven_pow2"] == "bitwise"
        assert results["parity_class_3d_uneven"] == "bound"
        assert results["parity_class_2d_uneven"] == "bound"  # 30 not a pow2
        assert results["parity_class_2d_uneven_pow2"] == "bitwise"  # axis 0 = 32

    def test_rfftn_bitwise_equals_fused(self, dist_results):
        """The FORWARD transform is bitwise for every shape class: padding
        is zeros-only and the per-axis passes run at true lengths."""
        results, _ = dist_results
        for name in _TRANSFORM_CASES:
            assert results[f"fwd_bitwise_{name}"], name

    def test_irfftn_bitwise_equals_fused(self, dist_results):
        """The INVERSE is bitwise exactly on "bitwise"-class shapes."""
        results, _ = dist_results
        assert results["inv_bitwise_3d"]
        assert results["inv_bitwise_2d"]
        assert results["inv_bitwise_3d_uneven_pow2"]
        assert results["inv_bitwise_2d_uneven_pow2"]

    def test_roundtrip_recovers_field(self, dist_results):
        results, _ = dist_results
        for name in _TRANSFORM_CASES:
            assert results[f"roundtrip_close_{name}"], name

    def test_overlapped_transposes_bitwise_neutral(self, dist_results):
        results, _ = dist_results
        assert results["overlap_bitwise"]

    def test_cross_mesh_spectrum_layouts_decode(self, dist_results):
        results, _ = dist_results
        assert results["cross_mesh_irfftn"]


class TestShardedCompressParity:
    def test_delta_abs_blob_bitwise(self, dist_results):
        results, _ = dist_results
        assert results["blob_bitwise_Delta_abs"]
        assert results["converged_Delta_abs"] and results["margins_ok_Delta_abs"]

    def test_pspec_blob_bitwise(self, dist_results):
        results, _ = dist_results
        assert results["blob_bitwise_pspec"]
        assert results["converged_pspec"] and results["margins_ok_pspec"]

    def test_2d_blob_bitwise(self, dist_results):
        results, _ = dist_results
        assert results["blob_bitwise_2d"]

    def test_decompress_sharded_bitwise(self, dist_results):
        results, _ = dist_results
        assert results["decompress_bitwise_Delta_abs"]
        assert results["decompress_bitwise_pspec"]

    def test_uneven_pow2_payload_bitwise_with_pad_meta(self, dist_results):
        """Uneven slabs of a pow2-class shape keep byte-identical payloads;
        the optional FFCP section records the decomposition."""
        results, _ = dist_results
        assert results["uneven_payload_bitwise"]
        assert results["uneven_pad_meta"]
        assert results["uneven_pad_meta_wire"]


class TestBoundClassShapes:
    def test_bounds_hold_outside_bitwise_contract(self, dist_results):
        results, _ = dist_results
        assert results["bound_class_bounds_hold"]

    def test_bound_class_blob_decodes_to_mesh(self, dist_results):
        results, _ = dist_results
        assert results["bound_class_decompress_bitwise"]

    def test_acceptance_shape_96_80_56(self, dist_results):
        """ISSUE 4 acceptance: FFCz.compress/decompress succeed on a
        slab-sharded non-power-of-two field at realistic scale with both
        bounds verified."""
        results, _ = dist_results
        assert results["accept_96_80_56"]
        assert results["accept_bounds_recheck"]


class TestShardedPowerSpectrum:
    def test_shells_match_gathered(self, dist_results):
        results, _ = dist_results
        assert results["pspec_shells_close"]
        assert results["pspec_shells_close_uneven"]


class TestShapeClassification:
    def test_rank_rejected(self):
        with pytest.raises(ValueError, match="rank"):
            classify_parity((128,), 2)
        with pytest.raises(ValueError, match="rank"):
            classify_parity((8, 8, 8, 8), 2)

    def test_degenerate_extent_rejected(self):
        with pytest.raises(ValueError, match="degenerate"):
            classify_parity((0, 8, 8), 2)

    def test_non_divisible_shapes_are_not_errors(self):
        """The PR 3 divisibility errors are a removed code path: any extent
        slab-decomposes (padded), classification only reflects parity."""
        assert classify_parity((30, 16, 12), 8) == "bound"  # 30 not pow2
        assert classify_parity((32, 12, 16), 8) == "bound"  # 12 not pow2
        assert classify_parity((4, 16, 12), 8) == "bitwise"  # uneven but pow2
        assert classify_parity((30, 48), 8) == "bound"  # 2-D, axis 0 not pow2

    def test_2d_c2c_axis_is_axis0_only(self):
        # 2-D: only axis 0 is a c2c pass; the last axis is r2c/c2r and
        # unconstrained (62 is not a power of two, 25 half columns uneven)
        assert classify_parity((32, 62), 8) == "bitwise"
        assert classify_parity((32, 48), 8) == "bitwise"

    def test_strict_bitwise_tri_state(self):
        # bitwise: accepted and classified
        assert validate_pencil_shape((32, 16, 12), 8) == "bitwise"
        assert validate_pencil_shape((4, 16, 12), 8) == "bitwise"
        # bound: error under strict, accepted (and classified) with opt-out
        with pytest.raises(ValueError, match="power of two"):
            validate_pencil_shape((24, 16, 12), 8)
        with pytest.raises(ValueError, match="power of two"):
            validate_pencil_shape((32, 24, 12), 8)
        assert validate_pencil_shape((24, 24, 10), 8, strict_bitwise=False) == "bound"
        # error: raised regardless of strictness
        with pytest.raises(ValueError, match="rank"):
            validate_pencil_shape((128,), 8, strict_bitwise=False)

    def test_last_axis_unconstrained(self):
        # the c2r axis scale sits inside one final pass either way: any
        # length is bitwise-safe (12 and 15 are not powers of two)
        assert validate_pencil_shape((32, 16, 12), 8) == "bitwise"
        assert validate_pencil_shape((32, 16, 15), 8) == "bitwise"

    def test_local_freq_shape(self):
        assert local_freq_shape((32, 16, 12), 8) == (4, 16, 7)
        assert local_freq_shape((32, 62), 8) == (32, 4)
        # uneven: slab rows and half columns round up
        assert local_freq_shape((30, 14, 10), 8) == (4, 14, 6)
        assert local_freq_shape((30, 48), 8) == (30, 4)  # H=25 -> ceil(25/8)=4

    def test_padded_shapes(self):
        assert padded_spatial_shape((30, 14, 10), 8) == (32, 14, 10)
        assert padded_spatial_shape((32, 16, 12), 8) == (32, 16, 12)
        assert padded_freq_shape((30, 14, 10), 8) == (32, 14, 6)
        assert padded_freq_shape((30, 48), 8) == (30, 32)  # H=25 -> 32


class TestInaccurateC2R:
    @pytest.mark.parametrize("shape", [(16, 16, 16), (12, 10, 16), (32, 62)])
    def test_payload_matches_one_device_mesh(self, shape, inaccurate_c2r):
        """Where XLA's C2R is inaccurate (TPU), the single-device field runs
        the slab transform's per-axis passes, so ``FFCz.compress`` of the
        field and of its one-device-mesh ``ShardedField`` write the same
        payload, and the decoded field holds both stored bounds."""
        import jax
        import numpy as np

        from repro.compressors import get_compressor
        from repro.core.ffcz import FFCz, FFCzConfig, ShardedField
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(3)
        x = np.exp(rng.standard_normal(shape)).astype(np.float32)
        # a tight Delta, so the loop projects rather than only checking
        codec = FFCz(get_compressor("szlike"), FFCzConfig(E_rel=1e-2, Delta_rel=1e-4))
        mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
        one = codec.compress(x)
        sharded = codec.compress(ShardedField.shard(x, mesh))
        assert one.stats.iterations > 1
        assert one.payload_bytes() == sharded.payload_bytes()
        err = codec.decompress(one).astype(np.float64) - x
        d = np.fft.rfftn(err)
        assert np.abs(err).max() <= one.E
        assert max(np.abs(d.real).max(), np.abs(d.imag).max()) <= one.Delta_scalar
